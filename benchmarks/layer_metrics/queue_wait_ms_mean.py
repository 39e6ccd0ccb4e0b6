"""Mean time a request waited in the scheduler's queue, enqueued to its
prefill entered: the program's `queue_wait_s` over `admitted`."""
from program_trace import counter_ratio_ms


def read(run):
    return counter_ratio_ms(run, ["queue_wait_s"], "admitted")
