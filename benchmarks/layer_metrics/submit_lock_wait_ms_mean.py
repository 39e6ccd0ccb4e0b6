"""Mean time a request spent in `scheduler.submit()` before it held the
scheduler's lock: the program's `lock_wait_s` over `submitted`, both
over the window.  With `queue_wait_ms_mean` it splits what
`submit_wait_ms_p95` sees from outside."""
from program_trace import counter_ratio_ms


def read(run):
    return counter_ratio_ms(run, ["lock_wait_s"], "submitted")
