"""Device ms a train step in the operations scoped `lm_head` (the tied
head's matmul) or `loss`, forward and backward: a backward operation
carries its forward's scope."""
from program_trace import scoped_ms_per_run


def read(run):
    return scoped_ms_per_run(run, r"/(lm_head|loss)/", "captured_step")
