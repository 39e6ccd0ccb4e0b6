"""Device ms a train step in the operations scoped `optimizer`."""
from program_trace import scoped_ms_per_run


def read(run):
    return scoped_ms_per_run(run, r"/optimizer/", "captured_step")
