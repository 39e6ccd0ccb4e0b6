"""Device ms a decode step in the gated memory units: the operations
scoped `layer<i>/gmu` (norm, in projection, the gate on the memory of the
last state-space layer, out projection)."""
from program_trace import scoped_ms_per_run


def read(run):
    return scoped_ms_per_run(run, r"/layer\d+/gmu/", "serve_decode")
