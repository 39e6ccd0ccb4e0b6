"""Device ms a decode step in the state-space layers' mixers: the
operations the program scopes `layer<i>/ssm` (in projection, convolution
over the slot's tail, x and dt projections, the one-token state update,
gate and out projection) and `layer<i>/state_write` (the rows' slots
written back in place)."""
from program_trace import scoped_ms_per_run


def read(run):
    return scoped_ms_per_run(run, r"/layer\d+/(ssm|state_write)/",
                             "serve_decode")
