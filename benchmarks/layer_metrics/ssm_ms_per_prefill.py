"""Device ms a prefill program run in the state-space layers' mixers: the
operations scoped `layer<i>/ssm` (projections, convolution, the `ssm_scan`
kernel over the prompt, gate) and `layer<i>/state_write`.  The mean over
the prefill runs in the traced window, whatever their buckets."""
from program_trace import scoped_ms_per_run


def read(run):
    return scoped_ms_per_run(run, r"/layer\d+/(ssm|state_write)/",
                             "serve_prefill")
