"""Device ms a decode step in the full-attention layers' paged attention:
the operations scoped `layer<i>/attn_global` (the walk list and the
grouped kernel over every page of each row)."""
from program_trace import scoped_ms_per_run


def read(run):
    return scoped_ms_per_run(run, r"/layer\d+/attn_global/", "serve_decode")
