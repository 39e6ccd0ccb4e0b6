"""Device ms a decode step in the cross-attention layers' paged
attention: the operations scoped `layer<i>/attn_cross` (the walk list and
the grouped kernel over every page the shared full layer holds of each
row; seven layers on one pool)."""
from program_trace import scoped_ms_per_run


def read(run):
    return scoped_ms_per_run(run, r"/layer\d+/attn_cross/", "serve_decode")
