"""Device ms a decode step in attention over the selected tokens: the
operations the program scopes `layer<i>/attn_sparse` (the gather of the
listed tokens' K and V rows and the `paged_attention_sparse` kernel)."""
from program_trace import scoped_ms_per_run


def read(run):
    return scoped_ms_per_run(run, r"/layer\d+/attn_sparse/", "serve_decode")
