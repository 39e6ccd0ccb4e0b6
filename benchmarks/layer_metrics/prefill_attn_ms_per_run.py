"""Device ms a prefill program run in the operations scoped
`layer<i>/attn`: the full score tensor, its softmax and the weighted
sum (not the projections, which are `attn_qkv` and `attn_out`)."""
from program_trace import scoped_ms_per_run


def read(run):
    return scoped_ms_per_run(run, r"/layer\d+/attn/", "serve_prefill")
