"""Device ms a prefill program run in attention over the prompt itself,
in a model with sliding and full layers: the operations scoped
`layer<i>/attn_window` / `layer<i>/attn_global` (scores, mask, softmax
and weighted sum; one tensor up to 1,024 positions, blocks with an
online softmax beyond).  The `while` that encloses a blocked layer's
body carries no scope and is not counted beside the operations of its
body, so each operation counts once.  The mean over the prefill runs in
the traced window, whatever their buckets.  `prefill_attn_ms_per_run`
stays the equal-heads block's (`layer<i>/attn`)."""
from program_trace import scoped_ms_per_run


def read(run):
    return scoped_ms_per_run(run, r"/layer\d+/attn_(global|window)/",
                             "serve_prefill")
