"""Device ms a decode step in the sliding-window layers' paged attention:
the operations scoped `layer<i>/attn_window` (the walk list and the
grouped kernel over the pages of each row's window)."""
from program_trace import scoped_ms_per_run


def read(run):
    return scoped_ms_per_run(run, r"/layer\d+/attn_window/", "serve_decode")
