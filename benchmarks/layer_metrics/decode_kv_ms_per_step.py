"""Device ms a decode step in the operations the program scopes
`kv_write` (the `.at[i, dest].set` into the pools) or `kv_read` (the
per-layer pool slices handed to the kernel)."""
from program_trace import scoped_ms_per_run


def read(run):
    return scoped_ms_per_run(run, r"/kv_(write|read)/", "serve_decode")
