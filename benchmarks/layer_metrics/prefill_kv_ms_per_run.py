"""Device ms a prefill program run in moving K and V into the pools:
the operations the program scopes `kv_write` (the scatter of the
prompt's K and V), and those XLA names after the pool arguments
themselves (`k_flat`, `v_flat`, `k_scale`, `v_scale`): the copies of a
whole pool into the layout the scatter wants.  The copies back on the
way out carry no name at all (my chip run, PR 25) and are not in here;
`^copy:` operations of the run are all of them."""
from program_trace import scoped_ms_per_run


def read(run):
    return scoped_ms_per_run(
        run, r"/kv_(write|read)/|^[kv]_(flat|scale):", "serve_prefill")
