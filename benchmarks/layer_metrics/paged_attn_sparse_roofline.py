"""Sparse paged attention's share of its roofline over the decode steps of
the traced window: the least time to read K and V of min(context, topk)
tokens a row once a KV head, q in and the result out, and do QK^T and PV
for every query head (`costs_sparse.sparse_decode`, times the layers),
over the device time of the operations scoped `attn_sparse` (the gather
and the kernel).  The rows' contexts are the runner's tap's."""
from costs_lm import in_window, mean_least_ms
from costs_sparse import sparse_decode
from program_trace import scoped_ms_per_run


def read(run):
    ms = scoped_ms_per_run(run, r"/layer\d+/attn_sparse/", "serve_decode")
    steps = in_window(run, "decode_rows")
    m = run.get("model") or {}
    if not ms or not steps or len(steps[0]) < 6 or "sparse_topk" not in m:
        return None
    least = mean_least_ms(
        [[(*sparse_decode(s[5], s[2], m["heads"], m["kv_heads"],
                          m["head_dim"], m["kv_itemsize"]), m["layers"])]
         for s in steps], run["peak"])
    return None if least is None else 100.0 * least / ms
