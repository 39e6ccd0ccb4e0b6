"""The `paged_index_scores` kernel's share of its roofline over the decode
steps of the traced window: the least time to read every cached indexer
key of each row once and the rows' queries, and do the heads' dot
products (`costs_sparse.index_decode`, times the layers), over the device
time of the kernel's own events."""
from costs_lm import in_window, mean_least_ms
from costs_sparse import index_decode
from program_trace import named_ops_ms_per_run


def read(run):
    ms = named_ops_ms_per_run(run, r"paged_index_scores", "serve_decode")
    steps = in_window(run, "decode_rows")
    m = run.get("model") or {}
    if not ms or not steps or len(steps[0]) < 6 or "index_heads" not in m:
        return None
    least = mean_least_ms(
        [[(*index_decode(s[4], s[2], m["index_heads"], m["index_head_size"],
                         m["kv_itemsize"]), m["layers"])] for s in steps],
        run["peak"])
    return None if least is None else 100.0 * least / ms
