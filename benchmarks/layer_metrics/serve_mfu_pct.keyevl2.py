"""Share of the chip's bf16 peak that the window's work needed
(`costs_sparse.serve_flops`): every prompt prefilled in the window and
every decode step in it, 2 FLOPs a parameter a position multiplies
against (projections, indexer, router, 8 experts a layer), the indexer's
scores over each position's context, attention over min(context, topk),
and the head once a prompt and once a decoded row; prompts' lengths and
the steps' rows and contexts from the runner's tap, over the window's
seconds and the peak.  The share of the whole step that bounds later
claims in this cell."""
from costs_sparse import serve_flops


def read(run):
    peak, m = run.get("peak"), run.get("model") or {}
    if peak is None or not run.get("seconds") or "sparse_topk" not in m \
            or "t_window" not in run or not run.get("decode_rows") \
            or len(run["decode_rows"][0]) < 6:
        return None
    t0, t1 = run["t_window"], run["t_window"] + run["seconds"]
    flops = serve_flops(
        m, [p[2] for p in run.get("prefill_rows") or [] if t0 <= p[0] <= t1],
        [(s[2], s[4], s[5]) for s in run["decode_rows"] if t0 <= s[0] <= t1])
    return 100.0 * flops / (
        run["seconds"] * peak["flops_bf16"] * run.get("chips", 1))
