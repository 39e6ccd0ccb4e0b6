"""Share of the cached keys the decode steps' sparse layers scored that
they then attended over: 100 x the program's counter
`sparse_tokens_selected` (sum of min(context, topk) a row, a layer, a
step) over `sparse_tokens_scored` (sum of the contexts), over the window."""


def read(run):
    c = run.get("counters") or {}
    if not c.get("sparse_tokens_scored"):
        return None
    return 100.0 * c["sparse_tokens_selected"] / c["sparse_tokens_scored"]
