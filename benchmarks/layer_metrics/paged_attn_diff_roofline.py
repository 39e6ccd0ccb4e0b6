"""Differential paged attention's share of its roofline over the decode
steps of the traced window: the least time to read K and V of what each
row has valid and inside the layer's window once a KV pair, q in and A_h
out, and do QK^T and PV for every query head (`costs_hybrid.
paged_decode_diff`: the full layer and the cross layers that read its
pool on every position, the sliding layers on at most the window), over
the device time of the operations scoped `attn_global`, `attn_cross` and
`attn_window`."""
from costs_hybrid import paged_decode_diff
from costs_lm import in_window, mean_least_ms
from program_trace import scoped_ms_per_run


def read(run):
    ms = scoped_ms_per_run(run, r"/layer\d+/attn_(global|cross|window)/",
                           "serve_decode")
    steps = in_window(run, "decode_rows")
    m = run.get("model") or {}
    if not ms or not steps or len(steps[0]) < 6 or "ssm_inner" not in m:
        return None
    kinds = m["layer_types"]
    whole = sum(1 for k in kinds if k in ("full", "cross"))
    sliding = sum(1 for k in kinds if k == "sliding")

    def cost(seen, rows):
        return paged_decode_diff(seen, rows, m["heads"], m["kv_heads"],
                                 m["head_dim"], m["kv_itemsize"])

    least = mean_least_ms(
        [[(*cost(s[4], s[2]), whole), (*cost(s[5], s[2]), sliding)]
         for s in steps], run["peak"])
    return None if least is None else 100.0 * least / ms
