"""The grouped, windowed paged-attention kernel's share of its roofline
over the decode steps of the traced window: the least time to read K and
V of what each row has valid and inside the layer's window, once a KV
head, and do QK^T and PV for every query head (`costs_lm.
paged_decode_gqa`, the full layers on every position, the sliding layers
on at most the window), over the device time of the operations scoped
`attn_global` and `attn_window`."""
from costs_lm import in_window, mean_least_ms, paged_decode_gqa
from program_trace import scoped_ms_per_run


def read(run):
    ms = scoped_ms_per_run(run, r"/layer\d+/attn_(global|window)/",
                           "serve_decode")
    steps = in_window(run, "decode_rows")
    m = run.get("model") or {}
    if not ms or not steps or len(steps[0]) < 6:
        return None
    sliding = sum(1 for t in m["layer_types"] if t == "sliding")

    def cost(seen, rows):
        return paged_decode_gqa(seen, rows, m["heads"], m["kv_heads"],
                                m["head_dim"], m["kv_itemsize"])

    least = mean_least_ms(
        [[(*cost(s[4], s[2]), m["layers"] - sliding),
          (*cost(s[5], s[2]), sliding)] for s in steps], run["peak"])
    return None if least is None else 100.0 * least / ms
