"""Share of the chip's bf16 peak that the window's tokens needed
(`costs_hybrid.serve_flops`): 2 FLOPs times the parameters a position
multiplies against — a prompt position the layers before the last full
one and that layer's K and V projections, a prompt once that layer's
rest, the gated memory units, the cross layers and the tied head, a
decoded row everything — times the program's counters `prefill_tokens`,
`admitted` and `decode_tokens`, over the window's seconds and the peak.
Attention's and the recurrence's own arithmetic is not in it: the share
of the whole step that bounds later claims in this cell."""
from costs_hybrid import serve_flops


def read(run):
    c, peak, m = run.get("counters") or {}, run.get("peak"), run.get("model")
    if peak is None or "prefill_tokens" not in c or not run.get("seconds") \
            or "ssm_inner" not in (m or {}):
        return None
    flops = serve_flops(m, c["prefill_tokens"], c["admitted"],
                        c["decode_tokens"])
    return 100.0 * flops / (
        run["seconds"] * peak["flops_bf16"] * run.get("chips", 1))
