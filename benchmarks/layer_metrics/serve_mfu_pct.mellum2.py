"""Share of the chip's bf16 peak that the window's tokens needed
(`costs_lm.serve_flops`): 2 FLOPs times the layers' parameters a token
multiplies against (projections, router, 8 experts a layer) times the
prompt positions prefilled and the rows decoded in the window, plus the
head once a prompt (the prefill program multiplies its last row only)
and once a decoded row; the program's counters `prefill_tokens`,
`admitted` and `decode_tokens`, over the window's seconds and the peak.
Attention's own arithmetic is not in it: the share of the whole step
that bounds later claims in this cell."""
from costs_lm import serve_flops


def read(run):
    c, peak = run.get("counters") or {}, run.get("peak")
    if peak is None or "prefill_tokens" not in c or not run.get("seconds"):
        return None
    flops = serve_flops(run["model"], c["prefill_tokens"], c["admitted"],
                        c["decode_tokens"])
    return 100.0 * flops / (
        run["seconds"] * peak["flops_bf16"] * run.get("chips", 1))
