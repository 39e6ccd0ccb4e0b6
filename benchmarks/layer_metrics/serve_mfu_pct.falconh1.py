"""Share of the chip's bf16 peak that the window's tokens needed
(`costs_ssd.serve_flops`): 2 FLOPs times the parameters a position
multiplies against — every layer's attention projections, Mamba-2 mixer
and SwiGLU for every prompt position and decoded row, the own head once a
prompt and once a decoded row — times the program's counters
`prefill_tokens`, `admitted` and `decode_tokens`, over the window's
seconds and the peak.  Attention's and the recurrence's own arithmetic is
not in it: the share of the whole step that bounds later claims in this
cell.  None for a model without Mamba-2 layers."""
from costs_ssd import serve_flops


def read(run):
    c, peak, m = run.get("counters") or {}, run.get("peak"), run.get("model")
    if peak is None or "prefill_tokens" not in c or not run.get("seconds") \
            or not (m or {}).get("ssm_heads"):
        return None
    flops = serve_flops(m, c["prefill_tokens"], c["admitted"],
                        c["decode_tokens"])
    return 100.0 * flops / (
        run["seconds"] * peak["flops_bf16"] * run.get("chips", 1))
