"""The Mamba-2 mixers' share of their roofline over the prefill runs of
the traced window: the least time for the projections, the convolution
and the chunked form's matmuls over each prompt's true length, the
matrices read once (`costs_ssd.ssd_prefill`, times the state layers),
over `ssm_ms_per_prefill`'s scopes (`layer<i>/ssm`, `layer<i>/state_write`
of the prefill programs).  None for a model without Mamba-2 layers."""
from costs_lm import in_window, mean_least_ms
from costs_ssd import ssd_prefill
from program_trace import scoped_ms_per_run


def read(run):
    m = run.get("model") or {}
    if not m.get("ssm_heads"):
        return None
    ms = scoped_ms_per_run(run, r"/layer\d+/(ssm|state_write)/",
                           "serve_prefill")
    runs = in_window(run, "prefill_rows")
    if not ms or not runs:
        return None
    least = mean_least_ms([[(*ssd_prefill(r[2], m), m["state_layers"])]
                           for r in runs], run["peak"])
    return None if least is None else 100.0 * least / ms
