"""The experts' share of their roofline in the prefills of the traced
window: the least time for each prompt's true length through 8 experts a
token with every expert's matrices read once (`costs_lm.moe_prefill`,
times the layers), over `moe_ms_per_prefill`."""
from costs_lm import in_window, mean_least_ms, moe_prefill
from lm_trace import moe_prefill_ms


def read(run):
    ms = moe_prefill_ms(run)
    prefills = in_window(run, "prefill_rows")
    m = run.get("model") or {}
    if not ms or not prefills:
        return None
    least = mean_least_ms(
        [[(*moe_prefill(p[2], m["experts"], m["hidden"], m["expert_width"],
                        m["experts_per_token"], m["weight_itemsize"]),
           m["layers"])] for p in prefills], run["peak"])
    return None if least is None else 100.0 * least / ms
