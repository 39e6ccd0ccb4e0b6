"""The experts' share of their roofline in the decode steps of the traced
window: the least time for each step's rows through their 8 experts with
the matrices of the experts the step touched read once
(`costs_lm.moe_decode`, times the layers), over `moe_ms_per_decode_step`.
How many experts a step touches is the window's mean from the program's
counter `moe_decode_experts_touched`."""
from costs_lm import in_window, mean_least_ms, moe_decode
from program_trace import scoped_ms_per_run


def read(run):
    ms = scoped_ms_per_run(run, r"/layer\d+/moe_experts/", "serve_decode")
    steps = in_window(run, "decode_rows")
    c, m = run.get("counters") or {}, run.get("model") or {}
    if not ms or not steps or not c.get("occupancy_steps") \
            or "moe_decode_experts_touched" not in c:
        return None
    touched = c["moe_decode_experts_touched"] / (
        c["occupancy_steps"] * m["layers"])
    least = mean_least_ms(
        [[(*moe_decode(s[2], touched, m["hidden"], m["expert_width"],
                       m["experts_per_token"], m["weight_itemsize"]),
           m["layers"])] for s in steps], run["peak"])
    return None if least is None else 100.0 * least / ms
