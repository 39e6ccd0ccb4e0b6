"""The state-space mixers' share of their roofline in the decode steps of
the traced window: the least time for each step's rows through the
layer's matrices read once, the rows' state read and written in float32
and their convolution tails (`costs_hybrid.ssm_decode`, times the
state-space layers), over `ssm_ms_per_decode_step`."""
from costs_hybrid import ssm_decode
from costs_lm import in_window, mean_least_ms
from program_trace import scoped_ms_per_run


def read(run):
    ms = scoped_ms_per_run(run, r"/layer\d+/(ssm|state_write)/",
                           "serve_decode")
    steps = in_window(run, "decode_rows")
    m = run.get("model") or {}
    if not ms or not steps or "ssm_inner" not in m:
        return None
    layers = sum(1 for k in m["layer_types"] if k == "ssm")
    least = mean_least_ms([[(*ssm_decode(s[2], m), layers)] for s in steps],
                          run["peak"])
    return None if least is None else 100.0 * least / ms
