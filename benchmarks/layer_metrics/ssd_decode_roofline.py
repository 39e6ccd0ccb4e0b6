"""The Mamba-2 mixers' share of their roofline in the decode steps of the
traced window: the least time for each step's rows through the mixer's
matrices read once, each row's float32 state read and written and its
convolution tail (`costs_ssd.ssd_decode`, times the state layers), over
`ssm_ms_per_decode_step`'s scopes (`layer<i>/ssm`, `layer<i>/state_write`).
None for a model without Mamba-2 layers."""
from costs_lm import in_window, mean_least_ms
from costs_ssd import ssd_decode
from program_trace import scoped_ms_per_run


def read(run):
    m = run.get("model") or {}
    if not m.get("ssm_heads"):
        return None
    ms = scoped_ms_per_run(run, r"/layer\d+/(ssm|state_write)/",
                           "serve_decode")
    steps = in_window(run, "decode_rows")
    if not ms or not steps:
        return None
    least = mean_least_ms([[(*ssd_decode(s[2], m), m["state_layers"])]
                           for s in steps], run["peak"])
    return None if least is None else 100.0 * least / ms
