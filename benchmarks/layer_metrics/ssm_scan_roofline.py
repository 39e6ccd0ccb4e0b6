"""The `ssm_scan` kernel's share of its roofline over the prefill runs of
the traced window: the least time to read delta, u, B and C of every
position of the bucket and write y (float32), and do the recurrence (6
FLOPs a state element a position) — `costs_hybrid.ssm_scan`, times the
state-space layers — over the device time of the kernel's own events."""
from costs_hybrid import ssm_scan
from costs_lm import in_window, mean_least_ms
from program_trace import named_ops_ms_per_run


def read(run):
    ms = named_ops_ms_per_run(run, r"^pallas:\w*ssm_scan", "serve_prefill")
    runs = in_window(run, "prefill_rows")
    m = run.get("model") or {}
    if not ms or not runs or "ssm_inner" not in m:
        return None
    layers = sum(1 for k in m["layer_types"] if k == "ssm")
    least = mean_least_ms([[(*ssm_scan(r[2], m), layers)] for r in runs],
                          run["peak"])
    return None if least is None else 100.0 * least / ms
