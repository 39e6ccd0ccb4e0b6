"""The paged-attention kernel's share of its roofline over the traced
window: the least time the chip could take for the decode steps that
ran there (costs.paged_decode from each step's rows and valid context,
times the layers), over the kernel's device time in those steps."""
import json
import os

from costs import least_seconds, paged_decode

HERE = os.path.dirname(os.path.abspath(__file__))


def read(run):
    tr, peak = run["trace"], run["peak"]
    if tr is None or peak is None or not run.get("trace_window"):
        return None
    spec = json.load(open(os.path.join(HERE, "paged_attn_ms_per_step.json")))
    per_run, runs = tr.per_run(spec)
    if not per_run:
        return None
    a, b = run["trace_window"]
    m = run["model"]
    steps = [(n, c) for t0, t1, n, c in run["decode_rows"] if a <= t0 and t1 <= b]
    if not steps:
        return None
    least = sum(least_seconds(*paged_decode(c, n, m["heads"], m["head_dim"],
                                            m["kv_itemsize"]), peak)[0]
                for n, c in steps) * m["layers"] / len(steps)
    return 100.0 * least / per_run
