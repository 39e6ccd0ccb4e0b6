"""The flash-attention kernels' share of their roofline in a train
step: the least time for forward and backward attention of every layer
(costs.flash_fwd_bwd; bf16, causal) over the kernels' device time a
step.  Compute bound at sequence 1024 and head size 64."""
import json
import os

from costs import flash_fwd_bwd, least_seconds

HERE = os.path.dirname(os.path.abspath(__file__))


def read(run):
    tr, peak = run["trace"], run["peak"]
    if tr is None or peak is None:
        return None
    spec = json.load(open(os.path.join(HERE, "flash_ms_per_step.json")))
    per_run, _ = tr.per_run(spec)
    if not per_run:
        return None
    m = run["model"]
    flops, nbytes = flash_fwd_bwd(m["batch"], m["heads"], m["seq"],
                                  m["head_dim"], 2)
    return 100.0 * m["layers"] * least_seconds(flops, nbytes, peak)[0] / per_run
