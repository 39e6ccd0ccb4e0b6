"""Device ms a prefill program run in the routed experts: the operations
scoped `layer<i>/moe_experts` (sort by expert, unsort and combine) and
the chip's grouped-matmul kernels of the three projections
(`lm_trace.moe_prefill_ms`)."""
from lm_trace import moe_prefill_ms


def read(run):
    return moe_prefill_ms(run)
