"""Share of the attention square's (block_q, block_k) tiles that the
flash kernels compute, over the calls the program dispatched to them:
the program's `pt_flash_chunks_total{state="visited"}` over
`{state="total"}`, booked beside each `flash_mha` dispatch from the
call's static plan.  100 is the whole square; a causal call skips the
tiles above its diagonal."""
from program_trace import registry_sum


def read(run):
    visited = registry_sum("pt_flash_chunks_total", state="visited")
    total = registry_sum("pt_flash_chunks_total", state="total")
    if not total or visited is None:
        return None
    return 100.0 * visited / total
