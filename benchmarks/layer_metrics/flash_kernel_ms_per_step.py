"""Device ms a train step in the flash-attention kernels, told by the
names the program gives its `pallas_call`s (`flash_fwd`, `flash_bwd_dq`,
`flash_bwd_dkv`, ...) and not by an operand shape as `flash_ms_per_step`
must.  A kernel's name is its Mosaic call's instruction name; jax puts
the transformation it was traced under around it, so the forward kernel
of a differentiated step is `jvp_flash_fwd_.47` (my chip run, PR 25)."""
from program_trace import named_ops_ms_per_run


def read(run):
    return named_ops_ms_per_run(run, r"^pallas:\w*flash_", "captured_step")
