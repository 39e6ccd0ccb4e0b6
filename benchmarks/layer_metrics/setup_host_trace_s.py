"""Host seconds the process spent turning Python into programs before
the window: jax's trace (Python to jaxpr, outermost jits only) and
lowering (jaxpr to MLIR) durations, as the program's compile watcher
books them (`pt_compile_seconds_total{stage}`)."""
from program_trace import registry_sum


def read(run):
    return registry_sum("pt_compile_seconds_total", stage=("trace", "lower"))
