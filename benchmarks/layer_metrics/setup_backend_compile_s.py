"""Host seconds the process spent in XLA's compiler or reading its
results back from the persistent cache
(`pt_compile_seconds_total{stage=backend_compile|cache_load}`)."""
from program_trace import registry_sum


def read(run):
    return registry_sum("pt_compile_seconds_total",
                        stage=("backend_compile", "cache_load"))
