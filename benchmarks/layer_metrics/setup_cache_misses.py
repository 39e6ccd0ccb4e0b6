"""Programs the persistent compile cache did not have
(`pt_compile_cache_total{result=miss}`): each was compiled anew."""
from program_trace import registry_sum


def read(run):
    return registry_sum("pt_compile_cache_total", result="miss")
