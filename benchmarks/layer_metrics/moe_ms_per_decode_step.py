"""Device ms a decode step in the routed experts: the operations the
program scopes `layer<i>/moe_experts` (the three projections of every
expert and the combine; not the router, which is `moe_route`)."""
from program_trace import scoped_ms_per_run


def read(run):
    return scoped_ms_per_run(run, r"/layer\d+/moe_experts/", "serve_decode")
