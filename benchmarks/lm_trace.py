"""Device time of the routed experts in a prefill program, for the two
readers that share it (`moe_ms_per_prefill`, `moe_prefill_roofline`).

The operations the program scopes `layer<i>/moe_experts` (the Pallas
grouped matmuls `moe_gmm` are among them) and, counted once each, any
`ragged-dot` kernel: where the program falls back to `jax.lax.ragged_dot`
the TPU compiler expands it into a custom call of its own that does not
keep the scope of the instruction it came from (my chip run, PR 27: the
scoped time alone read 120 % of the roofline), and nothing else in these
programs is a ragged dot."""
from __future__ import annotations

import re

from program_trace import op_scopes, scoped_events

SCOPE = re.compile(r"/layer\d+/moe_experts/")
KERNEL = re.compile(r"ragged-dot")


def moe_prefill_ms(run):
    """Device ms a run of a `serve_prefill` program in the traced window;
    None where the trace holds no such operation."""
    scopes = op_scopes(run)
    if not scopes:
        return None
    events, runs = scoped_events(run["trace"], scopes, "serve_prefill")
    hit = [ev[2] for scope, ev in events
           if SCOPE.search(scope) or KERNEL.search(ev[0])]
    if not runs or not hit:
        return None
    return sum(hit) / 1e6 / runs
