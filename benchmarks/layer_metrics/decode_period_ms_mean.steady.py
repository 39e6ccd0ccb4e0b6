"""Mean period of a decode step as the program books it, over the whole
window (the steady cell: a period holds no wait for an arrival): 1e3 x the
scheduler's `step_period_s` (a step's launch, or the read before it
where it was launched ahead, to its tokens on the host) over
`steps_timed`.  A step whose read waited behind a prefill is in neither:
its period on the host's clock holds the prefill.  None where the
program keeps no step log."""
from step_trace import per


def read(run):
    return per(run, "step_period_s", "steps_timed", 1e3)
