"""Share in % of the window's timed decode steps (those not read behind
a prefill) whose period ran over 1.5 x the scheduler's moving average of
all periods as it stood before them (`steps_slow` over `steps_timed`):
the tail a median hides (the backlog cells). Whatever made the step
slow counts: a stall, and the first step of a batch, launched from the
host's tokens, where launch, program and the token's way back come to
that much (the steady cell's 2 ms program: seven steps in a hundred). The
average holds the periods read behind a prefill too, so a stall in the
half dozen steps after a long prompt is not counted."""
from step_trace import per


def read(run):
    return per(run, "steps_slow", "steps_timed", 100.0)
