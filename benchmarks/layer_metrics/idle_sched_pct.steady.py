"""Share of the traced window in which the device was idle while the
scheduler thread was in one of its own spans: `pt:serve.wait`,
`.evict`, `.admit`, `.book`."""
from program_trace import SCHEDULER_SPANS, idle_pct


def read(run):
    return idle_pct(run, SCHEDULER_SPANS)
