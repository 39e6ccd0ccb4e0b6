"""Share of the traced window in which the device was idle while the
scheduler thread was inside an engine call: `pt:serve.prefill.*` and
`pt:serve.decode.*` (padding, the launch until it returns, the fetch)."""
from program_trace import ENGINE_SPANS, idle_pct


def read(run):
    return idle_pct(run, ENGINE_SPANS)
