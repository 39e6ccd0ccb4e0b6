"""Of the device time the serve programs' runs took in the traced window,
the share in % that went to `serve_prefill` runs (the `XLA Modules`
line, runs cut to the window): the split of the chip between prompts and
tokens."""
from step_trace import prefill_device_share_pct


def read(run):
    return prefill_device_share_pct(run)
