"""Median device ms of a run of a `serve_decode` program in the traced
window, from the `XLA Modules` line (the backlog cells). The mean period
less this is what the host adds a step."""
from step_trace import device_ms_p50


def read(run):
    return device_ms_p50(run, "decode")
