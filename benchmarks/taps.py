"""What the benchmark takes from the program and from jax's profiler,
in one place for every runner: the kernel dispatch counts, the profiler
started the one way, and spans written into its trace."""
from __future__ import annotations

import contextlib


def pallas_routes():
    """{kernel: {"pallas" | "fallback": count}} from the program's
    `pt_pallas_calls_total` (trace-time dispatch decisions)."""
    from paddle_tpu.observability.metrics import get_registry
    c = get_registry().counter("pt_pallas_calls_total",
                               labelnames=("kernel", "path"))
    routes = {}
    for (kernel, path), n in c.snapshot_values().items():
        routes.setdefault(kernel, {})[path] = int(n)
    return routes


def start_trace(trace_dir):
    """The device trace and the benchmark's spans; jax's tracer of
    Python calls is off, it slows the host it measures."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def span(name, on=True):
    """A span `bench:<name>` in the profiler's trace while `on`."""
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation("bench:" + name)
