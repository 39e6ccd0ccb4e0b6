"""Step-phase tracer tests: ring buffer, phase spans, overlap math,
Chrome export + cluster merge, analytic MFU, and the flight recorder.

Everything here follows the telemetry contract: disabled hooks are
no-ops, enable is explicit (or env-driven through ``get_tracer()``),
and nothing ever syncs the device or raises off the hot path.  The
multi-process half (per-rank exports stitched across real workers,
flight-on-SIGKILL) lives in ``tests/drills/test_trace_drills.py``.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np
import pytest

import paddle_tpu.observability as obs
from paddle_tpu.observability.merge import (
    discover_trace_files, merge_traces,
)
from paddle_tpu.observability.trace import (
    PEAK_FLOPS, PHASES, Tracer, current_tracer, get_tracer, peak_flops,
    program_flops, reset_tracer,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _isolated(monkeypatch):
    # env must never leak enablement into (or out of) a test
    for var in ("PT_TELEMETRY", "PT_TELEMETRY_DIR", "PT_METRICS_PORT",
                "PT_RECOMPILE_THRESHOLD", "PT_PROCESS_INDEX", "PT_RUN_ID",
                "PADDLE_TRAINER_ID", "PT_TRACE", "PT_TRACE_DIR",
                "PT_FLIGHT_RECORDER"):
        monkeypatch.delenv(var, raising=False)
    obs.reset()
    yield
    obs.reset()


# -- lifecycle / env enablement ---------------------------------------------

def test_singleton_disabled_by_default_and_hooks_noop(tmp_path):
    tr = get_tracer()
    assert tr.enabled is False
    assert current_tracer() is tr
    # every hook is a no-op while disabled
    with tr.phase("backward"):
        pass
    tr.phase_record("backward", 0, 10)
    tr.record_span("x", "compute", 0, 10)
    tr.on_step(0.1)
    assert tr.spans() == []
    assert tr.flight_dump() is None
    snap = tr.snapshot()
    assert snap["enabled"] is False
    assert snap["spans"] == 0


def test_env_pt_trace_auto_enables(monkeypatch, tmp_path):
    monkeypatch.setenv("PT_TRACE", "1")
    monkeypatch.setenv("PT_TRACE_DIR", str(tmp_path))
    tr = get_tracer()
    assert tr.enabled
    assert tr.trace_dir == str(tmp_path)
    assert tr.flight_path is None


def test_env_flight_recorder_implies_enable_and_arms(monkeypatch, tmp_path):
    flight = tmp_path / "flight"
    monkeypatch.setenv("PT_FLIGHT_RECORDER", str(flight))
    tr = get_tracer()
    assert tr.enabled
    assert tr.flight_path is not None
    # arming dumps immediately: a SIGKILL can land before the first
    # watchdog refresh and must still find a parseable file
    with open(tr.flight_path) as f:
        doc = json.load(f)
    assert doc["reason"] == "armed"
    assert doc["process_index"] == tr.process_index
    assert doc["run_id"] == tr.run_id


def test_enable_idempotent_and_identity_override(tmp_path):
    tr = Tracer()
    tr.enable(process_index=3, run_id="r9", trace_dir=str(tmp_path))
    tr.enable()  # second enable must not reset anything
    assert tr.process_index == 3 and tr.run_id == "r9"
    assert tr.default_trace_path().endswith("trace-r9-3.json")


# -- ring buffer + phase spans -----------------------------------------------

def test_ring_buffer_bounded_keeps_newest():
    tr = Tracer(capacity=8).enable()
    for i in range(20):
        tr.record_span(f"s{i}", "host", i, i + 1)
    spans = tr.spans()
    assert len(spans) == 8
    assert [s.name for s in spans] == [f"s{i}" for i in range(12, 20)]


def test_phase_ctx_manager_records_span_and_histogram():
    tr = Tracer().enable()
    with tr.phase("backward"):
        pass
    spans = tr.spans()
    assert len(spans) == 1
    assert spans[0].name == "backward" and spans[0].cat == "compute"
    assert spans[0].t1_ns >= spans[0].t0_ns
    assert "backward" in tr.phase_percentiles_ms()


def test_phase_span_skipped_inside_jax_trace():
    import jax

    tr = Tracer().enable()

    @jax.jit
    def f(x):
        with tr.phase("forward"):
            return x + 1

    f(np.ones(2, np.float32))
    # the trace ran the body, but wall-timing a tracer is meaningless:
    # no span may land
    assert tr.spans() == []


def test_phase_taxonomy_categories():
    tr = Tracer().enable()
    for p in PHASES:
        tr.phase_record(p, 0, 10)
    cats = {s.name: s.cat for s in tr.spans()}
    assert cats["forward"] == cats["backward"] == cats["optimizer"] \
        == "compute"
    assert cats["collective"] == "collective"
    assert cats["data_wait"] == cats["checkpoint"] == "host"


# -- overlap fraction --------------------------------------------------------

def test_overlap_fraction_math():
    tr = Tracer().enable()
    tr.record_span("bwd", "compute", 0, 100)
    tr.record_span("ar", "collective", 50, 150)
    assert tr.overlap_fraction() == pytest.approx(0.5)


def test_overlap_fraction_none_without_collectives():
    tr = Tracer().enable()
    tr.record_span("bwd", "compute", 0, 100)
    assert tr.overlap_fraction() is None


def test_overlap_fraction_merges_compute_and_caps_at_one():
    tr = Tracer().enable()
    # two overlapping compute spans must merge, not double-count
    tr.record_span("a", "compute", 0, 80)
    tr.record_span("b", "compute", 40, 120)
    tr.record_span("ar", "collective", 0, 100)
    assert tr.overlap_fraction() == pytest.approx(1.0)


# -- Chrome export + cluster merge -------------------------------------------

def test_export_chrome_without_path_raises():
    tr = Tracer().enable()
    with pytest.raises(ValueError):
        tr.export_chrome()


def test_chrome_export_roundtrips_through_merge(tmp_path):
    """Two standalone rank tracers export; ``merge --trace`` semantics
    stitch them into one timeline with pid = rank and a single
    process_name metadata event per rank."""
    trace_dir = str(tmp_path)
    for rank in (0, 1):
        tr = Tracer().enable(trace_dir=trace_dir, process_index=rank,
                             run_id="mergetest")
        tr.record_span("backward", "compute", 1000, 2000)
        tr.record_span("all_reduce", "collective", 1500, 2500)
        out = tr.export_chrome()
        assert out == os.path.join(trace_dir,
                                   f"trace-mergetest-{rank}.json")
    # a corrupt file must be skipped, never fatal
    with open(os.path.join(trace_dir, "trace-mergetest-2.json"), "w") as f:
        f.write("{not json")
    files = discover_trace_files([trace_dir])
    assert len(files) == 3
    doc, skipped = merge_traces(files)
    assert skipped == 1
    evs = doc["traceEvents"]
    meta = [e for e in evs if e.get("ph") == "M"]
    xs = [e for e in evs if e.get("ph") == "X"]
    assert {m["pid"] for m in meta} == {0, 1}
    assert {e["pid"] for e in xs} == {0, 1}
    assert len(xs) == 4
    ts = [e["ts"] for e in xs]
    assert ts == sorted(ts)
    for e in xs:
        assert set(e) >= {"name", "cat", "ts", "dur", "pid", "tid"}
        assert e["args"]["run_id"] == "mergetest"


def test_counter_events_export_as_chrome_counter_track():
    """record_counter lands ph:"C" events (the memory watermark track)
    carrying the series values in args, on the rank's pid."""
    tr = Tracer().enable(process_index=5)
    tr.record_counter("device_memory", 1_000_000,
                      {"bytes_in_use": 1024.0, "fragmentation": 64.0})
    tr.record_counter("device_memory", 2_000_000,
                      {"bytes_in_use": 2048.0, "fragmentation": 0.0})
    assert tr.snapshot()["counters"] == 2
    cs = [e for e in tr.chrome_events() if e["ph"] == "C"]
    assert len(cs) == 2
    for e in cs:
        assert e["name"] == "device_memory"
        assert e["pid"] == 5
        assert set(e["args"]) == {"bytes_in_use", "fragmentation"}
    assert cs[0]["ts"] < cs[1]["ts"]
    assert cs[1]["args"]["bytes_in_use"] == 2048.0
    # counters ride the ring-buffer clear like spans (the process_name
    # meta event survives by design)
    tr.clear()
    assert tr.counters() == []
    assert [e for e in tr.chrome_events() if e["ph"] != "M"] == []


def test_counter_hooks_noop_while_disabled():
    tr = Tracer()
    tr.record_counter("device_memory", 0, {"bytes_in_use": 1.0})
    assert tr.counters() == []


def test_merge_trace_stitches_counter_tracks_per_rank(tmp_path):
    """``merge --trace`` with counter events interleaved among duration
    spans: every rank's C events keep their pid (per-rank track
    identity), the merged stream stays ts-ordered across BOTH event
    kinds, and a corrupt per-rank file is skipped, never fatal."""
    trace_dir = str(tmp_path)
    for rank in (0, 1):
        tr = Tracer().enable(trace_dir=trace_dir, process_index=rank,
                             run_id="memtrack")
        # counters interleave INSIDE the span window on purpose
        tr.record_span("backward", "compute", 1000, 5000)
        tr.record_counter("device_memory", 2000,
                          {"bytes_in_use": float(100 * (rank + 1))})
        tr.record_counter("device_memory", 4000,
                          {"bytes_in_use": float(200 * (rank + 1))})
        tr.record_span("optimizer", "compute", 5000, 6000)
        assert tr.export_chrome() is not None
    with open(os.path.join(trace_dir, "trace-memtrack-7.json"),
              "w") as f:
        f.write("{torn")
    files = discover_trace_files([trace_dir])
    assert len(files) == 3
    doc, skipped = merge_traces(files)
    assert skipped == 1
    evs = [e for e in doc["traceEvents"] if e.get("ph") != "M"]
    cs = [e for e in evs if e["ph"] == "C"]
    xs = [e for e in evs if e["ph"] == "X"]
    assert len(cs) == 4 and len(xs) == 4
    # per-rank track identity: each rank's counter series survives on
    # its own pid with its own values
    for rank in (0, 1):
        mine = [e["args"]["bytes_in_use"] for e in cs
                if e["pid"] == rank]
        assert mine == [100.0 * (rank + 1), 200.0 * (rank + 1)]
    # one ts-ordered stream across spans AND counters
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts)
    # and the counters really interleave among the duration events
    kinds = [e["ph"] for e in sorted(evs, key=lambda e: e["ts"])
             if e["pid"] == 0]
    assert kinds.index("C") > 0 and "X" in kinds[kinds.index("C"):]


# -- analytic MFU ------------------------------------------------------------

def test_peak_flops_prefix_matching():
    assert peak_flops("TPU v5 lite podslice") == PEAK_FLOPS["TPU v5 lite"]
    assert peak_flops("TPU v4") == PEAK_FLOPS["TPU v4"]
    assert peak_flops("cpu") == PEAK_FLOPS["cpu"]
    assert peak_flops("Banana9000") is None
    assert peak_flops(None) is None
    # measurement paths ask strictly: an unknown device is an error
    with pytest.raises(KeyError, match="Banana9000"):
        peak_flops("Banana9000", strict=True)


def test_program_flops_and_mfu_on_cpu_jit():
    import jax
    import jax.numpy as jnp

    x = jnp.ones((64, 64), jnp.float32)
    jitted = jax.jit(lambda a: a @ a)
    flops = program_flops(jitted, x)
    assert flops and flops > 0
    tr = Tracer().enable()
    tr.record_program_flops("matmul", flops)
    assert tr.flops_per_step() == flops
    # backend is initialized by the lowering above, so device_kind is
    # the cpu backend's and the nominal cpu peak applies
    mfu = tr.mfu_analytic(step_seconds=0.01)
    assert mfu == pytest.approx(flops / (0.01 * PEAK_FLOPS["cpu"]))


def test_mfu_none_when_factors_missing():
    tr = Tracer().enable()
    assert tr.mfu_analytic(step_seconds=0.01) is None  # no flops
    tr.record_program_flops("p", 1e9)
    assert tr.mfu_analytic() is None  # no step time yet


def test_on_step_refreshes_overlap_and_mfu():
    tr = Tracer().enable()
    tr.record_span("bwd", "compute", 0, 100)
    tr.record_span("ar", "collective", 50, 150)
    tr.record_program_flops("p", 1e9)
    tr.on_step(0.25)
    assert tr._last_step_seconds == 0.25
    assert tr._last_overlap == pytest.approx(0.5)
    snap = tr.snapshot()
    assert snap["overlap_fraction"] == pytest.approx(0.5)
    assert snap["flops_per_step"] == 1e9


# -- flight recorder ---------------------------------------------------------

def test_flight_dump_document(tmp_path):
    tr = Tracer().enable(flight_dir=str(tmp_path), process_index=2,
                         run_id="fr")
    tr.record_span("bwd", "compute", 0, 100)
    path = tr.flight_dump(reason="manual")
    assert path == str(tmp_path / "flight-fr-2.json")
    with open(path) as f:
        doc = json.load(f)
    assert doc["reason"] == "manual"
    assert doc["process_index"] == 2 and doc["run_id"] == "fr"
    assert {"ts", "pid", "last_step_seconds", "overlap_fraction",
            "mfu_analytic", "program_flops", "spans",
            "telemetry"} <= set(doc)
    assert doc["spans"][-1]["name"] == "bwd"


def test_flight_watchdog_refreshes_from_hot_path(tmp_path):
    import time

    tr = Tracer().enable(flight_dir=str(tmp_path))
    tr._flight_last_ns = 0  # force the cadence check to fire
    now = time.perf_counter_ns()
    tr.phase_record("backward", now - 100, now)
    with open(tr.flight_path) as f:
        doc = json.load(f)
    assert doc["reason"] == "watchdog"
    assert tr._flight_last_ns > 0


def test_excepthook_dumps_then_chains(tmp_path, monkeypatch):
    seen = []
    monkeypatch.setattr(sys, "excepthook",
                        lambda *a: seen.append(a))
    tr = Tracer().enable(flight_dir=str(tmp_path))
    assert sys.excepthook == tr._excepthook
    err = ValueError("boom")
    sys.excepthook(ValueError, err, None)
    with open(tr.flight_path) as f:
        assert json.load(f)["reason"] == "crash:ValueError"
    assert seen and seen[0][1] is err  # previous hook still ran
    tr.disable()
    assert sys.excepthook is not tr._excepthook  # restored


def test_flight_dump_never_raises_on_bad_dir(tmp_path):
    target = tmp_path / "blocked"
    target.write_text("a file, not a dir")
    tr = Tracer().enable()
    tr.flight_path = str(target / "flight-x-0.json")
    assert tr.flight_dump() is None
    assert tr.dropped == 1


# -- integration: RecordEvent / capture / hapi / telemetry -------------------

def test_record_event_feeds_tracer():
    from paddle_tpu.core import RecordEvent

    tr = get_tracer().enable()
    with RecordEvent("io_read"):
        pass
    spans = tr.spans()
    assert [s.name for s in spans] == ["io_read"]
    assert spans[0].cat == "host"


def test_capture_step_books_compile_and_compute_spans():
    import paddle_tpu as pt
    import paddle_tpu.nn as nn

    tr = get_tracer().enable()
    pt.seed(0)
    model = nn.Linear(4, 2)
    opt = pt.optimizer.SGD(learning_rate=0.1,
                           parameters=model.parameters())
    mse = nn.MSELoss()

    @pt.jit.capture_step
    def step(x, y):
        loss = mse(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    x = pt.to_tensor(np.random.randn(4, 4).astype(np.float32))
    y = pt.to_tensor(np.random.randn(4, 2).astype(np.float32))
    for _ in range(3):
        step(x, y)
    # the capture layer no longer lowers the step a second time to read
    # cost_analysis(): FLOPs come from whoever has them (bench.py)
    assert tr.flops_per_step() is None
    # the first call traces+compiles and is booked honestly as a
    # compile: host span (badput); the two replays are compute spans
    comp = [s for s in tr.spans() if s.cat == "compute"]
    assert len(comp) == 2
    compiles = [s for s in tr.spans()
                if s.cat == "host" and s.name.startswith("compile:")]
    assert len(compiles) == 1
    tr.record_program_flops(compiles[0].name[len("compile:"):], 1e6)
    assert tr.mfu_analytic(step_seconds=1.0) is not None


def test_hapi_fit_records_step_phases():
    import paddle_tpu as pt
    from paddle_tpu.vision.datasets import FakeData

    tr = get_tracer().enable()
    net = pt.nn.Sequential(pt.nn.Flatten(), pt.nn.Linear(3 * 8 * 8, 4))
    model = pt.Model(net)
    model.prepare(
        optimizer=pt.optimizer.SGD(learning_rate=0.01,
                                   parameters=net.parameters()),
        loss=pt.nn.CrossEntropyLoss())
    model.fit(FakeData(size=32, image_shape=(3, 8, 8), num_classes=4),
              epochs=1, batch_size=16, verbose=0)
    phases = set(tr.phase_percentiles_ms())
    assert {"backward", "optimizer"} <= phases


def test_collective_bytes_histogram():
    from paddle_tpu.observability import get_registry, get_telemetry

    tel = get_telemetry().enable()
    tel.collective_op("all_reduce", nbytes=4096)
    tel.collective_op("all_reduce", nbytes=8192)
    snap = get_registry().snapshot()
    hist = snap["pt_collective_bytes"]["series"]["op=all_reduce"]
    assert hist["count"] == 2
    assert hist["sum"] == 12288
    assert snap["pt_collective_bytes_total"]["series"]["op=all_reduce"] \
        == 12288
    text = get_registry().prometheus_text()
    assert "pt_collective_bytes_bucket" in text


def test_observe_step_feeds_tracer_gauges():
    from paddle_tpu.observability import get_telemetry

    tr = get_tracer().enable()
    tel = get_telemetry().enable()
    tr.record_span("bwd", "compute", 0, 100)
    tr.record_span("ar", "collective", 0, 100)
    tel.observe_step(0.125)
    assert tr._last_step_seconds == 0.125
    assert tr._last_overlap == pytest.approx(1.0)


def test_healthz_surfaces_flight_path(tmp_path):
    from paddle_tpu.observability import get_telemetry

    tr = get_tracer().enable(flight_dir=str(tmp_path))
    tel = get_telemetry().enable()
    doc = tel.healthz()
    assert doc["flight_recorder"] == tr.flight_path


# -- aggregator retention ----------------------------------------------------

def test_retention_buffer_evicts_and_downsamples():
    from paddle_tpu.observability.aggregator import RetentionBuffer

    buf = RetentionBuffer(retention=10.0, max_points=8)
    for t in range(12):
        buf.append(float(t), {"v": t})
    pts = buf.points()
    # ts=12-built window: points older than last-10s are gone, and the
    # cap forced at least one halving pass on the older half
    assert all(ts >= 11 - 10.0 for ts, _ in pts)
    assert len(pts) <= 8
    assert pts[-1][0] == 11.0
    assert buf.downsampled_total > 0
    s = buf.summary()
    assert s["retention_seconds"] == 10.0
    assert s["max_points"] == 8
    assert s["points"] == len(pts)
    assert s["downsampled_total"] == buf.downsampled_total
    assert s["span_seconds"] >= 0


def test_retention_buffer_keeps_recent_resolution():
    from paddle_tpu.observability.aggregator import RetentionBuffer

    buf = RetentionBuffer(retention=1e9, max_points=4)
    for t in range(8):
        buf.append(float(t), t)
    pts = buf.points()
    # the newest points always survive downsampling intact
    assert pts[-1] == (7.0, 7)
    assert pts[-2] == (6.0, 6)


# -- the span primitive, compile stages, kernel names (PR 25) ------------------

def test_span_measures_itself_and_feeds_the_ring_only_when_enabled():
    from paddle_tpu.observability.trace import current_tracer, span
    assert current_tracer() is None
    with span("serve.decode.launch", rows=3, bucket=8) as sp:
        time.sleep(0.01)
    assert 0.01 <= sp.seconds < 1.0
    assert current_tracer() is None      # a span never creates the tracer
    tr = get_tracer().enable()
    with span("serve.book"):
        pass
    with span("compile:step", cat="host"):
        pass
    assert [(s.name, s.cat) for s in tr.spans()] == [
        ("serve.book", "host"), ("compile:step", "host")]
    tr.disable()
    with span("serve.book"):
        pass
    assert len(tr.spans()) == 2


def test_span_lands_in_a_profiler_session_as_pt_event(tmp_path):
    import glob
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData
    from paddle_tpu.observability.trace import span
    jax.profiler.start_trace(str(tmp_path))
    with span("serve.prefill.launch", request_id=7):
        jnp.ones((4,)).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    found = [(ev.name, {str(k): str(v) for k, v in ev.stats})
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith("pt:")]
    assert found == [("pt:serve.prefill.launch", {"request_id": "7"})]


def test_compile_listeners_book_stage_durations_and_leave_on_disable():
    import jax
    import jax.numpy as jnp
    from jax._src import monitoring
    before = (len(monitoring.get_event_duration_listeners()),
              len(monitoring.get_event_listeners()),
              len(monitoring.get_scalar_listeners()))
    tel = obs.get_telemetry().enable()

    @jax.jit
    def inner(x):
        return jnp.tanh(x) * 2

    @jax.jit
    def outer(x):
        for _ in range(8):
            x = inner(x) + 1.0
        return x

    t0 = time.perf_counter()
    outer(jnp.ones((5,))).block_until_ready()
    wall = time.perf_counter() - t0
    series = obs.get_registry().snapshot()[
        "pt_compile_seconds_total"]["series"]
    assert series["stage=trace"] > 0 and series["stage=backend_compile"] > 0
    assert series["stage=lower"] > 0
    # a jit traced inside another's trace is inside the outer's duration:
    # booked once, so the stages cannot add up to more than the call took
    assert sum(series.values()) <= wall
    assert obs.get_registry().snapshot()[
        "pt_compile_cache_total"]["series"] == {}      # no cache in tests
    tel.disable()
    assert (len(monitoring.get_event_duration_listeners()),
            len(monitoring.get_event_listeners()),
            len(monitoring.get_scalar_listeners())) == before


def test_compile_cache_results_and_load_time_are_booked_apart():
    from paddle_tpu.observability.telemetry import CompileWatcher
    tel = obs.get_telemetry().enable(compile_watch=False)
    w = CompileWatcher(tel)
    w._on_event("/jax/compilation_cache/cache_misses")
    w._on_event("/jax/compilation_cache/cache_hits")
    w._on_event("/jax/compilation_cache/cache_hits")
    # jax reports a hit's load time inside the backend-compile event
    w._on_duration("/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
    w._on_duration("/jax/core/compile/backend_compile_duration", 0.75)
    w._on_duration("/jax/core/compile/backend_compile_duration", 2.0)
    w._on_duration("/jax/some/other/event", 9.0)
    snap = obs.get_registry().snapshot()
    assert snap["pt_compile_cache_total"]["series"] == {
        "result=hit": 2.0, "result=miss": 1.0}
    assert snap["pt_compile_seconds_total"]["series"] == {
        "stage=backend_compile": 2.5, "stage=cache_load": 0.25}


def test_every_pallas_call_names_its_kernel():
    """A Mosaic call's `name=` is its instruction's name in the compiled
    program and so in a profiler trace: without one a kernel is
    `jvp__.80` there (PERF.md §6).  Each site has one, no two alike."""
    import ast
    import paddle_tpu.ops as ops_pkg
    root = os.path.dirname(ops_pkg.__file__)
    names = []
    for fname in sorted(os.listdir(root)):
        if not fname.endswith(".py"):
            continue
        tree = ast.parse(open(os.path.join(root, fname)).read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and \
                    ast.unparse(node.func).endswith("pallas_call"):
                kw = {k.arg: k.value for k in node.keywords}
                assert "name" in kw, f"{fname}:{node.lineno} has no name="
                names.append((fname, kw["name"]))
    literal = [v.value for _, v in names if isinstance(v, ast.Constant)]
    assert len(names) >= 14 and len(set(literal)) == len(literal)
    assert {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "layer_norm_fwd",
            "layer_norm_bwd", "softmax_xent_fwd", "softmax_xent_bwd",
            "w8a16_matmul"} <= set(literal)
    from paddle_tpu.ops import paged_attention as pa
    src = open(pa.__file__).read()
    assert 'name="paged_attention"' in src
    assert 'name="paged_attention_int8"' in src


# -- the serving spans' launch numbers (PR 36) --------------------------------

class _Recorded:
    """Stands in for ``jax.profiler.TraceAnnotation``: keeps what each
    span would hand the profiler, in the order the spans open."""

    seen: list = []

    def __init__(self, name, **ids):
        self.seen.append((name, ids))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture()
def serve_spans(monkeypatch):
    """(engine, spans): a tiny ServingEngine, and every ``pt:serve.*``
    span the code under test opens as (name, arguments)."""
    from paddle_tpu.observability import trace as trace_mod
    from paddle_tpu.serving import (ModelSpec, ServeConfig, ServingEngine,
                                    init_params)
    spec = ModelSpec(vocab_size=64, hidden=32, layers=2, heads=2,
                     max_seq_len=64)
    cfg = ServeConfig(decode_buckets=(2, 4), prefill_buckets=(16,),
                      kv_pages=64, page_size=4, max_new_tokens=8)
    engine = ServingEngine(spec, init_params(spec, seed=0), cfg)
    _Recorded.seen = []
    monkeypatch.setattr(trace_mod, "_annotation", _Recorded)
    try:
        yield engine, _Recorded.seen
    finally:
        monkeypatch.undo()
        engine.close()


def _by_launch(spans, prefix):
    out = {}
    for name, ids in spans:
        if name.startswith("pt:serve." + prefix) and ids:
            out.setdefault(ids["launch"], []).append(
                name.rsplit(".", 1)[1])
    return out


def test_the_six_engine_spans_carry_the_number_of_their_launch(serve_spans):
    engine, spans = serve_spans
    sched = engine.scheduler
    n0 = engine.launches
    a = sched.submit([1, 2, 3], max_new_tokens=5)
    b = sched.submit([4, 5], max_new_tokens=5)
    sched.drain()
    assert len(a.result(timeout=5.0)) == len(b.result(timeout=5.0)) == 5
    # the engine's six; the scheduler's own half of `decode.prep` (tables
    # and batch, before the engine is called) has no argument
    engine_spans = [(n, ids) for n, ids in spans if ids and n.startswith(
        ("pt:serve.prefill.", "pt:serve.decode."))]
    assert {n for n, ids in spans if not ids} == {
        "pt:serve.wait", "pt:serve.evict", "pt:serve.admit", "pt:serve.book",
        "pt:serve.decode.prep"}
    assert all("launch" in ids for _, ids in engine_spans)
    # a prefill's three spans share its number, beside the request's id
    prefills = _by_launch(spans, "prefill.")
    assert prefills == {n0 + 1: ["prep", "launch", "fetch"],
                        n0 + 2: ["prep", "launch", "fetch"]}
    rid = {ids["launch"]: ids["request_id"] for n, ids in engine_spans
           if n.startswith("pt:serve.prefill.")}
    assert rid == {n0 + 1: a.request_id, n0 + 2: b.request_id}
    # a decode step's prep, launch and fetch share its number, beside
    # rows and bucket; the numbers are the step log's
    decodes = _by_launch(spans, "decode.")
    assert sorted(decodes) == list(range(n0 + 3, n0 + 7))
    assert all(sorted(v) == ["fetch", "launch", "prep"]
               for v in decodes.values())
    assert all({"rows", "bucket", "launch"} <= set(ids)
               for n, ids in engine_spans if n.startswith("pt:serve.decode."))
    log = sched.step_log()
    assert [r[0] for r in log] == list(range(n0 + 1, n0 + 7))
    assert engine.launches == n0 + 6


def test_a_fetch_carries_the_launch_it_waits_for(serve_spans):
    """Launched ahead, step k+1's launch span opens before step k's
    fetch: the fetch still names k."""
    engine, spans = serve_spans
    sched = engine.scheduler
    sched.submit([1, 2, 3], max_new_tokens=6)
    sched.drain()
    order = [(n.rsplit(".", 1)[1], ids["launch"]) for n, ids in spans
             if n in ("pt:serve.decode.launch", "pt:serve.decode.fetch")]
    first = order[0][1]
    assert order[:6] == [("launch", first), ("launch", first + 1),
                         ("fetch", first), ("launch", first + 2),
                         ("fetch", first + 1), ("launch", first + 3)]
    assert order[-1] == ("fetch", first + 4)
    # the checks' calls are numbered too, and read at once
    n0, at = engine.launches, len(spans)
    row = engine.pool.admit_row(2, 2, engine.max_pages_per_seq)
    try:
        tok, _ = engine.prefill_logits([7, 8], row.table)
        row.advance(2)
        engine.decode_logits(np.asarray([tok], np.int32),
                             np.asarray([2], np.int32), row.table[None])
    finally:
        row.release()
    assert engine.launches == n0 + 2
    assert [(n, ids["launch"]) for n, ids in spans[at:]] == [
        ("pt:serve.prefill.prep", n0 + 1), ("pt:serve.prefill.launch", n0 + 1),
        ("pt:serve.prefill.fetch", n0 + 1), ("pt:serve.decode.prep", n0 + 2),
        ("pt:serve.decode.launch", n0 + 2), ("pt:serve.decode.fetch", n0 + 2)]
