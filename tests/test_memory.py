"""Device-memory observability unit tests: the guarded allocator read,
compile-time footprints + pre-flight fit check, live-buffer census
attribution, watermark timeline (gauges + Chrome counter track), the
OOM postmortem payload, env enablement, and the capture integration
(one compile with the monitor on, footprint harvested, postmortem
naming a parameter path).

Everything follows the telemetry contract: zero cost disabled, never
sync the device, never initialize a jax backend just to read allocator
stats, never raise into the run.  The multi-process half (flight dump
through a real OOM'd worker, fleet skew through the aggregator) lives
in ``tests/drills/test_oom_drills.py``.
"""
from __future__ import annotations

import gc
import json
import logging
import os

import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.nn as nn
import paddle_tpu.observability as obs
from paddle_tpu.observability import memory as memory_mod
from paddle_tpu.observability.memory import (
    KINDS, MemoryMonitor, current_memory_monitor, device_memory_stat,
    device_memory_stats, get_memory_monitor, is_oom_error,
    oom_postmortem, program_memory_analysis, reset_memory_monitor,
)
from paddle_tpu.observability.metrics import get_registry
from paddle_tpu.observability.trace import get_tracer


@pytest.fixture(autouse=True)
def _isolated(monkeypatch):
    # env must never leak enablement into (or out of) a test
    for var in ("PT_TELEMETRY", "PT_TELEMETRY_DIR", "PT_METRICS_PORT",
                "PT_PROCESS_INDEX", "PT_RUN_ID", "PT_TRACE",
                "PT_TRACE_DIR", "PT_FLIGHT_RECORDER", "PT_MEMORY",
                "PT_MEMORY_TOPK"):
        monkeypatch.delenv(var, raising=False)
    obs.reset()
    # the census counts every live buffer of the process: what an earlier
    # file of this worker left in reference cycles is not this file's
    gc.collect()
    yield
    obs.reset()


def _prom_value(name, **labels):
    """One sample value out of the process registry's exposition."""
    from paddle_tpu.observability.aggregator import parse_prometheus_text
    fams = parse_prometheus_text(get_registry().prometheus_text())
    fam = fams.get(name)
    if fam is None:
        return None
    for sname, slabels, value in fam["samples"]:
        if sname == name and all(slabels.get(k) == v
                                 for k, v in labels.items()):
            return value
    return None


# -- the one guarded allocator read -----------------------------------------

def test_device_memory_stats_cpu_backend_has_no_allocator():
    # cpu devices report no allocator stats: summed dict is empty, the
    # per-device list is empty — and nothing raised
    assert device_memory_stats() == {}
    assert device_memory_stats(per_device=True) == []
    assert device_memory_stat("bytes_in_use") == 0
    assert device_memory_stat("bytes_limit", device_index=7) == 0


def test_device_memory_stats_survives_backend_errors(monkeypatch):
    import jax
    monkeypatch.setattr(jax, "local_devices",
                        lambda: (_ for _ in ()).throw(RuntimeError("x")))
    assert device_memory_stats() == {}
    assert device_memory_stats(per_device=True) == []


def test_cuda_parity_shims_route_through_guarded_read():
    # paddle's cuda.* memory API returns plain ints (0 on cpu), never
    # raises, never initializes anything
    cuda = pt.device.cuda
    assert cuda.memory_allocated() == 0
    assert cuda.max_memory_allocated() == 0
    assert cuda.memory_reserved() == 0
    assert cuda.max_memory_reserved() == 0


def test_telemetry_device_memory_delegates_to_guarded_read():
    tel = obs.get_telemetry()
    assert tel.device_memory() == device_memory_stats()


# -- compile-time footprint -------------------------------------------------

def test_program_memory_analysis_harvests_real_jitted_fn():
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda a: a @ a)
    x = jnp.ones((32, 32), jnp.float32)
    mem = program_memory_analysis(f, x)
    assert mem is not None
    assert set(mem) == set(KINDS) | {"alias"}
    assert all(isinstance(v, int) and v >= 0 for v in mem.values())
    assert mem["output"] >= 32 * 32 * 4  # one f32 result buffer
    assert MemoryMonitor.required_bytes(mem) >= 32 * 32 * 4


def test_program_memory_analysis_never_raises():
    assert program_memory_analysis(object()) is None
    assert program_memory_analysis(None) is None


def test_required_bytes_credits_donation_aliasing():
    mem = {"argument": 100, "output": 50, "temp": 25,
           "generated_code": 25, "alias": 60}
    assert MemoryMonitor.required_bytes(mem) == 140
    mem["alias"] = 10_000  # aliasing can never go negative
    assert MemoryMonitor.required_bytes(mem) == 0
    assert MemoryMonitor.required_bytes({}) == 0


def test_record_program_memory_exports_gauges_and_fit_verdict():
    mm = MemoryMonitor()
    mm.enable()
    mm.record_program_memory("trainstep", {
        "argument": 1000, "output": 200, "temp": 300,
        "generated_code": 50, "alias": 200})
    snap = mm.snapshot()
    assert snap["programs"]["trainstep"]["argument"] == 1000
    # no bytes_limit on cpu -> fit verdict is unknown, not a failure
    assert snap["fit"]["trainstep"]["fits"] is None
    assert snap["fit"]["trainstep"]["required_bytes"] == 1350
    assert snap["fit_ok"] is None
    for kind, want in (("argument", 1000.0), ("output", 200.0),
                       ("temp", 300.0), ("generated_code", 50.0)):
        assert _prom_value("pt_program_memory_bytes",
                           program="trainstep", kind=kind) == want


def test_fit_check_warns_once_naming_program_and_shortfall(
        monkeypatch, caplog):
    mm = MemoryMonitor()
    mm.enable()
    monkeypatch.setattr(memory_mod, "device_memory_stats",
                        lambda per_device=False: {"bytes_limit": 1000})
    with caplog.at_level(logging.WARNING,
                         logger="paddle_tpu.observability.memory"):
        mm.record_program_memory("big", {"argument": 1200,
                                         "output": 300})
        mm.record_program_memory("big", {"argument": 1200,
                                         "output": 300})
    warns = [r for r in caplog.records if "fit check" in r.getMessage()]
    assert len(warns) == 1  # warn ONCE per program, not per compile
    msg = warns[0].getMessage()
    assert "'big'" in msg and "1500" in msg and "500" in msg
    snap = mm.snapshot()
    assert snap["fit"]["big"] == {
        "fits": False, "required_bytes": 1500, "limit_bytes": 1000,
        "shortfall_bytes": 500}
    assert snap["fit_ok"] is False
    # a second program that fits does not flip the aggregate back
    mm.record_program_memory("small", {"argument": 10})
    assert mm.snapshot()["fit_ok"] is False


def test_fit_ok_true_when_every_program_fits(monkeypatch):
    mm = MemoryMonitor()
    monkeypatch.setattr(memory_mod, "device_memory_stats",
                        lambda per_device=False: {"bytes_limit": 10**9})
    mm.record_program_memory("a", {"argument": 100})
    mm.record_program_memory("b", {"output": 200})
    assert mm.snapshot()["fit_ok"] is True


# -- live-buffer census -----------------------------------------------------

def test_census_attributes_bytes_to_registered_provider_names():
    import jax.numpy as jnp
    arr = jnp.ones((128, 64), jnp.float32)  # 32 KiB
    mm = MemoryMonitor(topk=5)
    mm.register_provider(lambda: {"param::model::w": arr})
    census = mm.live_buffer_census()
    assert census["by_category"]["param"] == arr.nbytes
    assert census["count"] >= 1
    assert census["total_bytes"] >= arr.nbytes
    mine = [r for r in census["top"] if r["name"] == "param::model::w"]
    assert mine and mine[0]["bytes"] == arr.nbytes
    assert mine[0]["shape"] == [128, 64]
    assert mine[0]["dtype"] == "float32"
    assert len(census["top"]) <= 5


def test_census_extra_named_and_unattributed_bucket():
    import jax.numpy as jnp
    a = jnp.zeros((16, 16), jnp.float32)
    b = jnp.zeros((8, 8), jnp.float32)  # nobody claims b
    mm = MemoryMonitor()
    census = mm.live_buffer_census(extra_named={"opt0::velocity::w": a})
    assert census["by_category"]["opt0"] == a.nbytes
    assert census["by_category"].get("unattributed", 0) >= b.nbytes
    del b


def test_census_provider_held_weakly_never_keeps_step_alive():
    import jax.numpy as jnp

    class Step:
        def __init__(self):
            self.arr = jnp.ones((4, 4), jnp.float32)

        def named(self):
            return {"param::m::w": self.arr}

    mm = MemoryMonitor()
    step = Step()
    mm.register_provider(step.named)
    assert "param" in mm.live_buffer_census()["by_category"]
    del step
    gc.collect()
    census = mm.live_buffer_census()  # dead provider dropped silently
    assert "param" not in census["by_category"]
    assert mm._providers == []


def test_census_without_jax_arrays_is_empty_shape():
    mm = MemoryMonitor()
    census = mm.live_buffer_census(extra_named=None, topk=3)
    assert set(census) == {"total_bytes", "count", "by_category", "top"}


# -- watermark timeline -----------------------------------------------------

def test_observe_sample_books_history_gauges_and_counter_track():
    tr = get_tracer().enable(process_index=2)
    mm = MemoryMonitor()
    mm.enable()
    mm.observe_sample({"bytes_in_use": 100, "peak_bytes_in_use": 250,
                       "bytes_reserved": 160}, t_ns=1_000)
    mm.observe_sample({"bytes_in_use": 120, "peak_bytes_in_use": 250},
                      t_ns=2_000)
    marks = mm.watermarks()
    assert [m["t_ns"] for m in marks] == [1_000, 2_000]
    assert marks[0] == {"t_ns": 1_000, "bytes_in_use": 100,
                        "peak_bytes_in_use": 250,
                        "fragmentation_bytes": 60}
    assert marks[1]["fragmentation_bytes"] == 0  # no reserved stat
    # gauges carry the LAST sample
    assert _prom_value("pt_memory_watermark_bytes",
                       stat="bytes_in_use") == 120.0
    assert _prom_value("pt_memory_watermark_bytes",
                       stat="peak_bytes_in_use") == 250.0
    assert _prom_value("pt_memory_watermark_bytes",
                       stat="fragmentation") == 0.0
    # and each sample became one Chrome counter event on this rank
    cs = [c for c in tr.counters() if c[0] == "device_memory"]
    assert len(cs) == 2
    assert cs[0][1] == 1_000
    assert cs[0][2] == {"bytes_in_use": 100.0,
                        "peak_bytes_in_use": 250.0,
                        "fragmentation": 60.0}
    snap = mm.snapshot()
    assert snap["samples"] == 2
    assert snap["bytes_in_use"] == 120
    assert snap["fragmentation_bytes"] == 0


def test_on_step_respects_sampling_cadence(monkeypatch):
    mm = MemoryMonitor()
    mm.enable(sample_every=4)
    reads = []
    monkeypatch.setattr(
        memory_mod, "device_memory_stats",
        lambda per_device=False: reads.append(1) or
        {"bytes_in_use": 7, "peak_bytes_in_use": 7})
    for step in range(12):
        mm.on_step(step)
    assert len(reads) == 3  # steps 4, 8, 12
    assert len(mm.watermarks()) == 3
    mm.disable()
    mm.on_step(99)
    assert len(reads) == 3  # disabled hook is a no-op


def test_sample_watermark_noop_without_allocator_stats():
    mm = MemoryMonitor()
    mm.enable()
    mm.sample_watermark()  # cpu: no stats, no sample, no raise
    assert mm.watermarks() == []


# -- OOM intercept + postmortem ---------------------------------------------

def test_is_oom_error_needles():
    assert is_oom_error(RuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
        "1073741824 bytes."))
    assert is_oom_error("Resource exhausted: hbm")
    assert is_oom_error(MemoryError("allocation OOM"))
    assert is_oom_error("requested shape exceeds the memory capacity")
    assert not is_oom_error(ValueError("shape mismatch (4, 8)"))
    assert not is_oom_error("INVALID_ARGUMENT: dtype")
    assert not is_oom_error(None)


def test_record_oom_books_flight_dump_with_memory_payload(tmp_path):
    import jax.numpy as jnp
    tr = get_tracer().enable(flight_dir=str(tmp_path),
                             process_index=0, run_id="unit")
    big = jnp.zeros((1024, 1024), jnp.float32)  # 4 MiB dominates
    mm = get_memory_monitor()
    mm.enable()
    mm.record_program_memory("prog", {"argument": 64, "output": 64})
    mm.observe_sample({"bytes_in_use": 5, "peak_bytes_in_use": 9},
                      t_ns=1)
    exc = RuntimeError("RESOURCE_EXHAUSTED: Out of memory.")
    # the module-level entry point the intercepts call
    doc = oom_postmortem(program="prog", exc=exc,
                         extra_named={"param::model::w": big})
    assert doc["program"] == "prog"
    assert doc["top_buffer"] == "param::model::w"
    assert "RESOURCE_EXHAUSTED" in doc["error"]
    snap = mm.snapshot()
    assert snap["oom_events"] == 1
    assert snap["last_oom"] == {"program": "prog",
                                "top_buffer": "param::model::w",
                                "error": doc["error"]}
    assert _prom_value("pt_oom_events_total") == 1.0
    with open(tr.flight_path) as f:
        flight = json.load(f)
    assert flight["reason"] == "oom:prog:param::model::w"
    mem = flight["extra"]["memory"]
    assert mem["top_buffer"] == "param::model::w"
    assert mem["census"]["by_category"]["param"] == big.nbytes
    assert mem["programs"]["prog"]["argument"] == 64
    assert mem["fit"]["prog"]["required_bytes"] == 128
    assert mem["watermarks"] == [{"t_ns": 1, "bytes_in_use": 5,
                                  "peak_bytes_in_use": 9,
                                  "fragmentation_bytes": 0}]


def test_record_oom_runs_even_while_disabled():
    mm = MemoryMonitor()  # never enabled: OOM is terminal, book anyway
    doc = mm.record_oom(program="p", exc=RuntimeError("oom"))
    assert doc is not None and mm.snapshot()["oom_events"] == 1


# -- env enablement + singleton ---------------------------------------------

def test_env_enablement_and_reset(monkeypatch):
    assert current_memory_monitor() is None  # read-only accessor
    mm = get_memory_monitor()
    assert mm.enabled is False  # no env -> created disabled
    assert current_memory_monitor() is mm
    monkeypatch.setenv("PT_MEMORY", "1")
    monkeypatch.setenv("PT_MEMORY_TOPK", "5")
    reset_memory_monitor()
    mm2 = get_memory_monitor()
    assert mm2 is not mm
    assert mm2.enabled is True and mm2.topk == 5


def test_telemetry_snapshot_carries_memory_block():
    mm = get_memory_monitor()
    mm.enable()
    mm.record_program_memory("s", {"argument": 1})
    snap = obs.get_telemetry().snapshot()["memory"]
    assert snap["enabled"] is True
    assert snap["programs"] == 1
    assert snap["fit_ok"] is None  # cpu: no limit to check against
    assert "oom_events" in snap or "fragmentation_bytes" in snap


# -- capture integration ----------------------------------------------------

def _captured_mlp(width=256):
    np.random.seed(0)
    pt.seed(0)
    model = nn.Sequential(nn.Linear(64, width), nn.ReLU(),
                          nn.Linear(width, 1))
    opt = pt.optimizer.SGD(learning_rate=0.05,
                           parameters=model.parameters())
    mse = nn.MSELoss()

    @pt.jit.capture_step
    def step(x, y):
        loss = mse(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    x = pt.to_tensor(np.random.randn(8, 64).astype(np.float32))
    y = pt.to_tensor(np.random.randn(8, 1).astype(np.float32))
    return step, x, y


def test_capture_harvests_footprint_with_one_compile():
    mm = get_memory_monitor()
    mm.enable()
    step, x, y = _captured_mlp()
    for _ in range(3):
        step(x, y)
    # the monitored step still compiles exactly once
    assert step.stats["compiles"] == 1
    assert step.stats["fallback"] is None
    entry = next(iter(step._cache.values()))
    # footprint harvested from the same cache-shared AOT compile
    assert entry.memory is not None
    assert entry.memory["output"] > 0
    snap = mm.snapshot()
    assert "captured_step(step)" in snap["programs"]
    assert snap["programs"]["captured_step(step)"] == entry.memory
    # the capture registered itself as a census attribution source:
    # parameter paths resolve (64*256*4 first-weight bytes present)
    census = mm.live_buffer_census()
    assert census["by_category"].get("param", 0) >= 64 * 256 * 4
    named = step._memory_named()
    assert "param::model::0.weight" in named
    assert "buffer::" not in "".join(n for n in named
                                     if not n.startswith(("param::",
                                                          "opt")))


def test_bench_eager_memory_contract_one_compile_under_one_percent():
    """The tentpole acceptance bar, enforced in tier-1 through the
    bench's own contract block: monitoring adds no compile, changes no
    math, books the footprint, and costs <1% per step with watermark
    sampling on every step."""
    import bench_eager
    res = bench_eager._memory_contract(pt)
    if not res["ok"]:
        # the timing leg can lose one round to machine noise; the
        # compile/bitwise legs are deterministic, so one retry only
        # ever re-runs the clock
        res = bench_eager._memory_contract(pt)
    assert res["compiles_off"] == 1 and res["compiles_on"] == 1
    assert res["footprint_harvested"] is True
    assert res["loss_bitwise_identical"] is True
    assert res["census_param_bytes"] >= 256 * 256 * 4
    assert res["oom_events"] == 0
    assert res["overhead_ratio"] < 1.01
    assert res["ok"] is True


def test_capture_replay_intercepts_oom_and_names_parameter_path():
    mm = get_memory_monitor()
    mm.enable()
    step, x, y = _captured_mlp(width=512)  # 64*512*4 = 128 KiB weight
    for _ in range(2):
        step(x, y)
    entry = next(iter(step._cache.values()))

    def _exhausted(*a, **k):
        raise RuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory while trying to "
            "allocate 1073741824 bytes.")

    entry.jitted = _exhausted
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        step(x, y)
    snap = mm.snapshot()
    assert snap["oom_events"] == 1
    assert snap["last_oom"]["program"] == "captured_step(step)"
    assert snap["last_oom"]["top_buffer"].startswith("param::")
    # a non-OOM failure must NOT book a postmortem
    def _other(*a, **k):
        raise ValueError("shape mismatch")

    entry.jitted = _other
    with pytest.raises(ValueError):
        step(x, y)
    assert mm.snapshot()["oom_events"] == 1
