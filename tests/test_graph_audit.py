"""Graph auditor: rule fixtures over synthetic jaxprs, the runtime
hook layer, baseline round-trips, and the tier-1 self-clean gate that
keeps every in-tree captured/served program free of new findings.

Mirrors test_tpu_lint.py's structure: each rule gets a violating
builder (must fire) and a clean builder encoding the idiom the rule
pushes toward (must stay silent), so an over-triggering rule fails
here before it ever gates a real capture.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.tools.audit import (
    AuditProgram, RULES, audit_enabled, default_rules, rule_catalog,
    run_rules, walk_jaxprs,
)
from paddle_tpu.tools.audit import runtime
from paddle_tpu.tools.audit.baseline import (
    default_baseline_path, diff_against_baseline, load_baseline,
    write_baseline,
)
from paddle_tpu.tools.audit.core import Finding

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def audit(prog, select=None):
    return run_rules([prog], default_rules(select))


def fired(findings, rule):
    return [f for f in findings if f.rule == rule]


@pytest.fixture
def audit_on():
    """Enable the auditor for one test and always clear the process
    ledger afterwards (runtime state is module-global)."""
    runtime.reset()
    runtime.enable()
    yield
    runtime.reset()


# -- rule fixtures: violating + clean jaxpr builders -------------------------

def test_aud001_fires_on_conflicting_constraints():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1,), ("mp",))

    def reshard(x):
        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P("mp", None)))
        x = x * 2.0
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(None, "mp")))

    jx = jax.make_jaxpr(reshard)(jnp.ones((4, 4)))
    hits = fired(audit(AuditProgram("reshard", jx)), "AUD001")
    assert hits and hits[0].severity == "error"
    assert "reshard[" in hits[0].provenance


def test_aud001_silent_on_consistent_constraints():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1,), ("mp",))

    def ok(x):
        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P("mp", None)))
        x = x * 2.0
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P("mp", None)))

    jx = jax.make_jaxpr(ok)(jnp.ones((4, 4)))
    assert not fired(audit(AuditProgram("ok", jx)), "AUD001")


def test_aud001_warns_on_non_canon_axis():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1,), ("rogue",))

    def f(x):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P("rogue")))

    jx = jax.make_jaxpr(f)(jnp.ones(4))
    hits = fired(audit(AuditProgram("rogue_axis", jx)), "AUD001")
    assert hits and hits[0].severity == "warning"
    assert "axis[" in hits[0].provenance


def test_aud002_fires_on_upcast_then_dot():
    def bad(a, b):
        return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32))

    jx = jax.make_jaxpr(bad)(jnp.ones((8, 8), jnp.bfloat16),
                             jnp.ones((8, 8), jnp.bfloat16))
    hits = fired(audit(AuditProgram("bad_amp", jx, kind="capture")),
                 "AUD002")
    assert hits and hits[0].severity == "error"
    assert "dot_general" in hits[0].provenance


def test_aud002_silent_on_preferred_element_type():
    # the accumulation contract: bf16 operands, f32 accumulation INSIDE
    # the dot — no standalone upcast, full MXU rate
    def good(a, b):
        return jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    jx = jax.make_jaxpr(good)(jnp.ones((8, 8), jnp.bfloat16),
                              jnp.ones((8, 8), jnp.bfloat16))
    assert not fired(audit(AuditProgram("good_amp", jx)), "AUD002")


def test_aud002_silent_on_native_f32_dot():
    # no narrow source anywhere: f32-in/f32-out is not a leak
    jx = jax.make_jaxpr(jnp.dot)(jnp.ones((8, 8)), jnp.ones((8, 8)))
    assert not fired(audit(AuditProgram("f32_dot", jx)), "AUD002")


def test_aud003_donation_both_ways():
    # the state-sized arg has a same-shape output: undonated -> the
    # buffer is allocated twice per step; donated -> aliased, silent
    def step(w, x):
        return w + 0.1 * x, jnp.sum(x)

    big = jnp.ones((512, 1024), jnp.float32)      # 2 MiB > 1 MiB floor
    jx = jax.make_jaxpr(step)(big, big)

    undonated = AuditProgram("step", jx, kind="capture",
                             arg_names=["w", "x"])
    hits = fired(audit(undonated), "AUD003")
    assert hits and hits[0].nbytes == 512 * 1024 * 4
    assert "undonated[w:" in hits[0].provenance

    donated = AuditProgram("step", jx, kind="capture", donated=[0],
                           arg_names=["w", "x"])
    assert not fired(audit(donated), "AUD003")


def test_aud003_small_buffers_below_floor_are_silent(monkeypatch):
    def step(w):
        return w * 2.0

    jx = jax.make_jaxpr(step)(jnp.ones((8, 8), jnp.float32))
    assert not fired(audit(AuditProgram("tiny", jx, kind="capture")),
                     "AUD003")
    # the floor is a lazily read env knob
    monkeypatch.setenv("PT_AUDIT_DONATION_MIN_BYTES", "1")
    assert fired(audit(AuditProgram("tiny", jx, kind="capture")),
                 "AUD003")


def test_aud004_callback_severity_tracks_program_kind():
    def with_cb(x):
        jax.debug.print("tok {}", x[0])
        return x * 2

    jx = jax.make_jaxpr(with_cb)(jnp.ones(4))
    # on the serving request path a host callback stalls a live
    # request: error.  In a training capture it is a warning.
    serve_hits = fired(audit(AuditProgram("dec", jx, kind="serve")),
                       "AUD004")
    assert serve_hits and serve_hits[0].severity == "error"
    cap_hits = fired(audit(AuditProgram("step", jx, kind="capture")),
                     "AUD004")
    assert cap_hits and cap_hits[0].severity == "warning"


def test_aud004_silent_on_pure_program():
    jx = jax.make_jaxpr(lambda x: x * 2)(jnp.ones(4))
    assert not fired(audit(AuditProgram("dec", jx, kind="serve")),
                     "AUD004")


def test_aud006_fires_on_shared_dequant():
    # one int8→f32 convert feeding two dots: the f32 copy outlives both
    def bad(w_q, x1, x2):
        w = w_q.astype(jnp.float32)
        return x1 @ w, x2 @ w

    jx = jax.make_jaxpr(bad)(jnp.ones((8, 8), jnp.int8),
                             jnp.ones((4, 8)), jnp.ones((4, 8)))
    hits = fired(audit(AuditProgram("srv", jx, kind="serve")), "AUD006")
    assert hits and hits[0].severity == "error"
    assert "dequant[" in hits[0].provenance and "x2]" in hits[0].provenance


def test_aud006_silent_on_per_dot_dequant():
    # the w8a16_matmul_reference form: one convert per dot, scale in
    # the epilogue — each upcast fuses into the dot it feeds
    def good(w_q, s, x1, x2):
        a = (x1 @ w_q.astype(jnp.float32)) * s
        b = (x2 @ w_q.astype(jnp.float32)) * s
        return a, b

    jx = jax.make_jaxpr(good)(jnp.ones((8, 8), jnp.int8), jnp.ones((8,)),
                              jnp.ones((4, 8)), jnp.ones((4, 8)))
    assert not fired(audit(AuditProgram("srv", jx, kind="serve")),
                     "AUD006")


def test_aud006_warning_outside_serve_and_follows_elementwise():
    # capture programs warn rather than error, and the walk follows the
    # scale multiply (dequant = convert * scale) to both dots
    def bad(w_q, s, x1, x2):
        w = w_q.astype(jnp.float32) * s
        return x1 @ w, x2 @ w

    jx = jax.make_jaxpr(bad)(jnp.ones((8, 8), jnp.int8), jnp.ones((8,)),
                             jnp.ones((4, 8)), jnp.ones((4, 8)))
    hits = fired(audit(AuditProgram("cap", jx, kind="capture")), "AUD006")
    assert hits and hits[0].severity == "warning"


def test_aud006_int8_serve_ladder_is_clean(audit_on):
    # the shipped int8 engine satisfies its own rule: every dequant in
    # the AOT ladder feeds exactly one dot
    from paddle_tpu.serving import ModelSpec, ServeConfig, init_params
    from paddle_tpu.serving.engine import ServingEngine
    spec = ModelSpec(vocab_size=64, hidden=32, layers=2, heads=2,
                     max_seq_len=64)
    cfg = ServeConfig(decode_buckets=(2,), prefill_buckets=(16,),
                      kv_pages=32, page_size=8, precision="int8")
    engine = ServingEngine(spec, init_params(spec, seed=0), cfg)
    engine.close()
    progs = runtime.snapshot()["programs"]
    assert any(p.endswith("_int8") for p in progs)
    assert not [f for f in runtime.findings()
                if f.program.endswith("_int8")]


# -- machinery ---------------------------------------------------------------

def test_catalog_covers_all_five_rule_classes():
    cat = rule_catalog()
    ids = {rid for rid, _, _ in cat}
    assert {"AUD001", "AUD002", "AUD003", "AUD004",
            "AUD006"} <= ids
    for rid, name, rationale in cat:
        assert rid.startswith("AUD") and len(rid) == 6
        assert name and rationale


def test_walk_jaxprs_descends_into_pjit_bodies():
    inner = jax.jit(lambda x: x * 2 + 1)

    def outer(x):
        return inner(x) + 3

    jx = jax.make_jaxpr(outer)(jnp.ones(4))
    paths = [p for _, p in walk_jaxprs(jx)]
    assert "" in paths
    assert any(p for p in paths if p)  # at least one nested body


def test_rules_detect_hazards_in_nested_bodies():
    # a callback buried in a jitted sub-function must still be found
    def cb_inner(x):
        jax.debug.print("x {}", x[0])
        return x

    inner = jax.jit(cb_inner)
    jx = jax.make_jaxpr(lambda x: inner(x) * 2)(jnp.ones(4))
    hits = fired(audit(AuditProgram("nested", jx, kind="serve")),
                 "AUD004")
    assert hits
    assert "inside jit" in hits[0].message


def test_crashing_rule_becomes_finding_not_exception():
    class Broken:
        id = "AUD999"

        def check(self, prog):
            raise RuntimeError("boom")

    jx = jax.make_jaxpr(lambda x: x)(jnp.ones(2))
    out = run_rules([AuditProgram("p", jx)], [Broken()])
    assert len(out) == 1
    assert out[0].rule == "AUD999"
    assert out[0].provenance == "rule-error"
    assert "boom" in out[0].message


def test_select_and_env_disable_narrow_the_rule_set(monkeypatch):
    def bad(a, b):
        return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32))

    jx = jax.make_jaxpr(bad)(jnp.ones((8, 8), jnp.bfloat16),
                             jnp.ones((8, 8), jnp.bfloat16))
    prog = AuditProgram("bad_amp", jx, kind="capture")
    assert fired(audit(prog), "AUD002")
    # --select semantics: only the chosen rules instantiate
    assert not audit(prog, select=["AUD004"])
    with pytest.raises(KeyError):
        default_rules(["AUD999"])
    # PT_AUDIT_DISABLE is the hook-side (rule-level) suppression — the
    # IR has no line to hang a disable comment on
    monkeypatch.setenv("PT_AUDIT_DISABLE", "AUD002")
    assert not fired(run_rules([prog], default_rules()), "AUD002")


# -- baseline round-trips ----------------------------------------------------

def _finding(prov="dot_general[8x8<-bf16]"):
    return Finding(rule="AUD002", severity="error", program="step",
                   provenance=prov, message="leak")


def test_baseline_round_trip(tmp_path):
    bl = str(tmp_path / "baseline.txt")
    assert write_baseline(bl, [_finding()]) == 1
    new, old, stale = diff_against_baseline([_finding()],
                                            load_baseline(bl))
    assert new == [] and len(old) == 1 and stale == []


def test_baseline_catches_new_and_stale(tmp_path):
    bl = str(tmp_path / "baseline.txt")
    write_baseline(bl, [_finding()])
    fresh = _finding(prov="undonated[w:f32[512,1024]]")
    new, old, stale = diff_against_baseline([fresh], load_baseline(bl))
    assert len(new) == 1 and new[0] is fresh
    assert old == [] and len(stale) == 1


def test_baseline_is_a_multiset(tmp_path):
    # two identical findings need two baseline entries — the third is new
    bl = str(tmp_path / "baseline.txt")
    write_baseline(bl, [_finding(), _finding()])
    new, old, _ = diff_against_baseline(
        [_finding(), _finding(), _finding()], load_baseline(bl))
    assert len(old) == 2 and len(new) == 1


# -- runtime hooks -----------------------------------------------------------

def test_audit_off_by_default_and_knob_is_lazy(monkeypatch):
    runtime.reset()
    monkeypatch.delenv("PT_AUDIT", raising=False)
    assert not audit_enabled()
    monkeypatch.setenv("PT_AUDIT", "1")   # after import: still honored
    assert audit_enabled()
    monkeypatch.setenv("PT_AUDIT", "0")
    assert not audit_enabled()
    runtime.enable()
    assert audit_enabled()                # programmatic override wins
    runtime.reset()


def test_audit_program_ledgers_and_books_metric(audit_on):
    def bad(a, b):
        return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32))

    jx = jax.make_jaxpr(bad)(jnp.ones((8, 8), jnp.bfloat16),
                             jnp.ones((8, 8), jnp.bfloat16))
    found = runtime.audit_program(
        AuditProgram("bad_amp", jx, kind="capture"))
    assert found
    snap = runtime.snapshot()
    assert snap["enabled"] and snap["programs"] == ["bad_amp"]
    assert snap["by_rule"].get("AUD002", 0) >= 1
    assert snap["by_severity"].get("error", 0) >= 1
    from paddle_tpu.observability.metrics import get_registry
    text = get_registry().prometheus_text()
    assert "pt_audit_findings_total" in text
    assert 'rule="AUD002"' in text


def test_capture_hook_audits_first_replay_only(audit_on):
    import paddle_tpu as pt
    import paddle_tpu.nn as nn
    pt.seed(0)
    model = nn.Linear(8, 8)
    opt = pt.optimizer.SGD(learning_rate=0.1,
                           parameters=model.parameters())
    mse = nn.MSELoss()

    @pt.jit.capture_step
    def small_step(x, y):
        loss = mse(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    x = pt.to_tensor(np.ones((4, 8), np.float32))
    y = pt.to_tensor(np.zeros((4, 8), np.float32))
    for _ in range(3):
        small_step(x, y)

    snap = runtime.snapshot()
    audited = [p for p in snap["programs"] if "small_step" in p]
    assert len(audited) == 1, (
        "the audit must run once per signature at compile time, "
        f"never per replay: {snap['programs']}")
    # a tiny clean step: params are donated, everything under the
    # donation floor, no callbacks — zero error findings
    assert not [f for f in runtime.findings()
                if "small_step" in f.program and f.severity == "error"]


def test_serving_hook_audits_every_bucket_program(audit_on):
    import tempfile
    from paddle_tpu.serving import (ModelSpec, ServeConfig, init_params,
                                    load_engine, save_served_model)
    spec = ModelSpec(vocab_size=64, hidden=32, layers=2, heads=2,
                     max_seq_len=64)
    cfg = ServeConfig(decode_buckets=(4,), prefill_buckets=(16,),
                      kv_pages=32, page_size=4, max_inflight=16,
                      max_new_tokens=8)
    with tempfile.TemporaryDirectory() as root:
        save_served_model(root, spec, init_params(spec, seed=0),
                          config=cfg)
        engine = load_engine(root)
        engine.close()
    progs = runtime.snapshot()["programs"]
    assert any(p.startswith("serve_prefill_s") for p in progs)
    assert any(p.startswith("serve_decode_b") for p in progs)
    # the shipped engine satisfies its own auditor: zero findings of
    # any severity on the AOT ladder
    assert not [f for f in runtime.findings()
                if f.program.startswith("serve_")]


def test_disabled_audit_costs_nothing_on_capture():
    import paddle_tpu as pt
    runtime.reset()  # no enable(): default off

    @pt.jit.capture_step
    def mul_step(a, b):
        return a * b

    x = pt.to_tensor(np.ones((4, 4), np.float32))
    mul_step(x, x)
    assert runtime.snapshot()["programs"] == []


# -- the tier-1 self-clean gate ----------------------------------------------

def test_cli_gate_exits_zero():
    """Every in-tree reference program (bench GPT captured step + the
    served-engine AOT ladder) audits clean against the committed
    baseline — new IR-level hazards fail tier-1 from this commit on."""
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.tools.audit"],
        cwd=ROOT, capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=420)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "0 new findings" in out.stdout


def test_cli_list_rules():
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.tools.audit", "--list-rules"],
        cwd=ROOT, capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=120)
    assert out.returncode == 0
    for rid in ("AUD001", "AUD002", "AUD003", "AUD004", "AUD006"):
        assert rid in out.stdout
    assert out.stdout.count("AUD0") == 5


def test_cli_rejects_unknown_select():
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.tools.audit",
         "--select", "AUD999"],
        cwd=ROOT, capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=120)
    assert out.returncode == 2


def test_committed_baseline_is_empty():
    """Nothing is grandfathered: a finding on an in-tree step must be
    fixed, not baselined."""
    assert not load_baseline(default_baseline_path())
