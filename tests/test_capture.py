"""jit.capture_step: trace-and-cache contract tests.

Covers the eager-fast-path acceptance surface: signature-cache hit/miss
semantics (no retrace on stable shapes, exactly one on a dtype flip),
numerical parity of captured vs eager training, donation safety for
caller-held arrays, graceful eager fallback on capture-unsafe code, and
the PT_CAPTURE=0 kill switch.
"""
import logging

import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.nn as nn
from paddle_tpu.observability import get_telemetry


def _mlp(seed=0):
    np.random.seed(seed)
    pt.seed(seed)
    model = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 1))
    opt = pt.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                parameters=model.parameters())
    return model, opt


def _batch(n=4, seed=1):
    rng = np.random.RandomState(seed)
    return (pt.to_tensor(rng.randn(n, 8).astype(np.float32)),
            pt.to_tensor(rng.randn(n, 1).astype(np.float32)))


def _train_step(model, opt):
    mse = nn.MSELoss()

    @pt.jit.capture_step
    def step(x, y):
        loss = mse(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    return step


def test_same_shapes_single_compile_sentinel_quiet():
    model, opt = _mlp()
    step = _train_step(model, opt)
    x, y = _batch()
    tel = get_telemetry()
    hits_before = tel.snapshot()["capture"]["hits"]
    for _ in range(10):
        step(x, y)
    assert step.stats["compiles"] == 1
    assert step.stats["hits"] == 9
    assert step.stats["misses"] == 1
    assert step.stats["fallback"] is None
    snap = tel.snapshot()
    assert snap["capture"]["hits"] - hits_before >= 9
    # the one compile must not read as churn to the recompile sentinel
    assert not [s for s in snap["recompile_storms"] if "captured_step" in s]


def test_dtype_change_exactly_one_retrace():
    @pt.jit.capture_step
    def f(a, b):
        return a * b + b

    xf = pt.to_tensor(np.ones((4, 4), np.float32))
    for _ in range(3):
        f(xf, xf)
    assert step_stats(f) == (1, 2, 1)
    xi = pt.to_tensor(np.ones((4, 4), np.int32))
    f(xi, xi)
    assert step_stats(f) == (2, 2, 2)  # one new trace, nothing dropped
    f(xf, xf)  # the float entry is still cached
    assert step_stats(f) == (2, 3, 2)


def step_stats(f):
    return (f.stats["misses"], f.stats["hits"], f.stats["compiles"])


def test_captured_matches_eager_10_steps():
    model, opt = _mlp()
    step = _train_step(model, opt)
    x, y = _batch()
    captured = [float(np.asarray(step(x, y)._data)) for _ in range(10)]

    model2, opt2 = _mlp()  # same seeds -> identical init
    mse = nn.MSELoss()
    x2 = pt.to_tensor(np.asarray(x._data))
    y2 = pt.to_tensor(np.asarray(y._data))
    eager = []
    for _ in range(10):
        loss = mse(model2(x2), y2)
        loss.backward()
        opt2.step()
        opt2.clear_grad()
        eager.append(float(np.asarray(loss._data)))

    # NOT bit-exact by design: the captured step is ONE fused XLA
    # program while eager runs per-op executables, and XLA reassociates
    # float math differently across fusion boundaries (~1 ULP at step
    # 0, observed <=1.2e-7 over 10 steps). The tolerance asserts the
    # trajectories are the same computation, not the same rounding.
    assert captured == pytest.approx(eager, abs=1e-5)
    for (n1, p1), (_, p2) in zip(model.named_parameters(),
                                 model2.named_parameters()):
        np.testing.assert_allclose(np.asarray(p1._data),
                                   np.asarray(p2._data), atol=1e-5,
                                   err_msg=n1)
    assert captured[-1] < captured[0]  # it actually trained


def test_replay_is_bit_deterministic():
    @pt.jit.capture_step
    def f(a, b):
        return a * b + b

    a = pt.to_tensor(np.random.RandomState(3).randn(8, 8)
                     .astype(np.float32))
    out1 = np.asarray(f(a, a)._data)
    out2 = np.asarray(f(a, a)._data)
    assert (out1 == out2).all()


def test_donation_safety_caller_arrays_survive():
    model, opt = _mlp()
    # caller-held references taken BEFORE capture: the capture layer
    # device-copies into private buffers, so donation must never
    # invalidate these
    held = {n: p._data for n, p in model.named_parameters()}
    before = {n: np.asarray(a).copy() for n, a in held.items()}
    step = _train_step(model, opt)
    x, y = _batch()
    for _ in range(5):
        step(x, y)
    for n, a in held.items():
        np.testing.assert_array_equal(np.asarray(a), before[n],
                                      err_msg=n)  # still readable + intact
    # while the live parameters did move
    moved = any(not np.array_equal(np.asarray(p._data), before[n])
                for n, p in model.named_parameters())
    assert moved


def test_capture_unsafe_falls_back_with_diagnostic(caplog):
    model, opt = _mlp()
    mse = nn.MSELoss()

    @pt.jit.capture_step
    def step(x, y):
        loss = mse(model(x), y)
        if float(np.asarray(loss._data)) > 1e9:  # host sync: unsafe
            return loss
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    x, y = _batch()
    with caplog.at_level(logging.WARNING, logger="paddle_tpu"):
        losses = [float(np.asarray(step(x, y)._data)) for _ in range(5)]
    assert step.fallback_reason == "capture_unsafe"
    assert step.stats["fallback"] == "capture_unsafe"
    assert step.stats["compiles"] == 0
    diags = [r.getMessage() for r in caplog.records
             if r.name.startswith("paddle_tpu")]
    assert any("falling back to eager" in m for m in diags)
    # the one-shot diagnostic names the offending user line
    assert any("test_capture.py" in m for m in diags)
    assert losses[-1] < losses[0]  # eager fallback still trains


def test_pt_capture_env_disables(monkeypatch):
    monkeypatch.setenv("PT_CAPTURE", "0")
    model, opt = _mlp()
    step = _train_step(model, opt)
    x, y = _batch()
    losses = [float(np.asarray(step(x, y)._data)) for _ in range(4)]
    assert step.stats["compiles"] == 0
    assert step.stats["hits"] == 0 and step.stats["misses"] == 0
    assert losses[-1] < losses[0]


def test_lr_change_does_not_retrace():
    model, opt = _mlp()
    step = _train_step(model, opt)
    x, y = _batch()
    for _ in range(3):
        step(x, y)
    opt.set_lr(0.01)  # lr rides in as a weak-f32 runtime arg
    for _ in range(3):
        step(x, y)
    assert step.stats["compiles"] == 1
    assert step.stats["hits"] == 5


def test_shape_change_compiles_second_entry():
    model, opt = _mlp()
    step = _train_step(model, opt)
    x, y = _batch(n=4)
    x8, y8 = _batch(n=8, seed=2)
    step(x, y)
    step(x8, y8)
    step(x, y)
    step(x8, y8)
    assert step.stats["compiles"] == 2
    assert step.stats["misses"] == 2
    assert step.stats["hits"] == 2


# -- scope names and the one lowering (PR 25) ----------------------------------

def _tiny_captured_step():
    pt.seed(1)

    class Net(pt.nn.Layer):
        def __init__(self):
            super().__init__()
            self.blocks = pt.nn.LayerList(
                [pt.nn.Linear(8, 8) for _ in range(2)])
            self.head = pt.nn.Linear(8, 4)

        def forward(self, x):
            for b in self.blocks:
                x = pt.nn.functional.relu(b(x))
            return self.head(x)

    model = Net()
    opt = pt.optimizer.AdamW(learning_rate=1e-3,
                             parameters=model.parameters())
    ce = pt.nn.CrossEntropyLoss()

    @pt.jit.capture_step
    def step(x, y):
        loss = ce(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    x = pt.to_tensor(np.random.randn(4, 8).astype("float32"))
    y = pt.to_tensor(np.array([0, 1, 2, 3]))
    return step, x, y


def test_captured_step_carries_layer_loss_and_optimizer_scopes():
    import re
    step, x, y = _tiny_captured_step()
    step(x, y)
    (entry,) = step._cache.values()
    st = step._state
    lowered = entry.jitted.lower(
        st.params, st.buffers, st.opt_states, st.rng_ctr,
        [float(o.get_lr()) for o in st.opts], [x._data, y._data])
    names = set(re.findall(r'"(jit\([^"]*)"',
                           lowered.as_text(debug_info=True)))
    scopes = {"/".join(n.split("/")[1:-1]) for n in names}
    # a layer's scope is its attribute path from the root; a LayerList is
    # transparent ("blocks/0", not "0"); the loss and the update have theirs
    assert {"blocks/0", "blocks/1", "head", "loss", "optimizer"} <= scopes
    # backward operations are emitted at loss.backward(), outside every
    # layer's call, and still carry their forward's scope
    assert any(n.startswith("jit(captured_step(step))/blocks/0/transpose(")
               for n in names), sorted(names)[:20]
    assert any("/loss/transpose(" in n for n in names)
    assert not any("/optimizer/" in n and "/blocks/" in n for n in names)


def test_layer_scope_names_follow_registration():
    inner = pt.nn.LayerList([pt.nn.Linear(2, 2)])
    root = pt.nn.Layer()
    assert inner[0]._scope_name == "0" and root._scope_name is None
    outer = pt.nn.LayerList([inner])
    assert inner[0]._scope_name == "0/0"
    root.stack = outer            # named after it was filled
    assert outer._scope_name == "stack"
    assert inner._scope_name == "stack/0"
    assert inner[0]._scope_name == "stack/0/0"
    inner.append(pt.nn.Linear(2, 2))
    assert inner[1]._scope_name == "stack/0/1"
    seq = pt.nn.Sequential(pt.nn.Linear(2, 2))
    root.seq = seq                # a Sequential is called: its own scope
    assert seq._scope_name == "seq" and seq[0]._scope_name == "0"
    assert pt.nn.MSELoss()._scope_name == "loss"


def test_traced_first_call_lowers_the_step_once():
    """With the tracer on, the capture layer used to lower and compile
    the whole step a second time to read cost_analysis()."""
    import jax
    from paddle_tpu.observability.trace import get_tracer, reset_tracer
    step, x, y = _tiny_captured_step()
    seen = []

    def listen(event, seconds, **kw):
        if "captured_step" in str(kw.get("fun_name")):
            seen.append(event.rsplit("/", 1)[-1])

    reset_tracer()
    get_tracer().enable()
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        step(x, y)
        step(x, y)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
        reset_tracer()
    assert seen.count("jaxpr_trace_duration") == 1, seen
    assert seen.count("jaxpr_to_mlir_module_duration") == 1, seen
    assert seen.count("backend_compile_duration") == 1, seen
