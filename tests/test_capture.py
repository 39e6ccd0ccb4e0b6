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


# -- one walk of the tape (PR 29) -------------------------------------------------

def _walk_counted_step(amp=False, optimizers=1):
    """A captured train step whose body counts how often Python runs it."""
    pt.seed(3)
    model = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    if amp:
        pt.amp.decorate(model, level="O2", dtype="bfloat16")
    params = model.parameters()
    if optimizers == 2:     # weights and biases under an optimizer each
        groups = [[p for p in params if len(p.shape) == 2],
                  [p for p in params if len(p.shape) == 1]]
    else:
        groups = [params]
    opts = [pt.optimizer.AdamW(learning_rate=1e-2, parameters=g,
                               multi_precision=amp) for g in groups]
    ce = nn.CrossEntropyLoss()
    walks = []

    @pt.jit.capture_step
    def step(x, y):
        walks.append(1)
        loss = ce(model(x), y)
        loss.backward()
        for o in opts:
            o.step()
            o.clear_grad()
        return loss

    return step, model, walks


def _xy(n=4, stop_gradient=True):
    rng = np.random.RandomState(n)
    x = pt.to_tensor(rng.randn(n, 8).astype(np.float32),
                     stop_gradient=stop_gradient)
    return x, pt.to_tensor(rng.randint(0, 4, (n,)))


WALK_CASES = {
    # name: (amp, monitors on, optimizers, second signature)
    "fp32": (False, "", 1, None),
    "amp_o2": (True, "", 1, None),
    "numerics": (False, "numerics", 1, None),
    "sdc": (False, "sdc", 1, None),
    "numerics_sdc": (False, "numerics sdc", 1, None),
    "memory": (False, "memory", 1, None),
    "tracer": (False, "tracer", 1, None),
    "two_optimizers": (False, "", 2, None),
    "amp_o2_every_monitor": (True, "numerics sdc memory tracer", 1,
                             "batch_shape"),
    "batch_shape": (False, "", 1, "batch_shape"),
    "input_dtype": (False, "", 1, "dtype"),
    "stop_gradient_flip": (False, "", 1, "stop_gradient"),
    "train_eval_flip": (False, "", 1, "eval"),
}


@pytest.fixture
def monitors_off():
    from paddle_tpu.observability import memory, numerics, sdc
    from paddle_tpu.observability.trace import reset_tracer

    def reset():
        numerics.reset_monitor()
        sdc.reset_monitor()
        memory.reset_memory_monitor()
        reset_tracer()

    reset()
    yield
    reset()


def _monitors_on(which):
    from paddle_tpu.observability import memory, numerics, sdc
    from paddle_tpu.observability.trace import get_tracer
    if "numerics" in which:
        numerics.get_monitor().enable(cadence=1)
    if "sdc" in which:
        sdc.get_monitor().enable(cadence=1, halt=False, rank=0)
    if "memory" in which:
        memory.get_memory_monitor().enable()
    if "tracer" in which:
        get_tracer().enable()


@pytest.mark.parametrize("case", sorted(WALK_CASES))
def test_step_body_is_walked_once(case, monitors_off):
    """The user's body runs once on the first call of a signature and
    never on a replay: the step is traced once (a graph pass used to walk
    it, find nothing to rewrite, and walk it again)."""
    amp, monitors, optimizers, second = WALK_CASES[case]
    _monitors_on(monitors)
    step, model, walks = _walk_counted_step(amp, optimizers)
    x, y = _xy()
    losses = [float(step(x, y)) for _ in range(3)]
    assert len(walks) == 1
    assert step.stats == {"hits": 2, "misses": 1, "compiles": 1,
                          "fallback": None}
    assert np.all(np.isfinite(losses)) and losses[2] < losses[0]
    if second is None:
        return
    if second == "batch_shape":
        x2, y2 = _xy(6)
    elif second == "dtype":
        x2, y2 = x.astype("bfloat16"), y
    elif second == "stop_gradient":
        x2, y2 = _xy(stop_gradient=False)
    else:
        model.eval()
        x2, y2 = x, y
    step(x2, y2)
    step(x2, y2)
    assert len(walks) == 2
    if second == "eval":
        model.train()
    step(x, y)              # the first signature's entry still replays
    assert len(walks) == 2
    assert step.stats["compiles"] == 2 and step.stats["hits"] == 4


def test_forward_only_body_and_a_static_argument_are_walked_once():
    """No optimizer, no backward; a Python scalar among the arguments is
    part of the signature: a new value is a new entry, walked once."""
    pt.seed(3)
    model = nn.Linear(8, 4)
    walks = []

    @pt.jit.capture_step
    def fwd(x, scale=1.0):
        walks.append(1)
        return model(x) * scale

    x, _ = _xy()
    a = [fwd(x).numpy() for _ in range(3)]
    assert len(walks) == 1
    b = [fwd(x, scale=2.0).numpy() for _ in range(2)]
    assert len(walks) == 2
    np.testing.assert_allclose(b[1], 2.0 * a[2], rtol=1e-6)
    fwd(x)
    assert len(walks) == 2 and fwd.stats["compiles"] == 2


def test_the_graph_audit_reads_the_trace_the_step_made(monitors_off):
    """`PT_AUDIT=1` asks `jax.make_jaxpr` for the entry's pure function
    at the first call of a signature; jax answers from the trace
    `jax.jit` has just made of the same function, so the audit reads the
    step's jaxpr and the body is still walked once."""
    from paddle_tpu.tools.audit import runtime as audit_rt
    audit_rt.reset()
    audit_rt.enable(True)
    try:
        step, _, walks = _walk_counted_step()
        x, y = _xy()
        for _ in range(3):
            step(x, y)
        assert audit_rt.snapshot()["programs"] == ["captured_step(step)"]
        assert len(walks) == 1
        assert step.stats["compiles"] == 1
    finally:
        audit_rt.enable(False)
        audit_rt.reset()


# four steps of benchmarks/configs/tiny-train.json's two-layer GPT (batch 4
# x 128, AdamW 1e-3, seed 29), recorded at the parent of PR 29 (4a129d2) on
# this container's CPU, where the graph pass on and off gave the same bits
GPT_GOLDEN = {
    "fp32": ["0x1.bc15c40000000p+2", "0x1.be30e80000000p+2",
             "0x1.bcd34c0000000p+2", "0x1.bd894e0000000p+2"],
    "amp_o2": ["0x1.bc17300000000p+2", "0x1.be2eda0000000p+2",
               "0x1.bcd4480000000p+2", "0x1.bd88180000000p+2"],
}
# a golden crosses CPUs: the same tree under --xla_cpu_max_isa=AVX2 / SSE4_2
# or single-threaded Eigen moved fp32 by 3e-7 and bf16 by 2e-5 (relative);
# a missed update, a dropped layer or another loss scale move the second
# step by 1e-3 and more
GPT_GOLDEN_RTOL = {"fp32": 2e-6, "amp_o2": 1e-4}


def _tiny_gpt_losses(amp, steps=4):
    import json
    import os
    from paddle_tpu.incubate.models import GPTConfig, GPTForCausalLM
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "tiny-train.json")) as f:
        spec = json.load(f)["model"]
    pt.seed(29)
    model = GPTForCausalLM(GPTConfig(**spec))
    if amp:
        pt.amp.decorate(model, level="O2", dtype="bfloat16")
    opt = pt.optimizer.AdamW(learning_rate=1e-3,
                             parameters=model.parameters(),
                             multi_precision=amp)
    ce = nn.CrossEntropyLoss()

    @pt.jit.capture_step
    def step(ids, labels):
        loss = ce(model(ids), labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    rng = np.random.default_rng(29)
    out = []
    for _ in range(steps):
        ids = rng.integers(0, spec["vocab_size"], (4, 128)).astype(np.int64)
        labels = rng.integers(0, spec["vocab_size"],
                              (4, 128)).astype(np.int64)
        out.append(float(step(pt.to_tensor(ids), pt.to_tensor(labels))))
    assert step.stats["compiles"] == 1 and step.stats["fallback"] is None
    return out


@pytest.mark.parametrize("monitored", ["plain", "monitored"])
@pytest.mark.parametrize("precision", ["fp32", "amp_o2"])
def test_tiny_gpt_loss_trajectory_is_the_parents(precision, monitored,
                                                 monitors_off):
    # the numerics and SDC monitors add outputs to the step, never
    # arithmetic to the loss: the same goldens hold with them on
    if monitored == "monitored":
        _monitors_on("numerics sdc")
    got = _tiny_gpt_losses(precision == "amp_o2")
    want = [float.fromhex(h) for h in GPT_GOLDEN[precision]]
    np.testing.assert_allclose(got, want, rtol=GPT_GOLDEN_RTOL[precision],
                               atol=0)
