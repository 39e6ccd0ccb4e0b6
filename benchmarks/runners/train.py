"""Runner kind `train`: the configuration's GPT under bf16 AMP O2 and
AdamW with float32 masters, fed a ring of seeded batches.

One chip: the loop body under `@pt.jit.capture_step`, as README "Eager
fast path" writes it (model set-up copied from `chip_smoke.py`).
A configuration with a `mesh` runs `distributed.build_train_step` on
that mesh instead.  Every step ends in `jax.block_until_ready`; the
window runs for at least `--seconds` and ends at the step boundary
after, so the rate is all the steps over all the time."""
from __future__ import annotations

import contextlib
import os
import time

import numpy as np

from costs import train_flops_per_token
from reference import gpt_train as ref
from taps import pallas_routes, span, start_trace
from traffic import train_batches

# |program's eval-mode loss - reference loss| on a sample of sequences,
# same weights.  The program computes in bfloat16 (AMP O2: 8 bits of
# mantissa through 24 layers), the reference in float32 at "highest";
# the loss is a mean over 2,048 tokens, which averages that rounding
# out: the two read 11.02647 and 11.02661, 0.00014 apart (my chip run,
# PR 23).  The bound is some thirty times that and still a twentieth of
# a bf16 ulp at 11 (0.0625): a loss rounded to bf16, a dropped layer, a
# wrong mask or a wrong QKV layout all fail it.
LOSS_ATOL = 0.004
SAMPLE_ROWS = 2
WARM_STEPS = 2      # after the first call, before the window


def run(ctx):
    import jax
    import paddle_tpu as pt
    from paddle_tpu.incubate.models import (GPTConfig, GPTForCausalLM,
                                            GPTPretrainingCriterion)
    from paddle_tpu.observability.telemetry import get_telemetry

    config, mix, seconds = ctx["config"], ctx["traffic"], ctx["seconds"]
    on_chip = jax.devices()[0].platform == "tpu"
    mesh_shape = config.get("mesh")
    get_telemetry().enable()     # dispatch counts (pt_pallas_calls_total)
    pt.seed(ctx["seed"] % (2 ** 31 - 1))
    cfg = GPTConfig(tensor_parallel=bool(mesh_shape), **config["model"])
    model = GPTForCausalLM(cfg)
    pt.amp.decorate(model, level="O2", dtype="bfloat16")
    opt = pt.optimizer.AdamW(learning_rate=float(config["train"]["lr"]),
                             parameters=model.parameters(),
                             multi_precision=True)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    batches = train_batches(mix, ctx["seed"], cfg.vocab_size)
    batch, seq = int(mix["batch"]), int(mix["seq"])

    # the program's eval-mode loss on a sample against the reference
    ids0, labels0 = (a[:SAMPLE_ROWS] for a in batches[0])
    named = {n: p._data for n, p in model.named_parameters()}
    want = float(ref.loss(
        named, ids0, labels0, layers=cfg.num_layers,
        heads=cfg.num_attention_heads))
    if mesh_shape:
        crit = GPTPretrainingCriterion()
        loss_of = lambda logits, labels: crit(logits, labels)
    else:
        ce = pt.nn.CrossEntropyLoss()
        loss_of = lambda logits, labels: ce(logits, labels)
    model.eval()
    with pt.no_grad():
        got = float(loss_of(model(pt.to_tensor(ids0)),
                            pt.to_tensor(labels0)))
    model.train()
    loss_err = abs(got - want)

    if mesh_shape:
        import paddle_tpu.distributed as dist
        from paddle_tpu.distributed.train_step import build_train_step
        mesh = dist.init_mesh(dict(mesh_shape))
        fn, state = build_train_step(model, crit, opt, mesh=mesh)
        box = {"state": state}

        def step(ids, labels):
            loss, box["state"] = fn(box["state"], ids, labels)
            return loss
        ring = batches
        # no counter of its own: count what jax's compile log shows
        tel = get_telemetry()
        tel.ensure_compile_watch()
        seen = []
        tel.add_compile_listener(lambda name, signature="": seen.append(name))
        compiles = lambda: len(seen)
    else:
        @pt.jit.capture_step
        def captured(ids, labels):
            loss = ce(model(ids), labels)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        def step(ids, labels):
            return captured(ids, labels)._data
        # the ring lives on the device: a step's batch is there before it
        ring = [(pt.to_tensor(a), pt.to_tensor(b)) for a, b in batches]
        compiles = lambda: captured.stats["compiles"]

    t0 = time.monotonic()
    jax.block_until_ready(step(*ring[0]))
    first_call_s = time.monotonic() - t0
    for k in range(WARM_STEPS):
        jax.block_until_ready(step(*ring[(1 + k) % len(ring)]))
    compiles0 = compiles()

    traced = ctx["trace"]
    trace_s = min(float(mix.get("trace_s", 4.0)), seconds / 2)
    trace_dir = os.path.join(ctx["out"], "trace")
    spans, losses = [], []
    tracing = trace_window = None
    profiler_s = 0.0    # spent starting and stopping the profiler: not the system's
    t_window = time.monotonic()
    i = 1 + WARM_STEPS
    while True:
        now = time.monotonic()
        if now - t_window >= seconds:
            break
        if traced and tracing is None and now - t_window >= (seconds - trace_s) / 2:
            start_trace(trace_dir)
            profiler_s += time.monotonic() - now
            tracing = contextlib.ExitStack()
            tracing.enter_context(span("window"))
            trace_window = [time.monotonic(), None]
        a = time.monotonic()
        with span("step", tracing is not None):
            loss = step(*ring[i % len(ring)])
            jax.block_until_ready(loss)
        b = time.monotonic()
        spans.append((a, b))
        losses.append(loss)
        i += 1
        if tracing is not None and trace_window[1] is None \
                and b - trace_window[0] >= trace_s:
            tracing.close()
            trace_window[1] = time.monotonic()
            jax.profiler.stop_trace()
            profiler_s += time.monotonic() - trace_window[1]
    t_end = time.monotonic()
    if tracing is not None and trace_window[1] is None:
        tracing.close()
        trace_window[1] = time.monotonic()
        jax.profiler.stop_trace()

    losses = [float(x) for x in losses]
    steps = len(spans)
    window = t_end - t_window - profiler_s
    tokens_per_s = batch * seq * steps / window
    head, tail = losses[:8], losses[-8:]
    recompiled = compiles() - compiles0
    routes = pallas_routes()
    fell_back = {k: v for k, v in routes.items() if v.get("fallback")}
    kernels_ok = True
    if on_chip and not mesh_shape:
        kernels_ok = not fell_back and all(
            routes.get(k, {}).get("pallas", 0) >= 1
            for k in config.get("kernels", []))
    correct = (loss_err <= LOSS_ATOL and all(np.isfinite(losses))
               and steps >= 16 and np.mean(tail) < np.mean(head)
               and not recompiled and kernels_ok)
    per_token = train_flops_per_token(n_params, cfg.num_layers, seq,
                                      cfg.hidden_size)
    values = {"train_tokens_per_s": tokens_per_s,
              "first_call_s": first_call_s}
    if ctx["peak"]:
        values["train_mfu_pct"] = 100.0 * tokens_per_s * per_token / (
            ctx["chips"] * ctx["peak"]["flops_bf16"])
    notes = {"steps": steps, "loss_first": float(np.mean(head)),
             "loss_last": float(np.mean(tail)), "loss_err": loss_err,
             "eval_loss": got, "reference_loss": want,
             "window_compiles": recompiled, "pallas_routes": routes,
             "n_params": n_params}
    return {"correct": bool(correct), "attempted": steps, "failed": 0,
            "values": values, "counters": {"steps": steps},
            "spans": {"step": spans}, "t_window": t_window, "notes": notes,
            "trace_dir": trace_dir if traced else None,
            "trace_window": trace_window,
            "model": {"heads": cfg.num_attention_heads,
                      "head_dim": cfg.hidden_size // cfg.num_attention_heads,
                      "layers": cfg.num_layers, "batch": batch, "seq": seq}}
