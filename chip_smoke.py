"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the two main paths once, through the entry points a
user calls, at the full width and depth of GPT-345M with random weights
made from a seed:

 - *train*: ``GPTForCausalLM`` under bf16 AMP O2 + AdamW, the loop body
   under ``@pt.jit.capture_step`` as README "Eager fast path" writes it;
 - *serve*: ``ServingEngine`` with its AOT ladder and scheduler thread,
   a handful of requests through ``scheduler.submit``;
 - *four chips* (only where ``jax.device_count() >= 4``): the dp2 x mp2
   ``build_train_step`` against a one-chip oracle.

Every phase asserts what it produced and any failure fails the run (no
``try`` around a phase).  The script refuses to start unless
``jax.devices()[0].platform == "tpu"``: it exits 2 and prints nothing on
stdout.  ``--rehearse-on-cpu`` runs the same phases at tiny widths to
debug the plumbing; it must be asked for, is never inferred from a
missing chip, and labels every line it prints as not a chip run.

The last line of stdout is one JSON object, ``{"ok": true, "device":
{"platform", "kind", "count"}}``, with the device as jax reports it.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import numpy as np

# Full size: gpt_345m (incubate/models/gpt.py) at sequence 1024, and the
# same widths through the serving stack.  Batch 8 without recompute fits
# the 16 GB v5e under capture (chip run, PR 21 — see PERF.md); batch 16
# was not tried.
FULL = {
    "train": {
        "model": dict(vocab_size=50304, hidden_size=1024, num_layers=24,
                      num_attention_heads=16, max_position_embeddings=1024),
        "batch": 8, "seq": 1024, "steps": 6, "lr": 1e-4,
    },
    "serve": {
        "spec": dict(vocab_size=50304, hidden=1024, layers=24, heads=16,
                     max_seq_len=1024),
        # the engine configuration the benchmark's serve cells face
        # (benchmarks/configs/gpt-345m-serve.json): seven programs
        "prefill_buckets": (128, 256, 512, 1024),
        "decode_buckets": (8, 16, 32),
        "kv_pages": 1024, "page_size": 16,
        "prompt_lens": (100, 500), "requests": 8, "max_new": 32,
    },
    # global batch 4: the one-chip oracle holds the whole model plus the
    # phase's leftovers on device 0, so it gets half the train batch
    "four_chips": {"batch": 4, "steps": 3, "lr": 1e-4},
}
# Rehearsal size: the same code at toy widths, CPU-debuggable in seconds.
TINY = {
    "train": {
        "model": dict(vocab_size=1024, hidden_size=128, num_layers=2,
                      num_attention_heads=4, max_position_embeddings=128),
        "batch": 2, "seq": 128, "steps": 6, "lr": 1e-3,
    },
    "serve": {
        "spec": dict(vocab_size=256, hidden=64, layers=2, heads=4,
                     max_seq_len=128),
        "prefill_buckets": (16, 32, 64), "decode_buckets": (2, 4),
        "kv_pages": 64, "page_size": 8,
        "prompt_lens": (10, 50), "requests": 4, "max_new": 8,
    },
    "four_chips": {"batch": 4, "steps": 3, "lr": 1e-3},
}

# |kernel - reference| bound for paged attention on f32 pages; the
# reference runs at "highest" matmul precision, the kernel contracts in
# f32, so what is left is summation order
PAGED_ATOL = 1e-4
# |four-chip step-0 loss - one-chip step-0 loss|: same weights, same
# batch, dropout off.  GPTPretrainingCriterion returns a bf16 loss, so
# the bound is two bf16 ulps at the ~ln(vocab) = 10.8 it starts from
FOUR_CHIP_LOSS_ATOL = 0.125


def report(phase, rehearsal, **facts):
    """One JSON line of printed facts per phase."""
    rec = {"phase": phase}
    if rehearsal:
        rec["rehearsal"] = "cpu run at tiny widths: NOT a chip run"
    rec.update(facts)
    print(json.dumps(rec), flush=True)


def device_facts():
    import jax
    import jaxlib
    from paddle_tpu import core
    from importlib import metadata
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    dev = jax.devices()[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": jax.device_count(), "jax": jax.__version__,
            "jaxlib": jaxlib.__version__, "libtpu": libtpu,
            "native_core": core.native_available()}


def peak_bytes():
    """Process-lifetime allocator peak of device 0 (None where the
    backend keeps no stats, i.e. the CPU)."""
    import jax
    stats = jax.devices()[0].memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def pallas_routes():
    """{kernel: {path: count}} from pt_pallas_calls_total."""
    from paddle_tpu.observability.metrics import get_registry
    c = get_registry().counter(
        "pt_pallas_calls_total", labelnames=("kernel", "path"))
    routes: dict = {}
    for (kernel, path), n in c.snapshot_values().items():
        routes.setdefault(kernel, {})[path] = int(n)
    return routes


def assert_routes(routes, expected_kernels):
    """On the chip every kernel the dispatch selects must have gone
    through Pallas, and nothing may have been routed to a fallback."""
    for kernel in expected_kernels:
        assert routes.get(kernel, {}).get("pallas", 0) >= 1, \
            f"{kernel} was not dispatched to Pallas: {routes}"
    fell_back = {k: v for k, v in routes.items() if v.get("fallback")}
    assert not fell_back, f"kernels routed to a fallback: {fell_back}"


def _gpt_batch(vocab, batch, seq):
    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, (batch, seq)).astype(np.int32)
    labels = rng.randint(0, vocab, (batch, seq)).astype(np.int32)
    return ids, labels


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------
def train_phase(size, rehearsal):
    import jax
    import paddle_tpu as pt
    from paddle_tpu.incubate.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.observability.trace import get_tracer, peak_flops

    tracer = get_tracer()
    pt.seed(0)
    cfg = GPTConfig(tensor_parallel=False, **size["model"])
    model = GPTForCausalLM(cfg)
    pt.amp.decorate(model, level="O2", dtype="bfloat16")
    opt = pt.optimizer.AdamW(learning_rate=size["lr"],
                             parameters=model.parameters(),
                             multi_precision=True)
    ce = pt.nn.CrossEntropyLoss()
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())

    @pt.jit.capture_step
    def step(ids, labels):
        loss = ce(model(ids), labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    batch, seq, steps = size["batch"], size["seq"], size["steps"]
    with tracer.phase("data_wait"):
        ids, labels = (pt.to_tensor(a) for a in
                       _gpt_batch(cfg.vocab_size, batch, seq))
    losses, times = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        loss = step(ids, labels)
        jax.block_until_ready(loss._data)
        times.append(time.perf_counter() - t0)
        losses.append(float(loss))

    stats = step.stats
    assert (stats["compiles"], stats["fallback"], stats["hits"]) == \
        (1, None, steps - 1), f"capture did not hold: {stats}"
    assert all(np.isfinite(losses)), f"non-finite loss: {losses}"
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    spans = tracer.spans()
    assert any(s.name == "data_wait" for s in spans), \
        "tracer.phase() recorded nothing"
    assert any(s.cat == "compute" for s in spans), \
        "no captured-step compute span"
    routes = pallas_routes()
    if not rehearsal:
        assert_routes(routes, ("flash_mha", "fused_layer_norm",
                               "fused_softmax_xent"))

    facts = {"batch": batch, "seq": seq, "n_params": n_params,
             "losses": [round(x, 4) for x in losses],
             "capture": {k: stats[k] for k in ("compiles", "hits",
                                               "fallback")},
             "pallas_routes": routes,
             "spans": len(spans)}
    if not rehearsal:
        warm = float(np.median(times[1:]))
        tok_s = batch * seq / warm
        # 6N per token fwd+bwd plus causal attention 6*L*S*H; recomputed
        # work is not counted (there is none here)
        per_token = 6 * n_params + 6 * cfg.num_layers * seq * cfg.hidden_size
        kind = jax.devices()[0].device_kind
        facts.update(
            # trace + lower + compile (or cache load) + step 1
            first_call_s=round(times[0], 2),
            warm_step_ms=round(warm * 1e3, 2),
            tokens_per_s=round(tok_s, 1),
            mfu_model=round(tok_s * per_token
                            / peak_flops(kind, strict=True), 4),
            peak_bytes_in_use=peak_bytes())
    report("train", rehearsal, **facts)


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------
def serve_phase(size, rehearsal):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.observability.telemetry import get_telemetry
    from paddle_tpu.ops.paged_attention import (paged_attention,
                                                paged_attention_reference)
    from paddle_tpu.serving import (ModelSpec, ServeConfig, ServingEngine,
                                    init_params)

    spec = ModelSpec(**size["spec"])
    cfg = ServeConfig(decode_buckets=size["decode_buckets"],
                      prefill_buckets=size["prefill_buckets"],
                      kv_pages=size["kv_pages"],
                      page_size=size["page_size"],
                      max_new_tokens=size["max_new"])
    params = init_params(spec, seed=0)
    jax.block_until_ready(params)

    # the compile watcher parses jax's own compile log; count what it
    # sees during the AOT build, or its later silence proves nothing
    tel = get_telemetry()
    watched = []

    def on_compile(name, signature=""):
        watched.append(name)

    tel.add_compile_listener(on_compile)
    t0 = time.perf_counter()
    engine = ServingEngine(spec, params, cfg)
    build_s = time.perf_counter() - t0
    tel.remove_compile_listener(on_compile)
    aot_watched = sum(n.startswith("serve_") for n in watched)
    assert aot_watched >= engine.compiled_programs, (
        f"compile watcher saw {aot_watched} serve compiles for "
        f"{engine.compiled_programs} AOT programs: {watched}")

    # what each program keeps in memory (the engine's counter): the pools
    # aliased through, and on the chip no temporary near a pool's size —
    # a copied, sliced or re-laid pool would show here
    program_bytes = engine.stats["program_bytes"]
    pool_bytes = int(engine.pool.k_pool.nbytes)
    for name, got in sorted(program_bytes.items()):
        print(f"[chip_smoke] serve program bytes {name}: "
              f"temp={got['temp']} argument={got['argument']} "
              f"alias={got['alias']} (one pool: {pool_bytes})", flush=True)
    assert len(program_bytes) == engine.compiled_programs, program_bytes
    if not rehearsal:
        for name, got in program_bytes.items():
            assert got["alias"] >= 2 * pool_bytes, (name, got)
            assert got["temp"] < pool_bytes / 10, (
                f"{name} holds {got['temp']} bytes of temporaries beside "
                f"pools of {pool_bytes}: a pool is being copied")

    rng = np.random.RandomState(1)
    lo, hi = size["prompt_lens"]
    prompts = [rng.randint(0, spec.vocab_size,
                           int(rng.randint(lo, hi + 1))).tolist()
               for _ in range(size["requests"])]
    engine.scheduler.start()
    try:
        t0 = time.perf_counter()
        streams = [engine.scheduler.submit(p, max_new_tokens=size["max_new"])
                   for p in prompts]
        outs = [st.result(timeout=300.0) for st in streams]
        serve_s = time.perf_counter() - t0
    finally:
        engine.scheduler.stop()
    health = engine.healthz()
    engine.close()

    assert [len(o) for o in outs] == [size["max_new"]] * len(prompts), \
        f"wrong token counts: {[len(o) for o in outs]}"
    assert all(0 <= t < spec.vocab_size for o in outs for t in o)
    assert health["ok"], f"engine unhealthy: {health}"
    assert engine.unexpected_compiles == 0, \
        f"{engine.unexpected_compiles} request-path compiles"

    # the kernel against its reference at the served shape (after the
    # engine is closed: these two programs are not request-path compiles)
    b = max(size["decode_buckets"])
    ps, pages = size["page_size"], size["kv_pages"]
    maxp = engine.max_pages_per_seq
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(kq, (b, spec.heads, spec.head_dim), jnp.float32)
    # whole pools as the engine holds them, (L, P, ps, H*D), two layers
    # deep, and the second layer read: the kernel's block picks the layer
    k_pool = jax.random.normal(kk, (2, pages, ps, spec.hidden), jnp.float32)
    v_pool = jax.random.normal(kv, (2, pages, ps, spec.hidden), jnp.float32)
    tables = jnp.asarray(rng.randint(1, pages, (b, maxp)), jnp.int32)
    lengths = jnp.asarray(rng.randint(1, maxp * ps + 1, (b,)), jnp.int32)
    # the rehearsal forces the kernel (interpret mode) so its numerics
    # are checked off the chip too; on the chip it is the default route
    force = {"use_pallas": True} if rehearsal else {}
    got = jax.jit(lambda *a: paged_attention(*a, layer=1, **force))(
        q, k_pool, v_pool, tables, lengths)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda *a: paged_attention_reference(*a, layer=1))(
            q, k_pool, v_pool, tables, lengths)
    err = float(jnp.max(jnp.abs(got - want)))
    assert err <= PAGED_ATOL, f"paged attention off its reference by {err}"
    routes = pallas_routes()
    if not rehearsal:
        assert_routes(routes, ("paged_attention",))

    facts = {"programs": engine.compiled_programs,
             "aot_compiles_watched": aot_watched,
             "unexpected_compiles": engine.unexpected_compiles,
             "requests": len(prompts),
             "prompt_lens": [len(p) for p in prompts],
             "new_tokens": size["max_new"],
             "paged_attention_max_err": err, "pallas_routes": routes,
             "program_bytes": program_bytes}
    if not rehearsal:
        n_tok = len(prompts) * size["max_new"]
        facts.update(aot_build_s=round(build_s, 2),
                     serve_wall_s=round(serve_s, 3),
                     ms_per_generated_token=round(serve_s / n_tok * 1e3, 3),
                     peak_bytes_in_use=peak_bytes())
    report("serve", rehearsal, **facts)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------
def four_chip_phase(size, model_kw, seq, rehearsal):
    import jax
    if jax.device_count() < 4:
        report("four_chips", rehearsal,
               skipped=f"{jax.device_count()} device(s)")
        return
    import paddle_tpu as pt
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.train_step import build_train_step
    from paddle_tpu.incubate.models import (GPTConfig, GPTForCausalLM,
                                            GPTPretrainingCriterion)

    mesh = dist.init_mesh({"dp": 2, "mp": 2})
    pt.seed(0)
    # the loss-parity oracle needs deterministic math: dropout off
    cfg = GPTConfig(tensor_parallel=True, hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0, **model_kw)
    model = GPTForCausalLM(cfg)
    pt.amp.decorate(model, level="O2", dtype="bfloat16")
    crit = GPTPretrainingCriterion()
    ids, labels = _gpt_batch(cfg.vocab_size, size["batch"], seq)

    def run(mesh, steps):
        opt = pt.optimizer.AdamW(learning_rate=size["lr"],
                                 parameters=model.parameters(),
                                 multi_precision=True)
        step, state = build_train_step(model, crit, opt, mesh=mesh)
        losses, times = [], []
        for _ in range(steps):
            t0 = time.perf_counter()
            loss, state = step(state, ids, labels)
            jax.block_until_ready(loss)
            times.append(time.perf_counter() - t0)
            losses.append(float(loss))
        return losses, times, state

    losses, times, state = run(mesh, size["steps"])
    assert all(np.isfinite(losses)), f"non-finite loss: {losses}"
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    # every chip must hold its share: an mp-sharded weight spans all four
    # devices (2 mp shards x 2 dp replicas) and each allocator is in use
    w = state["params"]["gpt.layers.0.attn.qkv_proj.weight"]
    assert "mp" in str(w.sharding.spec), f"qkv not mp-sharded: {w.sharding}"
    holders = {s.device for s in w.addressable_shards}
    mesh_devs = set(mesh.devices.flat)
    assert holders == mesh_devs and len(holders) == 4, \
        f"qkv shards on {holders}, mesh is {mesh_devs}"
    in_use = {}
    for d in mesh_devs:
        stats = d.memory_stats()
        if stats:  # the CPU backend keeps no allocator stats
            in_use[str(d.id)] = stats["bytes_in_use"]
            assert stats["bytes_in_use"] > 0, f"device {d} holds nothing"
    del state
    gc.collect()

    mesh1 = dist.init_mesh({"dp": 1}, devices=jax.devices()[:1])
    ref_losses, _, _ = run(mesh1, 1)
    delta = abs(losses[0] - ref_losses[0])
    assert delta <= FOUR_CHIP_LOSS_ATOL, (
        f"step-0 loss {losses[0]} on dp2 x mp2 vs {ref_losses[0]} on "
        f"one chip: off by {delta}")

    # under the GSPMD mesh dispatch takes the Pallas kernels off (Mosaic
    # kernels cannot be partitioned); the one-chip oracle runs them
    facts = {"mesh": {"dp": 2, "mp": 2}, "batch": size["batch"],
             "seq": seq, "losses": [round(x, 4) for x in losses],
             "pallas_routes": pallas_routes(),
             "one_chip_step0_loss": round(ref_losses[0], 4),
             "step0_loss_delta": round(delta, 5),
             "bytes_in_use_per_device": in_use}
    if not rehearsal:
        facts.update(first_call_s=round(times[0], 2),
                     warm_step_ms=round(
                         float(np.median(times[1:])) * 1e3, 2))
    report("four_chips", rehearsal, **facts)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--rehearse-on-cpu", action="store_true",
        help="debug the plumbing at tiny widths without a chip; the "
             "output is labelled as not a chip run")
    args = ap.parse_args(argv)
    rehearsal = args.rehearse_on_cpu

    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu" and not rehearsal:
        print(f"chip_smoke: no TPU — jax.devices()[0].platform is "
              f"{platform!r}; this script only runs on the chip "
              "(--rehearse-on-cpu debugs the plumbing at tiny widths)",
              file=sys.stderr)
        return 2

    from paddle_tpu.device import place_compile_cache
    from paddle_tpu.observability.telemetry import get_telemetry
    from paddle_tpu.observability.trace import get_tracer
    cache_dir = place_compile_cache()
    get_telemetry().enable()   # metrics + the compile watcher
    get_tracer().enable()      # spans
    size = TINY if rehearsal else FULL
    report("start", rehearsal, compile_cache_dir=cache_dir,
           **device_facts())

    train_phase(size["train"], rehearsal)
    gc.collect()
    serve_phase(size["serve"], rehearsal)
    gc.collect()
    four_chip_phase(size["four_chips"], size["train"]["model"],
                    size["train"]["seq"], rehearsal)

    dev = jax.devices()[0]
    final = {"ok": True, "device": {"platform": dev.platform,
                                    "kind": dev.device_kind,
                                    "count": len(jax.devices())}}
    if rehearsal:
        final["rehearsal"] = "cpu run at tiny widths: NOT a chip run"
    print(json.dumps(final), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
