"""Benchmark entry (runs on the TPU; ``BENCH_SMOKE=1`` is the tiny-shape
CPU structure check).

Measures BASELINE.md configs on a single chip:
 - configs[0]: ResNet-50 training throughput, CIFAR-10-shaped data
   (batch 256, 3x32x32), images/sec  -> the headline "value".
 - configs[3]-class: GPT-345M causal-LM training, seq 1024, bf16 AMP,
   tokens/sec/chip + MFU — the transformer fast path the framework is for.
 - BERT-base finetune step, ring attention at S=8192, and the packed
   ragged-varlen flash kernel vs its padded equivalent.

Each train step (forward + backward + optimizer update) is ONE jitted XLA
program with bf16 AMP. MFU comes from XLA's own cost analysis vs the chip's
public bf16 peak (plus the analytic 6N model MFU for GPT, since XLA cannot
see Pallas FLOPs).

One process per chip: the PARENT NEVER INITIALIZES JAX (asserted before
the first spawn); every device-touching leg runs in its own subprocess,
one at a time, sharing the persistent compile cache
(``paddle_tpu.device.place_compile_cache``).  The first leg reads the
device's identity; outside ``BENCH_SMOKE`` a platform other than ``tpu``
ends the run at once with a non-zero exit — there is no CPU
continuation.  The merged JSON line is re-printed after every leg, so
the last stdout line always carries every number measured so far.  The
exit code is 0 only when every leg that ran succeeded.

Prints its json line (last line = most complete):
{"metric", "value", "unit", "platform", "device_kind", ...}.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
import traceback
from paddle_tpu.device import enable_overlap_flags as _enable_overlap_flags
from paddle_tpu.distributed._jax_compat import shard_map as _shard_map, use_mesh as _use_mesh

# latency-hiding-scheduler / async-collective flags must precede backend
# init; idempotent + env-gated, inert off TPU (device/xla_flags.py)
_enable_overlap_flags()

SMOKE = bool(os.environ.get("BENCH_SMOKE"))  # tiny-shape CI structure check
RESNET_BATCH = 8 if SMOKE else 256
GPT_SEQ = 64 if SMOKE else 1024
BERT_SEQ = 128
WARMUP = 1 if SMOKE else 5
ITERS = 2 if SMOKE else 15       # steps per timed block
BLOCKS = 1 if SMOKE else 3       # timed blocks -> min/median/max spread

_HERE = os.path.dirname(os.path.abspath(__file__))
_GPT_CACHE = os.path.join(_HERE, ".bench_gpt_best.json")

# Wall-clock budget for the whole script: finish inside it and print
# what we have.
BUDGET_SEC = float(os.environ.get("BENCH_BUDGET_SEC",
                                  "900" if SMOKE else "2700"))

# Per-leg watchdog timeouts (seconds): a cold GPT-345M compile plus
# ~3 blocks * 15 steps of timing.
_T = (lambda full, smoke: smoke if SMOKE else full)
LEG_TIMEOUT = {
    "device": _T(180, 120),
    "resnet": _T(600, 300), "gpt": _T(900, 300), "bert": _T(600, 300),
    "ring": _T(600, 300), "packed": _T(600, 300), "kernels": _T(600, 300),
}

# bf16 peak FLOP/s per chip: the ONE shared table lives in
# observability.trace (PEAK_FLOPS) so bench records and the
# pt_mfu_analytic gauge can never disagree about a chip's peak
from paddle_tpu.observability.trace import peak_flops  # noqa: E402


def _peak_flops(device_kind):
    """Strict: a device that is not in the peak table is an error."""
    return peak_flops(device_kind, strict=True)


def _error_tail(tb: str) -> str:
    """Last *informative* line of a traceback: jax/XLA errors often end
    with decorative ===/--- rules (the BENCH_r03 gpt error recorded just
    '==========' before this existed)."""
    lines = [ln.strip() for ln in tb.strip().splitlines()]
    for ln in reversed(lines):
        if ln and any(c.isalnum() for c in ln):
            return ln[:400]
    return (lines[-1] if lines else "")[:400]


def _is_oom_str(s: str) -> bool:
    return any(t in s for t in (
        "RESOURCE_EXHAUSTED", "Resource exhausted", "out of memory",
        "Out of memory", "OOM", "Allocation failure",
        "exceeds the memory capacity", "exceeds available memory"))


def _flops_per_step(compiled):
    """Model FLOPs per step from XLA's own cost analysis (None if n/a)."""
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        return float(ca.get("flops", 0.0)) or None
    except Exception:
        return None


def _memory_report(compiled):
    """Per-step HBM footprint from XLA's memory analysis (the L1
    peak-memory reporting: arguments = resident state, temp = activation
    working set)."""
    try:
        ma = compiled.memory_analysis()
        return {
            "argument_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
            "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
            "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
        }
    except Exception:
        return None


def _feed_tracer(program, flops, step_seconds):
    """Feed the step tracer the leg's measured program cost so the
    record's ``trace`` block (and pt_mfu_analytic) agrees with the
    leg's own MFU arithmetic."""
    from paddle_tpu.observability.trace import get_tracer
    tr = get_tracer()
    if not tr.enabled:
        return
    if flops:
        tr.record_program_flops(program, flops)
    if step_seconds:
        tr.on_step(step_seconds)


def _stamp_device(result):
    """platform / device_kind / device count, as the device reports them.
    Outside BENCH_SMOKE anything but a TPU is an error."""
    import jax
    dev = jax.devices()[0]
    result["platform"] = dev.platform
    result["device_kind"] = dev.device_kind
    result["device_count"] = jax.device_count()
    if dev.platform != "tpu" and not SMOKE:
        raise RuntimeError(
            f"bench needs a TPU: jax.devices()[0].platform is "
            f"{dev.platform!r} (BENCH_SMOKE=1 is the CPU structure check)")


def _time_compiled(compiled, args, n_state):
    """Warmup + BLOCKS timed blocks of ITERS steps, each fenced by
    ``jax.block_until_ready``. The step's first n_state outputs feed
    back as its first n_state inputs (fresh buffers every call).
    Returns (per_step_seconds_list, final_out)."""
    import jax
    state = list(args[:n_state])
    rest = list(args[n_state:])
    out = None
    for _ in range(WARMUP):
        out = compiled(*state, *rest)
        state = list(out[1:1 + n_state])
    jax.block_until_ready(out)
    times = []
    for _ in range(BLOCKS):
        t0 = time.perf_counter()
        for _ in range(ITERS):
            out = compiled(*state, *rest)
            state = list(out[1:1 + n_state])
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) / ITERS)
    from paddle_tpu.observability import get_telemetry
    tel = get_telemetry()
    for t in times:  # block-averaged step times -> step histogram/p50/p95
        tel.observe_step(t, mode="bench")
    return times, out


def _spread_ms(times):
    s = sorted(t * 1000 for t in times)
    return {"min": round(s[0], 2), "median": round(s[len(s) // 2], 2),
            "max": round(s[-1], 2)}


def _cluster_snapshot():
    """Aggregated cluster view for the record: skew, per-rank step
    p50/p95, total recompiles — from a running aggregator when
    PT_AGGREGATOR_URL is set, else a single-rank local summary.  Must
    never sink a bench run: failures come back as {"error": ...}."""
    try:
        from paddle_tpu.observability import cluster_snapshot
        return cluster_snapshot(
            url=os.environ.get("PT_AGGREGATOR_URL") or None)
    except Exception as e:  # snapshot is best-effort by contract
        return {"error": str(e)[:200]}


# ---------------------------------------------------------------------------
# Legs (each runs inside its own subprocess; writes into `result`)
# ---------------------------------------------------------------------------

def leg_device(result):
    """First leg: the device's identity (stamped by ``_leg_main``) and a
    tiny matmul that proves the chip computes. Cheap on purpose."""
    import jax
    import jax.numpy as jnp
    x = jnp.ones((256, 256), jnp.bfloat16)
    y = jax.jit(lambda a: a @ a)(x)
    assert float(y[0, 0]) == 256.0


def bench_resnet(result):
    import numpy as np
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.jit.api import functional_call
    from paddle_tpu.tensor import Tensor

    pt.seed(0)
    net = pt.vision.models.resnet50(num_classes=10)
    pt.amp.decorate(net, level="O2", dtype="bfloat16")
    opt = pt.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                parameters=net.parameters(),
                                multi_precision=True)
    params = {k: p._data for k, p in net.named_parameters()}
    buffers = {k: b._data for k, b in net.named_buffers()}
    opt_state = opt.init_state_tree(params)
    fwd = getattr(net, "_orig_forward", net.forward)

    def train_step(params, buffers, opt_state, x, y):
        def loss_of(p):
            out, new_buffers = functional_call(
                net, p, buffers, (Tensor(x),), training=True, forward_fn=fwd)
            logits = out._data.astype(jnp.float32)
            logp = jax.nn.log_softmax(logits)
            loss = -jnp.take_along_axis(logp, y[:, None], axis=1).mean()
            return loss, new_buffers

        (loss, new_buffers), grads = jax.value_and_grad(
            loss_of, has_aux=True)(params)
        new_params, new_opt = opt.apply_gradients_tree(params, grads,
                                                       opt_state)
        return loss, new_params, new_buffers, new_opt

    step = jax.jit(train_step, donate_argnums=(0, 1, 2))
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(RESNET_BATCH, 3, 32, 32)
                    .astype(np.float32)).astype(jnp.bfloat16)
    y = jnp.asarray(rng.randint(0, 10, RESNET_BATCH).astype(np.int32))

    t0 = time.perf_counter()
    compiled = step.lower(params, buffers, opt_state, x, y).compile()
    result["resnet50_compile_sec"] = round(time.perf_counter() - t0, 2)
    flops = _flops_per_step(compiled)
    result["resnet50_flops_per_step"] = flops
    result["resnet50_memory"] = _memory_report(compiled)

    times, _ = _time_compiled(compiled, (params, buffers, opt_state, x, y),
                              3)
    result["resnet50_step_ms"] = _spread_ms(times)
    step = sorted(times)[len(times) // 2]
    ips = RESNET_BATCH / step
    result["value"] = round(ips, 2)
    peak = _peak_flops(result.get("device_kind"))
    if flops and peak:
        result["mfu"] = round(flops / step / peak, 4)
    _feed_tracer("resnet50_step", flops, step)
    return ips


def bench_gpt(result, batch, recompute=True):
    """GPT-345M-class train step (bf16, seq 1024) — tokens/sec/chip + MFU."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.jit.api import functional_call
    from paddle_tpu.tensor import Tensor
    from paddle_tpu.incubate.models import (GPTForCausalLM,
                                            GPTPretrainingCriterion,
                                            gpt_345m)

    pt.seed(0)
    if SMOKE:
        from paddle_tpu.incubate.models import gpt_tiny
        cfg = gpt_tiny(tensor_parallel=False, use_recompute=recompute)
    else:
        cfg = gpt_345m(tensor_parallel=False, use_recompute=recompute,
                       max_position_embeddings=GPT_SEQ)
    result["gpt345m_recompute"] = recompute
    model = GPTForCausalLM(cfg)
    pt.amp.decorate(model, level="O2", dtype="bfloat16")
    crit = GPTPretrainingCriterion()
    opt = pt.optimizer.AdamW(learning_rate=1e-4,
                             parameters=model.parameters(),
                             multi_precision=True)
    params = {k: p._data for k, p in model.named_parameters()}
    buffers = {k: b._data for k, b in model.named_buffers()}
    opt_state = opt.init_state_tree(params)
    fwd = getattr(model, "_orig_forward", model.forward)
    n_params = sum(int(np.prod(p.shape)) for p in params.values())
    result["gpt345m_n_params"] = n_params

    def train_step(params, buffers, opt_state, ids, labels):
        def loss_of(p):
            out, new_buffers = functional_call(
                model, p, buffers, (Tensor(ids),), training=True,
                forward_fn=fwd)
            loss = crit(out, Tensor(labels))
            return loss._data.astype(jnp.float32), new_buffers

        (loss, new_buffers), grads = jax.value_and_grad(
            loss_of, has_aux=True)(params)
        new_params, new_opt = opt.apply_gradients_tree(params, grads,
                                                       opt_state)
        return loss, new_params, new_buffers, new_opt

    step = jax.jit(train_step, donate_argnums=(0, 1, 2))
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, GPT_SEQ))
                      .astype(np.int32))
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, GPT_SEQ))
                         .astype(np.int32))

    t0 = time.perf_counter()
    traced = step.trace(params, buffers, opt_state, ids, labels)
    compiled = traced.lower().compile()
    result["gpt345m_compile_sec"] = round(time.perf_counter() - t0, 2)
    # graph audit: the AOT trace above already holds the step jaxpr, so
    # the auditor costs zero extra traces here (compile-time only)
    from paddle_tpu.tools.audit import runtime as _audit
    if _audit.audit_enabled():
        from paddle_tpu.tools.audit.core import AuditProgram
        n_donated = len(jax.tree_util.tree_leaves(
            (params, buffers, opt_state)))
        _audit.audit_program(AuditProgram(
            name="bench_gpt_step", jaxpr=traced.jaxpr, kind="capture",
            donated=range(n_donated)))
    flops = _flops_per_step(compiled)
    result["gpt345m_flops_per_step"] = flops
    result["gpt345m_memory"] = _memory_report(compiled)

    times, _ = _time_compiled(compiled,
                              (params, buffers, opt_state, ids, labels), 3)
    result["gpt345m_step_ms"] = _spread_ms(times)
    step = sorted(times)[len(times) // 2]
    tps = batch * GPT_SEQ / step
    result["gpt345m_tokens_per_sec"] = round(tps, 1)
    result["gpt345m_batch"] = batch
    result["gpt345m_seq"] = GPT_SEQ
    peak = _peak_flops(result.get("device_kind"))
    if flops and peak:
        # hardware utilization per XLA's cost analysis. Caveat: custom
        # Pallas kernels (flash attention) report no flops to XLA, so
        # this undercounts when the flash path is active.
        result["gpt345m_mfu"] = round(flops / step / peak, 4)
    if peak:
        # standard analytic MFU: 6N per token fwd+bwd + causal attention
        # 6*L*S*H (recomputed FLOPs deliberately NOT counted — the
        # convention used by the public scaling literature)
        per_token = (6 * n_params
                     + 6 * cfg.num_layers * GPT_SEQ * cfg.hidden_size)
        result["gpt345m_mfu_model"] = round(tps * per_token / peak, 4)
    _feed_tracer("gpt345m_step", flops, step)
    return tps


def bench_bert(result, batch):
    """BERT-base SST-2-style finetune step (config[1]): seq/sec via the
    compiled (to_static-equivalent) path, bf16 AMP."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.jit.api import functional_call
    from paddle_tpu.tensor import Tensor
    from paddle_tpu.incubate.models import (BertForSequenceClassification,
                                            bert_base, bert_tiny)

    pt.seed(0)
    cfg = bert_tiny() if SMOKE else bert_base()
    model = BertForSequenceClassification(cfg, num_classes=2)
    pt.amp.decorate(model, level="O2", dtype="bfloat16")
    opt = pt.optimizer.AdamW(learning_rate=2e-5,
                             parameters=model.parameters(),
                             multi_precision=True)
    params = {k: p._data for k, p in model.named_parameters()}
    buffers = {k: b._data for k, b in model.named_buffers()}
    opt_state = opt.init_state_tree(params)
    fwd = getattr(model, "_orig_forward", model.forward)
    seq = 32 if SMOKE else BERT_SEQ

    def train_step(params, buffers, opt_state, ids, y):
        def loss_of(p):
            out, new_buffers = functional_call(
                model, p, buffers, (Tensor(ids),), training=True,
                forward_fn=fwd)
            logits = out._data.astype(jnp.float32)
            logp = jax.nn.log_softmax(logits)
            loss = -jnp.take_along_axis(logp, y[:, None], axis=1).mean()
            return loss, new_buffers

        (loss, new_buffers), grads = jax.value_and_grad(
            loss_of, has_aux=True)(params)
        new_params, new_opt = opt.apply_gradients_tree(params, grads,
                                                       opt_state)
        return loss, new_params, new_buffers, new_opt

    step = jax.jit(train_step, donate_argnums=(0, 1, 2))
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq))
                      .astype(np.int32))
    y = jnp.asarray(rng.randint(0, 2, batch).astype(np.int32))

    t0 = time.perf_counter()
    compiled = step.lower(params, buffers, opt_state, ids, y).compile()
    result["bert_base_compile_sec"] = round(time.perf_counter() - t0, 2)
    flops = _flops_per_step(compiled)
    result["bert_base_flops_per_step"] = flops
    result["bert_base_memory"] = _memory_report(compiled)

    times, _ = _time_compiled(compiled, (params, buffers, opt_state, ids, y),
                              3)
    result["bert_base_step_ms"] = _spread_ms(times)
    step = sorted(times)[len(times) // 2]
    sps = batch / step
    result["bert_base_seq_per_sec"] = round(sps, 1)
    result["bert_base_batch"] = batch
    result["bert_base_seq_len"] = seq
    peak = _peak_flops(result.get("device_kind"))
    if flops and peak:
        result["bert_base_mfu"] = round(flops / step / peak, 4)
    _feed_tracer("bert_base_step", flops, step)
    return sps


def bench_ring(result):
    """Ring-attention leg: the Pallas flash kernel driven through the
    shard_map ring schedule on the real chip (1-device mesh still
    exercises the kernel lowering + collective plumbing), S=8192.

    Also records the compiled program's temp bytes: ring attention's
    working set must stay O(S_local * block) — far below the O(S^2)
    logits buffer a dense attention would need at this length."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from paddle_tpu.distributed.auto_parallel.spec_layout import \
        default_layout
    from paddle_tpu.distributed.fleet.meta_parallel.sequence_parallel \
        import ring_attention

    B, H, S, D = 1, 16, 512 if SMOKE else 8192, 64
    mesh = Mesh(np.array(jax.devices()[:1]), ("sep",))
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32)).astype(
        jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32)).astype(
        jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32)).astype(
        jnp.bfloat16)

    def fwd_bwd(q, k, v):
        def loss(q):
            ring_spec = default_layout().seq_heads(ndim=4, seq_dim=2)
            out = _shard_map(
                lambda a, b, c: ring_attention(a, b, c, causal=True),
                mesh=mesh, in_specs=(ring_spec,) * 3,
                out_specs=ring_spec)(q, k, v)
            return jnp.sum(out.astype(jnp.float32)), out
        (s, out), dq = jax.value_and_grad(loss, has_aux=True)(q)
        return s, dq

    step = jax.jit(fwd_bwd)
    t0 = time.perf_counter()
    compiled = step.lower(q, k, v).compile()
    result["ring_attn_compile_sec"] = round(time.perf_counter() - t0, 2)
    result["ring_attn_memory"] = _memory_report(compiled)

    def run(qq):
        s, dq = compiled(qq, k, v)
        return s, (dq.astype(jnp.float32) * 1e-3).astype(qq.dtype)

    s, qq = run(q)
    jax.block_until_ready(s)
    iters = 2 if SMOKE else 8
    t0 = time.perf_counter()
    for _ in range(iters):
        s, qq = run(qq)
    jax.block_until_ready(s)
    ms = (time.perf_counter() - t0) / iters * 1000
    result["ring_attn_fwdbwd_ms"] = round(ms, 2)
    result["ring_attn_seq"] = S
    # sanity: the temp working set must be far below the O(S^2) dense
    # logits buffer (B*H*S*S bf16)
    mem = result.get("ring_attn_memory") or {}
    dense_logits_bytes = 2 * B * H * S * S
    result["ring_attn_temp_vs_dense_logits"] = round(
        mem.get("temp_bytes", 0) / dense_logits_bytes, 4) \
        if mem.get("temp_bytes") else None
    return ms


def bench_packed(result):
    """Packed ragged-varlen flash attention on the real chip.

    Mixed lengths 64..1024 (sum 3392 vs 8*1024=8192 padded tokens;
    sum len^2 is 3.6x below B*max^2), fwd+bwd through all three packed
    kernels (fwd/dq/dkv), vs the SAME data through the padded batched
    flash kernel. Valid rows of both paths must agree (parity recorded),
    and packed should win by skipping off-band tiles."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_ops import mha, mha_packed

    H, D = 16, 64
    lens = [16, 32, 48, 24] if SMOKE else [64, 128, 896, 256, 1024, 192,
                                           512, 320]
    B, mx = len(lens), max(lens)
    total = sum(lens)
    cu = jnp.asarray(np.concatenate([[0], np.cumsum(lens)]).astype(np.int32))
    rng = np.random.RandomState(0)
    qp = jnp.asarray(rng.randn(total, H, D).astype(np.float32)).astype(
        jnp.bfloat16)
    kp = jnp.asarray(rng.randn(total, H, D).astype(np.float32)).astype(
        jnp.bfloat16)
    vp = jnp.asarray(rng.randn(total, H, D).astype(np.float32)).astype(
        jnp.bfloat16)
    # the same tokens scattered to a padded (B, H, mx, D) batch (mha's
    # layout); advanced indexing at axes 0/2 broadcasts (total, H, D)
    rows = np.concatenate([np.full(L, i) for i, L in enumerate(lens)])
    cols = np.concatenate([np.arange(L) for L in lens])

    def pad_batch(x):
        buf = jnp.zeros((B, H, mx, D), x.dtype)
        return buf.at[rows, :, cols].set(x)

    qb, kb, vb = pad_batch(qp), pad_batch(kp), pad_batch(vp)

    interp = None if SMOKE else False  # SMOKE runs on CPU via interpret

    def packed_fb(q):
        def loss(q):
            out = mha_packed(q, kp, vp, cu, cu, causal=True,
                             interpret=interp)
            return jnp.sum(out.astype(jnp.float32)), out
        (s, out), dq = jax.value_and_grad(loss, has_aux=True)(q)
        return s, out, dq

    def padded_fb(q):
        def loss(q):
            out = mha(q, kb, vb, causal=True, interpret=interp)
            return jnp.sum(out.astype(jnp.float32)), out
        (s, out), dq = jax.value_and_grad(loss, has_aux=True)(q)
        return s, out, dq

    cpk = jax.jit(packed_fb).lower(qp).compile()
    cpd = jax.jit(padded_fb).lower(qb).compile()
    result["packed_varlen_memory"] = _memory_report(cpk)

    # parity on valid rows (fwd outputs; bf16 tolerance)
    _, op, _ = cpk(qp)
    _, ob, _ = cpd(qb)
    err = float(jnp.max(jnp.abs(
        op.astype(jnp.float32) - ob[rows, :, cols].astype(jnp.float32))))
    result["packed_varlen_parity_err"] = round(err, 4)

    def timed(compiled, q0):
        s, _, dq = compiled(q0)
        jax.block_until_ready(s)
        qq, iters = q0, 2 if SMOKE else 10
        t0 = time.perf_counter()
        for _ in range(iters):
            s, _, dq = compiled(qq)
            qq = (qq.astype(jnp.float32)
                  + dq.astype(jnp.float32) * 1e-3).astype(qq.dtype)
        jax.block_until_ready((s, qq))
        return (time.perf_counter() - t0) / iters * 1000

    ms_packed = timed(cpk, qp)
    ms_padded = timed(cpd, qb)
    result["packed_varlen_fwdbwd_ms"] = round(ms_packed, 2)
    result["padded_equiv_fwdbwd_ms"] = round(ms_padded, 2)
    result["packed_varlen_speedup"] = round(ms_padded / ms_packed, 2)
    result["packed_varlen_tokens_per_sec"] = round(
        total / (ms_packed / 1000), 1)
    result["packed_varlen_lens"] = lens
    return ms_packed


def bench_kernels(result):
    """Fusion-cluster microbench: each fused Pallas kernel vs the XLA
    lowering of its pure-jnp reference, fwd+bwd, at the bench models'
    shapes (GPT-345M hidden/vocab, BERT hidden, ResNet50 head). The
    autotuner searches launch configs first — winning config, search
    seconds, and timed/pruned counts ride on the record's ``autotune``
    block — then the timed runs consume the cached winners exactly like
    a real train step would."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import autotune as at
    from paddle_tpu.ops import fused_kernels as fk
    from paddle_tpu.ops.pallas_ops import mha, mha_reference, tune_mha

    interp = None if SMOKE else False  # SMOKE runs on CPU via interpret
    iters = 2 if SMOKE else 20
    rng = np.random.RandomState(0)
    kernels: dict = {}

    def fwdbwd_ms(fn, *args):
        f = jax.jit(jax.grad(
            lambda *a: jnp.sum(fn(*a).astype(jnp.float32))))
        jax.block_until_ready(f(*args))
        t0 = time.perf_counter()
        for _ in range(iters):
            g = f(*args)
        jax.block_until_ready(g)
        return (time.perf_counter() - t0) / iters * 1000

    def record(name, pallas_ms, xla_ms):
        kernels[name] = {"pallas_ms": round(pallas_ms, 3),
                         "xla_ms": round(xla_ms, 3),
                         "speedup": round(xla_ms / max(pallas_ms, 1e-9), 2)}

    # -- fused layernorm: GPT-345M and BERT token×hidden shapes --------
    ln_shapes = [("gpt345m", 8 * GPT_SEQ, 1024), ("bert", 32 * BERT_SEQ,
                                                  768)]
    for tag, rows, d in ln_shapes:
        if SMOKE:
            rows, d = min(rows, 512), min(d, 256)
        x = jnp.asarray(rng.randn(rows, d).astype(np.float32)).astype(
            jnp.bfloat16)
        w = jnp.ones((d,), jnp.bfloat16)
        b = jnp.zeros((d,), jnp.bfloat16)
        fk.tune_layer_norm(x, w, b, interpret=interp)
        record(f"fused_layer_norm_{tag}",
               fwdbwd_ms(lambda a: fk.fused_layer_norm(
                   a, w, b, interpret=interp), x),
               fwdbwd_ms(lambda a: fk.layer_norm_reference(a, w, b), x))

    # -- residual+LN (in-kernel add before the stats) at the same shapes
    for tag, rows, d in ln_shapes:
        if SMOKE:
            rows, d = min(rows, 512), min(d, 256)
        x = jnp.asarray(rng.randn(rows, d).astype(np.float32)).astype(
            jnp.bfloat16)
        r = jnp.asarray(rng.randn(rows, d).astype(np.float32)).astype(
            jnp.bfloat16)
        w = jnp.ones((d,), jnp.bfloat16)
        b = jnp.zeros((d,), jnp.bfloat16)
        record(f"residual_ln_{tag}",
               fwdbwd_ms(lambda a, rr: fk.fused_layer_norm(
                   a, w, b, residual=rr, interpret=interp), x, r),
               fwdbwd_ms(lambda a, rr: fk.layer_norm_reference(
                   a, w, b, residual=rr), x, r))

    # -- fused softmax-xent: GPT vocab, BERT vocab, ResNet50 head ------
    xe_shapes = [("gpt345m", 1024, 50304), ("bert", 1024, 30592),
                 ("resnet50_head", 256, 1000)]
    for tag, rows, V in xe_shapes:
        if SMOKE:
            rows, V = min(rows, 64), min(V, 512)
        logits = jnp.asarray(
            rng.randn(rows, V).astype(np.float32)).astype(jnp.bfloat16)
        lab = jnp.asarray(rng.randint(0, V, rows).astype(np.int32))
        fk.tune_softmax_xent(logits, lab, interpret=interp)
        record(f"fused_softmax_xent_{tag}",
               fwdbwd_ms(lambda a: fk.fused_softmax_xent(
                   a, lab, interpret=interp), logits),
               fwdbwd_ms(lambda a: fk.softmax_xent_reference(a, lab),
                         logits))

    # -- flash attention at the GPT-345M attention shape ---------------
    S = GPT_SEQ
    q, k, v = (jnp.asarray(rng.randn(1, 16, S, 64).astype(
        np.float32)).astype(jnp.bfloat16) for _ in range(3))
    tune_mha(q, k, v, causal=True, interpret=interp)
    record("flash_mha_gpt345m",
           fwdbwd_ms(lambda a: mha(a, k, v, causal=True,
                                   interpret=interp), q),
           fwdbwd_ms(lambda a: mha_reference(a, k, v, causal=True), q))

    result["kernels"] = kernels
    result["autotune"] = at.summary()
    return kernels


# ---------------------------------------------------------------------------
# Leg subprocess plumbing
# ---------------------------------------------------------------------------

def _leg_main(name, batch, recompute):
    """Child entry: run one leg, print one JSON line, exit 0 always
    (errors travel in the JSON)."""
    from paddle_tpu.device import place_compile_cache
    from paddle_tpu.observability import get_telemetry
    from paddle_tpu.observability.trace import get_tracer
    from paddle_tpu.observability.goodput import get_goodput
    from paddle_tpu.observability.numerics import get_monitor
    from paddle_tpu.observability.sdc import get_monitor as sdc_monitor
    from paddle_tpu.observability.memory import get_memory_monitor
    from paddle_tpu.tools.audit import runtime as audit_rt
    place_compile_cache()           # shared by every leg subprocess
    tel = get_telemetry().enable()  # metrics + compile watch, no sink/server
    tr = get_tracer().enable()      # span sink + analytic-MFU accounting
    gp = get_goodput().enable()     # wall-clock decomposition over spans
    mm = get_memory_monitor().enable()  # footprints + watermarks + OOM
    audit_rt.enable()               # graph audit at capture/serve compiles
    fields: dict = {}
    rec = {"ok": True, "fields": fields}
    legs = {
        "device": lambda: leg_device(fields),
        "resnet": lambda: bench_resnet(fields),
        "gpt": lambda: bench_gpt(fields, batch, recompute=recompute),
        "bert": lambda: bench_bert(fields, batch),
        "ring": lambda: bench_ring(fields),
        "packed": lambda: bench_packed(fields),
        "kernels": lambda: bench_kernels(fields),
    }
    try:
        _stamp_device(fields)  # raises off the TPU (outside BENCH_SMOKE)
        legs[name]()
    except Exception:  # boundary: the error travels in the leg's record
        tb = traceback.format_exc(limit=20)
        rec["ok"] = False
        rec["error"] = _error_tail(tb)
        rec["oom"] = _is_oom_str(tb)
    # health snapshot rides along even when the leg died: compile count,
    # step p50/p95, peak device memory at the moment of failure
    fields[f"telemetry_{name}"] = tel.snapshot()
    fields[f"trace_{name}"] = tr.snapshot()
    fields[f"goodput_{name}"] = gp.snapshot()
    fields[f"numerics_{name}"] = get_monitor().snapshot()
    fields[f"sdc_{name}"] = sdc_monitor().snapshot()
    fields[f"memory_{name}"] = mm.snapshot()
    fields[f"audit_{name}"] = audit_rt.snapshot()
    print(json.dumps(rec), flush=True)


def _run_leg(name, timeout, args=(), extra_env=None):
    """Run one leg in a watchdog-guarded subprocess; parse its JSON line.
    Never raises: returns {"ok": False, "error": ...} on any failure."""
    cmd = [sys.executable, os.path.abspath(__file__), "--leg", name,
           *map(str, args)]
    try:
        out = subprocess.run(cmd, capture_output=True, text=True,
                             timeout=timeout, cwd=_HERE,
                             env={**os.environ, **(extra_env or {})})
    except subprocess.TimeoutExpired:
        return {"ok": False, "error": f"watchdog timeout after {timeout}s",
                "timeout": True}
    for line in reversed(out.stdout.strip().splitlines()):
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                break
    tail = (out.stderr.strip().splitlines() or ["no output"])[-1][:400]
    return {"ok": False, "error": f"leg rc={out.returncode}: {tail}",
            "oom": _is_oom_str(out.stderr)}


def _gpt_ladder_start():
    """Known-good GPT config (the committed ``.bench_gpt_best.json``;
    read, never rewritten). Avoids burning a ~100 s compile every run
    to rediscover that (16, no-remat) OOMs a 16G chip."""
    with open(_GPT_CACHE) as f:
        c = json.load(f)
    return int(c["batch"]), bool(c["recompute"])


def main():
    if len(sys.argv) >= 3 and sys.argv[1] == "--leg":
        name = sys.argv[2]
        batch = int(sys.argv[3]) if len(sys.argv) > 3 else 0
        recompute = bool(int(sys.argv[4])) if len(sys.argv) > 4 else True
        _leg_main(name, batch, recompute)
        return

    t_start = time.time()
    errors: dict = {}
    result: dict = {
        "metric": "resnet50_cifar10_train_throughput",
        "value": None,
        "unit": "images/sec",
        "platform": None,
        "device_kind": None,
        "device_count": None,
        # master-weight precision of the headline training legs (the
        # gpt AMP leg casts compute to bf16 under O2 but keeps fp32
        # masters); serving precision lives on bench_serve records
        "precision": "fp32",
    }

    # parent-side telemetry: cheap (the parent never touches the device —
    # its snapshot proves that: 0 steps, 0 compiles, no device memory),
    # but it carries pid/health onto every emitted record
    from paddle_tpu.observability import get_telemetry
    from paddle_tpu.observability.trace import get_tracer
    from paddle_tpu.observability.goodput import get_goodput
    from paddle_tpu.observability.numerics import get_monitor
    from paddle_tpu.observability.sdc import get_monitor as sdc_monitor
    from paddle_tpu.observability.memory import get_memory_monitor
    from paddle_tpu.tools.audit import runtime as audit_rt
    from paddle_tpu.distributed.supervisor import supervision_snapshot
    tel = get_telemetry().enable()
    tr = get_tracer().enable()
    gp = get_goodput().enable()
    mm = get_memory_monitor().enable()
    audit_rt.enable()

    def remaining():
        return BUDGET_SEC - (time.time() - t_start)

    def emit():
        # partial emission: the last printed line always carries
        # everything measured so far
        if errors:
            result["errors"] = dict(errors)
        else:
            result.pop("errors", None)
        result["telemetry_driver"] = tel.snapshot()
        result["telemetry_cluster"] = _cluster_snapshot()
        # the parent never trains or compiles: these blocks are its own
        # (mostly empty) view; per-leg <block>_<leg> fields carry what
        # happened inside the leg subprocesses
        result["trace"] = tr.snapshot()
        result["goodput"] = gp.snapshot()
        result["numerics"] = get_monitor().snapshot()
        result["sdc"] = sdc_monitor().snapshot()
        result["memory"] = mm.snapshot()
        result["audit"] = audit_rt.snapshot()
        result["supervision"] = supervision_snapshot()
        print(json.dumps(result), flush=True)

    def merge(rec, stage):
        for k, v in (rec.get("fields") or {}).items():
            if v is not None or k not in result:
                result[k] = v
        if rec.get("ok"):
            errors.pop(stage, None)
        else:
            errors[stage] = rec.get("error") or "leg failed"
        emit()
        return bool(rec.get("ok"))

    def finish():
        result["bench_wall_sec"] = round(time.time() - t_start, 1)
        emit()
        sys.exit(1 if errors else 0)

    # one process per chip: a parent that has touched jax holds the chip
    # and every leg would fail or hang behind it
    from jax._src import xla_bridge
    assert not xla_bridge.backends_are_initialized(), \
        "bench parent initialized a jax backend before spawning its legs"

    # --- device leg first: identity, and off the TPU the run ends here
    if not merge(_run_leg("device", LEG_TIMEOUT["device"]), "device"):
        finish()

    # --- host-side dispatch microbench (CPU subprocess, no chip needed)
    def run_eager():
        out = subprocess.run(
            [sys.executable, os.path.join(_HERE, "bench_eager.py")],
            capture_output=True, text=True, timeout=300,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        if out.returncode != 0:
            raise RuntimeError(out.stderr.strip().splitlines()[-1][:200]
                               if out.stderr.strip()
                               else f"bench_eager rc={out.returncode}")
        return json.loads(out.stdout.strip().splitlines()[-1])

    try:
        eager = run_eager()
        result["eager_dispatch_us_per_op"] = {
            k: eager[k] for k in ("raw_jax", "tape_off", "tape_on",
                                  "jit_chain", "tape_overhead_ratio")
            if k in eager}
        result["trace_eager"] = eager.get("trace")
    except Exception:  # boundary: recorded, and fails the run's exit code
        errors["eager_dispatch"] = _error_tail(traceback.format_exc(limit=5))
    emit()

    def leg_budget(name):
        t = min(LEG_TIMEOUT[name], max(remaining() - 60, 0))
        return t if t >= 180 or SMOKE else 0

    def try_leg(name, stage=None, args=()):
        t = leg_budget(name)
        if t <= 0:
            errors[stage or name] = "skipped: bench budget exhausted"
            emit()
            return None
        rec = _run_leg(name, t, args=args)
        merge(rec, stage or name)
        return rec

    def oom_ladder(leg, rungs):
        """Try ``(stage, args)`` rungs in order, descending on OOM only
        (any other error is real: a smaller batch won't help). Rungs
        that OOMed above a success are returned, not kept as errors."""
        oomed = []
        for stage, args in rungs:
            rec = try_leg(leg, stage=stage, args=args)
            if rec is not None and not rec.get("ok") and rec.get("oom"):
                oomed.append(stage)
                continue
            if rec is not None and rec.get("ok"):
                for st in oomed:
                    errors.pop(st, None)
            break
        return oomed

    try_leg("resnet")

    # GPT ladder from the known-good rung. One config per subprocess
    # (two 345M step builds in one process OOM the 16G chip).
    rungs = [(8, False), (8, True), (4, True), (2, True)]
    start = _gpt_ladder_start()
    if start not in rungs:
        rungs.insert(0, start)  # hand-edited file: trust it first
    result["gpt345m_oom_rungs"] = oom_ladder(
        "gpt", [(f"gpt345m_b{b}_rc{int(rc)}", (b, int(rc)))
                for b, rc in rungs[rungs.index(start):]])

    try_leg("packed")
    try_leg("ring")
    try_leg("kernels")

    oom_ladder("bert", [(f"bert_b{b}", (b,)) for b in (32, 16, 8)])

    finish()


if __name__ == "__main__":
    main()
