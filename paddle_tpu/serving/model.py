"""Serve-side decoder model: pure functions over a paged KV-cache.

The engine AOT-compiles two program families over these functions
(:mod:`.engine`):

 - ``prefill`` — one sequence, one padded seq-bucket: run the prompt
   through the stack with a causal+length mask, scatter the prompt's
   K/V into the sequence's pages, emit the first generated token.
 - ``decode`` — one padded batch-bucket: one new token per row,
   append its K/V at the row's write slot, attend over the row's page
   list via :func:`paddle_tpu.ops.paged_attention.paged_attention`.

Everything is shaped by :class:`ModelSpec`, a plain dataclass that
round-trips through ``serve_config.json`` so a served model dir is
self-describing (the `paddle/fluid/inference` saved-model contract).

Determinism contract (load-bearing for continuous batching): decode
math is strictly row-independent — same weights + same per-row state
produce bit-identical logits regardless of batch composition or
physical page placement.  The one XLA exception is batch=1, which hits
a gemv path with a different reduction order; the engine therefore
clamps its decode bucket ladder to >= 2 rows (see
``ServeConfig._normalize``), and tests pin the bit-identity claim.

Device-trace scopes: both steps run under ``jax.named_scope`` — ``embed``,
per layer ``layer<i>/attn_qkv``, ``layer<i>/kv_write`` (the pool
``.at[i, page, slot].set`` and the int8 scale writes), ``layer<i>/attn``,
``layer<i>/attn_out``, ``layer<i>/mlp``, then ``lm_head`` and ``sample``.
The kernel takes the pools whole, so nothing stands between the write
and the read: the ``kv_read`` scope of earlier versions has no operation
left and is gone.  Prefill writes each layer's K/V as whole pages under
that layer's ``kv_write`` too.  Scopes are HLO metadata only
(``op_name``): they name the operations in a profiler trace and change
nothing the program computes.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..ops.paged_attention import paged_attention, paged_attention_int8
from ..ops.quant_kernels import quantize_kv, w8a16_matmul

__all__ = ["ModelSpec", "init_params", "prefill_step", "decode_step",
           "QUANT_WEIGHT_NAMES"]

_LN_EPS = 1e-5


def QUANT_WEIGHT_NAMES(spec: "ModelSpec"):
    """The weight matrices the int8 serve path quantizes: every
    projection/MLP matmul.  Embedding, positional table, norms and
    biases stay f32 (tiny, and the tied logits matmul wants the full-
    precision embedding)."""
    names = []
    for i in range(spec.layers):
        names += [f"h{i}.attn.wq", f"h{i}.attn.wk", f"h{i}.attn.wv",
                  f"h{i}.attn.wo", f"h{i}.mlp.w1", f"h{i}.mlp.w2"]
    return names


def _matmul(params, name, x, tap=None):
    """Precision-dispatching matmul: a weight present as ``name::q`` +
    ``name::scale`` (the :mod:`.quant` checkpoint layout) runs through
    the w8a16 kernel; otherwise the plain dense path.  ``tap`` is the
    calibration hook — called with the matmul's input activation so the
    PTQ observers see the same tensors the serve program computes."""
    if tap is not None:
        tap(name, x)
    qk = name + "::q"
    if qk in params:
        return w8a16_matmul(x, params[qk], params[name + "::scale"])
    return x @ params[name]


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    """Architecture hyperparameters of a served decoder."""

    vocab_size: int = 256
    hidden: int = 64
    layers: int = 2
    heads: int = 4
    max_seq_len: int = 256
    ffn_mult: int = 4

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads

    def __post_init__(self):
        if self.hidden % self.heads:
            raise ValueError(
                f"hidden={self.hidden} not divisible by heads={self.heads}")

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ModelSpec":
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: int(v) for k, v in d.items() if k in names})


def init_params(spec: ModelSpec, seed: int = 0) -> Dict[str, jnp.ndarray]:
    """Flat ``path -> array`` dict (checkpoint-manager friendly)."""
    rng = jax.random.PRNGKey(seed)
    p: Dict[str, jnp.ndarray] = {}

    def _w(key, shape, scale=0.02):
        return (jax.random.normal(key, shape, jnp.float32) * scale)

    keys = jax.random.split(rng, 2 + spec.layers * 6)
    p["embed"] = _w(keys[0], (spec.vocab_size, spec.hidden))
    p["pos"] = _w(keys[1], (spec.max_seq_len, spec.hidden))
    for i in range(spec.layers):
        k = keys[2 + i * 6: 8 + i * 6]
        ffn = spec.hidden * spec.ffn_mult
        p[f"h{i}.ln1.w"] = jnp.ones((spec.hidden,), jnp.float32)
        p[f"h{i}.ln1.b"] = jnp.zeros((spec.hidden,), jnp.float32)
        p[f"h{i}.attn.wq"] = _w(k[0], (spec.hidden, spec.hidden))
        p[f"h{i}.attn.wk"] = _w(k[1], (spec.hidden, spec.hidden))
        p[f"h{i}.attn.wv"] = _w(k[2], (spec.hidden, spec.hidden))
        p[f"h{i}.attn.wo"] = _w(k[3], (spec.hidden, spec.hidden))
        p[f"h{i}.ln2.w"] = jnp.ones((spec.hidden,), jnp.float32)
        p[f"h{i}.ln2.b"] = jnp.zeros((spec.hidden,), jnp.float32)
        p[f"h{i}.mlp.w1"] = _w(k[4], (spec.hidden, ffn))
        p[f"h{i}.mlp.b1"] = jnp.zeros((ffn,), jnp.float32)
        p[f"h{i}.mlp.w2"] = _w(k[5], (ffn, spec.hidden))
        p[f"h{i}.mlp.b2"] = jnp.zeros((spec.hidden,), jnp.float32)
    p["lnf.w"] = jnp.ones((spec.hidden,), jnp.float32)
    p["lnf.b"] = jnp.zeros((spec.hidden,), jnp.float32)
    return p


def _ln(x, w, b):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    return (x32 - mu) * jax.lax.rsqrt(var + _LN_EPS) * w + b


def _mlp(spec, params, i, x, tap=None):
    h = _matmul(params, f"h{i}.mlp.w1", x, tap) + params[f"h{i}.mlp.b1"]
    h = jax.nn.gelu(h)
    return _matmul(params, f"h{i}.mlp.w2", h, tap) + params[f"h{i}.mlp.b2"]


def _page_slot(page_tables, positions, page_size):
    """``(page, slot)`` of each row's position through its page table:
    position ``t`` lives in page ``pt[b, t // ps]``, slot ``t % ps``.
    ``page_tables`` (B, max_pages), ``positions`` (B,)."""
    page = jnp.take_along_axis(
        page_tables, (positions // page_size)[:, None], axis=1)[:, 0]
    return page, positions % page_size


def prefill_step(spec: ModelSpec, params, k_pool, v_pool,
                 tokens, length, page_table, *, page_size: int,
                 k_scale=None, v_scale=None, tap=None):
    """Run one prompt (padded to a seq bucket) and seed its KV pages.

    Args:
      k_pool/v_pool: donated pools ``(L, P, ps, H*D)``
        (:func:`.kv_cache.pool_shapes`), written a whole page at a time.
      tokens: ``(S,)`` int32, padded prompt (bucket size S).
      length: scalar int32, true prompt length (1 <= length <= S).
      page_table: ``(max_pages,)`` int32 pages owned by this sequence
        (unused tail = 0, the reserved null page); at least
        ``ceil(S / ps)`` entries.
      page_size: static tokens-per-page (trace-time constant).
      k_scale/v_scale: donated scale pools ``(L, P, ps, H)`` f32 when
        the KV pool is int8 (``k_pool.dtype``); the prompt's K/V are
        quantized per (token, head) at write time.
      tap: optional calibration hook ``tap(site, activation)`` — only
        ever non-None in the eager PTQ harness, never in a serve trace.

    Returns ``(k_pool, v_pool, next_token, logits)``, with the two
    scale pools spliced in after ``v_pool`` when they were passed.
    Prefill attends over the in-layer full-precision K/V (the stored
    pages are for later decode steps), matching standard PTQ serving
    stacks.
    """
    s = tokens.shape[0]
    scope = jax.named_scope
    with scope("embed"):
        h = params["embed"][tokens] + params["pos"][:s]
    cdt = params["embed"].dtype
    pos_ids = jnp.arange(s, dtype=jnp.int32)
    # causal AND inside the true prompt: key j visible to query i iff
    # j <= i and j < length
    in_prompt = pos_ids < length
    mask = (pos_ids[None, :] <= pos_ids[:, None]) & in_prompt[None, :]
    scale = 1.0 / math.sqrt(spec.head_dim)
    quant = k_pool.dtype == jnp.int8
    n_pages = -(-s // page_size)
    # a page wholly past the prompt goes to the null page 0, so only
    # pages the sequence owns are written
    page_ids = jnp.where(
        jnp.arange(n_pages, dtype=jnp.int32) * page_size < length,
        page_table[:n_pages], 0)

    def write(pool, layer, rows):
        """``rows`` (S, ...) of one layer's K, V or scales into the
        prompt's pages.  Rows past ``length`` become zeros: the kernel
        masks those slots (``pos < length``) until the decode step that
        reaches each one overwrites it."""
        keep = in_prompt.reshape(s, *[1] * (rows.ndim - 1))
        rows = jnp.where(keep, rows, 0).astype(pool.dtype)
        rows = jnp.pad(rows, ((0, n_pages * page_size - s),)
                       + ((0, 0),) * (rows.ndim - 1))
        return pool.at[layer, page_ids].set(
            rows.reshape(n_pages, page_size, *rows.shape[1:]))

    for i in range(spec.layers):
        with scope(f"layer{i}/attn_qkv"):
            x = _ln(h, params[f"h{i}.ln1.w"],
                    params[f"h{i}.ln1.b"]).astype(cdt)
            q = _matmul(params, f"h{i}.attn.wq", x,
                        tap).reshape(s, spec.heads, spec.head_dim)
            k = _matmul(params, f"h{i}.attn.wk", x,
                        tap).reshape(s, spec.heads, spec.head_dim)
            v = _matmul(params, f"h{i}.attn.wv", x,
                        tap).reshape(s, spec.heads, spec.head_dim)
        with scope(f"layer{i}/attn"):
            att = jnp.einsum("ihd,jhd->hij", q, k,
                             preferred_element_type=jnp.float32) * scale
            att = jnp.where(mask[None, :, :], att, -1e30)
            w = jax.nn.softmax(att, axis=-1)
            o = jnp.einsum("hij,jhd->ihd", w.astype(v.dtype), v,
                           preferred_element_type=jnp.float32
                           ).reshape(s, spec.hidden).astype(cdt)
        with scope(f"layer{i}/attn_out"):
            h = h + _matmul(params, f"h{i}.attn.wo", o, tap)
        with scope(f"layer{i}/mlp"):
            x2 = _ln(h, params[f"h{i}.ln2.w"],
                     params[f"h{i}.ln2.b"]).astype(cdt)
            h = h + _mlp(spec, params, i, x2, tap)
        # the layer's K/V go into this sequence's pages, a whole page at
        # a time, and are dead after it: no (L, S, H*D) stack is held
        with scope(f"layer{i}/kv_write"):
            if quant:
                k, ksc = quantize_kv(k)
                v, vsc = quantize_kv(v)
                k_scale = write(k_scale, i, ksc)
                v_scale = write(v_scale, i, vsc)
            k_pool = write(k_pool, i, k.reshape(s, spec.hidden))
            v_pool = write(v_pool, i, v.reshape(s, spec.hidden))
            # the next layer waits for these writes: left free, XLA's
            # schedule puts all 2L of them after the stack and keeps
            # every layer's K and V alive until then.  (Not the int8
            # scale pools: their rows are small, and the chip re-lays a
            # (.., H)-minor pool once around all its scatters, which a
            # barrier a layer would repeat.)
            h, k_pool, v_pool = jax.lax.optimization_barrier(
                (h, k_pool, v_pool))
    with scope("lm_head"):
        hf = _ln(h, params["lnf.w"], params["lnf.b"]).astype(cdt)
        if tap is not None:
            tap("head", hf)
        # only the last prompt row feeds the sampler: one row against
        # the embedding, not an (S, V) product to pick a row from
        last = jax.lax.dynamic_slice_in_dim(hf, length - 1, 1, axis=0)
        logits = (last @ params["embed"].T)[0]                 # (V,)
    with scope("sample"):
        next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if k_scale is not None:
        return k_pool, v_pool, k_scale, v_scale, next_token, logits
    return k_pool, v_pool, next_token, logits


def decode_step(spec: ModelSpec, params, k_pool, v_pool,
                tokens, positions, page_tables, *, page_size: int,
                k_scale=None, v_scale=None, tap=None):
    """One decode step for a padded batch bucket.

    Args:
      k_pool/v_pool: donated pools ``(L, P, ps, H*D)``; each layer
        writes the rows' new K/V at ``[layer, page, slot]`` (B rows of
        H*D lanes) and hands the pools whole to the kernel.
      tokens: ``(B,)`` int32 current token per row.
      positions: ``(B,)`` int32 position of that token (0-based);
        padding rows point at position 0 with page_table row 0 so
        their writes land in the null page.
      page_tables: ``(B, max_pages)`` int32.
      page_size: static tokens-per-page (trace-time constant).
      k_scale/v_scale: donated scale pools ``(L, P, ps, H)`` f32 for an
        int8 pool; the step's K/V quantize per (token, head) at write
        time — a pure per-row function, so row bytes never depend on
        batch neighbours (the bit-identity contract survives int8).
      tap: optional calibration hook (eager PTQ harness only).

    Returns ``(k_pool, v_pool, next_tokens, logits)``, with the scale
    pools spliced in after ``v_pool`` when they were passed.
    """
    b = tokens.shape[0]
    quant = k_pool.dtype == jnp.int8
    scope = jax.named_scope
    with scope("embed"):
        page, slot = _page_slot(page_tables, positions, page_size)  # (B,)
        lengths = positions + 1
        h = params["embed"][tokens] + params["pos"][positions]
    cdt = params["embed"].dtype
    for i in range(spec.layers):
        with scope(f"layer{i}/attn_qkv"):
            x = _ln(h, params[f"h{i}.ln1.w"],
                    params[f"h{i}.ln1.b"]).astype(cdt)
            q = _matmul(params, f"h{i}.attn.wq", x,
                        tap).reshape(b, spec.heads, spec.head_dim)
            k = _matmul(params, f"h{i}.attn.wk", x,
                        tap).reshape(b, spec.heads, spec.head_dim)
            v = _matmul(params, f"h{i}.attn.wv", x,
                        tap).reshape(b, spec.heads, spec.head_dim)
        with scope(f"layer{i}/kv_write"):
            if quant:
                k, ksc = quantize_kv(k)
                v, vsc = quantize_kv(v)
                k_scale = k_scale.at[i, page, slot].set(ksc)
                v_scale = v_scale.at[i, page, slot].set(vsc)
            k_pool = k_pool.at[i, page, slot].set(
                k.reshape(b, spec.hidden).astype(k_pool.dtype))
            v_pool = v_pool.at[i, page, slot].set(
                v.reshape(b, spec.hidden).astype(v_pool.dtype))
        with scope(f"layer{i}/attn"):
            if quant:
                o = paged_attention_int8(q, k_pool, v_pool, k_scale,
                                         v_scale, page_tables, lengths,
                                         layer=i)
            else:
                o = paged_attention(q, k_pool, v_pool, page_tables,
                                    lengths, layer=i)
        with scope(f"layer{i}/attn_out"):
            h = h + _matmul(params, f"h{i}.attn.wo",
                            o.reshape(b, spec.hidden), tap)
        with scope(f"layer{i}/mlp"):
            x2 = _ln(h, params[f"h{i}.ln2.w"],
                     params[f"h{i}.ln2.b"]).astype(cdt)
            h = h + _mlp(spec, params, i, x2, tap)
    with scope("lm_head"):
        hf = _ln(h, params["lnf.w"], params["lnf.b"]).astype(cdt)
        if tap is not None:
            tap("head", hf)
        logits = hf @ params["embed"].T                        # (B, V)
    with scope("sample"):
        next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if k_scale is not None:
        return k_pool, v_pool, k_scale, v_scale, next_tokens, logits
    return k_pool, v_pool, next_tokens, logits
