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
``.at[i, page, slot].set`` and the int8 scale writes), ``layer<i>/attn``
(``layer<i>/attn_window`` / ``layer<i>/attn_global`` in a model that has
sliding layers; ``layer<i>/attn_cross`` in a layer that reads another
layer's pages), ``layer<i>/attn_out``, ``layer<i>/mlp`` (or
``layer<i>/moe_route`` and ``layer<i>/moe_experts``); a state-space layer
is ``layer<i>/ssm`` (projections, convolution, the scan or the one-token
update, gate) and ``layer<i>/state_write`` (its rows' slots), a gated
memory unit ``layer<i>/gmu``; a layer of learned sparse attention
(``sparse_topk``) is ``layer<i>/index_write`` (the indexer's key into the
index pool), ``layer<i>/indexer`` (the indexer's queries and the scores
against the row's cached keys, or a block of the prompt's),
``layer<i>/select`` (the exact top-k) and ``layer<i>/attn_sparse``; a
``parallel`` layer (Falcon-H1) is ``attn_qkv``, ``kv_write``,
``attn_global``, ``attn_out`` (the attention branch), ``ssm`` (the Mamba-2
branch: in-projection, convolution, delta, the chunked scan or the
one-token update of the rows' states where they lie, gated norm,
out-projection, and the two branches summed), ``state_write`` and
``mlp``; a ``kda`` layer is ``layer<i>/kda`` (projections, convolutions,
gates, the chunked delta rule or the one-token update of the rows' states
where they lie, gated norm, out-projection) and ``state_write``; a ``mla``
layer ``attn_qkv`` (q, the latent and its key part, and in prefill K and V
up-projected a head), ``latent_write`` (the latent and the key part into
the row's pages), ``attn_mla`` (prefill's blocked causal attention; in
decode the query absorbed through W_UK, the latent kernel and W_UV) and
``attn_out``; a shared expert ``layer<i>/moe_shared`` after
``moe_experts``; then ``lm_head`` and ``sample``.
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
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.paged_attention import (chunk_walk, paged_attention,
                                   paged_attention_diff,
                                   paged_attention_int8)
from ..ops.paged_sparse import (index_scores, paged_attention_sparse,
                                paged_index_scores, select_tokens, topk_mask)
from ..ops.quant_kernels import quantize_kv, w8a16_matmul
from ..ops.paged_mla import mla_walk, paged_attention_mla
from . import experts as _experts
from . import kda as _kda
from . import ssm as _ssm

__all__ = ["ModelSpec", "init_params", "prefill_step", "decode_step",
           "QUANT_WEIGHT_NAMES"]

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


LAYER_KINDS = ("full", "sliding", "ssm", "gmu", "cross", "parallel", "kda",
               "mla")


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    """Architecture of a served decoder: one block, driven by these
    kinds and sizes.  The defaults are the GPT-2 block (layer norm,
    learned positions, equal heads of ``hidden // heads``, GELU FFN of
    ``ffn_mult``, tied head); every other field names a departure.

    ``kv_heads`` / ``head_size``: 0 means ``heads`` / ``hidden // heads``.
    ``norm``: ``layer`` (with bias) or ``rms``.  ``positions``:
    ``learned`` (a table of ``max_seq_len``), ``rotary`` (rotate-half
    pairing, base ``rope_theta``) or ``none``.  ``layer_types``: per
    layer its mixer (empty: all full) — ``full`` or ``sliding`` attention
    (a sliding layer sees the last ``window`` positions), ``ssm`` (a
    selective state-space layer, :mod:`.ssm`: ``ssm_inner`` channels,
    ``ssm_state``, ``ssm_conv``, ``ssm_dt_rank``), ``gmu`` (a gated memory
    unit on the output ``y`` of the nearest ``ssm`` layer before it; no
    state of its own) or ``cross`` (attention with a query of its own
    over the K and V of the nearest ``full`` layer before it; no cache of
    its own).  ``gmu`` and ``cross`` layers come last, after a ``full``
    layer: a prompt's prefill runs them, and that layer's query side, on
    its last position only, since nothing later reads them elsewhere.
    ``attn_bias``: biases on the attention projections.  ``diff_attn``:
    differential attention (arXiv:2410.05258; :func:`_differential`).
    ``yarn_factor`` > 0 rescales the rotary frequencies of the *full*
    layers (YaRN: ``yarn_original_len``,
    ``yarn_beta_fast`` / ``_slow``, and ``yarn_attention_factor`` on cos
    and sin, 0 meaning ``0.1 ln(factor) + 1``), at every length; sliding
    layers keep the plain frequencies.  ``ffn``: ``gelu``, ``swiglu``
    (gate and up in one matrix of ``2 * ffn_mult * hidden``, no biases)
    or ``moe`` (``experts`` routed SwiGLU experts of ``expert_width``,
    ``experts_per_token`` a token, no drops: :mod:`.experts`, which
    picks its regime by the program's row count).  ``tie_head``: logits
    against the embedding, or an own ``head`` matrix.  ``qk_norm``: an RMS
    norm a head (weights of ``head_dim``) on q and on k before the rotary.
    ``sparse_topk`` > 0: every attention layer is learned sparse attention
    (:mod:`..ops.paged_sparse`): an indexer of ``index_heads`` query heads
    and one key head of ``index_head_size`` lanes (the key through a layer
    norm, both rotated on all their lanes) scores every visible key, and
    the layer attends over the ``sparse_topk`` best alone (all of them up
    to that many).  Such a layer is still kind ``full``: it owns pages of
    the full layers' pool, and a page of the index pool beside each.
    ``parallel``: full attention and a Mamba-2 mixer (:func:`.ssm.
    mamba2_prefill`: ``ssm_heads`` heads of ``ssm_inner / ssm_heads``
    channels, a decay a head, B and C shared by ``ssm_groups`` groups,
    prompts in chunks of ``ssm_chunk``) read the same normed input and
    their outputs are summed into the residual (Falcon-H1); such a layer
    owns pages of the full layers' pool *and* a place in a row's state
    slot.  ``ffn_width``: the dense FFN's width where it is no whole
    multiple of ``hidden`` (0: ``hidden * ffn_mult``).  The µP multipliers
    (1, or empty, for a model without them): ``embed_mult`` on the
    embedding, ``attn_out_mult`` on the attention branch's output (its
    input's is 1 where the model has one), ``key_mult`` on k, ``ssm_in_mult`` /
    ``ssm_out_mult`` on the Mamba-2 branch's, ``ssm_mults`` on the five
    segments of its in-projection (z, x, B, C, dt), ``mlp_mults`` on the
    SwiGLU's gate and on its down projection, ``head_mult`` on the logits.
    ``kda``: Kimi Delta Attention (:mod:`.kda`: ``kda_heads`` heads of
    ``kda_head_dim`` lanes, a convolution of ``kda_conv``, prompts in
    chunks of ``kda_chunk``); such a layer owns a place in a row's state
    slot and no pages.  ``mla``: multi-head latent attention without
    positions (``heads`` query heads of ``qk_nope_dim + qk_rope_dim`` lanes,
    values of ``v_head_dim``, K and V up-projected from a latent of
    ``kv_lora_rank`` through its RMS norm); it owns pages of the full
    layers' pool, which then caches a token's latent and its key's
    ``qk_rope_dim`` lanes (:func:`.kv_cache.pool_shapes`), and decodes in
    the absorbed form (:mod:`..ops.paged_mla`).  Routed experts beside
    the softmax router: ``dense_layers`` leading layers of a dense SwiGLU
    of ``ffn_width``; ``router`` ``sigmoid`` (float32 sigmoid scores, the
    top-k of scores plus a selection bias ``moe.router_bias``, the chosen
    scores renormalised and times ``routed_scale``); ``router_experts``
    the router's width where the layer holds fewer (``experts`` of them,
    ids ``expert_first ..``: this chip's share; the rest's part of the
    result is left out); ``shared_expert_width`` a SwiGLU every token
    passes beside the routed experts.
    """

    vocab_size: int = 256
    hidden: int = 64
    layers: int = 2
    heads: int = 4
    max_seq_len: int = 256
    ffn_mult: int = 4
    kv_heads: int = 0
    head_size: int = 0
    norm: str = "layer"
    norm_eps: float = 1e-5
    positions: str = "learned"
    rope_theta: float = 10000.0
    yarn_factor: float = 0.0
    yarn_original_len: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 0.0
    layer_types: Tuple[str, ...] = ()
    window: int = 0
    ffn: str = "gelu"
    experts: int = 0
    experts_per_token: int = 0
    expert_width: int = 0
    tie_head: bool = True
    attn_bias: bool = False
    diff_attn: bool = False
    ssm_inner: int = 0
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_dt_rank: int = 0
    qk_norm: bool = False
    sparse_topk: int = 0
    index_heads: int = 0
    index_head_size: int = 0
    ffn_width: int = 0
    ssm_heads: int = 0
    ssm_groups: int = 1
    ssm_chunk: int = 128
    embed_mult: float = 1.0
    attn_out_mult: float = 1.0
    key_mult: float = 1.0
    ssm_in_mult: float = 1.0
    ssm_out_mult: float = 1.0
    ssm_mults: Tuple[float, ...] = ()
    mlp_mults: Tuple[float, ...] = ()
    head_mult: float = 1.0
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 4
    kda_chunk: int = 64
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    dense_layers: int = 0
    router: str = "softmax"
    router_experts: int = 0
    expert_first: int = 0
    routed_scale: float = 1.0
    shared_expert_width: int = 0

    @property
    def head_dim(self) -> int:
        return self.head_size or self.hidden // self.heads

    @property
    def ffn_dim(self) -> int:
        """The dense FFN's width."""
        return self.ffn_width or self.hidden * self.ffn_mult

    @property
    def ssm_conv_channels(self) -> int:
        """Channels of the state-space mixer's convolution: its inner
        channels (Mamba-1), or x, B and C side by side (Mamba-2)."""
        if self.ssm_heads:
            return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state
        return self.ssm_inner

    @property
    def n_kv_heads(self) -> int:
        return self.kv_heads or self.heads

    @property
    def latent_lanes(self) -> Tuple[int, int]:
        """Lanes of a token's row in the latent cache's two pools: the
        latent's, and its key's rotary-free part's padded with zeros to
        whole 128-lane tiles (the chip lays a narrower row out in that
        much room anyway, and a kernel copies whole tiles of a page)."""
        return (self.kv_lora_rank, -(-self.qk_rope_dim // 128) * 128)

    @property
    def n_router_experts(self) -> int:
        """Experts the router chooses among."""
        return self.router_experts or self.experts

    @property
    def held_share(self) -> bool:
        """Whether the expert layers hold fewer experts than they route
        over (a chip's share)."""
        return self.n_router_experts != self.experts

    def __post_init__(self):
        for name in ("layer_types", "ssm_mults", "mlp_mults"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        if not self.head_size and self.hidden % self.heads:
            raise ValueError(
                f"hidden={self.hidden} not divisible by heads={self.heads}")
        if self.heads % self.n_kv_heads:
            raise ValueError(f"heads={self.heads} not a multiple of "
                             f"kv_heads={self.n_kv_heads}")
        for name, value, allowed in (
                ("norm", self.norm, ("layer", "rms")),
                ("positions", self.positions, ("learned", "rotary",
                                               "none")),
                ("ffn", self.ffn, ("gelu", "swiglu", "moe"))):
            if value not in allowed:
                raise ValueError(f"{name}={value!r} not in {allowed}")
        if self.layer_types:
            if len(self.layer_types) != self.layers or \
                    set(self.layer_types) - set(LAYER_KINDS):
                raise ValueError(
                    f"layer_types must name {self.layers} layers, each of "
                    f"{LAYER_KINDS}: {self.layer_types}")
            if "sliding" in self.layer_types and self.window < 1:
                raise ValueError("sliding layers need window >= 1")
            if self._of_kind("ssm") and (self.ssm_heads or not (
                    self.ssm_inner > 0 and self.ssm_dt_rank > 0
                    and self.ssm_state > 0 and self.ssm_conv > 1)):
                raise ValueError("ssm layers (Mamba-1) need ssm_inner, "
                                 "ssm_state, ssm_conv and ssm_dt_rank, "
                                 "and no ssm_heads")
            if self._of_kind("parallel") and not (
                    self.ssm_heads > 0 and self.ssm_state > 0
                    and self.ssm_conv > 1 and self.ssm_chunk > 0
                    and self.ssm_inner % self.ssm_heads == 0
                    and self.ssm_heads % self.ssm_groups == 0
                    and len(self.ssm_mults) == 5
                    and not (self.diff_attn or self.sparse_topk)):
                raise ValueError(
                    "parallel layers (Mamba-2) need ssm_heads dividing "
                    "ssm_inner, ssm_groups dividing ssm_heads, ssm_state, "
                    "ssm_conv, ssm_chunk, five ssm_mults and "
                    "plain attention")
            tail = self.tail_start
            if set(self.layer_types[:tail]) & {"gmu", "cross"} or (
                    tail < self.layers and (
                        tail == 0 or self.layer_types[tail - 1] != "full"
                        or ("gmu" in self.layer_types[tail:]
                            and not self.ssm_layers))):
                raise ValueError(
                    "gmu and cross layers come last, after a full layer "
                    f"(and a gmu after an ssm layer): {self.layer_types}")
        if self.diff_attn and (self.heads % 4 or self.n_kv_heads % 2
                               or self.heads != 2 * self.n_kv_heads):
            raise ValueError("diff_attn pairs the query heads and the KV "
                             "heads, two query pairs a KV pair")
        if self.ffn == "moe" and not (
                0 < self.experts_per_token <= self.n_router_experts
                and self.experts > 0 and self.expert_width > 0):
            raise ValueError("ffn='moe' needs experts, experts_per_token "
                             "and expert_width")
        if self._of_kind("kda") and not (
                self.kda_heads > 0 and self.kda_head_dim > 0
                and self.kda_conv > 1 and self.kda_chunk % 16 == 0
                and self.positions == "none"):
            raise ValueError("kda layers need kda_heads, kda_head_dim, "
                             "kda_conv, a kda_chunk of 16s and no positions")
        if self._of_kind("mla") and not (
                self.kv_lora_rank > 0 and self.qk_nope_dim > 0
                and self.qk_rope_dim > 0 and self.v_head_dim > 0
                and self.positions == "none" and not (
                    self.diff_attn or self.sparse_topk or self.window_layers
                    or self._of_kind("full") or self._of_kind("parallel"))):
            raise ValueError(
                "mla layers need kv_lora_rank, qk_nope_dim, qk_rope_dim "
                "and v_head_dim, no positions and no other attention")
        if self.router not in ("softmax", "sigmoid") or (
                self.router != "softmax" or self.held_share
                or self.shared_expert_width or self.dense_layers) and (
                self.ffn != "moe" or not 0 <= self.expert_first
                <= self.n_router_experts - self.experts):
            raise ValueError("a sigmoid router, a held share of experts, a "
                             "shared expert and dense leading layers are "
                             "ffn='moe''s, the share inside the router's")
        if self.sparse_topk and not (
                self.index_heads > 0 and self.index_head_size > 0
                and self.index_head_size % 2 == 0
                and self.sparse_topk <= self.max_seq_len
                and len(self.global_layers) == self.layers
                and not self.diff_attn):
            raise ValueError(
                "sparse_topk needs index_heads and an even index_head_size, "
                "at most max_seq_len, full layers only and no diff_attn")

    def layer_kind(self, i: int) -> str:
        return self.layer_types[i] if self.layer_types else "full"

    def layer_window(self, i: int) -> int:
        """Layer ``i``'s window in positions, 0 for any other kind."""
        return self.window if self.layer_kind(i) == "sliding" else 0

    def _of_kind(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i in range(self.layers)
                     if self.layer_kind(i) == kind)

    @property
    def window_layers(self) -> Tuple[int, ...]:
        return self._of_kind("sliding")

    @property
    def global_layers(self) -> Tuple[int, ...]:
        """The layers that own pages of the full layers' pool."""
        return tuple(i for i in range(self.layers)
                     if self.layer_kind(i) in ("full", "parallel", "mla"))

    @property
    def ssm_layers(self) -> Tuple[int, ...]:
        """The layers that own a place in a row's state slot."""
        return tuple(i for i in range(self.layers)
                     if self.layer_kind(i) in ("ssm", "parallel", "kda"))

    @property
    def kda_layers(self) -> Tuple[int, ...]:
        return self._of_kind("kda")

    @property
    def mla_layers(self) -> Tuple[int, ...]:
        return self._of_kind("mla")

    @property
    def moe_layers(self) -> Tuple[int, ...]:
        """The layers of routed experts."""
        return (tuple(range(self.dense_layers, self.layers))
                if self.ffn == "moe" else ())

    @property
    def cross_layers(self) -> Tuple[int, ...]:
        return self._of_kind("cross")

    @property
    def tail_start(self) -> int:
        """First layer of the trailing run that keeps nothing from token
        to token (``gmu`` / ``cross``); ``layers`` where there is none."""
        i = self.layers
        while i and self.layer_kind(i - 1) in ("gmu", "cross"):
            i -= 1
        return i

    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["layer_types"] = list(self.layer_types)
        return d

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ModelSpec":
        """Each value cast to its field's own type (ints stay ints,
        ``norm_eps`` a float, ``layer_types`` a tuple of strings)."""
        kinds = {f.name: type(f.default) for f in dataclasses.fields(cls)}
        return cls(**{k: kinds[k](v) for k, v in d.items() if k in kinds})


def init_params(spec: ModelSpec, seed: int = 0,
                dtype=jnp.float32) -> Dict[str, jnp.ndarray]:
    """Flat ``path -> array`` dict (checkpoint-manager friendly), drawn
    in ``dtype``: a large model is made in the precision it is served in
    and never exists in float32."""
    rng = jax.random.PRNGKey(seed)
    p: Dict[str, jnp.ndarray] = {}

    def _w(key, shape, scale=0.02):
        return (jax.random.normal(key, shape, dtype) * scale)

    def _extra(i, j):       # keys beside the GPT block's own split
        return jax.random.fold_in(jax.random.fold_in(rng, 1000 + i), j)

    hd, kvd = spec.heads * spec.head_dim, spec.n_kv_heads * spec.head_dim
    keys = jax.random.split(rng, 2 + spec.layers * 6)
    p["embed"] = _w(keys[0], (spec.vocab_size, spec.hidden))
    if spec.positions == "learned":
        p["pos"] = _w(keys[1], (spec.max_seq_len, spec.hidden))

    def _norm(name):
        p[name + ".w"] = jnp.ones((spec.hidden,), dtype)
        if spec.norm == "layer":
            p[name + ".b"] = jnp.zeros((spec.hidden,), dtype)

    def _attention(i, k, own_kv):
        """Layer ``i``'s attention weights; a cross layer has no K and V
        of its own.  Differential attention's output is a pair of V heads
        a pair of query heads: as wide as q."""
        names = [("q", hd)] + [("k", kvd), ("v", kvd)] * own_kv
        for j, (n, width) in enumerate(names):
            p[f"h{i}.attn.w{n}"] = _w(k[j], (spec.hidden, width))
        p[f"h{i}.attn.wo"] = _w(k[3], (hd, spec.hidden))
        if spec.attn_bias:
            for n, width in names + [("o", spec.hidden)]:
                p[f"h{i}.attn.b{n}"] = jnp.zeros((width,), dtype)
        if spec.qk_norm:
            for j, n in enumerate(("qnorm", "knorm")):
                p[f"h{i}.attn.{n}.w"] = 1 + _w(_extra(i, 8 + j),
                                               (spec.head_dim,), 0.1)
        if spec.sparse_topk:
            # the indexer: its query heads, its one key head (through a
            # layer norm with scale and bias), a weight a query head
            n, di = spec.index_heads, spec.index_head_size
            p[f"h{i}.idx.wq"] = _w(_extra(i, 10), (spec.hidden, n * di))
            p[f"h{i}.idx.wk"] = _w(_extra(i, 11), (spec.hidden, di))
            p[f"h{i}.idx.ww"] = _w(_extra(i, 12), (spec.hidden, n))
            p[f"h{i}.idx.knorm.w"] = 1 + _w(_extra(i, 13), (di,), 0.1)
            p[f"h{i}.idx.knorm.b"] = _w(_extra(i, 14), (di,), 0.1)
        if spec.diff_attn:
            for j, n in enumerate(("lq1", "lk1", "lq2", "lk2")):
                p[f"h{i}.attn.{n}"] = _w(_extra(i, 3 + j),
                                         (spec.head_dim,), 0.1)
            p[f"h{i}.attn.subln.w"] = jnp.ones((2 * spec.head_dim,), dtype)

    def _ssm(i, k):
        """A selective state-space layer (:mod:`.ssm`), the published
        initialisers: ``A = -(1 .. d_state)`` a channel, ``D = 1``, and a
        bias that starts delta log-uniform in [0.001, 0.1]."""
        n, r, rank = spec.ssm_inner, spec.ssm_state, spec.ssm_dt_rank
        p[f"h{i}.ssm.win"] = _w(k[0], (spec.hidden, 2 * n))
        p[f"h{i}.ssm.conv.w"] = _w(k[1], (spec.ssm_conv, n), 0.2)
        p[f"h{i}.ssm.conv.b"] = jnp.zeros((n,), dtype)
        p[f"h{i}.ssm.wx"] = _w(k[2], (n, rank + 2 * r))
        p[f"h{i}.ssm.wdt"] = _w(k[3], (rank, n))
        dt = jnp.exp(jax.random.uniform(
            _extra(i, 2), (n,), jnp.float32, math.log(1e-3), math.log(1e-1)))
        p[f"h{i}.ssm.bdt"] = (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
        p[f"h{i}.ssm.A_log"] = jnp.broadcast_to(jnp.log(jnp.arange(
            1, r + 1, dtype=jnp.float32))[:, None], (r, n)).astype(dtype)
        p[f"h{i}.ssm.D"] = jnp.ones((n,), dtype)
        p[f"h{i}.ssm.wout"] = _w(_extra(i, 7), (n, spec.hidden))

    def _mamba2(i):
        """A Mamba-2 mixer (:func:`.ssm.mamba2_prefill`): ``A = -(1 ..
        heads)``, ``D = 1``, a bias that starts delta log-uniform in
        [0.001, 0.1] a head, the gated norm's weight 1."""
        h, n = spec.ssm_heads, spec.ssm_inner
        width = n + spec.ssm_conv_channels + h
        p[f"h{i}.ssm.win"] = _w(_extra(i, 20), (spec.hidden, width))
        p[f"h{i}.ssm.conv.w"] = _w(_extra(i, 21),
                                   (spec.ssm_conv, spec.ssm_conv_channels),
                                   0.2)
        p[f"h{i}.ssm.conv.b"] = jnp.zeros((spec.ssm_conv_channels,), dtype)
        dt = jnp.exp(jax.random.uniform(
            _extra(i, 22), (h,), jnp.float32, math.log(1e-3), math.log(1e-1)))
        p[f"h{i}.ssm.dt_bias"] = (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
        p[f"h{i}.ssm.A_log"] = jnp.log(
            jnp.arange(1, h + 1, dtype=jnp.float32)).astype(dtype)
        p[f"h{i}.ssm.D"] = jnp.ones((h,), dtype)
        p[f"h{i}.ssm.norm.w"] = jnp.ones((n,), dtype)
        p[f"h{i}.ssm.wout"] = _w(_extra(i, 23), (n, spec.hidden))

    def _kda(i):
        """A KDA mixer (:mod:`.kda`): ``A = log U(1, 16)`` a head, a bias
        that starts the decay rate softplus(dt_bias) log-uniform in
        [0.001, 0.1] a key channel, the gated norm's weight 1."""
        h, dk = spec.kda_heads, spec.kda_head_dim
        inner = h * dk
        n = f"h{i}.kda"
        p[n + ".wqkv"] = _w(_extra(i, 30), (spec.hidden, 3 * inner))
        p[n + ".conv.w"] = _w(_extra(i, 31), (spec.kda_conv, 3 * inner))
        p[n + ".wfa"] = _w(_extra(i, 32), (spec.hidden, dk))
        p[n + ".wfb"] = _w(_extra(i, 33), (dk, inner))
        dt = jnp.exp(jax.random.uniform(
            _extra(i, 34), (inner,), jnp.float32, math.log(1e-3),
            math.log(1e-1)))
        p[n + ".dt_bias"] = (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
        p[n + ".A_log"] = jnp.log(jax.random.uniform(
            _extra(i, 35), (h,), jnp.float32, 1.0, 16.0)).astype(dtype)
        p[n + ".wb"] = _w(_extra(i, 36), (spec.hidden, h))
        p[n + ".wga"] = _w(_extra(i, 37), (spec.hidden, dk))
        p[n + ".wgb"] = _w(_extra(i, 38), (dk, inner))
        p[n + ".norm.w"] = jnp.ones((dk,), dtype)
        p[n + ".wo"] = _w(_extra(i, 39), (inner, spec.hidden))

    def _mla(i):
        """A latent attention layer: q, the latent and its rotary-free
        key, the latent's norm (weight 1), K and V's up-projection, out."""
        h, r, dr = spec.heads, spec.kv_lora_rank, spec.qk_rope_dim
        n = f"h{i}.mla"
        p[n + ".wq"] = _w(_extra(i, 40), (spec.hidden,
                                          h * (spec.qk_nope_dim + dr)))
        p[n + ".wkva"] = _w(_extra(i, 41), (spec.hidden, r + dr))
        p[n + ".kvnorm.w"] = jnp.ones((r,), dtype)
        p[n + ".wkvb"] = _w(_extra(i, 42), (r, h * (spec.qk_nope_dim
                                                    + spec.v_head_dim)))
        p[n + ".wo"] = _w(_extra(i, 43), (h * spec.v_head_dim, spec.hidden))

    for i in range(spec.layers):
        k = keys[2 + i * 6: 8 + i * 6]
        _norm(f"h{i}.ln1")
        kind = spec.layer_kind(i)
        if kind == "kda":
            _kda(i)
        elif kind == "mla":
            _mla(i)
        elif kind == "ssm":
            _ssm(i, k)
        elif kind == "gmu":
            p[f"h{i}.gmu.win"] = _w(k[0], (spec.hidden, spec.ssm_inner))
            p[f"h{i}.gmu.wout"] = _w(k[1], (spec.ssm_inner, spec.hidden))
        else:
            _attention(i, k, kind != "cross")
        if kind == "parallel":
            _mamba2(i)
        _norm(f"h{i}.ln2")
        if spec.ffn == "swiglu" or i < spec.dense_layers:
            ffn = spec.ffn_dim
            p[f"h{i}.mlp.wgu"] = _w(k[4], (spec.hidden, 2 * ffn))
            p[f"h{i}.mlp.wd"] = _w(k[5], (ffn, spec.hidden))
        elif spec.ffn == "moe":
            e, f = spec.experts, spec.expert_width
            p[f"h{i}.moe.router"] = _w(k[4], (spec.hidden,
                                              spec.n_router_experts))
            p[f"h{i}.moe.wg"] = _w(k[5], (e, spec.hidden, f))
            p[f"h{i}.moe.wu"] = _w(_extra(i, 0), (e, spec.hidden, f))
            p[f"h{i}.moe.wd"] = _w(_extra(i, 1), (e, f, spec.hidden))
            if spec.router == "sigmoid":
                p[f"h{i}.moe.router_bias"] = _w(
                    _extra(i, 44), (spec.n_router_experts,))
            if spec.shared_expert_width:
                sw = spec.shared_expert_width
                p[f"h{i}.moe.swgu"] = _w(_extra(i, 45), (spec.hidden, 2 * sw))
                p[f"h{i}.moe.swd"] = _w(_extra(i, 46), (sw, spec.hidden))
        else:
            ffn = spec.ffn_dim
            p[f"h{i}.mlp.w1"] = _w(k[4], (spec.hidden, ffn))
            p[f"h{i}.mlp.b1"] = jnp.zeros((ffn,), dtype)
            p[f"h{i}.mlp.w2"] = _w(k[5], (ffn, spec.hidden))
            p[f"h{i}.mlp.b2"] = jnp.zeros((spec.hidden,), dtype)
    _norm("lnf")
    if not spec.tie_head:
        p["head"] = _w(_extra(spec.layers, 0),
                       (spec.hidden, spec.vocab_size))
    return p


def _norm(spec, params, name, x):
    """Layer norm or RMS norm in float32, by the spec."""
    x32 = x.astype(jnp.float32)
    if spec.norm == "rms":
        ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        return x32 * jax.lax.rsqrt(ms + spec.norm_eps) * params[name + ".w"]
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + spec.norm_eps)
            * params[name + ".w"] + params[name + ".b"])


def _mlp(spec, params, i, x, tap=None):
    if spec.ffn in ("swiglu", "moe"):       # a moe model's dense layers
        gate, up = jnp.split(_matmul(params, f"h{i}.mlp.wgu", x, tap), 2,
                             axis=-1)
        gate = gate.astype(jnp.float32)
        if spec.mlp_mults:
            gate = gate * spec.mlp_mults[0]
        act = up.astype(jnp.float32) * jax.nn.silu(gate)
        out = _matmul(params, f"h{i}.mlp.wd", act.astype(x.dtype), tap)
        if spec.mlp_mults:
            out = (out.astype(jnp.float32) * spec.mlp_mults[1]
                   ).astype(out.dtype)
        return out
    h = _matmul(params, f"h{i}.mlp.w1", x, tap) + params[f"h{i}.mlp.b1"]
    h = jax.nn.gelu(h)
    return _matmul(params, f"h{i}.mlp.w2", h, tap) + params[f"h{i}.mlp.b2"]


def _ffn(spec, params, i, h, tap, valid, counts, chosen=None):
    """The block's second half: ``h`` plus the FFN of its normed rows —
    the GELU or SwiGLU MLP under ``layer<i>/mlp``, or the routed experts
    under ``layer<i>/moe_route`` (norm, router, top-k; ``counts`` gains
    the layer's tokens per expert, and a list ``chosen`` the router's
    ``(T, k)`` choices among all its experts) and ``layer<i>/moe_experts``."""
    cdt = params["embed"].dtype
    if spec.ffn != "moe" or i < spec.dense_layers:
        with jax.named_scope(f"layer{i}/mlp"):
            x = _norm(spec, params, f"h{i}.ln2", h).astype(cdt)
            return h + _mlp(spec, params, i, x, tap)
    with jax.named_scope(f"layer{i}/moe_route"):
        x = _norm(spec, params, f"h{i}.ln2", h).astype(cdt)
        if spec.router == "softmax":
            gates, idx = _experts.route(x, params[f"h{i}.moe.router"],
                                        spec.experts_per_token)
        else:
            gates, idx = _experts.route(
                x, params[f"h{i}.moe.router"], spec.experts_per_token,
                form=spec.router, bias=params[f"h{i}.moe.router_bias"],
                scale=spec.routed_scale)
        if chosen is not None:
            chosen.append(idx)
        if spec.held_share:
            idx = _experts.held(idx, spec.expert_first, spec.experts)
        counts.append(_experts.expert_counts(idx, spec.experts, valid))
    with jax.named_scope(f"layer{i}/moe_experts"):
        out = _experts.experts(
            x, gates, idx, params[f"h{i}.moe.wg"], params[f"h{i}.moe.wu"],
            params[f"h{i}.moe.wd"], valid=valid, partial=spec.held_share)
    if spec.shared_expert_width:
        with jax.named_scope(f"layer{i}/moe_shared"):
            gate, up = jnp.split(jnp.dot(x, params[f"h{i}.moe.swgu"],
                                         preferred_element_type=jnp.float32),
                                 2, axis=-1)
            out = out + jnp.dot((jax.nn.silu(gate) * up).astype(cdt),
                                params[f"h{i}.moe.swd"],
                                preferred_element_type=jnp.float32)
    return h + out.astype(cdt)


def _rope_tables(spec, positions):
    """``{layer kind: (cos, sin)}`` of ``positions`` (T,), each (T, D/2)
    float32; ``full`` differs from ``sliding`` only under YaRN."""
    d = spec.head_dim
    i = np.arange(0, d, 2, dtype=np.float64) / d
    plain = spec.rope_theta ** -i
    freqs, factor = {"sliding": plain, "full": plain}, {}
    if spec.yarn_factor:
        s, l0 = spec.yarn_factor, spec.yarn_original_len

        def corr(beta):
            return (d * math.log(l0 / (2 * math.pi * beta))
                    / (2 * math.log(spec.rope_theta)))

        low = max(math.floor(corr(spec.yarn_beta_fast)), 0)
        high = min(math.ceil(corr(spec.yarn_beta_slow)), d - 1)
        ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3),
                       0.0, 1.0)
        freqs["full"] = plain / s * ramp + plain * (1.0 - ramp)
        factor["full"] = (spec.yarn_attention_factor
                          or 0.1 * math.log(s) + 1.0)
    if spec.sparse_topk:        # the indexer's lanes, the plain frequencies
        di = spec.index_head_size
        freqs["index"] = spec.rope_theta ** -(
            np.arange(0, di, 2, dtype=np.float64) / di)
    out = {}
    for kind in ("full", "sliding") + ("index",) * bool(spec.sparse_topk):
        ang = (positions.astype(jnp.float32)[:, None]
               * jnp.asarray(freqs[kind], jnp.float32)[None, :])
        m = factor.get(kind, 1.0)
        out[kind] = (jnp.cos(ang) * m, jnp.sin(ang) * m)
    return out


def _rotate(x, cos_sin):
    """Rotary embedding, rotate-half pairing: lane ``i`` pairs with lane
    ``i + D/2``.  ``x`` (T, heads, D); float32 inside, ``x.dtype`` out."""
    cos, sin = (t[:, None, :] for t in cos_sin)
    half = x.shape[-1] // 2
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def _project(spec, params, i, x, which, heads, tap=None):
    """Normed rows ``x`` through layer ``i``'s W<which> (and its bias,
    where the spec has them): (T, heads, D)."""
    y = _matmul(params, f"h{i}.attn.w{which}", x, tap)
    if spec.attn_bias:
        y = y + params[f"h{i}.attn.b{which}"]
    return y.reshape(x.shape[0], heads, spec.head_dim)


def _qkv(spec, params, i, h, rope, tap, n=None):
    """Layer ``i``'s normed rows through Wq, Wk, Wv: q (T, H, D) and
    k, v (T, KVH, D), rotated where the spec says rotary.  ``n``: the
    normed rows float32, where the caller has them (a ``parallel`` layer's
    two branches read the same)."""
    cdt = params["embed"].dtype
    if n is None:
        n = _norm(spec, params, f"h{i}.ln1", h)
    x = n.astype(cdt)
    q = _project(spec, params, i, x, "q", spec.heads, tap)
    k = _project(spec, params, i, x, "k", spec.n_kv_heads, tap)
    v = _project(spec, params, i, x, "v", spec.n_kv_heads, tap)
    if spec.key_mult != 1.0:
        k = (k.astype(jnp.float32) * spec.key_mult).astype(k.dtype)
    if spec.qk_norm:
        q = _head_norm(spec, params[f"h{i}.attn.qnorm.w"], q)
        k = _head_norm(spec, params[f"h{i}.attn.knorm.w"], k)
    if rope is not None:
        cs = rope["sliding" if spec.layer_window(i) else "full"]
        q, k = _rotate(q, cs), _rotate(k, cs)
    return q, k, v


def _head_norm(spec, w, x):
    """RMS norm over each head's lanes, float32 inside, ``x.dtype`` out."""
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(ms + spec.norm_eps) * w).astype(x.dtype)


def _index_key(spec, params, i, x, rope):
    """The indexer's key of normed rows ``x``: ``rot(LayerNorm(x Wk))``,
    (T, DI) in ``x.dtype``."""
    k = (x @ params[f"h{i}.idx.wk"]).astype(jnp.float32)
    mu = jnp.mean(k, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(k - mu), axis=-1, keepdims=True)
    k = ((k - mu) * jax.lax.rsqrt(var + spec.norm_eps)
         * params[f"h{i}.idx.knorm.w"] + params[f"h{i}.idx.knorm.b"])
    return _rotate(k.astype(x.dtype)[:, None, :], rope["index"])[:, 0]


def _index_queries(spec, params, i, x, rope):
    """The indexer's queries ``rot(x Wq)`` (T, J, DI) in ``x.dtype`` and
    their weights ``x Ww / sqrt(J DI)`` (T, J) float32."""
    n, di = spec.index_heads, spec.index_head_size
    q = (x @ params[f"h{i}.idx.wq"]).reshape(x.shape[0], n, di)
    w = (x @ params[f"h{i}.idx.ww"]).astype(jnp.float32) / math.sqrt(n * di)
    return _rotate(q, rope["index"]), w


def _attn_scope(spec, i):
    """``layer<i>/attn`` for a model of full layers only (the name its
    metrics read), else ``attn_window`` / ``attn_global`` by the layer."""
    if spec.sparse_topk:
        return f"layer{i}/attn_sparse"
    if not spec.window_layers and "parallel" not in spec.layer_types:
        return f"layer{i}/attn"
    return f"layer{i}/attn_" + ("window" if spec.layer_window(i)
                                else "global")


_DENSE_PREFILL_MAX = 1024   # longest bucket whose (H, S, S) scores are held
_PREFILL_BLOCK = 512        # queries and keys a block of the blocked form


def _prefill_attention(spec, q, k, v, length, window, select=None):
    """Causal (and windowed) attention of one padded prompt, grouped
    heads: q (S, H, D), k / v (S, KVH, D) -> (S, H*D) float32.  Key ``u``
    is visible to query ``p`` iff ``u <= p``, ``u < length`` and, with a
    window, ``p - u < window``.  Up to ``_DENSE_PREFILL_MAX`` positions
    the scores are one ``(H, S, S)`` tensor; beyond, blocks of
    ``_PREFILL_BLOCK`` queries walk the key blocks they can see with an
    online softmax, so nothing of size S x S exists.  ``select(qpos)``,
    where given, is the keys each of those queries keeps, ``(len(qpos),
    S)`` bool (a sparse layer's selection): a block of queries asks once."""
    s, kvh, d = k.shape
    dv = v.shape[-1]            # wider than d under differential attention
    g = spec.heads // kvh
    scale = 1.0 / math.sqrt(d)
    qg = q.reshape(s, kvh, g, d)
    blk = _PREFILL_BLOCK

    def visible(qpos, kpos):
        m = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] < length)
        if window:
            m &= qpos[:, None] - kpos[None, :] < window
        return m

    if s <= _DENSE_PREFILL_MAX or s % blk:
        pos = jnp.arange(s, dtype=jnp.int32)
        # equal heads carry no group axis: a unit axis in the score
        # tensor is one more shape for the compiler to lay out
        scores, values = (("ihd,jhd->hij", "hij,jhd->ihd") if g == 1 else
                          ("ikgd,jkd->kgij", "kgij,jkd->ikgd"))
        att = jnp.einsum(scores, q if g == 1 else qg, k,
                         preferred_element_type=jnp.float32) * scale
        seen = visible(pos, pos)
        if select is not None:
            seen &= select(pos)
        att = jnp.where(seen, att, -1e30)
        w = jax.nn.softmax(att, axis=-1)
        return jnp.einsum(values, w.astype(v.dtype), v,
                          preferred_element_type=jnp.float32
                          ).reshape(s, spec.heads * dv)

    nb = s // blk
    qb = qg.reshape(nb, blk, kvh, g, d)
    kb, vb = k.reshape(nb, blk, kvh, d), v.reshape(nb, blk, kvh, dv)
    within = jnp.arange(blk, dtype=jnp.int32)

    def one_block(i):
        qi, qpos = qb[i], i * blk + within
        lo = jnp.maximum(i * blk - window + 1, 0) // blk if window else 0
        # a block of queries wholly past the prompt walks nothing
        hi = jnp.where(i * blk < length, i + 1, lo)
        kept = None if select is None else select(qpos)

        def step(j, carry):
            m, l, acc = carry
            sc = jnp.einsum("qkgd,skd->kgqs", qi, kb[j],
                            preferred_element_type=jnp.float32) * scale
            vis = visible(qpos, j * blk + within)
            if kept is not None:
                vis &= jax.lax.dynamic_slice_in_dim(kept, j * blk, blk,
                                                    axis=1)
            vis = vis[None, None]
            sc = jnp.where(vis, sc, -1e30)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
            p = jnp.where(vis, jnp.exp(sc - m_new[..., None]), 0.0)
            alpha = jnp.exp(m - m_new)
            pv = jnp.einsum("kgqs,skd->kgqd", p.astype(v.dtype), vb[j],
                            preferred_element_type=jnp.float32)
            return (m_new, alpha * l + jnp.sum(p, axis=-1),
                    acc * alpha[..., None] + pv)

        m0 = jnp.full((kvh, g, blk), -1e30, jnp.float32)
        _, l, acc = jax.lax.fori_loop(
            lo, hi, step, (m0, jnp.zeros_like(m0),
                           jnp.zeros((kvh, g, blk, dv), jnp.float32)))
        o = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
        return jnp.transpose(o, (2, 0, 1, 3))              # (blk, KVH, G, D)

    return jax.lax.map(one_block, jnp.arange(nb, dtype=jnp.int32)
                       ).reshape(s, spec.heads * dv)


def _prefill_selection(spec, qi, wi, ki, length):
    """``select`` of :func:`_prefill_attention` for a layer of learned
    sparse attention: for a block of query positions ``qpos`` the indexer's
    scores against every key of the prompt, and of the keys a query sees
    (``u <= p``, ``u < length``) the ``sparse_topk`` best, exactly.  A
    block whose queries all see at most ``sparse_topk`` keys keeps what
    it sees and scores nothing."""
    s = ki.shape[0]
    kpos = jnp.arange(s, dtype=jnp.int32)

    def select(qpos):
        seen = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] < length)

        def best(_):
            with jax.named_scope("indexer"):
                scores = index_scores(
                    jax.lax.dynamic_slice_in_dim(qi, qpos[0], qpos.shape[0]),
                    jax.lax.dynamic_slice_in_dim(wi, qpos[0], qpos.shape[0]),
                    ki, last=jnp.minimum(qpos[-1], length - 1))
            with jax.named_scope("select"):
                return topk_mask(scores, seen, jnp.full(
                    qpos.shape, spec.sparse_topk, jnp.int32))

        if qpos.shape[0] == s:      # one block: the whole (short) prompt
            return best(None)
        return jax.lax.cond(
            (qpos[-1] >= spec.sparse_topk) & (qpos[0] < length),
            best, lambda _: seen, None)

    return select


# Differential attention (arXiv:2410.05258).  Heads of D in order: query
# heads 2j, 2j+1 are the pair (q1_j, q2_j), K heads 2m, 2m+1 the pair
# (k1_m, k2_m), V heads 2m, 2m+1 side by side one V_m of 2D lanes, and
# query pair j reads KV pair j // 2.  So query head h takes a plain
# softmax over K head ``2 (h // 4) + h % 2`` and weighs V pair ``h // 4``
# with it: A_h (2D lanes).  The layer's output is, a query pair,
# ``(1 - l0) RMSNorm(A_2j - l A_2j+1)``.

def _diff_prefill_attention(spec, q, k, v, length, window):
    """A_h of every position of one padded prompt, (S, H, 2D) float32:
    :func:`_prefill_attention` with the query heads regrouped by the K
    head they read (two each) and each K head's V pair beside it."""
    s, kvh, d = k.shape
    pairs = kvh // 2
    by_k = jnp.transpose(q.reshape(s, pairs, 2, 2, d), (0, 1, 3, 2, 4))
    vv = jnp.repeat(v.reshape(s, pairs, 2 * d), 2, axis=1)
    att = _prefill_attention(spec, by_k.reshape(s, spec.heads, d), k, vv,
                             length, window)
    att = jnp.transpose(att.reshape(s, pairs, 2, 2, 2 * d), (0, 1, 3, 2, 4))
    return att.reshape(s, spec.heads, 2 * d)


def _last_row_attention(spec, q, k, v, length):
    """The prompt's last position alone: q (1, H, D) of position
    ``length - 1`` over k, v (S, KVH, D), all ``length`` of them visible.
    (1, H, D) float32, or A_h (1, H, 2D) under differential attention."""
    s, kvh, d = k.shape
    heads = np.arange(spec.heads)
    if spec.diff_attn:
        k_of, v = 2 * (heads // 4) + heads % 2, v.reshape(s, kvh // 2, 2 * d)
        v_of = heads // 4
    else:
        k_of = v_of = heads // (spec.heads // kvh)
    att = jnp.einsum("hd,shd->hs", q[0], k[:, k_of],
                     preferred_element_type=jnp.float32) / math.sqrt(d)
    seen = jnp.arange(s, dtype=jnp.int32)[None, :] < length
    w = jax.nn.softmax(jnp.where(seen, att, -1e30), axis=-1)
    return jnp.einsum("hs,shd->hd", w.astype(v.dtype), v[:, v_of],
                      preferred_element_type=jnp.float32)[None]


def _differential(spec, params, i, att):
    """``att`` (T, H, 2D) float32, A_h of each query head, to the layer's
    (T, H*D) float32 before Wo: ``(1 - l0) RMSNorm(A_2j - l A_2j+1)`` with
    ``l = exp(lq1 . lk1) - exp(lq2 . lk2) + l0`` and the depth's
    ``l0 = 0.8 - 0.6 exp(-0.3 i)``."""
    t, d = att.shape[0], spec.head_dim

    def vec(name):
        return params[f"h{i}.attn.{name}"].astype(jnp.float32)

    lam0 = 0.8 - 0.6 * math.exp(-0.3 * i)
    lam = (jnp.exp(jnp.sum(vec("lq1") * vec("lk1")))
           - jnp.exp(jnp.sum(vec("lq2") * vec("lk2"))) + lam0)
    pair = att.reshape(t, spec.heads // 2, 2, 2 * d)
    o = pair[:, :, 0] - lam * pair[:, :, 1]
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                          + spec.norm_eps) * vec("subln.w")
    return ((1.0 - lam0) * o).reshape(t, spec.heads * d)


def _attn_out(spec, params, i, h, att, tap=None):
    """``h`` plus Wo of the attention's rows ``att`` ((T, H*D) or (T, H,
    D); A_h (T, H, 2D) under differential attention), and its bias.  In
    a ``parallel`` layer the branch alone, ``attn_out_mult`` times Wo of
    ``att``, float32: the caller sums the two branches into ``h``."""
    cdt = params["embed"].dtype
    if spec.diff_attn:
        att = _differential(spec, params, i, att)
    att = att.reshape(att.shape[0], spec.heads * spec.head_dim)
    if spec.layer_kind(i) == "parallel":
        o = jnp.dot(att.astype(cdt), params[f"h{i}.attn.wo"],
                    preferred_element_type=jnp.float32)
        return o * spec.attn_out_mult if spec.attn_out_mult != 1.0 else o
    o = _matmul(params, f"h{i}.attn.wo", att.astype(cdt), tap)
    if spec.attn_bias:
        o = o + params[f"h{i}.attn.bo"]
    return h + o


def _mla_in(spec, params, i, x):
    """Layer ``i``'s latent attention inputs of normed rows ``x`` (T,
    hidden): the queries (T, H, qk_nope_dim + qk_rope_dim) in ``x.dtype``,
    the latent through its RMS norm (T, kv_lora_rank) and the key's
    rotary-free part (T, qk_rope_dim), both float32."""
    r = spec.kv_lora_rank
    q = (x @ params[f"h{i}.mla.wq"]).reshape(
        x.shape[0], spec.heads, spec.qk_nope_dim + spec.qk_rope_dim)
    kva = jnp.dot(x, params[f"h{i}.mla.wkva"],
                  preferred_element_type=jnp.float32)
    c = kva[:, :r]
    c = c * jax.lax.rsqrt(jnp.mean(jnp.square(c), axis=-1, keepdims=True)
                          + spec.norm_eps)
    return q, c * params[f"h{i}.mla.kvnorm.w"], kva[:, r:]


def _lanes(x, pool):
    """``x`` padded with zeros on its last axis to the pool's lanes."""
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1)
                   + ((0, pool.shape[-1] - x.shape[-1]),))


def _mla_up(spec, params, i):
    """Layer ``i``'s up-projection of the latent a head, ``(W_UK (R, H,
    qk_nope_dim), W_UV (R, H, v_head_dim))``."""
    w = params[f"h{i}.mla.wkvb"].reshape(spec.kv_lora_rank, spec.heads, -1)
    return w[..., :spec.qk_nope_dim], w[..., spec.qk_nope_dim:]


def _embed(spec, params, tokens):
    """The tokens' rows of the embedding, times ``embed_mult``."""
    h = params["embed"][tokens]
    if spec.embed_mult != 1.0:
        h = (h.astype(jnp.float32) * spec.embed_mult).astype(h.dtype)
    return h


def _page_slot(page_tables, positions, page_size):
    """``(page, slot)`` of each row's position through its page table:
    position ``t`` lives in page ``pt[b, t // ps]``, slot ``t % ps``.
    ``page_tables`` (B, max_pages), ``positions`` (B,)."""
    page = jnp.take_along_axis(
        page_tables, (positions // page_size)[:, None], axis=1)[:, 0]
    return page, positions % page_size


def _head(spec, params, hf):
    """Logits of normed rows: against the embedding when tied, else the
    model's own head matrix."""
    logits = hf @ (params["embed"].T if spec.tie_head else params["head"])
    return logits * spec.head_mult if spec.head_mult != 1.0 else logits


def _results(spec, pools, token, logits, counts, selected=(), chosen=None,
             heads=()):
    """A step's outputs in program order: the donated pools, the sampled
    token(s), the logits and, for routed experts, the tokens each layer
    sent to each expert ``(L, E)``; then what a decode step asked for its
    ``selection`` decided, for a check to read (:func:`decode_step`): a
    sparse layer's ``(positions, scores)`` in ``selected``, or the
    routers' choices ``chosen`` and the latent layers' ``heads``."""
    out = tuple(p for p in pools if p is not None) + (token, logits)
    if spec.ffn == "moe":
        out += (jnp.stack(counts),)
    if selected:
        out += (jnp.stack([pos for pos, _ in selected]),
                *[scores for _, scores in selected])
    elif chosen is not None:
        out += (jnp.stack(chosen) if chosen else jnp.zeros(
            (0, token.shape[0], spec.experts_per_token), jnp.int32), *heads)
    return out


def prefill_step(spec: ModelSpec, params, k_pool, v_pool,
                 tokens, length, page_table, *, page_size: int,
                 k_scale=None, v_scale=None, index_pool=None, kw_pool=None,
                 vw_pool=None, conv_pool=None, ssm_pool=None, tap=None):
    """Run one prompt (padded to a seq bucket) and seed its KV pages.

    Args:
      k_pool/v_pool: donated pools ``(Lg, P, ps, KVH*D)`` of the full
        layers (:func:`.kv_cache.pool_shapes`), written a whole page at
        a time; of a model of latent attention, the latents ``(Lg, P, ps,
        R)`` and their keys' rotary-free part ``(Lg, P, ps, 128)``; of a
        model of learned sparse attention, ``k_pool`` the one token-major
        pool ``(Lg, P*ps, 2*KVH, D)`` (a token's K heads, then its V
        heads) and ``v_pool`` None.
      tokens: ``(S,)`` int32, padded prompt (bucket size S).
      length: scalar int32, true prompt length (1 <= length <= S).
      page_table: ``(max_pages,)`` int32 pages owned by this sequence
        (unused tail = 0, the reserved null page); at least
        ``ceil(S / ps)`` entries.  A model with sliding layers takes
        ``(2, max_pages)``: row 0 the full layers' pages, row 1 the
        sliding layers', indexed by logical page alike, null wherever
        the sequence holds no page (the pages before its window).
      page_size: static tokens-per-page (trace-time constant).
      k_scale/v_scale: donated scale pools ``(L, P, ps, H)`` f32 when
        the KV pool is int8 (``k_pool.dtype``); the prompt's K/V are
        quantized per (token, head) at write time.
      index_pool: donated pool ``(Lg, P, DI, ps)`` of the indexer's keys
        (learned sparse attention), page for page with ``k_pool``: a page
        is the keys of its ``ps`` tokens, transposed.
      kw_pool/vw_pool: donated pools ``(Lw, Pw, ps, KVH*D)`` of the
        sliding layers.  A prompt longer than the window writes only
        the last ``window / ps + 1`` pages there: what a later decode
        step can read.
      conv_pool/ssm_pool: donated state pools of the ``ssm`` (and
        ``parallel`` and ``kda``) layers (:class:`.kv_cache.StateSlots`):
        ``(Ls, slots, d_conv - 1, C)`` and ``(Ls, slots, R, N)`` float32,
        or ``(Ls, slots, H, P, R)`` for Mamba-2, ``(Ls, slots, H, K, V)``
        for KDA.  The page table then has a
        third row whose first entry is the sequence's slot; the scan
        starts from zero and the slot is overwritten whole with the
        state after ``length`` tokens.
      tap: optional calibration hook ``tap(site, activation)`` — only
        ever non-None in the eager PTQ harness, never in a serve trace.

    Returns ``(k_pool, v_pool, next_token, logits)``, with the scale
    pools, then the index pool, then the sliding layers' pools, then the
    state pools, spliced
    in after ``v_pool`` when they were passed, and the experts' token
    counts ``(L, E)`` appended for ``ffn='moe'``.
    Prefill attends over the in-layer full-precision K/V (the stored
    pages are for later decode steps), matching standard PTQ serving
    stacks.
    """
    s = tokens.shape[0]
    scope = jax.named_scope
    pos_ids = jnp.arange(s, dtype=jnp.int32)
    with scope("embed"):
        h = _embed(spec, params, tokens)
        if spec.positions == "learned":
            h = h + params["pos"][:s]
        rope = (_rope_tables(spec, pos_ids)
                if spec.positions == "rotary" else None)
    cdt = params["embed"].dtype
    in_prompt = pos_ids < length
    quant = k_pool.dtype == jnp.int8
    n_pages = -(-s // page_size)
    tables = page_table if page_table.ndim == 2 else page_table[None]

    def write(pool, layer, rows, table, first, count, transposed=False):
        """``rows`` (S, ...) of one layer's K, V or scales into the
        prompt's pages ``first .. first + count`` (``transposed``: a page
        is its tokens' rows side by side, the index pool's).  Rows past ``length``
        become zeros: the kernel masks those slots (``pos < length``)
        until the decode step that reaches each one overwrites it.  A
        page wholly past the prompt, or one the sequence holds no page
        for, goes to the null page 0."""
        keep = in_prompt.reshape(s, *[1] * (rows.ndim - 1))
        rows = jnp.where(keep, rows, 0).astype(pool.dtype)
        rows = jnp.pad(rows, ((0, n_pages * page_size - s),)
                       + ((0, 0),) * (rows.ndim - 1))
        if count < n_pages:
            rows = jax.lax.dynamic_slice_in_dim(
                rows, first * page_size, count * page_size)
            table = jax.lax.dynamic_slice_in_dim(table, first, count)
            logical = first + jnp.arange(count, dtype=jnp.int32)
        else:
            table = table[:n_pages]
            logical = jnp.arange(n_pages, dtype=jnp.int32)
        page_ids = jnp.where(logical * page_size < length, table, 0)
        pages = rows.reshape(count, page_size, *rows.shape[1:])
        if transposed:
            pages = jnp.swapaxes(pages, 1, 2)
        return pool.at[layer, page_ids].set(pages)

    def write_kv(i, k, v):
        """Layer ``i``'s K and V (S, KVH, D) into this sequence's pages,
        a whole page at a time; they are dead after it, so no (L, S,
        H*D) stack is held."""
        nonlocal k_pool, v_pool, k_scale, v_scale, kw_pool, vw_pool
        kvd = spec.n_kv_heads * spec.head_dim
        window = spec.layer_window(i)
        if window:
            # only the pages a decode step can still read: the
            # window's span from the first position it will see
            count = min(n_pages, -(-window // page_size) + 1)
            first = jnp.clip(
                jnp.maximum(length + 1 - window, 0) // page_size,
                0, n_pages - count)
            li = spec.window_layers.index(i)
            kw_pool = write(kw_pool, li, k.reshape(s, kvd), tables[1],
                            first, count)
            vw_pool = write(vw_pool, li, v.reshape(s, kvd), tables[1],
                            first, count)
            return
        li = spec.global_layers.index(i)
        if spec.sparse_topk:
            # the token-major pool as pages of ps whole tokens
            layers, tokens, rows, d = k_pool.shape
            pages = k_pool.reshape(layers, tokens // page_size, page_size,
                                   rows, d)
            k_pool = write(pages, li, jnp.concatenate([k, v], axis=1),
                           tables[0], 0, n_pages).reshape(k_pool.shape)
            return
        if quant:
            k, ksc = quantize_kv(k)
            v, vsc = quantize_kv(v)
            k_scale = write(k_scale, li, ksc, tables[0], 0, n_pages)
            v_scale = write(v_scale, li, vsc, tables[0], 0, n_pages)
        k_pool = write(k_pool, li, k.reshape(s, kvd), tables[0], 0,
                       n_pages)
        v_pool = write(v_pool, li, v.reshape(s, kvd), tables[0], 0,
                       n_pages)

    def write_state(i, h, conv, state):
        """Layer ``i``'s convolution tail and state into the prompt's
        slot, under ``layer<i>/state_write``: the whole slot (what a
        released row left there is never read)."""
        nonlocal conv_pool, ssm_pool
        with scope(f"layer{i}/state_write"):
            li, slot = spec.ssm_layers.index(i), tables[2, 0]
            conv_pool = conv_pool.at[li, slot].set(
                conv.astype(conv_pool.dtype))
            ssm_pool = ssm_pool.at[li, slot].set(state)
            h, conv_pool, ssm_pool = jax.lax.optimization_barrier(
                (h, conv_pool, ssm_pool))
        return h

    counts = []
    tail = spec.tail_start      # layers from here on keep nothing
    memory = shared = None      # an ssm layer's y, a full layer's (k, v)
    for i in range(spec.layers):
        kind = spec.layer_kind(i)
        if kind == "kda":
            with scope(f"layer{i}/kda"):
                mixed, conv, state = _kda.kda_prefill(
                    spec, params, f"h{i}.kda",
                    _norm(spec, params, f"h{i}.ln1", h), length)
                h = (h.astype(jnp.float32) + mixed).astype(cdt)
            h = _ffn(spec, params, i, h, tap, in_prompt, counts)
            h = write_state(i, h, conv, state)
            continue
        if kind == "mla":
            with scope(f"layer{i}/attn_qkv"):
                x = _norm(spec, params, f"h{i}.ln1", h).astype(cdt)
                q, latent, kr = _mla_in(spec, params, i, x)
                kv = (latent.astype(cdt) @ params[f"h{i}.mla.wkvb"]
                      ).reshape(s, spec.heads, -1)
                k = jnp.concatenate(
                    [kv[..., :spec.qk_nope_dim], jnp.broadcast_to(
                        kr.astype(cdt)[:, None],
                        (s, spec.heads, spec.qk_rope_dim))], axis=-1)
            with scope(f"layer{i}/attn_mla"):
                o = _prefill_attention(spec, q, k, kv[..., spec.qk_nope_dim:],
                                       length, 0)
            with scope(f"layer{i}/attn_out"):
                h = h + o.astype(cdt) @ params[f"h{i}.mla.wo"]
            h = _ffn(spec, params, i, h, tap, in_prompt, counts)
            with scope(f"layer{i}/latent_write"):
                li = spec.global_layers.index(i)
                k_pool = write(k_pool, li, latent, tables[0], 0, n_pages)
                v_pool = write(v_pool, li, _lanes(kr, v_pool), tables[0], 0,
                               n_pages)
                h, k_pool, v_pool = jax.lax.optimization_barrier(
                    (h, k_pool, v_pool))
            continue
        if kind == "ssm":
            with scope(f"layer{i}/ssm"):
                x = _norm(spec, params, f"h{i}.ln1", h).astype(cdt)
                y, z, conv, state = _ssm.prefill(params, f"h{i}.ssm", x,
                                                 length)
                # what a gated memory unit of the tail reads: its own
                # position's y, and the tail runs on the last one only
                memory = jax.lax.dynamic_slice_in_dim(y, length - 1, 1)
                h = h + _ssm.gate_out(params, f"h{i}.ssm", y, z)
            h = _ffn(spec, params, i, h, tap, in_prompt, counts)
            h = write_state(i, h, conv, state)
            continue
        if i >= tail - 1 and tail < spec.layers:
            # the decoder's tail, on the prompt's last position only: the
            # full layer before it projects K and V for every position
            # (the cache every cross layer reads) and its query for one
            with scope(f"layer{i}/attn_qkv" if kind != "gmu"
                       else f"layer{i}/gmu"):
                x = _norm(spec, params, f"h{i}.ln1", h).astype(cdt)
                if kind == "full":
                    shared = (_project(spec, params, i, x, "k",
                                       spec.n_kv_heads, tap),
                              _project(spec, params, i, x, "v",
                                       spec.n_kv_heads, tap))
                    x = jax.lax.dynamic_slice_in_dim(x, length - 1, 1)
                    h = jax.lax.dynamic_slice_in_dim(h, length - 1, 1)
                if kind == "gmu":
                    h = h + _ssm.gate_out(
                        params, f"h{i}.gmu", memory,
                        _matmul(params, f"h{i}.gmu.win", x, tap))
                else:
                    q = _project(spec, params, i, x, "q", spec.heads, tap)
            if kind != "gmu":
                with scope(f"layer{i}/attn_cross" if kind == "cross"
                           else _attn_scope(spec, i)):
                    att = _last_row_attention(spec, q, *shared, length)
                with scope(f"layer{i}/attn_out"):
                    h = _attn_out(spec, params, i, h, att, tap)
            h = _ffn(spec, params, i, h, tap, jnp.ones((1,), bool), counts)
            if kind == "full":
                with scope(f"layer{i}/kv_write"):
                    write_kv(i, *shared)
            continue
        window = spec.layer_window(i)
        parallel = kind == "parallel"
        with scope(f"layer{i}/attn_qkv"):
            # a parallel layer's two branches read one normed input
            n = _norm(spec, params, f"h{i}.ln1", h) if parallel else None
            q, k, v = _qkv(spec, params, i, h, rope, tap, n=n)
        select = None
        if spec.sparse_topk:
            with scope(f"layer{i}/indexer"):
                x = _norm(spec, params, f"h{i}.ln1", h).astype(cdt)
                ki = _index_key(spec, params, i, x, rope)
                select = _prefill_selection(
                    spec, *_index_queries(spec, params, i, x, rope), ki,
                    length)
        with scope(_attn_scope(spec, i)):
            if spec.diff_attn:
                o = _diff_prefill_attention(spec, q, k, v, length, window)
            elif select is not None:
                o = _prefill_attention(spec, q, k, v, length, window,
                                       select=select)
            else:
                o = _prefill_attention(spec, q, k, v, length, window)
        with scope(f"layer{i}/attn_out"):
            o = _attn_out(spec, params, i, h, o, tap)
        if parallel:
            with scope(f"layer{i}/ssm"):
                mixed, conv, state = _ssm.mamba2_prefill(
                    spec, params, f"h{i}.ssm", n, length)
                o = (h.astype(jnp.float32) + o + mixed).astype(cdt)
        h = _ffn(spec, params, i, o, tap, in_prompt, counts)
        if parallel:
            h = write_state(i, h, conv, state)
        if spec.sparse_topk:
            with scope(f"layer{i}/index_write"):
                index_pool = write(
                    index_pool, spec.global_layers.index(i), ki, tables[0],
                    0, n_pages, transposed=True)
                h, index_pool = jax.lax.optimization_barrier((h, index_pool))
        # the next layer waits for these writes: left free, XLA's
        # schedule puts all 2L of them after the stack and keeps
        # every layer's K and V alive until then.  (Not the int8
        # scale pools: their rows are small, and the chip re-lays a
        # (.., H)-minor pool once around all its scatters, which a
        # barrier a layer would repeat.)
        with scope(f"layer{i}/kv_write"):
            write_kv(i, k, v)
            if window:
                h, kw_pool, vw_pool = jax.lax.optimization_barrier(
                    (h, kw_pool, vw_pool))
            else:
                h, k_pool, v_pool = jax.lax.optimization_barrier(
                    (h, k_pool, v_pool))
    with scope("lm_head"):
        hf = _norm(spec, params, "lnf", h).astype(cdt)
        if tap is not None:
            tap("head", hf)
        # only the last prompt row feeds the sampler: one row against
        # the embedding, not an (S, V) product to pick a row from
        last = hf if tail < spec.layers else \
            jax.lax.dynamic_slice_in_dim(hf, length - 1, 1, axis=0)
        logits = _head(spec, params, last)[0]                  # (V,)
    with scope("sample"):
        next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return _results(spec, (k_pool, v_pool, k_scale, v_scale, index_pool,
                           kw_pool, vw_pool, conv_pool, ssm_pool),
                    next_token, logits, counts)


def decode_walk(spec: ModelSpec, batch: int, k_pool, max_pages: int,
                kw_pool=None):
    """What the paged-attention kernels of :func:`decode_step` walk for a
    bucket of ``batch`` rows over ``k_pool`` and, for a model with
    sliding layers, ``kw_pool`` (shapes and dtypes: arrays or
    ``ShapeDtypeStruct``s): ``{"chunk_tokens", "grid_steps"}`` of the
    work list over the full layers' pool (one list, however many layers
    read it) and, under ``"window"``, the same two of the list over the
    sliding layers' pool beside the window's ``"tokens"``.  ``None``
    where no list is walked (``ops.paged_attention.chunk_walk`` tells:
    an int8 pool; learned sparse attention reads the tokens it listed).
    A model of latent attention walks its latent cache (``k_pool`` and
    the keys' pool beside it) by ``ops.paged_mla.mla_walk``."""
    heads, lanes = spec.heads, spec.head_dim
    if spec.sparse_topk:            # a list of tokens, no list of pages
        return None
    if spec.mla_layers:             # the latent cache's own kernel
        keys = jax.ShapeDtypeStruct(
            (*k_pool.shape[:3], spec.latent_lanes[1]), k_pool.dtype)
        chunk, grid = mla_walk(batch, k_pool, keys, max_pages,
                               steps=_pages_walked(k_pool.shape[1], batch))
        return {"chunk_tokens": chunk * k_pool.shape[2], "grid_steps": grid}
    if spec.diff_attn:              # a KV pair a head of 2D lanes
        lanes *= 2

    def walk(pool, window=None):
        found = chunk_walk(
            jax.ShapeDtypeStruct((batch, heads, lanes), pool.dtype), pool,
            max_pages, window=window,
            steps=_pages_walked(pool.shape[1], batch))
        return found and {"chunk_tokens": found[0], "grid_steps": found[1]}

    out = {}
    if spec.global_layers:
        out.update(walk(k_pool) or {})
    if spec.window_layers and kw_pool is not None:
        sliding = walk(kw_pool, spec.window)
        if sliding:
            out["window"] = dict(sliding, tokens=spec.window)
    return out or None


def _pages_walked(pages: int, batch: int) -> int:
    """The allocator's bound on the pages a decode batch walks: no two
    rows share a page, so the pool's usable pages and one a row."""
    return pages - 1 + batch


def decode_step(spec: ModelSpec, params, k_pool, v_pool,
                tokens, positions, page_tables, *, page_size: int,
                k_scale=None, v_scale=None, index_pool=None, kw_pool=None,
                vw_pool=None, conv_pool=None, ssm_pool=None, tap=None,
                selection: bool = False):
    """One decode step for a padded batch bucket.

    Args:
      k_pool/v_pool: donated pools ``(Lg, P, ps, KVH*D)`` of the full
        layers; each layer writes the rows' new K/V at ``[layer, page,
        slot]`` (B rows of KVH*D lanes) and hands the pools whole to the
        kernel.  Learned sparse attention: ``k_pool`` the token-major
        pool (:func:`prefill_step`), written at ``[layer, page * ps +
        slot]``, and ``v_pool`` None.
      tokens: ``(B,)`` int32 current token per row.
      positions: ``(B,)`` int32 position of that token (0-based);
        padding rows point at position 0 with page_table row 0 so
        their writes land in the null page.
      page_tables: ``(B, max_pages)`` int32, or ``(B, 2, max_pages)``
        for a model with sliding layers (see :func:`prefill_step`).
      page_size: static tokens-per-page (trace-time constant).
      k_scale/v_scale: donated scale pools ``(L, P, ps, H)`` f32 for an
        int8 pool; the step's K/V quantize per (token, head) at write
        time — a pure per-row function, so row bytes never depend on
        batch neighbours (the bit-identity contract survives int8).
      index_pool: donated pool of the indexer's keys (learned sparse
        attention): each layer writes the rows' new key at ``[layer, page,
        :, slot]``, scores every cached key of each row, selects, and
        attends over what it selected.
      kw_pool/vw_pool: donated pools of the sliding layers; the kernel
        walks only the pages of a row's window there.
      conv_pool/ssm_pool: donated state pools of the ``ssm`` (and
        ``parallel``) layers; each reads its rows' slots
        (``page_tables[:, 2, 0]``; a padding row's is the null slot 0) and
        writes them back in place.
      tap: optional calibration hook (eager PTQ harness only).
      selection: the same step, its outputs followed by what it decided:
        with learned sparse attention the positions each sparse layer
        selected for each row ``(L, B, topk)`` and, a layer, the scores it
        selected them by ``(B, max_pages * ps)`` float32 (what lies past a
        row's length is not written); with latent attention every router's
        choices among all its experts ``(L_moe, B, k)`` int32 and, a
        latent layer, its heads' outputs before the out-projection ``(B,
        heads * v_head_dim)``, in the compute dtype the out-projection
        reads them in.  The step a server runs does not
        carry them: five more outputs, 16 MB of them, were 0.25 - 0.34 ms
        of a 16 ms step on a v5e.

    Returns ``(k_pool, v_pool, next_tokens, logits)``, with the scale
    pools, then the index pool, then the sliding layers' pools, spliced
    in after ``v_pool``
    when they were passed, and the experts' token counts ``(L, E)`` of
    the rows that hold a page appended for ``ffn='moe'``.
    """
    b = tokens.shape[0]
    quant = k_pool.dtype == jnp.int8
    scope = jax.named_scope
    tables = (page_tables if page_tables.ndim == 3
              else page_tables[:, None])
    with scope("embed"):
        lengths = positions + 1
        h = _embed(spec, params, tokens)
        if spec.positions == "learned":
            h = h + params["pos"][positions]
        rope = (_rope_tables(spec, positions)
                if spec.positions == "rotary" else None)
        # a padding row's tables are all null: it routes to no count
        live = jnp.any(tables != 0, axis=(1, 2))
    cdt = params["embed"].dtype
    kvd = spec.n_kv_heads * spec.head_dim
    def attend(q, k_pool, v_pool, table, li, window=None):
        """The rows' paged read of layer ``li`` of these pools."""
        read = paged_attention_diff if spec.diff_attn else paged_attention
        return read(q, k_pool, v_pool, table, lengths, layer=li,
                    window=window,
                    steps=_pages_walked(k_pool.shape[1], b))

    counts = []
    selected = []               # a sparse layer's (positions, scores)
    chosen = [] if selection else None      # each router's (B, k)
    heads_out = []              # a latent layer's (B, heads * v) float32
    memory = None               # the nearest ssm layer's y, (B, N) float32
    shared = None               # the nearest full layer's place in k_pool
    for i in range(spec.layers):
        kind = spec.layer_kind(i)
        if kind == "kda":
            li, slots = spec.ssm_layers.index(i), tables[:, 2, 0]
            with scope(f"layer{i}/kda"):
                # the rows' states are updated where they lie (kda.py)
                mixed, conv, ssm_pool = _kda.kda_decode(
                    spec, params, f"h{i}.kda",
                    _norm(spec, params, f"h{i}.ln1", h),
                    conv_pool[li, slots], ssm_pool, li, slots)
                h = (h.astype(jnp.float32) + mixed).astype(cdt)
            with scope(f"layer{i}/state_write"):
                conv_pool = conv_pool.at[li, slots].set(
                    conv.astype(conv_pool.dtype))
            h = _ffn(spec, params, i, h, tap, live, counts, chosen)
            continue
        if kind == "mla":
            li, table = spec.global_layers.index(i), tables[:, 0]
            with scope(f"layer{i}/attn_qkv"):
                x = _norm(spec, params, f"h{i}.ln1", h).astype(cdt)
                q, latent, kr = _mla_in(spec, params, i, x)
                page, slot = _page_slot(table, positions, page_size)
            with scope(f"layer{i}/latent_write"):
                k_pool = k_pool.at[li, page, slot].set(
                    latent.astype(k_pool.dtype))
                v_pool = v_pool.at[li, page, slot].set(
                    _lanes(kr, v_pool).astype(v_pool.dtype))
            with scope(f"layer{i}/attn_mla"):
                # the absorbed form: a head's query through W_UK onto the
                # latent, the latent's weighted sum through W_UV
                w_uk, w_uv = _mla_up(spec, params, i)
                qt = jnp.einsum("bhd,chd->bhc", q[..., :spec.qk_nope_dim],
                                w_uk, preferred_element_type=jnp.float32)
                ot = paged_attention_mla(
                    qt.astype(cdt), _lanes(q[..., spec.qk_nope_dim:], v_pool),
                    k_pool, v_pool, table, lengths, layer=li,
                    sm_scale=1.0 / math.sqrt(spec.qk_nope_dim
                                             + spec.qk_rope_dim),
                    steps=_pages_walked(k_pool.shape[1], b))
                o = jnp.einsum("bhc,chd->bhd", ot.astype(cdt), w_uv,
                               preferred_element_type=jnp.float32)
                if selection:
                    # the bfloat16 heads the out-projection reads: a float32
                    # output would move the cast out of the einsum's fusion
                    # and re-tile the out-projection, so the twin's logits
                    # would round apart from the scheduler's program's
                    heads_out.append(o.reshape(b, -1).astype(cdt))
            with scope(f"layer{i}/attn_out"):
                h = h + o.reshape(b, -1).astype(cdt) @ params[
                    f"h{i}.mla.wo"]
            h = _ffn(spec, params, i, h, tap, live, counts, chosen)
            continue
        if kind in ("ssm", "gmu"):
            with scope(f"layer{i}/{kind}"):
                x = _norm(spec, params, f"h{i}.ln1", h).astype(cdt)
                if kind == "gmu":
                    h = h + _ssm.gate_out(
                        params, f"h{i}.gmu", memory,
                        _matmul(params, f"h{i}.gmu.win", x, tap))
                else:
                    li, slots = spec.ssm_layers.index(i), tables[:, 2, 0]
                    memory, z, conv, state = _ssm.decode(
                        params, f"h{i}.ssm", x, conv_pool[li, slots],
                        ssm_pool[li, slots])
                    h = h + _ssm.gate_out(params, f"h{i}.ssm", memory, z)
            if kind == "ssm":
                with scope(f"layer{i}/state_write"):
                    conv_pool = conv_pool.at[li, slots].set(
                        conv.astype(conv_pool.dtype))
                    ssm_pool = ssm_pool.at[li, slots].set(state)
            h = _ffn(spec, params, i, h, tap, live, counts)
            continue
        window = spec.layer_window(i)
        table = tables[:, 1 if window else 0]
        if kind == "cross":
            # a query of its own over the pages of the full layer before
            with scope(f"layer{i}/attn_qkv"):
                x = _norm(spec, params, f"h{i}.ln1", h).astype(cdt)
                q = _project(spec, params, i, x, "q", spec.heads, tap)
            with scope(f"layer{i}/attn_cross"):
                o = attend(q, k_pool, v_pool, table, shared)
            with scope(f"layer{i}/attn_out"):
                h = _attn_out(spec, params, i, h, o, tap)
            h = _ffn(spec, params, i, h, tap, live, counts)
            continue
        parallel = kind == "parallel"
        with scope(f"layer{i}/attn_qkv"):
            # a parallel layer's two branches read one normed input
            n = _norm(spec, params, f"h{i}.ln1", h) if parallel else None
            q, k, v = _qkv(spec, params, i, h, rope, tap, n=n)
            page, slot = _page_slot(table, positions, page_size)  # (B,)
        if window:
            li = spec.window_layers.index(i)
            with scope(f"layer{i}/kv_write"):
                kw_pool = kw_pool.at[li, page, slot].set(
                    k.reshape(b, kvd).astype(kw_pool.dtype))
                vw_pool = vw_pool.at[li, page, slot].set(
                    v.reshape(b, kvd).astype(vw_pool.dtype))
            with scope(_attn_scope(spec, i)):
                o = attend(q, kw_pool, vw_pool, table, li, window)
        else:
            li = shared = spec.global_layers.index(i)
            with scope(f"layer{i}/kv_write"):
                if quant:
                    k, ksc = quantize_kv(k)
                    v, vsc = quantize_kv(v)
                    k_scale = k_scale.at[li, page, slot].set(ksc)
                    v_scale = v_scale.at[li, page, slot].set(vsc)
                if spec.sparse_topk:    # a token's K heads, then its V's
                    k_pool = k_pool.at[li, page * page_size + slot].set(
                        jnp.concatenate([k, v], axis=1).astype(k_pool.dtype))
                else:
                    k_pool = k_pool.at[li, page, slot].set(
                        k.reshape(b, kvd).astype(k_pool.dtype))
                    v_pool = v_pool.at[li, page, slot].set(
                        v.reshape(b, kvd).astype(v_pool.dtype))
            if spec.sparse_topk:
                with scope(f"layer{i}/index_write"):
                    x = _norm(spec, params, f"h{i}.ln1", h).astype(cdt)
                    index_pool = index_pool.at[li, page, :, slot].set(
                        _index_key(spec, params, i, x, rope
                                   ).astype(index_pool.dtype))
                with scope(f"layer{i}/indexer"):
                    scores = paged_index_scores(
                        *_index_queries(spec, params, i, x, rope),
                        index_pool, table, lengths, layer=li,
                        steps=_pages_walked(index_pool.shape[1], b))
                with scope(f"layer{i}/select"):
                    chosen, addresses, listed = select_tokens(
                        scores, lengths, table, topk=spec.sparse_topk,
                        page_size=page_size, pool_pages=index_pool.shape[1])
                    selected.append((chosen, scores))
            with scope(_attn_scope(spec, i)):
                if quant:
                    o = paged_attention_int8(q, k_pool, v_pool, k_scale,
                                             v_scale, table, lengths,
                                             layer=li)
                elif spec.sparse_topk:
                    # a padding row lists nothing
                    o = paged_attention_sparse(
                        q, k_pool, addresses, jnp.where(live, listed, 0),
                        layer=li)
                else:
                    o = attend(q, k_pool, v_pool, table, li)
        with scope(f"layer{i}/attn_out"):
            o = _attn_out(spec, params, i, h, o, tap)
        if parallel:
            si, slots = spec.ssm_layers.index(i), tables[:, 2, 0]
            with scope(f"layer{i}/ssm"):
                # the rows' states are updated where they lie (ssm.py)
                mixed, conv, ssm_pool = _ssm.mamba2_decode(
                    spec, params, f"h{i}.ssm", n, conv_pool[si, slots],
                    ssm_pool, si, slots)
                o = (h.astype(jnp.float32) + o + mixed).astype(cdt)
            with scope(f"layer{i}/state_write"):
                conv_pool = conv_pool.at[si, slots].set(
                    conv.astype(conv_pool.dtype))
        h = _ffn(spec, params, i, o, tap, live, counts)
    with scope("lm_head"):
        hf = _norm(spec, params, "lnf", h).astype(cdt)
        if tap is not None:
            tap("head", hf)
        logits = _head(spec, params, hf)                       # (B, V)
    with scope("sample"):
        next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return _results(spec, (k_pool, v_pool, k_scale, v_scale, index_pool,
                           kw_pool, vw_pool, conv_pool, ssm_pool),
                    next_tokens, logits, counts,
                    selected if selection else (),
                    chosen if spec.mla_layers else None, heads_out)
