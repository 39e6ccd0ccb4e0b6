"""GPT model family — the flagship hybrid-parallel LLM.

The reference ships GPT in PaddleNLP built from the in-repo pieces this
framework re-designs: VocabParallelEmbedding / Column-Row parallel linears
(``fleet/layers/mpu/mp_layers.py``), fused attention+FFN
(``paddle/phi/kernels/fusion/``), flash attention
(``paddle/phi/kernels/gpu/flash_attn_kernel.cu``), recompute
(``fleet/recompute/``), hybrid dp×mp×pp scheduling (SURVEY §3.3, baseline
config[3]: GPT-3 1.3B).

TPU-first design decisions:
 - ONE logical model: parameters carry ``PartitionSpec`` annotations
   (embedding/vocab over ``mp``, QKV/out/MLP per Megatron, everything
   optionally fsdp-sharded over ``sharding``); GSPMD partitions the jitted
   train step — no per-rank model surgery.
 - attention is ``F.scaled_dot_product_attention`` (Pallas flash kernel on
   TPU hardware), bf16-first.
 - sequence axis can be sharded (``sep``) for long context — constraint
   hints are placed on the activations; ring attention rides
   ``paddle_tpu.nn.functional.ring_attention`` when enabled.
 - recompute per decoder block via ``jax.checkpoint``.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np
import jax
import jax.numpy as jnp

from ...tensor import Tensor
from ...nn.layer.layers import Layer
from ...nn.layer.common import Linear, Dropout, Embedding
from ...nn.layer.norm import LayerNorm
from ...nn.layer.container import LayerList
from ...nn import functional as F
from ...nn import initializer as I
from ...distributed.fleet.meta_parallel import (
    VocabParallelEmbedding, ColumnParallelLinear, RowParallelLinear,
    ParallelCrossEntropy)
from ...distributed import mesh as _mesh_mod
from ..nn.functional import fused_rotary_position_embedding

__all__ = ["GPTConfig", "GPTModel", "GPTForCausalLM",
           "GPTPretrainingCriterion", "gpt_tiny", "gpt_345m", "gpt_1p3b",
           "gpt_6p7b", "gpt_13b"]


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304          # padded to a multiple of 128 for MXU
    hidden_size: int = 2048
    num_layers: int = 24
    num_attention_heads: int = 16
    intermediate_size: int = 0       # 0 → 4*hidden
    max_position_embeddings: int = 2048
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    initializer_range: float = 0.02
    layer_norm_epsilon: float = 1e-5
    use_rope: bool = False           # GPT-3 uses learned positions
    tie_word_embeddings: bool = True
    use_recompute: bool = False
    recompute_policy: str | None = None  # see fleet.recompute._POLICIES
    tensor_parallel: bool = True     # annotate megatron specs

    def __post_init__(self):
        if self.intermediate_size == 0:
            self.intermediate_size = 4 * self.hidden_size


def _seq_constraint(t: Tensor) -> Tensor:
    """Shard the sequence axis over 'sep' when that axis exists (>1)."""
    if _mesh_mod.mesh_axis_size("sep") <= 1:
        return t
    mesh = _mesh_mod.get_mesh(create_default=False)
    if mesh is None or not isinstance(t._data, jax.core.Tracer):
        return t
    from jax.sharding import NamedSharding
    from ...distributed.auto_parallel.spec_layout import default_layout
    try:
        t._data = jax.lax.with_sharding_constraint(
            t._data, NamedSharding(mesh, default_layout().batch_seq()))
    except Exception:
        pass
    return t


class GPTAttention(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        h, heads = cfg.hidden_size, cfg.num_attention_heads
        self.num_heads = heads
        self.head_dim = h // heads
        self.use_rope = cfg.use_rope
        init = I.Normal(std=cfg.initializer_range)
        if cfg.tensor_parallel:
            self.qkv_proj = ColumnParallelLinear(
                h, 3 * h, gather_output=False, weight_attr=init)
            self.out_proj = RowParallelLinear(
                h, h, input_is_parallel=True, weight_attr=init)
        else:
            self.qkv_proj = Linear(h, 3 * h, weight_attr=init)
            self.out_proj = Linear(h, h, weight_attr=init)
        self.attn_dropout_p = cfg.attention_probs_dropout_prob

    def forward(self, x, attn_mask=None):
        B, S = x.shape[0], x.shape[1]
        qkv = self.qkv_proj(x)
        qkv = qkv.reshape([B, S, self.num_heads, 3 * self.head_dim])
        q = qkv[..., : self.head_dim]
        k = qkv[..., self.head_dim: 2 * self.head_dim]
        v = qkv[..., 2 * self.head_dim:]
        if self.use_rope:
            q, k, _ = fused_rotary_position_embedding(q, k)
        out = F.scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask, is_causal=attn_mask is None,
            dropout_p=self.attn_dropout_p, training=self.training)
        out = out.reshape([B, S, self.num_heads * self.head_dim])
        return self.out_proj(out)


class GPTMLP(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        init = I.Normal(std=cfg.initializer_range)
        out_init = I.Normal(
            std=cfg.initializer_range / math.sqrt(2 * cfg.num_layers))
        if cfg.tensor_parallel:
            self.fc1 = ColumnParallelLinear(
                cfg.hidden_size, cfg.intermediate_size, gather_output=False,
                weight_attr=init)
            self.fc2 = RowParallelLinear(
                cfg.intermediate_size, cfg.hidden_size,
                input_is_parallel=True, weight_attr=out_init)
        else:
            self.fc1 = Linear(cfg.hidden_size, cfg.intermediate_size,
                              weight_attr=init)
            self.fc2 = Linear(cfg.intermediate_size, cfg.hidden_size,
                              weight_attr=out_init)

    def forward(self, x):
        return self.fc2(F.gelu(self.fc1(x), approximate=True))


class GPTDecoderLayer(Layer):
    """Pre-LN decoder block."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.ln1 = LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_epsilon)
        self.attn = GPTAttention(cfg)
        self.ln2 = LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_epsilon)
        self.mlp = GPTMLP(cfg)
        self.dropout1 = Dropout(cfg.hidden_dropout_prob)
        self.dropout2 = Dropout(cfg.hidden_dropout_prob)

    def forward(self, x, attn_mask=None):
        x = _seq_constraint(x)
        x = x + self.dropout1(self.attn(self.ln1(x), attn_mask))
        x = x + self.dropout2(self.mlp(self.ln2(x)))
        return x


class GPTEmbeddings(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        init = I.Normal(std=cfg.initializer_range)
        if cfg.tensor_parallel:
            self.word_embeddings = VocabParallelEmbedding(
                cfg.vocab_size, cfg.hidden_size, weight_attr=init)
        else:
            self.word_embeddings = Embedding(
                cfg.vocab_size, cfg.hidden_size, weight_attr=init)
        self.use_rope = cfg.use_rope
        if not cfg.use_rope:
            self.position_embeddings = Embedding(
                cfg.max_position_embeddings, cfg.hidden_size,
                weight_attr=init)
        self.dropout = Dropout(cfg.hidden_dropout_prob)

    def forward(self, input_ids, position_ids=None):
        x = self.word_embeddings(input_ids)
        if not self.use_rope:
            if position_ids is None:
                S = input_ids.shape[1]
                position_ids = Tensor(jnp.arange(S, dtype=jnp.int32)[None, :])
            x = x + self.position_embeddings(position_ids)
        return self.dropout(x)


class GPTModel(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.config = cfg
        self.embeddings = GPTEmbeddings(cfg)
        self.layers = LayerList([GPTDecoderLayer(cfg)
                                 for _ in range(cfg.num_layers)])
        self.final_ln = LayerNorm(cfg.hidden_size,
                                  epsilon=cfg.layer_norm_epsilon)
        self.use_recompute = cfg.use_recompute
        self.recompute_policy = cfg.recompute_policy

    def forward(self, input_ids, position_ids=None, attention_mask=None):
        x = self.embeddings(input_ids, position_ids)
        from ...distributed.fleet.meta_parallel.pp_spmd import \
            current_pipeline_executor
        pexec = current_pipeline_executor()
        if pexec is not None:
            # compiled SPMD pipeline over the decoder stack (pp mesh axis)
            x = pexec(x, attention_mask)
        elif self.use_recompute:
            from ...distributed.fleet.recompute import recompute
            for layer in self.layers:
                x = recompute(layer, x, attention_mask,
                              policy=self.recompute_policy)
        else:
            for layer in self.layers:
                x = layer(x, attention_mask)
        return self.final_ln(x)


class GPTForCausalLM(Layer):
    """GPT + LM head (tied to the word embedding by default, like the
    reference's GPTForPretraining)."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.config = cfg
        self.gpt = GPTModel(cfg)
        self.tie = cfg.tie_word_embeddings
        if not self.tie:
            init = I.Normal(std=cfg.initializer_range)
            if cfg.tensor_parallel:
                self.lm_head = ColumnParallelLinear(
                    cfg.hidden_size, cfg.vocab_size, has_bias=False,
                    gather_output=False, weight_attr=init)
            else:
                self.lm_head = Linear(cfg.hidden_size, cfg.vocab_size,
                                      weight_attr=init, bias_attr=False)

    def pipeline_blocks(self):
        """Pipeline-parallel adapter (consumed by
        ``distributed.train_step.build_train_step`` when the mesh has a
        ``pp`` axis): the homogeneous decoder stack to shard over stages.

        Returns (block_param_prefixes, block_layer): prefixes name each
        block's parameters in ``named_parameters()`` order; ``block_layer``
        is one representative block for functional per-stage calls.
        """
        n = len(self.gpt.layers)
        return ([f"gpt.layers.{i}." for i in range(n)], self.gpt.layers[0])

    def forward(self, input_ids, position_ids=None, attention_mask=None):
        x = self.gpt(input_ids, position_ids, attention_mask)
        if self.tie:
            from ...ops.op_utils import nary
            w = self.gpt.embeddings.word_embeddings.weight
            with jax.named_scope("lm_head"):
                logits = nary(
                    lambda h, wt: jnp.einsum("bsh,vh->bsv", h, wt),
                    [x, w], name="lm_head_tied")
        else:
            logits = self.lm_head(x)
        return logits


class GPTPretrainingCriterion(Layer):
    """Causal-LM loss over (possibly vocab-sharded) logits."""

    _scope_name = "loss"

    def __init__(self, cfg: GPTConfig | None = None):
        super().__init__()
        self.ce = ParallelCrossEntropy()

    def forward(self, logits, labels, loss_mask=None):
        loss = self.ce(logits, labels)  # [B, S, 1]
        from ... import ops
        loss2d = loss.reshape([-1])
        if loss_mask is not None:
            m = loss_mask.reshape([-1]).astype("float32")
            return (loss2d * m).sum() / ops.math.clip(m.sum(), 1e-6, None)
        return loss2d.mean()


# -- canonical configs ------------------------------------------------------

def gpt_tiny(**kw):
    return GPTConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                     num_attention_heads=4, max_position_embeddings=128,
                     **kw)


def gpt_345m(**kw):
    return GPTConfig(hidden_size=1024, num_layers=24,
                     num_attention_heads=16, **kw)


def gpt_1p3b(**kw):
    """Baseline config[3]: GPT-3 1.3B (hidden 2048, 24 layers, 16 heads)."""
    return GPTConfig(hidden_size=2048, num_layers=24,
                     num_attention_heads=16, **kw)


def gpt_6p7b(**kw):
    return GPTConfig(hidden_size=4096, num_layers=32,
                     num_attention_heads=32, **kw)


def gpt_13b(**kw):
    return GPTConfig(hidden_size=5120, num_layers=40,
                     num_attention_heads=40, **kw)
