"""Plain reference for the `gpt-345m` and `gpt3-1p3b` configurations:
the GPT-2/GPT-3 decoder (learned positions, pre-LN blocks, one fused
QKV projection whose columns are laid out head by head as [q | k | v],
tanh-GELU MLP, tied output head) and its mean next-token cross-entropy,
as one float32 `jax.numpy` forward without dropout, at matmul precision
"highest".  It reads a flat {name: array} dict under the names
`GPTForCausalLM.named_parameters()` gives and nothing else of the
program; weights are (in, out)."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def _ln(x, w, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * w + b


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("layers", "heads"))
def loss(params, ids, labels, *, layers, heads):
    """Mean cross-entropy of `labels` (B, S) under the model on `ids`."""
    with jax.default_matmul_precision("highest"):
        p = {k: v.astype(jnp.float32) for k, v in params.items()}
        b, s = ids.shape
        h = (p["gpt.embeddings.word_embeddings.weight"][ids]
             + p["gpt.embeddings.position_embeddings.weight"][:s][None])
        d = h.shape[-1] // heads
        causal = jnp.tril(jnp.ones((s, s), bool))
        for i in range(layers):
            pre = f"gpt.layers.{i}."
            x = _ln(h, p[pre + "ln1.weight"], p[pre + "ln1.bias"])
            qkv = x @ p[pre + "attn.qkv_proj.weight"] \
                + p[pre + "attn.qkv_proj.bias"]
            qkv = qkv.reshape(b, s, heads, 3 * d)
            q, k, v = qkv[..., :d], qkv[..., d:2 * d], qkv[..., 2 * d:]
            att = jnp.einsum("bihd,bjhd->bhij", q, k) / math.sqrt(d)
            att = jnp.where(causal[None, None], att, -jnp.inf)
            w = jax.nn.softmax(att, axis=-1)
            o = jnp.einsum("bhij,bjhd->bihd", w, v).reshape(b, s, heads * d)
            h = h + o @ p[pre + "attn.out_proj.weight"] \
                + p[pre + "attn.out_proj.bias"]
            x = _ln(h, p[pre + "ln2.weight"], p[pre + "ln2.bias"])
            m = _gelu(x @ p[pre + "mlp.fc1.weight"] + p[pre + "mlp.fc1.bias"])
            h = h + m @ p[pre + "mlp.fc2.weight"] + p[pre + "mlp.fc2.bias"]
        h = _ln(h, p["gpt.final_ln.weight"], p["gpt.final_ln.bias"])
        logits = h @ p["gpt.embeddings.word_embeddings.weight"].T
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)
        return -jnp.mean(picked)
