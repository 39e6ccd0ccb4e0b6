"""Plain reference for the `gpt-345m-serve` configuration: a GPT-2
style decoder (learned positions, pre-LN, tanh-GELU MLP of 4x, tied
output head) as one full causal forward in float32 `jax.numpy`, no
cache, no kernels, no batching, matmul precision "highest".  It reads
the flat weight dict the serving stack is given (`embed`, `pos`,
`h<i>.ln1.w` ... `lnf.b`) and nothing else of the program."""
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


@functools.partial(jax.jit, static_argnames=("layers", "heads", "rows"))
def forward(params, tokens, first_row, *, layers, heads, rows):
    """Logits (rows, V) of the positions first_row .. first_row + rows
    of one sequence `tokens` (S,); tokens after them do not matter."""
    with jax.default_matmul_precision("highest"):
        s = tokens.shape[0]
        h = params["embed"][tokens] + params["pos"][:s]
        d = h.shape[-1] // heads
        causal = jnp.tril(jnp.ones((s, s), bool))
        for i in range(layers):
            x = _ln(h, params[f"h{i}.ln1.w"], params[f"h{i}.ln1.b"])
            q = (x @ params[f"h{i}.attn.wq"]).reshape(s, heads, d)
            k = (x @ params[f"h{i}.attn.wk"]).reshape(s, heads, d)
            v = (x @ params[f"h{i}.attn.wv"]).reshape(s, heads, d)
            att = jnp.einsum("ihd,jhd->hij", q, k) / math.sqrt(d)
            att = jnp.where(causal[None], att, -jnp.inf)
            w = jax.nn.softmax(att, axis=-1)
            o = jnp.einsum("hij,jhd->ihd", w, v).reshape(s, heads * d)
            h = h + o @ params[f"h{i}.attn.wo"]
            x = _ln(h, params[f"h{i}.ln2.w"], params[f"h{i}.ln2.b"])
            m = _gelu(x @ params[f"h{i}.mlp.w1"] + params[f"h{i}.mlp.b1"])
            h = h + m @ params[f"h{i}.mlp.w2"] + params[f"h{i}.mlp.b2"]
        h = _ln(h, params["lnf.w"], params["lnf.b"])
        h = jax.lax.dynamic_slice_in_dim(h, first_row, rows)
        return h @ params["embed"].T
