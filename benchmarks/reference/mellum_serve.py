"""Plain reference for the `mellum2-12b-a2p5b-serve` configuration
(Mellum2-12B-A2.5B-Instruct, https://huggingface.co/JetBrains/
Mellum2-12B-A2.5B-Instruct/blob/main/config.json): one full causal
forward in float32 `jax.numpy` at matmul precision "highest", no cache,
no kernel, no batching, every expert computed for every token and masked.
It reads the published `config.json` keys (`cfg`) and the flat weight
dict the serving stack is given, and nothing else of the program:

    embed (V, h)                h<i>.ln1.w, h<i>.ln2.w, lnf.w (h,)
    h<i>.attn.wq (h, H*D)       h<i>.attn.wk, .wv (h, KVH*D)
    h<i>.attn.wo (H*D, h)       h<i>.moe.router (h, E)
    h<i>.moe.wg, .wu (E, h, F)  h<i>.moe.wd (E, F, h)      head (h, V)

A layer: x = rms(h; ln1); q, k, v = x Wq, x Wk, x Wv; rotary (rotate-half
pairing, theta from `rope_parameters`; YaRN on the full-attention layers
at every length); query head j reads KV head j // (H / KVH); key u is
visible to query p iff u <= p and, in a sliding layer, p - u <
`sliding_window`; softmax in float32; h += o Wo.  y = rms(h; ln2);
r = softmax(y Wr) over all experts; the `num_experts_per_tok` largest,
renormalised; h += sum_e g_e (silu(y Wg_e) * (y Wu_e)) Wd_e.  After the
last layer rms(h; lnf) head.

Departures from the published description, each because `config.json`
has no key that settles it (the configuration file lists them under
`assumed`): no per-head norm on q and k; softmax before top-k; rotate-
half pairing; the multi-token-prediction head is not computed.

So that 12 layers at 8k tokens fit on the chip beside the engine, the
weights (bfloat16 there) are upcast one matrix or one expert at a time,
and attention is computed a query head and a block of rows at a time:
nothing of size S x S x heads exists.  `round_to` rounds every weight to
a lower precision first: how the benchmark shows that its tolerance
tells the stated precision from the one below it.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

ROW_BLOCK = 1024    # query rows a block of the attention
HEAD_BLOCKS = 8     # blocks of the vocabulary the head is upcast in


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * w


def inv_frequencies(rope, head_dim):
    """(frequencies (D/2,), factor on cos and sin) of one entry of
    `rope_parameters`."""
    i = np.arange(head_dim // 2, dtype=np.float64)
    f = float(rope["rope_theta"]) ** (-2.0 * i / head_dim)
    if rope.get("rope_type", "default") != "yarn":
        return f, 1.0
    s, l0 = float(rope["factor"]), float(rope["original_max_position_embeddings"])

    def corr(beta):
        return (head_dim * math.log(l0 / (2.0 * math.pi * beta))
                / (2.0 * math.log(float(rope["rope_theta"]))))

    low = max(math.floor(corr(rope["beta_fast"])), 0)
    high = min(math.ceil(corr(rope["beta_slow"])), head_dim - 1)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    factor = rope.get("attention_factor") or 0.1 * math.log(s) + 1.0
    return f / s * ramp + f * (1.0 - ramp), float(factor)


def _rotary(x, freqs, factor):
    """x (S, heads, D) at positions 0..S-1, rotate-half pairing."""
    s, _, d = x.shape
    ang = (jnp.arange(s, dtype=jnp.float32)[:, None]
           * jnp.asarray(freqs, jnp.float32)[None, :])
    cos, sin = jnp.cos(ang)[:, None, :] * factor, jnp.sin(ang)[:, None, :] * factor
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(q, k, v, window):
    """q (S, H, D), k / v (S, KVH, D) -> (S, H*D); one query head and
    ROW_BLOCK rows at a time."""
    s, h, d = q.shape
    group = h // k.shape[1]
    block = min(ROW_BLOCK, s)
    assert s % block == 0, (s, block)
    cols = jnp.arange(s)

    def one_head(j):
        qh, kh, vh = q[:, j], k[:, j // group], v[:, j // group]

        def rows(r):
            qr = jax.lax.dynamic_slice_in_dim(qh, r * block, block)
            p = r * block + jnp.arange(block)
            seen = cols[None, :] <= p[:, None]
            if window:
                seen &= p[:, None] - cols[None, :] < window
            att = jnp.where(seen, qr @ kh.T / math.sqrt(d), -jnp.inf)
            return jax.nn.softmax(att, axis=-1) @ vh

        return jax.lax.map(rows, jnp.arange(s // block)).reshape(s, d)

    out = jax.lax.map(one_head, jnp.arange(h))              # (H, S, D)
    return jnp.transpose(out, (1, 0, 2)).reshape(s, h * d)


@functools.partial(jax.jit, static_argnames=("cfg", "rows", "round_to"))
def _forward(params, tokens, first_row, *, cfg, rows, round_to):
    cfg = dict(cfg)
    f32 = jnp.float32

    def w(name, *index):
        a = params[name]
        if name == "head":      # (h, V) seen as (h, blocks, V / blocks)
            a = a.reshape(a.shape[0], -1, a.shape[1] // (
                HEAD_BLOCKS if a.shape[1] % HEAD_BLOCKS == 0 else 1))
        for i in index:
            a = a[i]
        if round_to is not None:
            a = a.astype(round_to)
        return a.astype(f32)

    with jax.default_matmul_precision("highest"):
        s = tokens.shape[0]
        d, heads = cfg["head_dim"], cfg["num_attention_heads"]
        kvh, eps = cfg["num_key_value_heads"], cfg["rms_norm_eps"]
        n_exp, top_k = cfg["num_experts"], cfg["num_experts_per_tok"]
        rope = {kind: inv_frequencies(dict(p), d)
                for kind, p in cfg["rope_parameters"]}
        h = params["embed"][tokens]
        if round_to is not None:
            h = h.astype(round_to)
        h = h.astype(f32)
        routed = []
        for i, kind in enumerate(cfg["layer_types"]):
            x = _rms(h, w(f"h{i}.ln1.w"), eps)
            q = (x @ w(f"h{i}.attn.wq")).reshape(s, heads, d)
            k = (x @ w(f"h{i}.attn.wk")).reshape(s, kvh, d)
            v = (x @ w(f"h{i}.attn.wv")).reshape(s, kvh, d)
            q, k = _rotary(q, *rope[kind]), _rotary(k, *rope[kind])
            window = cfg["sliding_window"] \
                if kind == "sliding_attention" else 0
            h = h + _attention(q, k, v, window) @ w(f"h{i}.attn.wo")
            y = _rms(h, w(f"h{i}.ln2.w"), eps)
            r = jax.nn.softmax(y @ w(f"h{i}.moe.router"), axis=-1)
            top, idx = jax.lax.top_k(r, top_k)
            if cfg["norm_topk_prob"]:
                top = top / jnp.sum(top, axis=-1, keepdims=True)
            # (S, E): the router's weight of each expert, 0 if unchosen
            gate = jnp.sum(jax.nn.one_hot(idx, n_exp, dtype=f32)
                           * top[..., None], axis=1)
            routed.append(gate > 0)

            def add_expert(e, acc, i=i, y=y, gate=gate):
                up = jax.nn.silu(y @ w(f"h{i}.moe.wg", e)) \
                    * (y @ w(f"h{i}.moe.wu", e))
                return acc + (up @ w(f"h{i}.moe.wd", e)) * gate[:, e, None]

            h = jax.lax.fori_loop(0, n_exp, add_expert, h)
        h = _rms(h, w("lnf.w"), eps)
        h = jax.lax.dynamic_slice_in_dim(h, first_row, rows)
        # the head a block of the vocabulary at a time: (h, V) in
        # float32 is as large again as the matrix itself
        vocab = params["head"].shape[1]
        blocks = HEAD_BLOCKS if vocab % HEAD_BLOCKS == 0 else 1
        logits = jax.lax.map(
            lambda b: h @ w("head", (slice(None), b)),
            jnp.arange(blocks))                  # (blocks, rows, V/blocks)
        return (jnp.transpose(logits, (1, 0, 2)).reshape(rows, vocab),
                jnp.stack(routed))


def forward(params, tokens, first_row, *, cfg, rows, round_to=None):
    """(logits (rows, V), routed (L, S, E) bool) of one sequence
    `tokens` (S,): the logits of positions first_row .. first_row + rows,
    and which experts each position of each layer was routed to.  Tokens
    after those rows do not matter (the model is causal).  `cfg` holds
    the published keys; `layer_types` gives the depth."""
    rope = tuple(sorted((kind, tuple(sorted(p.items())))
                        for kind, p in cfg["rope_parameters"].items()))
    keep = ("head_dim", "num_attention_heads", "num_key_value_heads",
            "rms_norm_eps", "num_experts", "num_experts_per_tok",
            "norm_topk_prob", "sliding_window")
    frozen = tuple(sorted(
        [(k, cfg[k]) for k in keep]
        + [("layer_types", tuple(cfg["layer_types"])),
           ("rope_parameters", rope)]))
    return _forward(params, tokens, first_row, cfg=frozen, rows=rows,
                    round_to=round_to)
