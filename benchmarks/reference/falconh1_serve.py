"""Plain reference for the `falcon-h1-34b-serve` configuration
(Falcon-H1-34B-Instruct, https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/
blob/main/config.json; the block of transformers' `modeling_falcon_h1.py`):
one full causal forward over one sequence in float32 `jax.numpy` at matmul
precision "highest".  No cache, no kernel, no batching, no state slot: every
layer runs over every position, and the Mamba-2 recurrence is a `lax.scan`
**one position a step** from zero state — not the chunked form the program
runs, so the two share no algorithm.  It reads the published keys (`cfg`:
the configuration file's) and the flat weight dict the serving stack is
given, and nothing else of the program.

Layer `i`, m. the config's µP multipliers, RMSNorm (eps `rms_norm_eps`) with
a weight and no bias:

    n = RMSNorm(x)
    A = attention_out_multiplier * Attn(attention_in_multiplier * n) W_o
    M = ssm_out_multiplier * GroupRMSNorm(y * silu(z)) W_out
    x = x + A + M
    x = x + mlp_multipliers[1] * (silu(mlp_multipliers[0] * n2 W_g) * n2 W_u) W_d
        with n2 = RMSNorm(x)

Attention: q = a W_q (20 heads of 128), k = key_multiplier * a W_k, v = a W_v
(4 KV heads), no biases; rotary on all 128 lanes, rotate-half pairing, base
`rope_theta`; causal, scale 1 / sqrt(128); query head h reads KV head h // 5.
Mamba-2: [z, xBC, dt] = (ssm_in_multiplier * n) W_in * mu, mu the five
`ssm_multipliers` on z, x, B, C, dt; xBC = silu(causal depthwise conv of width
`mamba_d_conv` + bias); x 32 heads of 128, B and C `mamba_n_groups` groups of
`mamba_d_state`; delta = softplus(dt + dt_bias), a = -exp(A_log), a scalar a
head; for head h in group g = h // 16:
    S_t = exp(delta_t a) S_t-1 + delta_t x_t (x) B_g,t,   y_t = S_t C_g,t + D x_t
GroupRMSNorm over `mamba_n_groups` groups of 2,048 channels with one weight of
4,096 (`mamba_rms_norm` true, `mamba_norm_before_gate` false: gate first).
x_0 = embedding_multiplier * E[token]; logits = lm_head_multiplier *
RMSNorm(x_L) W_head (own head, `tie_word_embeddings` false).

Weights, as the serving stack names and lays them out (`serving/model.py`
`init_params`); each differs from the published tensor only as said:

    embed (V, h)   head (h, V): lm_head.weight transposed
    h<i>.ln1.w, h<i>.ln2.w, lnf.w (h,): input_layernorm, pre_ff_layernorm,
        final_layernorm
    h<i>.attn.wq (h, H*D), .wk, .wv (h, KVH*D), .wo (H*D, h): transposed
    h<i>.mlp.wgu (h, 2F): gate_proj and up_proj side by side, gate first,
        transposed   h<i>.mlp.wd (F, h): down_proj transposed
    h<i>.ssm.win (h, 9248): in_proj transposed, in its order z, x, B, C, dt
        .conv.w (K, 5120): conv1d.weight (5120, 1, K) transposed  .conv.b
        .dt_bias (32,)  .A_log (32,)  .D (32,)  .norm.w (4096,)
        .wout (4096, h): out_proj transposed

Departures from `modeling_falcon_h1.py`, which is not on this machine (the
equations of ISSUE 38 are the specification): no padding mask (one sequence,
causal), `time_step_limit` (0, inf) is no clamp and so is left out, no
`mamba_chunk_size` (the recurrence is sequential), everything float32.

So that the reference fits beside the engine's weights, they (bfloat16 there)
are upcast one matrix at a time, attention runs a query head and a block of
rows at a time, and the head a block of the vocabulary at a time.  The knobs
make the variants the benchmark's limits must tell apart: `round_to` rounds
every weight to a lower precision first; `state_dtype` rounds the SSM state to
it after every position; `norm_groups` overrides the gated norm's groups;
`swap_bc` swaps the two groups' B and C; `no_key_mult` leaves out
`key_multiplier`.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

ROW_BLOCK = 128     # query rows a block of the attention
HEAD_BLOCKS = 8     # blocks of the vocabulary the head is upcast in


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * w


def _rotary(x, theta):
    """Rotate-half rotary over all of each head's lanes: x (S, heads, D)."""
    s, _, d = x.shape
    freq = jnp.asarray(theta ** -(np.arange(0, d, 2, dtype=np.float64) / d),
                       jnp.float32)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _causal(q, k, v):
    """One head: q, k, v (S, D) -> (S, D), causal, ROW_BLOCK rows at a
    time."""
    s, d = q.shape
    block = min(ROW_BLOCK, s)
    assert s % block == 0, (s, block)
    cols = jnp.arange(s)

    def rows(r):
        qr = jax.lax.dynamic_slice_in_dim(q, r * block, block)
        p = r * block + jnp.arange(block)
        att = jnp.where(cols[None, :] <= p[:, None],
                        qr @ k.T / math.sqrt(d), -jnp.inf)
        return jax.nn.softmax(att, axis=-1) @ v

    return jax.lax.map(rows, jnp.arange(s // block)).reshape(s, d)


def mamba2_scan(x, delta, a, bmat, cmat, state_dtype=None, upto=None):
    """The recurrence one position a step from zero state: x (S, H, P),
    delta (S, H), a (H,), bmat / cmat (S, G, N), float32.  Returns y (S, H,
    P) without D, and the state (H, P, N) after the first `upto` positions
    (all of them where None).  `state_dtype` rounds the state to it after
    every position."""
    s, h, p = x.shape
    g, n = bmat.shape[1:]
    group = np.arange(h) // (h // g)
    upto = s if upto is None else upto

    def step(carry, xs):
        st, kept = carry
        t, d, xt, bt, ct = xs
        st = (jnp.exp(d * a)[:, None, None] * st
              + (d[:, None] * xt)[:, :, None] * bt[group][:, None, :])
        if state_dtype is not None:
            # reduce_precision, not a cast there and back: XLA may drop
            # such a pair of converts (xla_allow_excess_precision)
            fi = jnp.finfo(state_dtype)
            st = jax.lax.reduce_precision(st, fi.nexp, fi.nmant)
        y = jnp.sum(st * ct[group][:, None, :], axis=-1)
        return (st, jnp.where(t < upto, st, kept)), y

    zero = jnp.zeros((h, p, n), jnp.float32)
    (_, kept), y = jax.lax.scan(step, (zero, zero),
                                (jnp.arange(s), delta, x, bmat, cmat))
    return y, kept


def _mamba2(n, w, name, cfg, state_dtype, upto, norm_groups, swap_bc):
    """The Mamba-2 branch over n (S, h): (M (S, h), the state (H, P, N)
    after `upto` positions)."""
    s = n.shape[0]
    heads, d_head = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, r = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    inner = heads * d_head
    mu = np.repeat(np.asarray(cfg["ssm_multipliers"], np.float32),
                   (inner, inner, g * r, g * r, heads))
    proj = (n * cfg["ssm_in_multiplier"]) @ w(name + ".win") * mu
    z, xbc, dt = jnp.split(proj, [inner, 2 * inner + 2 * g * r], axis=-1)
    cw = w(name + ".conv.w")                                 # (K, C)
    k = cw.shape[0]
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[j:j + s] * cw[j] for j in range(k))
                      + w(name + ".conv.b"))
    x = xbc[:, :inner].reshape(s, heads, d_head)
    bm = xbc[:, inner:inner + g * r].reshape(s, g, r)
    cm = xbc[:, inner + g * r:].reshape(s, g, r)
    if swap_bc:
        bm, cm = bm[:, ::-1], cm[:, ::-1]
    delta = jax.nn.softplus(dt + w(name + ".dt_bias"))
    a = -jnp.exp(w(name + ".A_log"))
    y, kept = mamba2_scan(x, delta, a, bm, cm, state_dtype, upto)
    y = y + w(name + ".D")[:, None] * x
    groups = norm_groups or g
    gated = (y.reshape(s, inner) * jax.nn.silu(z)).reshape(s, groups, -1)
    gated = gated / jnp.sqrt(jnp.mean(jnp.square(gated), axis=-1,
                                      keepdims=True) + cfg["rms_norm_eps"])
    u = gated.reshape(s, inner) * w(name + ".norm.w")
    return cfg["ssm_out_multiplier"] * (u @ w(name + ".wout")), kept


@functools.partial(jax.jit, static_argnames=(
    "cfg", "rows", "round_to", "state_dtype", "norm_groups", "swap_bc",
    "no_key_mult"))
def _forward(params, tokens, first_row, *, cfg, rows, round_to,
             state_dtype, norm_groups, swap_bc, no_key_mult):
    cfg = dict(cfg)
    f32 = jnp.float32

    def w(name):
        a = params[name]
        if round_to is not None:
            a = a.astype(round_to)
        return a.astype(f32)

    with jax.default_matmul_precision("highest"):
        s = tokens.shape[0]
        heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        d = cfg["head_dim"]
        eps = cfg["rms_norm_eps"]
        mg, md = cfg["mlp_multipliers"]
        key_mult = 1.0 if no_key_mult else cfg["key_multiplier"]
        h = params["embed"][tokens]
        if round_to is not None:
            h = h.astype(round_to)
        h = h.astype(f32) * cfg["embedding_multiplier"]
        states = []
        for i in range(cfg["num_hidden_layers"]):
            n = _rms(h, w(f"h{i}.ln1.w"), eps)
            at = f"h{i}.attn."
            a = n * cfg["attention_in_multiplier"]
            q = _rotary((a @ w(at + "wq")).reshape(s, heads, d),
                        cfg["rope_theta"])
            kk = _rotary((a @ w(at + "wk")).reshape(s, kvh, d) * key_mult,
                         cfg["rope_theta"])
            v = (a @ w(at + "wv")).reshape(s, kvh, d)

            def head(j, q=q, kk=kk, v=v):
                m = j // (heads // kvh)
                return _causal(jnp.take(q, j, axis=1),
                               jnp.take(kk, m, axis=1),
                               jnp.take(v, m, axis=1))

            o = jnp.transpose(jax.lax.map(head, jnp.arange(heads)),
                              (1, 0, 2))
            attn = cfg["attention_out_multiplier"] * (
                o.reshape(s, heads * d) @ w(at + "wo"))
            mixed, kept = _mamba2(n, w, f"h{i}.ssm", cfg, state_dtype,
                                  first_row + rows, norm_groups, swap_bc)
            states.append(kept)
            h = h + attn + mixed
            n = _rms(h, w(f"h{i}.ln2.w"), eps)
            gate, up = jnp.split(n @ w(f"h{i}.mlp.wgu"), 2, axis=-1)
            h = h + md * ((up * jax.nn.silu(mg * gate)) @ w(f"h{i}.mlp.wd"))
        h = _rms(h, w("lnf.w"), eps)
        h = jax.lax.dynamic_slice_in_dim(h, first_row, rows)
        # the head a block of the vocabulary at a time: (h, V) in float32
        # is twice the bfloat16 head the engine holds
        vocab = params["head"].shape[1]
        blocks = HEAD_BLOCKS if vocab % HEAD_BLOCKS == 0 else 1
        head_w = params["head"].reshape(-1, blocks, vocab // blocks)

        def head_block(b):
            e = head_w[:, b]
            if round_to is not None:
                e = e.astype(round_to)
            return h @ e.astype(f32)

        logits = jax.lax.map(head_block, jnp.arange(blocks))
        logits = jnp.transpose(logits, (1, 0, 2)).reshape(rows, vocab)
        return cfg["lm_head_multiplier"] * logits, jnp.stack(states)


KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "num_hidden_layers", "rms_norm_eps", "rope_theta",
        "mamba_n_heads", "mamba_d_head", "mamba_n_groups", "mamba_d_state",
        "embedding_multiplier", "attention_in_multiplier",
        "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
        "ssm_out_multiplier", "ssm_multipliers", "mlp_multipliers",
        "lm_head_multiplier")


def forward(params, tokens, first_row, *, cfg, rows, round_to=None,
            state_dtype=None, norm_groups=None, swap_bc=False,
            no_key_mult=False):
    """(logits (rows, V), states (layers, H, P, N)) of one sequence
    `tokens` (S,): the logits of positions first_row .. first_row + rows,
    and each layer's Mamba-2 state after the last of them.  Tokens after
    those rows do not matter (the model is causal).  `cfg` holds the
    published keys."""
    frozen = tuple((k, tuple(cfg[k]) if isinstance(cfg[k], list)
                    else cfg[k]) for k in KEYS)
    return _forward(params, tokens, first_row, cfg=frozen, rows=rows,
                    round_to=round_to, state_dtype=state_dtype,
                    norm_groups=norm_groups, swap_bc=swap_bc,
                    no_key_mult=no_key_mult)
