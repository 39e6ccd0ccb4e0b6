"""Plain reference for the `phi4-mini-flash-serve` configuration
(Phi-4-mini-flash-reasoning, https://huggingface.co/microsoft/
Phi-4-mini-flash-reasoning/blob/main/config.json; the SambaY decoder of
arXiv:2507.06607): one full causal forward over one sequence in float32
`jax.numpy` at matmul precision "highest".  No cache, no kernel, no
batching, no state slot: every layer runs over every position, the scan
is a `lax.scan` a position from zero state, and a cross layer attends the
full layer's K and V of the whole sequence.  It reads the published keys
(`cfg`: the configuration file's, with its `assumed` block) and the flat
weight dict the serving stack is given, and nothing else of the program.

Layer `i` of `N`, pre-norm, LayerNorm with bias, no positions anywhere:
`h = x + Mixer_i(LN1(x)); out = h + MLP(LN2(h))`, MLP `W_d (up * silu(gate))`
with `[gate, up] = n W_gu`.  Mixers (ISSUE 31 has the equations in full):

 - even i <= N/2: Mamba-1.  `[xs, z] = n W_in; xc = silu(conv(xs));
   [dt, B, C] = xc W_x; delta = softplus(dt W_dt + b_dt); A = -exp(A_log);
   s_t = exp(delta_t A) s_{t-1} + (delta_t xc_t) B_t^T; y_t = s_t C_t +
   D xc_t; out = (y * silu(z)) W_out`.  Layer N/2's `y` is the memory `m`.
 - even i > N/2: gated memory unit, `(m * silu(n W_in)) W_out`.
 - odd i < N/2: differential attention, sliding (key u visible to query p
   iff 0 <= p - u < `sliding_window`); i = N/2 + 1: the same, causal, and
   its K and V are what every odd i > N/2 + 1 (a query of its own) reads.
   Heads of 64 in order; query heads 2j, 2j+1 = (q1_j, q2_j), K heads 2m,
   2m+1 = (k1_m, k2_m), V heads 2m, 2m+1 side by side = V_m, pair j reads
   KV pair j // 2; `A1 = softmax(q1 K1^T / 8) V, A2 = softmax(q2 K2^T / 8) V;
   l = exp(lq1 . lk1) - exp(lq2 . lk2) + l0; l0 = 0.8 - 0.6 exp(-0.3 i);
   O_j = (1 - l0) RMSNorm(A1 - l A2)`; concat, `W_o`, bias.

Weights, as the serving stack names and lays them out (`serving/model.py`
`init_params`); each differs from the published tensor only as said:

    embed (V, h)    h<i>.ln1.w/.b, h<i>.ln2.w/.b, lnf.w/.b (h,)
    h<i>.mlp.wgu (h, 2F) gate first     h<i>.mlp.wd (F, h)
    h<i>.attn.wq (h, H*D), .wk, .wv (h, KVH*D), .bq, .bk, .bv: the
        published fused W_qkv and its bias, split at 2560 and 3840
    h<i>.attn.wo (H*D, h), .bo   .lq1 .lk1 .lq2 .lk2 (D,)   .subln.w (2D,)
    h<i>.ssm.win (h, 2N) xs first   .conv.w (4, N): the published (N, 4)
        transposed   .conv.b (N,)   .wx (N, rank + 2R)   .wdt (rank, N)
        .bdt (N,)   .A_log (R, N): the published (N, R) transposed
        .D (N,)   .wout (N, h)
    h<i>.gmu.win (h, N)   .wout (N, h)

Departures from the published files, neither of which is on this machine
(the equations of ISSUE 31 are the specification): none known in the
arithmetic; the two transposed layouts and the split W_qkv above.

So that 32 layers fit beside the engine, the weights (bfloat16 there) are
upcast one matrix at a time, attention runs a query head and a block of
rows at a time, and the head a block of the vocabulary at a time.  Four
knobs make the variants that the benchmark's limit must tell apart:
`round_to` rounds every weight to a lower precision first; `state_dtype`
rounds the SSM state to it after every position; `lambda0_layer` uses
that layer's l0 in every layer; `window` overrides `sliding_window`.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

ROW_BLOCK = 128     # query rows a block of the attention
HEAD_BLOCKS = 8     # blocks of the vocabulary the head is upcast in


def layer_kinds(n):
    """The mixer of each of `n` layers, as `modeling_phi4flash.py` derives
    them from the depth (ISSUE 31)."""
    half = n // 2
    return tuple(("mamba" if i <= half else "gmu") if i % 2 == 0
                 else ("sliding" if i < half
                       else "full" if i == half + 1 else "cross")
                 for i in range(n))


def _ln(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _softmax_rows(q, k, v, window):
    """One head: q (S, D), k (S, D), v (S, Dv) -> (S, Dv), causal (and
    windowed), ROW_BLOCK rows at a time."""
    s, d = q.shape
    block = min(ROW_BLOCK, s)
    assert s % block == 0, (s, block)
    cols = jnp.arange(s)

    def rows(r):
        qr = jax.lax.dynamic_slice_in_dim(q, r * block, block)
        p = r * block + jnp.arange(block)
        seen = cols[None, :] <= p[:, None]
        if window:
            seen &= p[:, None] - cols[None, :] < window
        att = jnp.where(seen, qr @ k.T / math.sqrt(d), -jnp.inf)
        return jax.nn.softmax(att, axis=-1) @ v

    return jax.lax.map(rows, jnp.arange(s // block)).reshape(s, v.shape[1])


def _differential(q, k, v, lam, lam0, subln, eps, window):
    """q (S, H, D), k / v (S, KVH, D) -> (S, H*D): per query pair j,
    (1 - l0) RMSNorm(A1_j - l A2_j)."""
    s, h, d = q.shape

    def pair(j):
        m = j // 2
        vm = jax.lax.dynamic_slice_in_dim(v, 2 * m, 2, axis=1
                                          ).reshape(s, 2 * d)
        a1 = _softmax_rows(q[:, 2 * j], k[:, 2 * m], vm, window)
        a2 = _softmax_rows(q[:, 2 * j + 1], k[:, 2 * m + 1], vm, window)
        o = a1 - lam * a2
        o = o / jnp.sqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                         + eps) * subln
        return (1.0 - lam0) * o

    out = jax.lax.map(pair, jnp.arange(h // 2))             # (H/2, S, 2D)
    return jnp.transpose(out, (1, 0, 2)).reshape(s, h * d)


def _mamba(n, w, name, state_dtype, upto):
    """The Mamba mixer over n (S, h): (y (S, N) before the gate, out,
    the state (N, R) after `upto` positions)."""
    s = n.shape[0]
    xs, z = jnp.split(n @ w(name + ".win"), 2, axis=-1)
    cw = w(name + ".conv.w")                                 # (K, N)
    k = cw.shape[0]
    padded = jnp.pad(xs, ((k - 1, 0), (0, 0)))
    xc = jax.nn.silu(sum(padded[j:j + s] * cw[j] for j in range(k))
                     + w(name + ".conv.b"))
    a = -jnp.exp(w(name + ".A_log")).T                       # (N, R)
    r = a.shape[1]
    rank = w(name + ".wdt").shape[0]
    dt, bm, cm = jnp.split(xc @ w(name + ".wx"), [rank, rank + r], axis=-1)
    delta = jax.nn.softplus(dt @ w(name + ".wdt") + w(name + ".bdt"))

    def step(carry, xs_t):
        st, kept = carry
        t, d, x, b, c = xs_t
        st = jnp.exp(d[:, None] * a) * st + (d * x)[:, None] * b[None, :]
        if state_dtype is not None:
            # reduce_precision, not a cast there and back: XLA may drop
            # such a pair of converts (xla_allow_excess_precision)
            fi = jnp.finfo(state_dtype)
            st = jax.lax.reduce_precision(st, fi.nexp, fi.nmant)
        return (st, jnp.where(t < upto, st, kept)), st @ c

    zero = jnp.zeros(a.shape, jnp.float32)
    (_, kept), y = jax.lax.scan(step, (zero, zero),
                                (jnp.arange(s), delta, xc, bm, cm))
    y = y + w(name + ".D") * xc
    return y, (y * jax.nn.silu(z)) @ w(name + ".wout"), kept


@functools.partial(jax.jit, static_argnames=(
    "cfg", "rows", "round_to", "state_dtype", "lambda0_layer", "window"))
def _forward(params, tokens, first_row, *, cfg, rows, round_to,
             state_dtype, lambda0_layer, window):
    cfg = dict(cfg)
    f32 = jnp.float32

    def w(name, *index):
        a = params[name]
        for i in index:
            a = a[i]
        if round_to is not None:
            a = a.astype(round_to)
        return a.astype(f32)

    with jax.default_matmul_precision("highest"):
        s = tokens.shape[0]
        heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        d = cfg["hidden_size"] // heads
        eps = cfg["layer_norm_eps"]
        win = cfg["sliding_window"] if window is None else window
        h = params["embed"][tokens]
        if round_to is not None:
            h = h.astype(round_to)
        h = h.astype(f32)
        memory = shared = None
        states = []
        for i, kind in enumerate(layer_kinds(cfg["num_hidden_layers"])):
            n = _ln(h, w(f"h{i}.ln1.w"), w(f"h{i}.ln1.b"), eps)
            if kind == "mamba":
                memory, out, kept = _mamba(n, w, f"h{i}.ssm", state_dtype,
                                           first_row + rows)
                states.append(kept)
            elif kind == "gmu":
                out = (memory * jax.nn.silu(n @ w(f"h{i}.gmu.win"))
                       ) @ w(f"h{i}.gmu.wout")
            else:
                at = f"h{i}.attn."
                q = (n @ w(at + "wq") + w(at + "bq")).reshape(s, heads, d)
                if kind != "cross":
                    shared = (
                        (n @ w(at + "wk") + w(at + "bk")).reshape(s, kvh, d),
                        (n @ w(at + "wv") + w(at + "bv")).reshape(s, kvh, d))
                j = i if lambda0_layer is None else lambda0_layer
                lam0 = 0.8 - 0.6 * math.exp(-0.3 * j)
                lam = (jnp.exp(jnp.sum(w(at + "lq1") * w(at + "lk1")))
                       - jnp.exp(jnp.sum(w(at + "lq2") * w(at + "lk2")))
                       + lam0)
                o = _differential(q, *shared, lam, lam0, w(at + "subln.w"),
                                  eps, win if kind == "sliding" else 0)
                out = o @ w(at + "wo") + w(at + "bo")
            h = h + out
            n = _ln(h, w(f"h{i}.ln2.w"), w(f"h{i}.ln2.b"), eps)
            gate, up = jnp.split(n @ w(f"h{i}.mlp.wgu"), 2, axis=-1)
            h = h + (up * jax.nn.silu(gate)) @ w(f"h{i}.mlp.wd")
        h = _ln(h, w("lnf.w"), w("lnf.b"), eps)
        h = jax.lax.dynamic_slice_in_dim(h, first_row, rows)
        # the tied head a block of the vocabulary at a time: (V, h) in
        # float32 is as large again as the embedding itself
        vocab = params["embed"].shape[0]
        blocks = HEAD_BLOCKS if vocab % HEAD_BLOCKS == 0 else 1
        embed = params["embed"].reshape(blocks, vocab // blocks, -1)

        def head_block(b):
            e = embed[b]
            if round_to is not None:
                e = e.astype(round_to)
            return h @ e.astype(f32).T

        logits = jax.lax.map(head_block, jnp.arange(blocks))
        return (jnp.transpose(logits, (1, 0, 2)).reshape(rows, vocab),
                jnp.stack(states))


KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
        "num_hidden_layers", "layer_norm_eps", "sliding_window")


def forward(params, tokens, first_row, *, cfg, rows, round_to=None,
            state_dtype=None, lambda0_layer=None, window=None):
    """(logits (rows, V), states (Mamba layers, N, R)) of one sequence
    `tokens` (S,): the logits of positions first_row .. first_row + rows,
    and each Mamba layer's state after the last of them.  Tokens after
    those rows do not matter (the model is causal).  `cfg` holds the
    published keys."""
    frozen = tuple((k, cfg[k]) for k in KEYS)
    return _forward(params, tokens, first_row, cfg=frozen, rows=rows,
                    round_to=round_to, state_dtype=state_dtype,
                    lambda0_layer=lambda0_layer, window=window)
