"""Plain reference for the `keye-vl2-30b-a3b-serve` configuration: the
language model of Keye-VL-2.0-30B-A3B (Kwai-Keye, https://huggingface.co/
Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json), one full causal
forward in float32 `jax.numpy` at matmul precision "highest", no cache, no
kernel, no batching.  It reads the published `config.json` keys (`cfg`,
with `sa_config` inside) and the flat weight dict the serving stack is
given, and nothing else of the program:

    embed (V, h)                  h<i>.ln1.w, h<i>.ln2.w, lnf.w (h,)
    h<i>.attn.wq (h, H*D)         h<i>.attn.wk, .wv (h, KVH*D)
    h<i>.attn.qnorm.w, .knorm.w (D,)        h<i>.attn.wo (H*D, h)
    h<i>.idx.wq (h, J*DI)         h<i>.idx.wk (h, DI)     h<i>.idx.ww (h, J)
    h<i>.idx.knorm.w, .b (DI,)    h<i>.moe.router (h, E)
    h<i>.moe.wg, .wu (E, h, F)    h<i>.moe.wd (E, F, h)   head (h, V)

A layer, position t, x = rms(h; ln1):

 1. indexer: qI[t, j] = rot(x Wq_I)[j] (J heads of DI lanes), kI[s] =
    rot(LayerNorm(x_s Wk_I)) (one key head), w[t, j] = (x Ww)[j] /
    sqrt(J DI); I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]), s <= t.
 2. selection: S_t = the `sa_config.topk` positions s <= t of largest
    I[t, s] (all of them while t + 1 <= topk), ties to the lower position:
    the k-th largest value from `jax.lax.top_k`, what is above it, and of
    what equals it the first by position.
 3. attention: q = rot(rms_head(x Wq)), k = rot(rms_head(x Wk)), v = x Wv,
    query head j reads KV head j // (H / KVH), softmax over s in S_t of
    q . k / sqrt(D) in float32; h += o Wo.
 4. experts: y = rms(h; ln2); r = softmax(y Wr) over all experts, the
    `num_experts_per_tok` largest, renormalised; h += sum_e g_e
    (silu(y Wg_e) * (y Wu_e)) Wd_e.
After the last layer rms(h; lnf) head.  The rotary is rotate-half (lane i
pairs with lane i + D/2), theta `rope_theta`, on all D lanes of q and k
and all DI lanes of qI and kI.

Departures from the published description, each because `config.json`
has no key that settles it (the configuration file lists them under
`assumed`): the vision tower is not there and the three M-RoPE position
streams are equal (text positions), so the rotary is the plain one; the
per-head RMS norm on q and k (Qwen3 lineage); the indexer's queries from
x directly, the LayerNorm with scale and bias on kI, rotary on all its
lanes, the two scale factors on w and the ReLU (DeepSeek-V3.2-Exp's
indexer); the tie rule; `q_chunk_size` / `kv_chunk_size` read as tile
sizes that do not change S_t; no multi-token-prediction head.

So that four layers at 32k tokens fit on the chip, attention runs a block
of query rows at a time (`ROW_BLOCK`; scores and selection of a block
against all keys: nothing of size S x S exists), a query head at a time;
the experts run the token-expert pairs sorted by expert through
`jax.lax.ragged_dot` a slab of tokens at a time (every pair's arithmetic
once: 128 experts for every token would be sixteen times the work).

`given` hands the forward a selection for its last `rows - 1` positions
(the decode steps of the check): a second track of those positions alone
runs beside the free one, with S_t as given in every layer and its own K,
V, residual stream and experts, everything before it as the free track
has it.  That is the arithmetic apart from the discrete choice.  The
other keywords build the wrong references that the check's limits have to
tell apart: `round_to` (every weight), `index_key_dtype` / `kv_dtype` (the
indexer's keys, K and V rounded as a cache in that precision would hold
them; in layer `only_layer` alone where that is given), `topk`,
`index_keys_written="prompt"` (no indexer key after the prompt's: what a
decode step that does not write its key leaves), `index_relu=False` (the
heads' dot products summed as they are), `select="page"` (whole
pages of `page` tokens by their best score until `topk` tokens are in),
`qk_norm=False`, `window` (the last `window` positions in place of the
selection).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

ROW_BLOCK = 1024    # query rows a block of the attention
SLAB = 4096         # tokens a slab of the experts
HEAD_BLOCKS = 8     # blocks of the vocabulary the head is upcast in
HIGHEST = jax.lax.Precision.HIGHEST


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * w


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _rotary(x, pos, theta):
    """x (T, heads, D) at positions `pos` (T,), rotate-half pairing."""
    d = x.shape[-1]
    freqs = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(
        freqs, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def index_scores(qi, w, ki, relu=True):
    """I (Q, S) of queries qi (Q, J, DI), w (Q, J) against keys ki (S, DI),
    a head at a time (`relu=False`: a wrong indexer)."""
    def head(j, acc):
        dots = qi[:, j] @ ki.T
        return acc + w[:, j, None] * (jax.nn.relu(dots) if relu else dots)

    return jax.lax.fori_loop(
        0, qi.shape[1], head, jnp.zeros((qi.shape[0], ki.shape[0])))


def select(scores, seen, topk, *, by="token", page=128, window=0):
    """S_t as a mask (Q, S): of the keys a query sees (`seen`) the `topk`
    of largest score, all of them if it sees no more, ties to the lower
    position.  `by="page"` and `window` are the wrong selections."""
    q, s = scores.shape
    if window:
        last = jnp.sum(seen, axis=1, keepdims=True)
        return seen & (jnp.arange(s)[None, :] >= last - window)
    if by == "page":
        best = jnp.max(jnp.where(seen, scores, -jnp.inf).reshape(
            q, s // page, page), axis=2)
        order = jnp.argsort(-best, axis=1, stable=True)
        rank = jnp.argsort(order, axis=1)            # a page's place
        return seen & (jnp.repeat(rank, page, axis=1) < topk // page)
    scores = jnp.where(scores == 0.0, 0.0, scores)           # -0 is 0
    k = min(topk, s)
    top, _ = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf), k)
    count = jnp.minimum(jnp.sum(seen, axis=1), topk)
    kth = jnp.take_along_axis(top, jnp.maximum(count - 1, 0)[:, None],
                              axis=1)
    above = seen & (scores > kth)
    equal = seen & (scores == kth)
    need = count - jnp.sum(above, axis=1)
    return above | (equal & (jnp.cumsum(equal, axis=1) <= need[:, None]))


def _attend(q, k, v, mask):
    """q (Q, H, D), k / v (S, KVH, D), mask (Q, S) -> (Q, H*D); a query
    head at a time."""
    qn, h, d = q.shape
    group = h // k.shape[1]

    def one_head(j):
        att = jnp.where(mask, q[:, j] @ k[:, j // group].T / math.sqrt(d),
                        -jnp.inf)
        return jax.nn.softmax(att, axis=-1) @ v[:, j // group]

    out = jax.lax.map(one_head, jnp.arange(h))               # (H, Q, D)
    return jnp.transpose(out, (1, 0, 2)).reshape(qn, h * d)


def _pieces(a):
    """float32 `a` as three bfloat16 arrays that sum to it (its 24 bits of
    mantissa, eight a piece)."""
    out = []
    for _ in range(3):
        out.append(a.astype(jnp.bfloat16))
        a = a - out[-1].astype(jnp.float32)
    return out


def _by_expert(a, w, sizes):
    """Rows `a` (float32, sorted by expert) through each row's expert's
    matrix of `w` (E, K, N), exactly as float32 at "highest" would: a
    bfloat16 `w` stays as it is stored (128 experts upcast are 0.8 GB a
    matrix) and meets the rows' three bfloat16 pieces, every product exact,
    the sums in float32."""
    if w.dtype != jnp.bfloat16:
        return jax.lax.ragged_dot(a, w.astype(jnp.float32), sizes,
                                  precision=HIGHEST)
    # one pass each: bfloat16 operands, whatever the context's precision
    return sum(jax.lax.ragged_dot(p, w, sizes,
                                  precision=jax.lax.Precision.DEFAULT,
                                  preferred_element_type=jnp.float32)
               for p in _pieces(a))


def _experts(y, router, wg, wu, wd, top_k, renorm):
    """(out (T, h), routed (T, E) bool): each token through its `top_k`
    experts, the token-expert pairs sorted by expert, a slab of tokens at
    a time.  `wg`, `wu`, `wd` as stored (`_by_expert`)."""
    t, n_exp = y.shape[0], router.shape[1]
    r = jax.nn.softmax(y @ router, axis=-1)
    top, idx = jax.lax.top_k(r, top_k)
    if renorm:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    routed = jnp.sum(jax.nn.one_hot(idx, n_exp, dtype=jnp.int32),
                     axis=1) > 0
    slab = SLAB if t % SLAB == 0 else t

    def one_slab(args):
        ys, tops, ids = args
        flat = ids.reshape(-1)
        order = jnp.argsort(flat, stable=True)
        sizes = jnp.sum(jax.nn.one_hot(flat, n_exp, dtype=jnp.int32), axis=0)
        rows = ys[order // top_k]

        def mm(a, w):
            return _by_expert(a, w, sizes)

        out = mm(jax.nn.silu(mm(rows, wg)) * mm(rows, wu), wd)
        out = out[jnp.argsort(order)].reshape(slab, top_k, -1)
        return jnp.sum(out * tops[..., None], axis=1)

    out = jax.lax.map(one_slab, tuple(
        a.reshape(t // slab, slab, *a.shape[1:]) for a in (y, top, idx)))
    return out.reshape(t, -1), routed


@functools.partial(jax.jit, static_argnames=("cfg", "rows", "variant"))
def _forward(params, tokens, first_row, given, *, cfg, rows, variant):
    cfg, variant = dict(cfg), dict(variant)
    f32 = jnp.float32
    round_to = variant.get("round_to")

    def rounded(a, dtype):
        """`a` as `dtype` would hold it, back in its own dtype.  Behind a
        barrier: the compiler drops a narrowing it can see the widening
        of."""
        if dtype is None:
            return a
        return jax.lax.optimization_barrier(a.astype(dtype)).astype(a.dtype)

    def stored(name):
        """A weight as stored, rounded to `round_to` first if asked."""
        return rounded(params[name], round_to)

    def w(name):
        return stored(name).astype(f32)

    with jax.default_matmul_precision("highest"):
        s = tokens.shape[0]
        d, heads = cfg["head_dim"], cfg["num_attention_heads"]
        kvh, eps = cfg["num_key_value_heads"], cfg["rms_norm_eps"]
        theta = float(cfg["rope_theta"])
        n_idx, d_idx = cfg["indexer_num_heads"], cfg["indexer_head_dim"]
        topk = variant.get("topk", cfg["topk"])
        qk_norm = variant.get("qk_norm", True)
        pos = jnp.arange(s)
        block = min(ROW_BLOCK, s)
        assert s % block == 0, (s, block)
        tail = rows - 1             # the given track's positions
        tpos = first_row + 1 + jnp.arange(tail)

        def project(x, i, at):
            """q, k, v and the indexer's qI, kI, w of rows x at `at`."""
            q = (x @ w(f"h{i}.attn.wq")).reshape(-1, heads, d)
            k = (x @ w(f"h{i}.attn.wk")).reshape(-1, kvh, d)
            v = (x @ w(f"h{i}.attn.wv")).reshape(-1, kvh, d)
            if qk_norm:
                q = _rms(q, w(f"h{i}.attn.qnorm.w"), eps)
                k = _rms(k, w(f"h{i}.attn.knorm.w"), eps)
            q, k = _rotary(q, at, theta), _rotary(k, at, theta)
            qi = _rotary((x @ w(f"h{i}.idx.wq")).reshape(-1, n_idx, d_idx),
                         at, theta)
            ki = _rotary(_layer_norm(
                x @ w(f"h{i}.idx.wk"), w(f"h{i}.idx.knorm.w"),
                w(f"h{i}.idx.knorm.b"), eps)[:, None, :], at, theta)[:, 0]
            wi = (x @ w(f"h{i}.idx.ww")) / math.sqrt(n_idx * d_idx)
            here = variant.get("only_layer", i) == i
            kv = variant.get("kv_dtype") if here else None
            return (q, rounded(k, kv), rounded(v, kv), qi, rounded(
                ki, variant.get("index_key_dtype") if here else None), wi)

        def moe(hh, i):
            y = _rms(hh, w(f"h{i}.ln2.w"), eps)
            out, routed = _experts(
                y, w(f"h{i}.moe.router"), stored(f"h{i}.moe.wg"),
                stored(f"h{i}.moe.wu"), stored(f"h{i}.moe.wd"),
                cfg["num_experts_per_tok"], cfg["norm_topk_prob"])
            return hh + out, routed

        h = rounded(params["embed"][tokens], round_to).astype(f32)
        hb = jax.lax.dynamic_slice_in_dim(h, first_row + 1, tail)
        routed, scores_out, masks_out, cached = [], [], [], []
        for i in range(cfg["num_hidden_layers"]):
            q, k, v, qi, ki, wi = project(_rms(h, w(f"h{i}.ln1.w"), eps),
                                          i, pos)
            if (variant.get("index_keys_written") == "prompt"
                    and variant.get("only_layer", i) == i):
                ki = jnp.where((pos > first_row)[:, None], 0.0, ki)
            # what a cache holds of the layer, a token a row
            cached.append((k.reshape(s, -1), v.reshape(s, -1), ki))

            relu = variant.get("index_relu", True) or (
                variant.get("only_layer", i) != i)

            def rows_of(r, q=q, k=k, v=v, qi=qi, ki=ki, wi=wi, relu=relu):
                def cut(a):
                    return jax.lax.dynamic_slice_in_dim(a, r * block, block)

                p = r * block + jnp.arange(block)
                seen = pos[None, :] <= p[:, None]
                sc = index_scores(cut(qi), cut(wi), ki, relu)
                mask = select(sc, seen, topk, by=variant.get("select",
                                                             "token"),
                              page=variant.get("page", 128),
                              window=variant.get("window", 0))
                # the check's rows: their scores and what they selected
                at = first_row - r * block + jnp.arange(rows)
                mine = (at >= 0) & (at < block)
                at = jnp.clip(at, 0, block - 1)
                return (_attend(cut(q), k, v, mask),
                        jnp.where(mine[:, None], sc[at], 0.0),
                        mine[:, None] & mask[at])

            att, sc, mk = jax.lax.map(rows_of, jnp.arange(s // block))
            scores_out.append(jnp.sum(sc, axis=0))
            masks_out.append(jnp.any(mk, axis=0))
            # the given track: the last rows again, S_t as handed over
            if tail:
                qb, kb, vb, _, _, _ = project(
                    _rms(hb, w(f"h{i}.ln1.w"), eps), i, tpos)
                kk = jax.lax.dynamic_update_slice_in_dim(k, kb, first_row + 1,
                                                         axis=0)
                vv = jax.lax.dynamic_update_slice_in_dim(v, vb, first_row + 1,
                                                         axis=0)
                hb = hb + _attend(qb, kk, vv, given[i]) @ w(f"h{i}.attn.wo")
                hb, _ = moe(hb, i)
            h = h + att.reshape(s, heads * d) @ w(f"h{i}.attn.wo")
            h, r = moe(h, i)
            routed.append(r)

        def logits_of(hh):
            hh = _rms(hh, w("lnf.w"), eps)
            head = params["head"]
            vocab = head.shape[1]
            blocks = HEAD_BLOCKS if vocab % HEAD_BLOCKS == 0 else 1
            head = head.reshape(head.shape[0], blocks, vocab // blocks)

            def part(b):
                return hh @ rounded(head[:, b], round_to).astype(f32)

            out = jax.lax.map(part, jnp.arange(blocks))
            return jnp.transpose(out, (1, 0, 2)).reshape(-1, vocab)

        free = logits_of(jax.lax.dynamic_slice_in_dim(h, first_row, rows))
        return (free, logits_of(hb) if tail else free[:0],
                jnp.stack(routed), jnp.stack(scores_out),
                jnp.stack(masks_out),
                tuple(jnp.stack(a) for a in zip(*cached)))


def forward(params, tokens, first_row, *, cfg, rows, given=None, **variant):
    """Of one sequence `tokens` (S,): `(logits (rows, V), given-track
    logits (rows - 1, V), routed (L, S, E) bool, scores (L, rows, S),
    selected (L, rows, S) bool, every layer's (K (L, S, KVH*D), V, indexer
    keys (L, S, DI)) as a cache holds them)`; the rows are positions first_row ..
    first_row + rows.  Tokens after those rows do not matter (the model is
    causal).  `given` (L, rows - 1, S) bool is the selection handed to the
    second track's positions first_row + 1 ..; without it there is no
    second track to read (None in its place).  `cfg` holds the published
    keys and `sa_config`."""
    sa = cfg["sa_config"]
    frozen = tuple(sorted(
        [(k, cfg[k]) for k in (
            "head_dim", "num_attention_heads", "num_key_value_heads",
            "rms_norm_eps", "rope_theta", "num_experts_per_tok",
            "norm_topk_prob", "num_hidden_layers")]
        + [("indexer_num_heads", sa["indexer_num_heads"]),
           ("indexer_head_dim", sa["indexer_head_dim"]),
           ("topk", sa["topk"])]))
    free_only = given is None
    if free_only:       # no selection to hand over: the track sees nothing
        given = np.zeros((cfg["num_hidden_layers"], rows - 1,
                          tokens.shape[0]), bool)
    out = _forward(params, tokens, first_row, jnp.asarray(given),
                   cfg=frozen, rows=rows,
                   variant=tuple(sorted(variant.items())))
    if free_only:       # no selection was handed over: no second track
        return (out[0], None) + out[2:]
    return out
