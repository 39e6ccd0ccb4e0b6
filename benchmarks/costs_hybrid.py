"""Operations and bytes of the calls a decoder-hybrid-decoder adds (runner
`serve_hybrid`), computed from shapes: the denominators of its roofline
shares and of `serve_mfu_pct.phi4flash`.  `costs.py` keeps the dense
GPT's, `costs_lm.py` the sparse grouped-query decoder's (`in_window`,
`mean_least_ms` and `least_seconds` are shared from there).  All counts
are what the algorithm needs, not what a particular form of it does: the
zero half of a query that the paged kernel multiplies is not counted.
`m` is the runner's `model_facts`."""
from __future__ import annotations


def paged_decode_diff(seen_tokens, rows, heads, kv_heads, head_dim,
                      itemsize):
    """(flops, bytes) of one layer's differential decode attention over
    a paged cache: `seen_tokens` is the sum over the rows of the
    positions that are valid and inside the layer's window.  K and V of
    each are read once (a KV pair's two K heads and its V of two heads,
    for the four query heads that read them); every query head does QK^T
    over its K head of `head_dim` and PV over the pair's `2 head_dim`.
    q comes in in the pool's precision; A_h goes out in float32."""
    flops = 6 * seen_tokens * heads * head_dim
    nbytes = (2 * seen_tokens * kv_heads * head_dim * itemsize
              + rows * heads * head_dim * itemsize
              + rows * heads * 2 * head_dim * 4)
    return flops, nbytes


def ssm_params(m):
    """Matrix parameters of one state-space mixer: in, x, dt and out
    projections and the convolution."""
    n, r, rank = m["ssm_inner"], m["ssm_state"], m["ssm_dt_rank"]
    return (m["hidden"] * 2 * n + n * (rank + 2 * r) + rank * n
            + m["ssm_conv"] * n + n * m["hidden"])


def ssm_decode(rows, m):
    """(flops, bytes) of one state-space layer's mixer in a decode step:
    its matrices once (2 FLOPs a parameter a row), each row's state read
    and written in float32 and its convolution tail read and written, the
    recurrence (decay, input and read-out: 6 FLOPs a state element), the
    rows in and out."""
    n, r = m["ssm_inner"], m["ssm_state"]
    flops = rows * (2 * ssm_params(m) + 6 * n * r)
    nbytes = (ssm_params(m) * m["weight_itemsize"]
              + rows * (2 * r * n * 4
                        + 2 * (m["ssm_conv"] - 1) * n * m["kv_itemsize"]
                        + 2 * m["hidden"] * m["weight_itemsize"]))
    return flops, nbytes


def ssm_scan(positions, m):
    """(flops, bytes) of one state-space layer's scan over a prompt of
    `positions` tokens from zero state: delta and u read and y written
    in float32 a channel, B and C a state element, the decay matrix in
    and the last state out; the recurrence and its read-out, 6 FLOPs a
    state element a position."""
    n, r = m["ssm_inner"], m["ssm_state"]
    return (6 * positions * n * r,
            4 * (positions * (3 * n + 2 * r) + 2 * r * n))


def mixer_params(m, kind):
    """Parameters a position multiplies against in one layer's mixer."""
    hd, kvd = m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    if kind == "ssm":
        return ssm_params(m)
    if kind == "gmu":
        return 2 * m["hidden"] * m["ssm_inner"]
    if kind == "cross":
        return 2 * m["hidden"] * hd
    return 2 * m["hidden"] * hd + 2 * m["hidden"] * kvd


def mlp_params(m):
    """Gate, up and down of the dense SwiGLU FFN."""
    return 3 * m["hidden"] * m["ffn"]


def head_params(m):
    return m["hidden"] * m["vocab_size"]


def tail_start(m):
    """First layer of the trailing run of gmu / cross layers."""
    i = m["layers"]
    while i and m["layer_types"][i - 1] in ("gmu", "cross"):
        i -= 1
    return i


def position_params(m):
    """(every prompt position, once a prompt, a decoded row): parameters
    multiplied against.  A prefill runs the layers before the last full
    one, and that layer's K and V projections, over every position; that
    layer's query, output and FFN, the gmu and cross layers and the head
    over the last position only.  A decoded row meets everything."""
    kinds = m["layer_types"]
    tail = tail_start(m)
    every = once = 0
    for i, kind in enumerate(kinds):
        whole = mixer_params(m, kind) + mlp_params(m)
        if tail == m["layers"] or i < tail - 1:
            every += whole
        elif i == tail - 1:
            kv = 2 * m["hidden"] * m["kv_heads"] * m["head_dim"]
            every += kv
            once += whole - kv
        else:
            once += whole
    once += head_params(m)
    all_layers = sum(mixer_params(m, k) + mlp_params(m) for k in kinds)
    return every, once, all_layers + head_params(m)


def serve_flops(m, prefill_tokens, prefills, decode_tokens):
    """FLOPs the matrices need for a window's tokens, 2 a parameter.
    Attention's and the recurrence's own arithmetic is not in it."""
    every, once, row = position_params(m)
    return 2 * (every * prefill_tokens + once * prefills
                + row * decode_tokens)
