"""Operations and bytes of the calls a sparse grouped-query decoder adds
(runner `serve_lm`), computed from shapes: the denominators of its
roofline shares and of `serve_mfu_pct.mellum2`.  `costs.py` keeps the
dense GPT's; `least_seconds` is shared from there.  All counts are what
the algorithm needs, not what a particular form of it does: the decode
regime's dense expert arithmetic, eightfold redundant, is not counted."""
from __future__ import annotations

from costs import least_seconds


def expert_params(hidden, width):
    """Parameters of one SwiGLU expert: gate, up and down."""
    return 3 * hidden * width


def moe_decode(rows, touched, hidden, width, top_k, itemsize):
    """(flops, bytes) of one layer's experts in a decode step: each row
    through its `top_k` experts (2 FLOPs a parameter), and the matrices
    of the `touched` experts (those any row of the step chose) read once
    each, plus the rows in and out."""
    flops = 2 * rows * top_k * expert_params(hidden, width)
    nbytes = (touched * expert_params(hidden, width)
              + 2 * rows * hidden) * itemsize
    return flops, nbytes


def moe_prefill(tokens, experts, hidden, width, top_k, itemsize):
    """(flops, bytes) of one layer's experts over a prompt of `tokens`
    positions: each token through its `top_k` experts; every expert's
    matrices once (a prompt of hundreds of tokens reaches them all), each
    token-expert pair's row read, its two hidden rows of `width` written
    and read, and its result written."""
    pairs = tokens * top_k
    flops = 2 * pairs * expert_params(hidden, width)
    nbytes = (experts * expert_params(hidden, width)
              + pairs * (2 * hidden + 4 * width)) * itemsize
    return flops, nbytes


def paged_decode_gqa(seen_tokens, rows, heads, kv_heads, head_dim,
                     itemsize):
    """(flops, bytes) of one layer's decode attention over a paged cache
    with grouped heads: `seen_tokens` is the sum over the rows of the
    positions that are valid and inside the layer's window.  K and V of
    each are read once a KV head (not once a query head); QK^T and PV are
    done for every query head."""
    flops = 4 * seen_tokens * heads * head_dim
    nbytes = (2 * seen_tokens * kv_heads * head_dim
              + 2 * rows * heads * head_dim) * itemsize
    return flops, nbytes


def layer_params(m):
    """Parameters every position multiplies against in the layers as
    run: attention projections, router, `experts_per_token` experts a
    layer (the embedding is a lookup)."""
    hd, kvd = m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    layer = (2 * m["hidden"] * hd + 2 * m["hidden"] * kvd
             + m["hidden"] * m["experts"]
             + m["experts_per_token"] * expert_params(m["hidden"],
                                                      m["expert_width"]))
    return m["layers"] * layer


def head_params(m):
    """Parameters of the output head: only a position whose logits are
    wanted multiplies against them (a prompt's last, every decoded
    row)."""
    return m["hidden"] * m["vocab_size"]


def serve_flops(m, prefill_tokens, prefills, decode_tokens):
    """FLOPs the matrices need for a window's tokens, 2 a parameter:
    every prompt position and decoded row through the layers, the head
    once a prompt and once a decoded row.  Attention's own arithmetic is
    not in it."""
    return 2 * (layer_params(m) * (prefill_tokens + decode_tokens)
                + head_params(m) * (prefills + decode_tokens))


def in_window(run, key):
    """Rows of `run[key]` (a call's two host stamps first) wholly inside
    the traced window; [] where the run has none."""
    if not run.get("trace_window") or not run.get(key):
        return []
    a, b = run["trace_window"]
    return [r for r in run[key] if a <= r[0] and r[1] <= b]


def mean_least_ms(calls, peak):
    """Mean over calls of the least ms the chip could take: `calls` holds,
    a call, a list of (flops, bytes, times) terms."""
    if not calls or peak is None:
        return None
    total = sum(sum(least_seconds(f, n, peak)[0] * times
                    for f, n, times in call) for call in calls)
    return 1e3 * total / len(calls)
