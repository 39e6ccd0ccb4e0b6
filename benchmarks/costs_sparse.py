"""Operations and bytes of the calls learned sparse attention adds (runner
`serve_sparse`), computed from shapes: the denominators of its roofline
shares and of `serve_mfu_pct.keyevl2`.  `costs_lm.py` keeps the routed
experts' and the grouped paged attention's (`in_window`, `mean_least_ms`
and `layer_params` / `head_params` are shared from there).  All counts are
of the published mathematics, not of a particular form of it: a score
written to memory and read again by the selection, or a token's row read
twice, is not counted.  `m` is the runner's `model_facts`."""
from __future__ import annotations

from costs_lm import head_params, layer_params


def index_decode(context_tokens, rows, heads, head_size, itemsize):
    """(flops, bytes) of one layer's indexer scores in a decode step:
    `context_tokens` is the sum of the rows' contexts; every cached key
    (one head of `head_size`) is read once and meets each of the row's
    `heads` queries (a dot product, then the ReLU and the weight); the
    rows' queries come in."""
    flops = 2 * context_tokens * heads * (head_size + 1)
    nbytes = (context_tokens * head_size
              + rows * heads * head_size) * itemsize
    return flops, nbytes


def sparse_decode(selected_tokens, rows, heads, kv_heads, head_dim,
                  itemsize):
    """(flops, bytes) of one layer's attention over the selected tokens in
    a decode step: `selected_tokens` is the sum over the rows of min(
    context, topk).  K and V of each are read once a KV head; QK^T and PV
    are done for every query head; q comes in and the result goes out."""
    flops = 4 * selected_tokens * heads * head_dim
    nbytes = (2 * selected_tokens * kv_heads * head_dim
              + 2 * rows * heads * head_dim) * itemsize
    return flops, nbytes


def index_params(m):
    """Parameters of one layer's indexer: its queries', its key's and its
    weights' projections."""
    return m["hidden"] * (m["index_heads"] * m["index_head_size"]
                          + m["index_head_size"] + m["index_heads"])


def token_flops(m, scored, attended, tokens, logits):
    """FLOPs of `tokens` positions through the layers as run (2 a
    parameter of the projections, the indexer, the router and
    `experts_per_token` experts), their indexers over `scored` keys and
    their attention over `attended` keys in all (both summed over the
    positions, a layer's worth), and `logits` rows through the head."""
    per_layer = (
        index_decode(scored, 0, m["index_heads"], m["index_head_size"], 0)[0]
        + sparse_decode(attended, 0, m["heads"], m["kv_heads"],
                        m["head_dim"], 0)[0])
    return (2 * tokens * (layer_params(m) + m["layers"] * index_params(m))
            + m["layers"] * per_layer + 2 * logits * head_params(m))


def prompt_keys(length, topk):
    """(scored, attended) keys of a prompt of `length` positions, summed
    over them: position t scores t + 1 keys and attends over min(t + 1,
    topk)."""
    scored = length * (length + 1) // 2
    short = min(length, topk)
    return scored, short * (short + 1) // 2 + (length - short) * topk


def serve_flops(m, prompt_lengths, decode_steps):
    """FLOPs the model needs for a window's work: every prompt in
    `prompt_lengths` prefilled (the head once a prompt), every decode step
    in `decode_steps` as (rows, sum of the rows' contexts, sum of their
    min(context, topk))."""
    total = 0
    for n in prompt_lengths:
        total += token_flops(m, *prompt_keys(n, m["sparse_topk"]), n, 1)
    for rows, scored, attended in decode_steps:
        total += token_flops(m, scored, attended, rows, rows)
    return total
