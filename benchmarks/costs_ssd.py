"""Operations and bytes of the calls a decoder of parallel Mamba-2 and
attention layers adds (runner `serve_ssd`; Falcon-H1), computed from
shapes: the denominators of `ssd_decode_roofline`, `ssd_prefill_roofline`
and `serve_mfu_pct.falconh1`.  `costs_lm.py` keeps the grouped paged
attention's (`paged_decode_gqa`, `in_window`, `mean_least_ms`).  All counts
are what the algorithm needs, not what a particular form of it does.  `m`
is the runner's `model_facts`."""
from __future__ import annotations


def ssd_params(m):
    """Matrix parameters of one Mamba-2 mixer: the in-projection (z, x, B,
    C and dt), the depthwise convolution and the out-projection."""
    return (m["hidden"] * (m["ssm_inner"] + m["ssm_conv_channels"]
                           + m["ssm_heads"])
            + m["ssm_conv"] * m["ssm_conv_channels"]
            + m["ssm_inner"] * m["hidden"])


def state_elems(m):
    """Elements of one row's state in one layer: heads x head size x
    state size."""
    return m["ssm_inner"] * m["ssm_state"]


def ssd_decode(rows, m):
    """(flops, bytes) of one layer's Mamba-2 mixer in a decode step: its
    matrices once (2 FLOPs a parameter a row), each row's float32 state
    read and written and its convolution tail read and written, the
    update (decay, input and read-out: 6 FLOPs a state element), the rows
    in and out."""
    flops = rows * (2 * ssd_params(m) + 6 * state_elems(m))
    nbytes = (ssd_params(m) * m["weight_itemsize"]
              + rows * (2 * state_elems(m) * 4
                        + 2 * (m["ssm_conv"] - 1) * m["ssm_conv_channels"]
                        * m["kv_itemsize"]
                        + 2 * m["hidden"] * m["weight_itemsize"]))
    return flops, nbytes


def chunk_flops(m):
    """FLOPs a prompt position needs in the chunked form, chunks of Q:
    C B^T within its chunk (a group), the decayed scores against x (a
    head), its share of its chunk's end state and its read of the state
    the chunk starts from (a head): 2 (G Q N + H Q P + 2 H P N)."""
    q, g, n = m["ssm_chunk"], m["ssm_groups"], m["ssm_state"]
    return 2 * (g * q * n + m["ssm_inner"] * q + 2 * m["ssm_inner"] * n)


def ssd_prefill(positions, m):
    """(flops, bytes) of one layer's Mamba-2 mixer over a prompt of
    `positions` tokens from zero state: the projections and the
    convolution of every position, the chunked form's matmuls
    (`chunk_flops`); the matrices read once, each position's row in and
    out, the state and the tail written."""
    flops = positions * (2 * ssd_params(m) + chunk_flops(m))
    nbytes = (ssd_params(m) * m["weight_itemsize"]
              + positions * 2 * m["hidden"] * m["weight_itemsize"]
              + state_elems(m) * 4
              + (m["ssm_conv"] - 1) * m["ssm_conv_channels"]
              * m["kv_itemsize"])
    return flops, nbytes


def layer_params(m):
    """Parameters a position multiplies against in one layer: attention's
    four projections, the Mamba-2 mixer's and the SwiGLU's three."""
    hd, kvd = m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    return (2 * m["hidden"] * hd + 2 * m["hidden"] * kvd + ssd_params(m)
            + 3 * m["hidden"] * m["ffn"])


def serve_flops(m, prefill_tokens, prefills, decode_tokens):
    """FLOPs the matrices need for a window's tokens, 2 a parameter:
    every prompt position and decoded row through the layers, the head
    once a prompt and once a decoded row.  Attention's and the
    recurrence's own arithmetic is not in it."""
    head = m["hidden"] * m["vocab_size"]
    return 2 * (m["layers"] * layer_params(m) * (prefill_tokens
                                                 + decode_tokens)
                + head * (prefills + decode_tokens))
