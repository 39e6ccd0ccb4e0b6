"""Operations and bytes a call needs, computed from its shapes: the
denominators of every roofline share and of `train_mfu_pct`.  Kept here
so that no later PR can move them.  Copied arithmetic: the train FLOPs
per token are `bench.py`'s and `chip_smoke.py`'s (6N + 6 L S H, recompute
not counted); the attention counts are what `ops/pallas_ops.py`
`_mha_cost_fn` falls back to (4 b h sq skv d forward), written out."""
from __future__ import annotations


def train_flops_per_token(n_params, layers, seq, hidden):
    """Forward + backward of a dense decoder: 6 FLOPs a parameter a
    token, plus attention scores and values, 12 L S H a token halved
    by the causal mask.  Recomputed work is not counted."""
    return 6 * n_params + 6 * layers * seq * hidden


def flash_fwd_bwd(batch, heads, seq, head_dim, itemsize, causal=True):
    """(flops, bytes) of one layer's attention, forward and backward.
    Forward is QK^T and PV: 4 b h s s d; backward recomputes the scores
    and makes dQ, dK, dV: 2.5 times the forward's matmuls (10 b h s s d).
    A causal mask needs half.  Bytes: q, k, v, o read or written once
    forward; q, k, v, o, do read and dq, dk, dv written backward."""
    mm = batch * heads * seq * seq * head_dim
    flops = (4 + 10) * mm * (0.5 if causal else 1.0)
    tensor = batch * heads * seq * head_dim * itemsize
    return flops, (4 + 8) * tensor


def paged_decode(context_tokens, rows, heads, head_dim, itemsize):
    """(flops, bytes) of one layer's decode attention over a paged
    cache: each row reads K and V of its own context once and does
    QK^T and PV over it.  `context_tokens` is the sum of the rows'
    valid lengths: pages a row does not own are not needed work."""
    hd = heads * head_dim
    flops = 4 * context_tokens * hd
    return flops, 2 * context_tokens * hd * itemsize + 2 * rows * hd * itemsize


def least_seconds(flops, nbytes, peak):
    """The roofline's floor for a call and which side bounds it."""
    t_f = flops / peak["flops_bf16"]
    t_b = nbytes / peak["hbm_bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "memory")
