"""Arithmetic the benchmark's numbers rest on: percentiles, the fixed
quantile grids traffic is drawn from, the contract's spread, the knee of
a rate sweep.  Plain Python and numpy; `selftest.py` checks each."""
from __future__ import annotations

import math
import statistics

import numpy as np


def percentile(values, q):
    """The q-th percentile (0..100) by linear interpolation between
    order statistics; None for no values."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def quantile_grid(dist, n):
    """`n` values of `dist` at the quantiles (i + 1/2) / n, in rising
    order: the same multiset for every seed.  `dist` is a dict with
    `kind`: `constant` (value), `loguniform_int` (lo, hi, inclusive),
    `uniform_int` (lo, hi) or `exponential` (mean)."""
    u = (np.arange(n, dtype=np.float64) + 0.5) / n
    kind = dist["kind"]
    if kind == "constant":
        return [dist["value"]] * n
    if kind == "loguniform_int":
        lo, hi = math.log(dist["lo"]), math.log(dist["hi"] + 1)
        v = np.floor(np.exp(lo + u * (hi - lo))).astype(np.int64)
        return np.clip(v, dist["lo"], dist["hi"]).tolist()
    if kind == "uniform_int":
        v = np.floor(dist["lo"] + u * (dist["hi"] + 1 - dist["lo"]))
        return np.clip(v.astype(np.int64), dist["lo"], dist["hi"]).tolist()
    if kind == "exponential":
        return (-np.log1p(-u) * dist["mean"]).tolist()
    raise ValueError(f"unknown distribution kind {kind!r}")


def stratified(dist, n, rng, block=None):
    """The quantile grid of `dist`, shuffled by `rng`: two seeds draw
    the same values in another order.  With `block`, the shuffle keeps
    every run of about `block` consecutive draws a spread over the whole
    distribution (draw i of the sorted grid goes to block i mod the
    number of blocks; blocks and their insides are shuffled), so no
    stretch of a window is all short or all long by chance."""
    v = quantile_grid(dist, n)
    if not block or block >= n:
        return [v[i] for i in rng.permutation(n)]
    blocks = -(-n // int(block))
    out = []
    for b in rng.permutation(blocks):
        members = np.arange(b, n, blocks)
        out += [v[i] for i in rng.permutation(members)]
    return out


def spread(values):
    """Distance between the first and third quartile as a share of the
    median, as the contract measures it."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def queue_growth(depths_first_half, depths_second_half):
    """Mean backlog in the second half of a window less the first."""
    a = float(np.mean(depths_first_half)) if len(depths_first_half) else 0.0
    b = float(np.mean(depths_second_half)) if len(depths_second_half) else 0.0
    return b - a


def knee(rows, grow_limit):
    """`rows` are a sweep's (rate, backlog growth, unfinished) in rising
    rate.  A rate holds when its backlog grew by less than `grow_limit`
    requests through the window and nothing was left unfinished; the
    knee is the last rate that holds before the first that does not
    (None if the first already fails)."""
    held = None
    for rate, growth, unfinished in rows:
        if growth >= grow_limit or unfinished:
            break
        held = rate
    return held
