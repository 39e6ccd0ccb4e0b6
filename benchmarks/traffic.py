"""The one traffic generator.  A mix is a data file under `traffic/`;
this turns it and a seed into the list of requests a run offers.

Serve mixes (`"kind": "serve"`):
  arrival   {"kind": "poisson", "rate": r}            open loop, r requests/s
            {"kind": "burst", "rate": r, "burst": {dist}}   bursts of k at once, r requests/s overall
            {"kind": "all_at_once", "requests": n}    n requests due at 0
            {"kind": "closed", "callers": c, "pool": n, "cycles": k, "ramp_s": s}
                      a closed loop draws from k cycles of one grid of n
                      lengths, each cycle shuffled anew, so any n requests
                      in a row are nearly the same work
  prompt_len, new_tokens   length distributions (stats.quantile_grid)
  classes   optional list of {"share", "prompt_len", "new_tokens"} in
            place of the two above: a mixed queue
  prefix    optional {"tokens": n, "turns": t}: sessions of t requests
            that share their first n prompt tokens
  drain_s   how long after the window an open loop waits for its last

  block     optional: every run of about that many consecutive requests
            spans the whole of each distribution (stats.stratified), so
            that the order a seed draws moves a tail less

Every list is a fixed quantile grid shuffled by the seed, so two seeds
offer the same work in another order.
"""
from __future__ import annotations

import numpy as np

from stats import stratified


def _lengths(mix, n, rng):
    """(prompt_len, new_tokens) lists of length n."""
    block = mix.get("block")
    classes = mix.get("classes") or [
        {"share": 1.0, "prompt_len": mix["prompt_len"],
         "new_tokens": mix["new_tokens"]}]
    prompts, news = [], []
    left = n
    for i, c in enumerate(classes):
        k = left if i == len(classes) - 1 else int(round(c["share"] * n))
        k = min(k, left)
        left -= k
        prompts += stratified(c["prompt_len"], k, rng, block)
        news += stratified(c["new_tokens"], k, rng, block)
    if len(classes) > 1:    # interleave the classes
        order = rng.permutation(n)
        prompts, news = ([x[i] for i in order] for x in (prompts, news))
    return prompts, news


def serve_requests(mix, seed, seconds, vocab):
    """Returns (requests, closed): `requests` is a list of dicts with
    `due` (seconds from the window's start; None in a closed loop),
    `prompt` (list of token ids) and `new_tokens`; `closed` is None or
    {"callers", "ramp_s"}."""
    rng = np.random.RandomState(seed % (2 ** 32))
    arr = mix["arrival"]
    kind = arr["kind"]
    closed = None
    if kind == "poisson":
        n = max(1, int(round(arr["rate"] * seconds)))
        gaps = np.asarray(stratified(
            {"kind": "exponential", "mean": 1.0}, n, rng, mix.get("block")))
        # request i is due after the first i gaps; the grid's gaps are
        # scaled to fill the window exactly, so the last gap is the
        # quiet after the last arrival, whatever the order
        due = (np.cumsum(gaps) - gaps) * (seconds / gaps.sum())
    elif kind == "burst":
        n = max(1, int(round(arr["rate"] * seconds)))
        sizes = []
        while sum(sizes) < n:
            sizes += stratified(arr["burst"], 8, rng)
        cut, total = [], 0
        for s in sizes:
            s = min(s, n - total)
            if s <= 0:
                break
            cut.append(s)
            total += s
        starts = np.arange(len(cut)) * (seconds / len(cut))
        due = np.repeat(starts, cut)
    elif kind == "all_at_once":
        n = int(arr["requests"])
        due = np.zeros(n)
    elif kind == "closed":
        n = int(arr["pool"]) * int(arr.get("cycles", 1))
        due = [None] * n
        closed = {"callers": int(arr["callers"]),
                  "ramp_s": float(arr.get("ramp_s", 0.0))}
    else:
        raise ValueError(f"unknown arrival kind {kind!r}")
    prompt_len, new_tokens = [], []
    grid = int(arr["pool"]) if closed else n
    for _ in range(n // grid):
        p, t = _lengths(mix, grid, rng)
        prompt_len += p
        new_tokens += t
    prefix = mix.get("prefix")
    shared = None
    requests = []
    for i in range(n):
        plen = int(prompt_len[i])
        if prefix and i % int(prefix["turns"]) == 0:
            shared = rng.randint(0, vocab, int(prefix["tokens"])).tolist()
        body = rng.randint(0, vocab, plen).tolist()
        if prefix:
            body = (shared + body)[:max(plen, len(shared) + 1)]
        requests.append({"due": None if closed else float(due[i]),
                         "prompt": body, "new_tokens": int(new_tokens[i])})
    return requests, closed


def train_batches(mix, seed, vocab):
    """A ring of `ring` seeded (ids, labels) int32 batches, labels the
    ids shifted by one."""
    rng = np.random.RandomState(seed % (2 ** 32))
    b, s = int(mix["batch"]), int(mix["seq"])
    out = []
    for _ in range(int(mix.get("ring", 8))):
        tok = rng.randint(0, vocab, (b, s + 1)).astype(np.int32)
        out.append((tok[:, :-1].copy(), tok[:, 1:].copy()))
    return out
