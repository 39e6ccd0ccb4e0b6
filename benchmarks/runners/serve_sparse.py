"""Runner kind `serve_sparse`: a grouped-query decoder with routed experts
whose every attention layer is learned sparse attention (an indexer, an
exact top-k a query, attention over the selected tokens alone;
Keye-VL-2.0-30B-A3B's language model), its configuration file holding the
published `config.json` keys, served through the same `ServingEngine`,
scheduler and load loop as the other serve runners.

From `runners/serve.py` come `Load`, `measure`, `reduce_window` and
`sweep` as they are, from `runners/serve_lm.py` its `Tap` (the positions
each decode step's rows see, all of them and at most `topk` of them).
This file's own: the published keys and `sa_config` to a `ModelSpec`, the
check against `reference/keyevl2_serve.py`, its limits, and the `model`
dict the readers of `costs_sparse.py` take.

The check drives the engine's half **before** the window (the run's first
`decode_buckets[-1]` prompts each prefilled through `prefill_logits`, then
`STEPS` decode steps of all of them side by side through `decode_logits`,
each step once more through `engine.decode_selection`, the same step's
second program whose outputs also hold what every sparse layer scored and
selected for every row: the scheduler's program does not carry them, so the
two programs' logits are held to each other), and runs the reference's forwards
**after** it, with the pools freed: float32 matmuls at "highest" before a
window left the chip's memory side slower for a minute in one process of
seven (PERF.md section 7).  The reference runs `check.rows` of the rows
(by length: the shortest, the longest, and evenly between).  The shortest
is a prompt cut to `topk - STEPS` tokens (`pick_rows`): a row that never
sees more than `topk` positions selects them all, in program and reference
alike, so its hidden states agree in every layer and what the deeper
layers score and cache can be held to the reference's as layer 0's is.
"""
from __future__ import annotations

import time

import numpy as np

from reference import keyevl2_serve as ref
from runners import serve as base
from runners.serve_lm import Tap
from taps import pallas_routes
from traffic import serve_requests

STEPS = 4

# Every limit below: what some 35 runs of the cell read on the chip (a weights
# seed and 24 prompts of 8k-32k tokens each, three of them compared, in the
# last ten of them one of the three the row cut to `topk`;
# bfloat16 weights, activations, K, V and indexer keys against the
# reference's float32 at "highest" over the same bfloat16 weights), and
# what the wrong references read on one run's engine and two rows (those
# planted in layer 2 alone: on the row cut to `topk`; `exp/keyevl2_limits.py`;
# my chip run, PR 34; PERF.md section 2).  With
# random weights the selection is near-chaotic: the 2,048th of 20k scores
# has neighbours within the bfloat16 rounding, and a prompt position that
# selects other tokens has another hidden state in the next layer.  So the
# clean quantities of a long row are layer 0's, which sees the embedding
# through one norm; the layers past it are held on the logits there, and
# layer by layer (`*_layers`) on the row cut to `topk` tokens, which has
# nothing to choose.  That row is not clean to the last token either: the
# router's top-8 of 128 near-equal weights differs in 0.5 % of decisions, so
# 4 % of tokens a layer carry another expert's output on (a token's K, V or
# indexer key then differs by 0.1-0.4 of its size, a score by up to 0.4):
# past layer 0 a maximum over tokens tells nothing, and the `*_layers`
# quantities are quantiles over tokens, which a fault in every token moves.
#
# |program's I[t, s] - reference's| of layer 0, the check's decode queries,
# every position a query sees; the scores' own size is about 0.5.  Read
# 0.0095-0.020; indexer keys as a float8 e4m3 cache would hold them 0.082,
# float8 weights 0.21.  (Over all layers: 1.3-2.0 either way, notes only.)
INDEX_SCORE_ATOL = 0.045
# The median of the same over a layer's (decode query, position) pairs, the
# worst of layers 1 and deeper, on the row cut to `topk` tokens.  Read
# 0.0062-0.014 (ten seeds); layer 2's indexer without its ReLU 0.21.  (Its
# keys as a float8 cache would hold them 0.012: inside, `CACHE_LAYERS_RTOL`
# is what that fails.  The maximum reads 0.38-1.06 as it should be.)
INDEX_SCORE_LAYERS_ATOL = 0.05
# Share of (query, selected position) pairs in which the program's S_t and
# the reference's own differ: bfloat16 against float32 near the topk-th
# score, both selecting for themselves, over the rows that have a choice.
# Read 0.106-0.185 (six times the
# worst for routing, bounded as `ROUTING_DIFF_MAX` is: under twice); a
# selection by page 0.71, the last 2,048 positions 0.82.
SELECT_DIFF_MAX = 0.30
# Positions only one of the two selected: the program, and the reference's
# selection rule applied to the program's own scores.  The discrete choice
# alone, no rounding in it, so none may differ.  A top-k of 2,047 reads 32
# (one a query), a selection by page 44,590, the last 2,048 positions 53,687.
SELECT_RULE_DIFF_MAX = 0
# |program's logits - reference's| over the decode steps, the reference's
# second track handed the program's S_t (the arithmetic apart from the
# decode queries' discrete choice: the tight one).  Read 0.10-0.26 on logits
# whose own deviation is 0.9; float8 weights 1.17, no q/k norm 0.66, a
# selection by page 0.47, the last 2,048 positions 2.6 (K and V as a float8
# cache 0.31: inside, `CACHE_RTOL` is what it fails).
LOGIT_ATOL = 0.50
# ... and free-running, the prefill's row and the decode steps': where the
# two select differently a row reads other tokens' V, which moves a logit
# by more than rounding does: 0.28-0.70 over those runs.  A backstop for
# what is grossly wrong (the last 2,048 positions read 4.8).
LOGIT_FREE_ATOL = 1.5
# |logits of the scheduler's decode program - logits of its twin that also
# returns what the sparse layers scored and selected|, all rows of the
# check's steps, and 1 if a sampled token differs: the twin is the same
# step with more outputs, compiled to the same arithmetic.  Read 0.0 in
# every run; nothing rounds between the two, so none is allowed.
SELECTION_LOGIT_ATOL = 0.0
# Relative difference (Frobenius norms over a row's positions) between
# what the pools hold of layer 0 for a row after the last decode step - K,
# V and the indexer's keys, read through the row's table - and the
# reference's, the worst of the three and of the rows.  Read 0.0028-0.0029
# (bfloat16's rounding); K and V, or the indexer's keys, as a float8 e4m3
# cache would hold them 0.0267, float8 weights 0.053.  The logits cannot
# tell a float8 K and V (0.31 against 0.26): attention averages 2,048 rows.
CACHE_RTOL = 0.01
# Layers 1 and deeper, on the row cut to `topk` tokens: a token's K, V and
# indexer key against the reference's, |difference| over the larger norm,
# the worst payload; of that the lower quartile over the row's tokens, the
# worst layer.  Read 0.0095-0.0107 (ten seeds; layer 0's own 0.0026);
# layer 2's indexer keys as a float8 e4m3 cache would hold them 0.0262, its
# K and V 0.0286.  (The median reads 0.010-0.012 against 0.028-0.030, the
# 99th percentile 0.3 either way.)
CACHE_LAYERS_RTOL = 0.017
# ... and its maximum over the tokens the check's decode steps wrote (four a
# layer, which a quantile cannot hold).  Read 0.010-0.013 in seven runs of
# ten and 0.07, 0.10, 0.22 where one of those tokens had changed experts
# (the worst of all a row's tokens, 60,000 token-layers read: 0.51); a
# decode step that leaves layer 2's indexer key unwritten 1.0.
CACHE_TAIL_LAYERS_RTOL = 0.7
# Share of routing decisions (token, layer, one of its k experts) of the
# checked rows' prefills in which program and reference chose differently
# (`serve_lm.ROUTING_DIFF_MAX`'s quantity).  Read 0.37-0.50 %; float8
# weights 12.6 %, no q/k norm 8.2 %.
ROUTING_DIFF_MAX = 0.02


def spec_from_config(config):
    """The published keys, `sa_config` and `serve.max_seq_len` as the
    serving stack's `ModelSpec`."""
    from paddle_tpu.serving import ModelSpec
    sa = config["sa_config"]
    return ModelSpec(
        vocab_size=config["vocab_size"], hidden=config["hidden_size"],
        layers=int(config["num_hidden_layers"]),
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        head_size=config["head_dim"],
        max_seq_len=config["serve"]["max_seq_len"],
        norm="rms", norm_eps=config["rms_norm_eps"], positions="rotary",
        rope_theta=float(config["rope_theta"]), ffn="moe",
        experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        tie_head=bool(config["tie_word_embeddings"]), qk_norm=True,
        sparse_topk=sa["topk"], index_heads=sa["indexer_num_heads"],
        index_head_size=sa["indexer_head_dim"])


def build_engine(config, seed):
    import functools
    import jax
    import jax.numpy as jnp
    from paddle_tpu.observability.telemetry import get_telemetry
    from paddle_tpu.serving import ServeConfig, ServingEngine, init_params
    get_telemetry().enable()     # the compile watcher and dispatch counts
    spec = spec_from_config(config)
    cfg = ServeConfig.from_dict(config["serve"])
    dtype = {"fp32": jnp.float32, "bf16": jnp.bfloat16}[cfg.precision]
    # all weights in one jitted call, on the device, from the seed, in
    # the precision they are served in
    make = jax.jit(functools.partial(init_params, spec, dtype=dtype))
    params = make(np.int32(seed % (2 ** 31 - 1)))
    t0 = time.monotonic()
    engine = ServingEngine(spec, params, cfg)
    return engine, params, spec, time.monotonic() - t0


def pick_rows(requests, page_size, rows, topk):
    """The prompts of the check: the run's first `rows` requests (the
    largest decode bucket full), the second cut so that its second decode
    step writes the last position of a page and its third crosses into
    the next, the third cut to `topk - STEPS` tokens: through its last
    decode step it sees at most `topk` positions and selects them all."""
    prompts = [list(r["prompt"]) for r in requests[:rows]]
    if len(prompts) > 1:
        n = len(prompts[1]) - (len(prompts[1]) + 2) % page_size
        if n > 0:
            prompts[1] = prompts[1][:n]
    if len(prompts) > 2:
        prompts[2] = prompts[2][:max(1, topk - STEPS)]
    return prompts


def compared(prompts, count):
    """Indices of the `count` rows the reference runs: by length the
    shortest, the longest and evenly between."""
    order = np.argsort([len(p) for p in prompts], kind="stable")
    at = np.linspace(0, len(order) - 1, min(count, len(order)))
    return sorted({int(order[int(round(a))]) for a in at})


def drive_rows(engine, prompts, keep):
    """The engine's half of the check, what the window drives: every
    prompt prefilled through the engine's programs and cache, then `STEPS`
    decode steps of all of them in one call each, through the public
    logits calls, and each step again through the selection program (it
    writes what the step wrote).  For the rows `keep`: their `STEPS + 1`
    rows of logits, their tokens, their prefill's expert counts, and a
    decode step what each sparse layer selected (`positions` (L, topk),
    best `count` of them) from which scores (`scores` (L, length)).  Then
    a bucket's prefill seconds, and the largest difference between the
    two programs' logits (all rows) beside whether any token differed."""
    lens = np.asarray([len(p) for p in prompts], np.int32)
    topk = engine.spec.sparse_topk
    rows, got, toks, counts, picked = [], [], [], [], {i: [] for i in keep}
    took = {}                   # a prefill bucket's calls, seconds each
    apart = 0.0                 # the two decode programs' logits
    try:
        for prompt in prompts:                   # one prefill a request
            row = engine.pool.admit_row(len(prompt), STEPS + 1,
                                        engine.max_pages_per_seq)
            if row is None:
                raise RuntimeError(f"the pool cannot hold the check's "
                                   f"{len(prompts)} rows")
            rows.append(row)
            t0 = time.monotonic()
            first, logits = engine.prefill_logits(prompt, row.table)
            took.setdefault(engine.prefill_bucket_for(len(prompt)),
                            []).append(time.monotonic() - t0)
            got.append([logits])
            toks.append([first])
            counts.append(engine.expert_counts())        # (L, E)
        for k in range(STEPS):                   # every row in each step
            for row, n in zip(rows, lens):
                row.advance(int(n) + k)
            step = (np.asarray([t[-1] for t in toks], np.int32), lens + k,
                    np.stack([row.table for row in rows]))
            nxt, logits = engine.decode_logits(*step)
            same, again, positions, scores = engine.decode_selection(*step)
            apart = max(apart, float(np.max(np.abs(again - logits))),
                        float(np.any(same != nxt)))
            for i in range(len(rows)):
                got[i].append(logits[i])
                toks[i].append(int(nxt[i]))
            for i in keep:
                length = int(lens[i]) + k + 1
                picked[i].append({
                    "count": min(length, topk),
                    "positions": positions[:, i].copy(),
                    "scores": np.stack([s[i, :length] for s in scores])})
        # every layer of a row that selected all it saw, else layer 0
        cached = {i: read_cache(
            engine.pool, rows[i], int(lens[i]) + STEPS,
            engine.spec.layers if lens[i] + STEPS <= topk else 1)
            for i in keep}
    finally:
        for row in rows:
            row.release()
    driven = {i: {"logits": np.stack(got[i]), "tokens": toks[i],
                  "counts": counts[i], "steps": picked[i],
                  "cached": cached[i]} for i in keep}
    return driven, {
        "selection_logit_diff": apart,
        "prefill_ms_p50_by_bucket": {bucket: 1e3 * float(np.median(s))
                                     for bucket, s in sorted(took.items())}}


def read_cache(pool, row, length, layers):
    """The first `layers` layers' K, V (layers, length, KVH*D) and indexer
    keys (layers, length, DI) of a row's first `length` positions, out of
    the pools through its table."""
    pages = np.asarray(row.table).reshape(-1, row.table.shape[-1])[0][
        :pool.pages_needed(length)]
    at = (np.arange(layers)[:, None], pages[None, :])   # one gather a pool
    k, v, keys = (np.asarray(p[at], np.float32)
                  for p in (pool.k_pool, pool.v_pool, pool.index_pool))
    keys = np.swapaxes(keys, 2, 3)              # a page: (ps, DI) again
    return tuple(a.reshape(layers, -1, a.shape[-1])[:, :length]
                 for a in (k, v, keys))


def rule_diff(step, pad, topk, **rule):
    """Positions that only one of the two selected: the program, and the
    reference's selection rule (`ref.select`, with the variant's `topk`,
    `select`, `page` and `window` where it has them) applied to the
    program's own scores, summed over the layers."""
    layers, length = step["scores"].shape
    scores = np.zeros((layers, pad), np.float32)
    scores[:, :length] = step["scores"]
    seen = np.broadcast_to(np.arange(pad) < length, (layers, pad))
    want = np.asarray(ref.select(scores, seen, topk, **rule))
    have = np.zeros((layers, pad), bool)
    for l in range(layers):
        have[l, step["positions"][l, :step["count"]]] = True
    return int(np.sum(want != have)), have


def compare_rows(params, config, prompts, driven, facts=None, **variant):
    """The reference's half: for every driven row the plain reference's
    forward over that row's tokens alone, once, its second track handed
    the program's S_t.  Returns the check's numbers (the module's limits
    say what each is), `drive_rows`' own (`facts`) among them."""
    pads = sorted(config["check"]["pads"])
    layers, top_k = config["num_hidden_layers"], config["num_experts_per_tok"]
    out = {"index_score_err": 0.0, "index_score_err_layers": 0.0,
           "index_score_err_deep": 0.0, "logit_err": 0.0,
           "logit_err_free": 0.0, "cache_err": 0.0, "cache_err_layers": 0.0,
           "cache_tail_err_layers": 0.0, "rows_within_topk": 0}
    pairs = differ = rule = flips = decisions = 0
    for i, row in driven.items():
        n = len(prompts[i])
        # it selected all it saw: every layer of it is held, not layer 0's
        whole = n + STEPS <= config["sa_config"]["topk"]
        out["rows_within_topk"] += whole
        pad = next(p for p in pads if p >= n + STEPS)   # few shapes
        seq = np.zeros((pad,), np.int32)
        seq[:n + STEPS] = list(prompts[i]) + row["tokens"][:STEPS]
        given = np.zeros((layers, STEPS, pad), bool)
        for k, step in enumerate(row["steps"]):
            diff, given[:, k] = rule_diff(
                step, pad, variant.get("topk", config["sa_config"]["topk"]),
                **{"by" if key == "select" else key: variant[key]
                   for key in ("select", "page", "window") if key in variant})
            rule += diff
        *found, cached = ref.forward(
            params, seq, np.int32(n - 1), cfg=config, rows=STEPS + 1,
            given=given, **variant)
        free, handed, routed, scores, masks = (np.asarray(a) for a in found)
        token_err = 0.0         # (layers read, tokens): the worst payload
        for mine, theirs in zip(row["cached"], cached):
            theirs = np.asarray(theirs[:mine.shape[0], :mine.shape[1]])
            out["cache_err"] = max(out["cache_err"], float(
                np.linalg.norm(mine[0] - theirs[0])
                / np.linalg.norm(theirs[0])))
            token_err = np.maximum(token_err, _token_err(mine, theirs))
        if whole:
            # a token whose experts the two chose differently in an earlier
            # layer has another hidden state (a few in a hundred a layer:
            # the router's near-ties): the tokens' lower quartile does not
            # see them, a cache in another precision moves every token
            out["cache_err_layers"] = max(
                out["cache_err_layers"], float(np.quantile(
                    token_err[1:], 0.25, axis=1).max(initial=0.0)))
            out["cache_tail_err_layers"] = max(
                out["cache_tail_err_layers"],
                float(token_err[1:, n:].max(initial=0.0)))
            out["cache_err_quantiles_by_layer"] = _quantiles(token_err)
        out["logit_err_free"] = max(out["logit_err_free"], float(
            np.max(np.abs(row["logits"] - free))))
        out["logit_err"] = max(out["logit_err"], float(
            np.max(np.abs(row["logits"][1:] - handed))))
        for k, step in enumerate(row["steps"]):
            length = n + k + 1
            err = np.max(np.abs(step["scores"]
                                - scores[:, k + 1, :length]), axis=1)
            out["index_score_err"] = max(out["index_score_err"],
                                         float(err[0]))
            if whole:       # no choice in it, so none to differ in
                continue
            out["index_score_err_deep"] = max(out["index_score_err_deep"],
                                              float(err.max()))
            differ += int(np.sum(given[:, k] != masks[:, k + 1])) // 2
            pairs += layers * step["count"]
        if whole:
            apart = np.concatenate([
                np.abs(step["scores"] - scores[:, k + 1, :n + k + 1])
                for k, step in enumerate(row["steps"])], axis=1)
            out["index_score_err_layers"] = max(
                out["index_score_err_layers"],
                float(np.median(apart[1:], axis=1).max(initial=0.0)))
            out["index_score_err_quantiles_by_layer"] = _quantiles(apart)
        # the prefill routed positions 0 .. n-1
        flips += np.abs(row["counts"] - routed[:, :n].astype(np.int64)
                        .sum(axis=1)).sum() / 2.0
        decisions += n * top_k * layers
    out["selection_logit_diff"] = (facts or {}).get("selection_logit_diff",
                                                     0.0)
    out.update(select_diff_share=differ / max(1, pairs),
               select_rule_diff=rule,
               routing_diff_share=float(flips) / max(1, decisions))
    return out


def _token_err(mine, theirs):
    """(layers, tokens): |mine - theirs| of a token's lanes over the larger
    of the two norms (1 where one of them is nothing)."""
    size = np.maximum(np.linalg.norm(mine, axis=2),
                      np.linalg.norm(theirs, axis=2))
    return np.linalg.norm(mine - theirs, axis=2) / np.maximum(size, 1e-30)


def _quantiles(errs):
    """A layer a row: the 10th, 25th, 50th, 90th and 99th percentile and
    the maximum of `errs` (layers, n), for the notes."""
    return [[float(np.quantile(e, q))
             for q in (0.1, 0.25, 0.5, 0.9, 0.99, 1.0)] for e in errs]


def check_against_reference(engine, params, config, prompts, **variant):
    """Both halves at once, every row compared (tests)."""
    keep = compared(prompts, config["check"]["rows"])
    return compare_rows(params, config, prompts,
                        *drive_rows(engine, prompts, keep), **variant)


LIMITS = {"index_score_err": INDEX_SCORE_ATOL,
          "cache_tail_err_layers": CACHE_TAIL_LAYERS_RTOL,
          "index_score_err_layers": INDEX_SCORE_LAYERS_ATOL,
          "select_diff_share": SELECT_DIFF_MAX,
          "select_rule_diff": SELECT_RULE_DIFF_MAX,
          "logit_err": LOGIT_ATOL, "logit_err_free": LOGIT_FREE_ATOL,
          "selection_logit_diff": SELECTION_LOGIT_ATOL,
          "cache_err": CACHE_RTOL, "cache_err_layers": CACHE_LAYERS_RTOL,
          "routing_diff_share": ROUTING_DIFF_MAX}


def within_limits(found):
    return all(found[name] <= limit for name, limit in LIMITS.items())


def model_facts(spec, cfg):
    """What the readers of `costs_sparse.py` (and of `costs_lm.py`) need
    to know of the model."""
    return {"layers": spec.layers, "heads": spec.heads,
            "kv_heads": spec.n_kv_heads, "head_dim": spec.head_dim,
            "hidden": spec.hidden, "vocab_size": spec.vocab_size,
            "layer_types": ["full"] * spec.layers, "window": 0,
            "page_size": cfg.page_size, "kv_itemsize": 2,
            "weight_itemsize": 2, "experts": spec.experts,
            "experts_per_token": spec.experts_per_token,
            "expert_width": spec.expert_width,
            "sparse_topk": spec.sparse_topk,
            "index_heads": spec.index_heads,
            "index_head_size": spec.index_head_size}


def run(ctx):
    import jax
    config, mix = ctx["config"], ctx["traffic"]
    on_chip = jax.devices()[0].platform == "tpu"
    engine, params, spec, first_call_s = build_engine(config, ctx["seed"])
    tap = Tap(engine, spec.sparse_topk)
    requests, closed = serve_requests(mix, ctx["seed"], ctx["seconds"],
                                      spec.vocab_size)
    prompts = pick_rows(requests, engine.config.page_size,
                        engine.config.decode_buckets[-1], spec.sparse_topk)
    keep = compared(prompts, config["check"]["rows"])
    t_check = time.monotonic()
    from paddle_tpu.serving.engine import aot_build_phase
    with aot_build_phase():
        driven, drove = drive_rows(engine, prompts, keep)
    check_s = time.monotonic() - t_check
    routes = pallas_routes()
    fell_back = {k: v for k, v in routes.items() if v.get("fallback")}
    engine.scheduler.start()
    try:
        if ctx["sweep"]:
            return base.sweep(ctx, engine, tap, mix, spec.vocab_size)
        m = base.measure(engine, tap, requests, closed, ctx["seconds"],
                         float(mix.get("drain_s", 10.0)),
                         mix.get("trace_s", 4.0) if ctx["trace"] else 0,
                         ctx["out"])
    finally:
        engine.scheduler.stop()
    decode_rows = [d + seen for d, seen in zip(tap.decode, tap.decode_seen)]
    prefills = [(a, b, len(p)) for a, b, p in tap.prefill]
    red = base.reduce_window(m, tap, requests, closed)
    health = engine.healthz()
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    engine.close()
    facts = model_facts(spec, engine.config)
    for a in engine.pool.state():       # room for the reference
        a.delete()
    t_check = time.monotonic()
    with aot_build_phase():   # the reference compiles; nothing is in flight
        found = compare_rows(params, config, prompts, driven, drove)
    check_s += time.monotonic() - t_check
    kv = health["kv"]
    notes = dict(
        found, limits=LIMITS,
        check_rows=len(prompts),
        compared_prompt_lens=[len(prompts[i]) for i in keep],
        check_s=check_s, build_s=first_call_s,
        check_prefill_ms_p50_by_bucket=drove["prefill_ms_p50_by_bucket"],
        window_compiles=m["compiles"], pallas_routes=routes,
        decode_steps=len(red["spans"]["decode"]),
        prefills=len(red["spans"]["prefill"]),
        completed_tokens_per_s=red["values"]["completed_tokens_per_s"],
        step_period_ms_p50=red["values"]["step_period_ms_p50"],
        kv_consistent=health["kv_consistent"],
        kv={k: kv[k] for k in ("pages", "index_pages", "high_watermark",
                               "reserve_refusals")},
        refused_kv=health.get("refused_kv"),
        memory_peak_bytes_before_reference=peak,
        program_bytes=health["program_bytes"])
    kernels = ("paged_attention_sparse", "paged_index_scores",
               "index_scores", "moe_gmm")
    correct = (within_limits(found) and m["compiles"] == 0
               and (not fell_back or not on_chip)
               and (not on_chip or all(
                   routes.get(k, {}).get("pallas", 0) >= 1 for k in kernels))
               and found["rows_within_topk"] >= 1
               and red["attempted"] > 0 and health["kv_consistent"])
    red["values"]["first_call_s"] = first_call_s
    return dict(red, correct=correct, t_window=m["t0"], notes=notes,
                trace_dir=m["trace_dir"], trace_window=m["trace_window"],
                decode_rows=decode_rows, prefill_rows=prefills, model=facts)
