"""Runner kind `serve_lm`: a decoder whose configuration file holds its
published `config.json` keys (grouped-query attention, rotary positions,
sliding and full layers, routed experts) served through the same
`ServingEngine`, scheduler and load loop as runner `serve`.

From `runners/serve.py` come `EngineTap`, `Load`, `measure`,
`reduce_window` and `sweep` as they are.  This file's own: the engine
build (the published keys to a `ModelSpec`; all weights in one jitted
call from the seed, on the device, in the precision served), the check
against `reference/mellum_serve.py` through the engine's public
`prefill_logits` / `decode_logits` with the largest decode bucket full,
its tolerance, and the `model` dict the roofline readers of
`costs_lm.py` take.
"""
from __future__ import annotations

import time

import numpy as np

from reference import mellum_serve as ref
from runners import serve as base
from taps import pallas_routes
from traffic import serve_requests

# |engine logits - reference logits| over what the window drives: the
# run's first 64 requests (the largest decode bucket full: 48 prompts of
# 256-1024 tokens and 16 of 4096-7680 in this cell) each prefilled, then
# four decode steps of all 64 rows side by side; every row's five rows of
# logits against the reference's forward over that row alone.  The
# configuration serves in bfloat16 (weights, activations, cache; router
# and softmaxes float32); the reference computes the same bfloat16
# weights in float32 at "highest".  Over 12 layers that rounding read
# 0.126 to 0.190 in thirteen runs (median 0.140; a weights seed and 64
# prompts each), on logits whose own standard deviation is 0.96 (my chip
# run, PR 27; PERF.md section 2 has the readings), so the bound is 1.6
# times the worst of 832 rows.  It stands twice under what the nearest
# precision below reads on one run's engine and rows: the reference with
# weights rounded to float8 e4m3 0.62; and under YaRN's attention factor
# 1.0 for 1.277, 0.51.  (Two rows instead of 64 had read 0.062 to 0.158
# in 43 checks, no window 3.9, no YaRN 0.84.)
LOGIT_ATOL = 0.30
# Share of routing decisions (token, layer, one of its k experts) in which
# the program and the reference chose differently, from a call's counts
# of tokens an expert (a prefill's positions, a decode step's rows).
# Top-k flips on near-ties between bfloat16 and float32 activations; a
# flipped choice swaps two experts of nearly equal small weight, which
# the logit limit above absorbs.  The program's routing is never handed
# to the reference: both route for themselves and the share is bounded
# at five times the worst reading: 0.33 to 0.35 % in those thirteen runs.
# Top-7 routing reads 7.2 % (and 0.24 on the logits: this limit is the
# one it fails), float8 weights 4.7 %, YaRN's factor 1.0 2.6 %.
ROUTING_DIFF_MAX = 0.02
STEPS = 4


class Tap(base.EngineTap):
    """`EngineTap`, and beside each decode span the positions the step's
    rows can see with and without the window (`decode_seen`, in step
    with `decode`): what the grouped kernel has to read."""

    def __init__(self, engine, window):
        self.window = window
        self.decode_seen = []   # (visible in a full layer, in a sliding)
        super().__init__(engine)

    def decode_call(self, tokens, positions, page_tables):
        lengths = np.asarray(positions, np.int64) + 1
        self.decode_seen.append(
            (int(lengths.sum()),
             int(np.minimum(lengths, self.window or lengths.max()).sum())))
        return super().decode_call(tokens, positions, page_tables)

    def reset(self):
        super().reset()
        self.decode_seen = []


def spec_from_config(config):
    """The published keys (and `serve.max_seq_len`) as the serving
    stack's `ModelSpec`."""
    from paddle_tpu.serving import ModelSpec
    n = int(config["num_hidden_layers"])
    rope = config["rope_parameters"]
    yarn = rope["full_attention"] \
        if rope["full_attention"].get("rope_type") == "yarn" else {}
    return ModelSpec(
        vocab_size=config["vocab_size"], hidden=config["hidden_size"],
        layers=n, heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        head_size=config["head_dim"],
        max_seq_len=config["serve"]["max_seq_len"],
        norm="rms", norm_eps=config["rms_norm_eps"], positions="rotary",
        rope_theta=float(rope["sliding_attention"]["rope_theta"]),
        yarn_factor=float(yarn.get("factor", 0.0)),
        yarn_original_len=int(
            yarn.get("original_max_position_embeddings", 0)),
        yarn_beta_fast=float(yarn.get("beta_fast", 32.0)),
        yarn_beta_slow=float(yarn.get("beta_slow", 1.0)),
        yarn_attention_factor=float(yarn.get("attention_factor", 0.0)),
        layer_types=[t.replace("_attention", "")
                     for t in config["layer_types"][:n]],
        window=config["sliding_window"], ffn="moe",
        experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        tie_head=bool(config["tie_word_embeddings"]))


def reference_config(config):
    """The published keys the reference reads, cut to the depth run."""
    n = int(config["num_hidden_layers"])
    return dict(config, layer_types=config["layer_types"][:n])


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


def pick_rows(requests, config, page_size, rows):
    """The prompts of the check: the run's first `rows` requests (the
    largest decode bucket full, lengths in the mix the window offers),
    the first of them cut to stay under `check.short_below` positions
    (the window) through its decode steps, and the first request of
    `check.long_from` tokens or more brought in last if none is among
    them; those two cut so that their second decode step writes the last
    position of a page and their third crosses into the next."""
    check = config["check"]

    def cut(prompt, most):
        n = min(len(prompt), most)
        n -= (n + 2) % page_size
        return prompt[:n] if n > 0 else prompt[:min(len(prompt), most)]

    prompts = [r["prompt"] for r in requests[:rows]]
    long_at = next((i for i, p in enumerate(prompts)
                    if i and len(p) >= check["long_from"]), None)
    if long_at is None:
        long_at = len(prompts) - 1
        prompts[long_at] = next(r["prompt"] for r in requests[1:]
                                if len(r["prompt"]) >= check["long_from"])
    prompts[0] = cut(prompts[0], check["short_below"] - STEPS - 1)
    prompts[long_at] = cut(prompts[long_at], len(prompts[long_at]))
    return prompts


def check_against_reference(engine, params, config, prompts, round_to=None):
    """What the window drives, held against the plain reference: every
    prompt prefilled through the engine's programs and cache, then
    `STEPS` decode steps of all of them in one call each (the largest
    decode bucket full, rows of mixed lengths side by side), through the
    public logits calls; every row's logits against the reference's full
    forward over that row's tokens alone.  Returns the worst absolute
    difference over all rows and the share of routing decisions that
    differ (a call's counts of tokens an expert, the reference's summed
    over the rows of the call)."""
    import jax.numpy as jnp
    cfg = reference_config(config)
    top_k = config["num_experts_per_tok"]
    pads = sorted(config["check"]["pads"])
    lens = np.asarray([len(p) for p in prompts], np.int32)
    rows, got, toks, counts = [], [], [], []
    try:
        for prompt in prompts:                   # one prefill a request
            row = engine.pool.admit_row(len(prompt), STEPS + 1,
                                        engine.max_pages_per_seq)
            if row is None:
                raise RuntimeError(f"the pools cannot hold the check's "
                                   f"{len(prompts)} rows")
            rows.append(row)
            first, logits = engine.prefill_logits(prompt, row.table)
            got.append([logits])
            toks.append([first])
            counts.append(engine.expert_counts())        # (L, E) each
        for k in range(STEPS):                   # every row in each step
            for row, n in zip(rows, lens):
                row.advance(int(n) + k)
            nxt, logits = engine.decode_logits(
                np.asarray([t[-1] for t in toks], np.int32), lens + k,
                np.stack([row.table for row in rows]))
            for i in range(len(rows)):
                got[i].append(logits[i])
                toks[i].append(int(nxt[i]))
            counts.append(engine.expert_counts())
    finally:
        for row in rows:
            row.release()
    worst, theirs = 0.0, [0] * STEPS
    flips = 0.0
    for i, (prompt, n) in enumerate(zip(prompts, lens)):
        n = int(n)
        pad = next(p for p in pads if p >= n + STEPS)   # few shapes
        seq = np.zeros((pad,), np.int32)
        seq[:n + STEPS] = list(prompt) + toks[i][:STEPS]
        want, routed = ref.forward(params, jnp.asarray(seq), np.int32(n - 1),
                                   cfg=cfg, rows=STEPS + 1,
                                   round_to=round_to)
        worst = max(worst, float(np.max(np.abs(
            np.stack(got[i]) - np.asarray(want)))))
        routed = np.asarray(routed).astype(np.int64)         # (L, S, E)
        # the prefill routed positions 0 .. n-1, decode step k position n+k
        flips += np.abs(counts[i] - routed[:, :n].sum(axis=1)).sum() / 2.0
        theirs = [t + routed[:, n + k] for k, t in enumerate(theirs)]
    flips += sum(np.abs(a - b).sum() / 2.0
                 for a, b in zip(counts[len(rows):], theirs))
    decisions = int(lens.sum() + STEPS * len(rows)) * top_k * len(
        cfg["layer_types"])
    return worst, flips / max(1, decisions)


def model_facts(config, spec, cfg):
    """What the readers of `costs_lm.py` need to know of the model."""
    return {"layers": spec.layers, "heads": spec.heads,
            "kv_heads": spec.n_kv_heads, "head_dim": spec.head_dim,
            "hidden": spec.hidden, "vocab_size": spec.vocab_size,
            "layer_types": list(spec.layer_types), "window": spec.window,
            "page_size": cfg.page_size, "kv_itemsize": 2,
            "weight_itemsize": 2, "experts": spec.experts,
            "experts_per_token": spec.experts_per_token,
            "expert_width": spec.expert_width}


def run(ctx):
    import jax
    config, mix = ctx["config"], ctx["traffic"]
    on_chip = jax.devices()[0].platform == "tpu"
    engine, params, spec, first_call_s = build_engine(config, ctx["seed"])
    tap = Tap(engine, spec.window)
    requests, closed = serve_requests(mix, ctx["seed"], ctx["seconds"],
                                      spec.vocab_size)
    prompts = pick_rows(requests, config, engine.config.page_size,
                        engine.config.decode_buckets[-1])
    t_check = time.monotonic()
    from paddle_tpu.serving.engine import aot_build_phase
    with aot_build_phase():   # the reference compiles; nothing is in flight
        logit_err, routing_diff = check_against_reference(
            engine, params, config, prompts)
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
    engine.close()
    notes = {"logit_err": logit_err, "logit_atol": LOGIT_ATOL,
             "routing_diff_share": routing_diff,
             "check_rows": len(prompts),
             "check_prompt_lens": sorted(len(p) for p in prompts),
             "check_s": check_s, "build_s": first_call_s,
             "window_compiles": m["compiles"], "pallas_routes": routes,
             "decode_steps": len(red["spans"]["decode"]),
             "prefills": len(red["spans"]["prefill"]),
             "completed_tokens_per_s":
                 red["values"]["completed_tokens_per_s"],
             "step_period_ms_p50": red["values"]["step_period_ms_p50"],
             "kv_consistent": health["kv_consistent"],
             "kv": {k: health["kv"][k] for k in
                    ("pages", "high_watermark", "reserve_refusals")},
             "kv_window": {k: health["kv"].get("window", {}).get(k) for k in
                           ("pages", "high_watermark", "pages_returned",
                            "row_pages_max")},
             "program_bytes": health["program_bytes"]}
    correct = (logit_err <= LOGIT_ATOL and routing_diff <= ROUTING_DIFF_MAX
               and m["compiles"] == 0
               and (not fell_back or not on_chip)
               and (not on_chip or routes.get("paged_attention", {})
                    .get("pallas", 0) >= 1)
               and red["attempted"] > 0 and health["kv_consistent"])
    red["values"]["first_call_s"] = first_call_s
    return dict(red, correct=correct, t_window=m["t0"], notes=notes,
                trace_dir=m["trace_dir"], trace_window=m["trace_window"],
                decode_rows=decode_rows, prefill_rows=prefills,
                model=model_facts(config, spec, engine.config))
