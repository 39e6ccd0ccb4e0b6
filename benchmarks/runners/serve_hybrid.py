"""Runner kind `serve_hybrid`: a decoder-hybrid-decoder (state-space,
sliding and full differential attention, then gated memory units and
cross-attention over one shared KV layer; Phi-4-mini-flash-reasoning)
whose configuration file holds its published `config.json` keys, served
through the same `ServingEngine`, scheduler and load loop as runners
`serve` and `serve_lm`.

From `runners/serve.py` come `EngineTap` (under `serve_lm.Tap`, which
adds the positions each decode step's rows see with and without the
window), `Load`, `measure`, `reduce_window` and `sweep` as they are.
This file's own: the published keys and the `assumed` block to a
`ModelSpec`, the check against `reference/phi4flash_serve.py` through
the engine's public `prefill_logits` / `decode_logits` with the largest
decode bucket full (the engine's half before the window, the reference's
forwards after it), its limits, and the `model` dict the readers of
`costs_hybrid.py` take.
"""
from __future__ import annotations

import time

import numpy as np

from reference import phi4flash_serve as ref
from runners import serve as base
from runners.serve_lm import Tap
from taps import pallas_routes
from traffic import serve_requests

# |engine logits - reference logits| over what the window drives: the
# run's first 64 requests (the largest decode bucket full; one cycle of
# the mix's grid, prompts 256-2048) each prefilled, then four decode steps
# of all 64 rows side by side; every row's five rows of logits against
# the reference's forward over that row alone.  The configuration serves
# in bfloat16 (weights, activations, K/V; softmaxes, norms, delta, the
# recurrence and the SSM state float32); the reference computes the same
# bfloat16 weights in float32 at "highest".  Over 32 layers that rounding
# read 0.302 to 0.335 in ten runs (a weights seed and 64 prompts each; my
# chip run, PR 31): the sub-layer norm of a difference of two attentions
# amplifies it.  The limit stands 1.6 times over the worst of them and as
# far under the nearest wrong reference: a window of 511 reads 0.874,
# weights rounded to float8 e4m3 1.95, layer 0's lambda0 in every layer
# 5.03 (16 rows each).  A reference whose SSM state is rounded to
# bfloat16 every position reads 0.305 to 0.324: inside, hence STATE_RTOL.
LOGIT_ATOL = 0.55
# Relative difference (Frobenius norms) between the first state-space
# layer's state in a row's slot after the last decode step and the
# reference's after as many tokens, over the state's slow elements (those
# whose decay delta x A at the layer's initial delta gives them a memory
# of over SLOW_MEMORY positions), the worst of the rows.  Layer 0 sees
# the embedding through one norm and nothing else, so its state differs
# from the reference's only by the bfloat16 rounding of its own inputs,
# which averages out over an element's memory; a state kept in bfloat16
# rounds the state itself every position, which adds up over it.  The
# logits cannot tell that apart, nor can the fast elements, which hold
# little more than their last input.  Nine runs read 0.0032 to 0.0044,
# the bfloat16-state reference 0.0514, float8 weights 0.027 (my chip run,
# PR 31; PERF.md section 2).
STATE_RTOL = 0.015
SLOW_MEMORY = 100
STEPS = 4
KINDS = {"mamba": "ssm"}     # the reference's names to ModelSpec's


def spec_from_config(config):
    """The published keys, the `assumed` block and `serve.max_seq_len` as
    the serving stack's `ModelSpec`."""
    from paddle_tpu.serving import ModelSpec
    a = config["assumed"]
    n = int(config["num_hidden_layers"])
    return ModelSpec(
        vocab_size=config["vocab_size"], hidden=config["hidden_size"],
        layers=n, heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        max_seq_len=config["serve"]["max_seq_len"],
        ffn_mult=config["intermediate_size"] // config["hidden_size"],
        norm="layer", norm_eps=config["layer_norm_eps"], positions="none",
        layer_types=[KINDS.get(k, k) for k in ref.layer_kinds(n)],
        window=config["sliding_window"], ffn="swiglu",
        tie_head=bool(config["tie_word_embeddings"]),
        attn_bias=bool(a["attention_bias"]),
        diff_attn=bool(a["differential_attention"]),
        ssm_inner=a["mamba_d_inner"], ssm_state=a["mamba_d_state"],
        ssm_conv=a["mamba_d_conv"], ssm_dt_rank=a["mamba_dt_rank"])


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
    largest decode bucket full; a closed mix's first cycle, so its
    longest prompt is among them), the first cut to stay under
    `check.short_below` positions (the window) through its decode steps,
    the second cut so that its second decode step writes the last
    position of a page and its third crosses into the next."""
    prompts = [list(r["prompt"]) for r in requests[:rows]]
    short = config["check"]["short_below"] - STEPS - 1
    prompts[0] = prompts[0][:max(1, min(len(prompts[0]), short))]
    if len(prompts) > 1:
        n = len(prompts[1]) - (len(prompts[1]) + 2) % page_size
        if n > 0:
            prompts[1] = prompts[1][:n]
    return prompts


def drive_rows(engine, prompts):
    """The engine's half of the check, what the window drives: every
    prompt prefilled through the engine's programs and cache (pages of
    both pools and a state slot each), then `STEPS` decode steps of all
    of them in one call each, through the public logits calls.  Returns
    a row its `STEPS + 1` rows of logits, its tokens, and every
    state-space layer's state in its slot after the last step."""
    lens = np.asarray([len(p) for p in prompts], np.int32)
    rows, got, toks = [], [], []
    try:
        for prompt in prompts:                   # one prefill a request
            row = engine.pool.admit_row(len(prompt), STEPS + 1,
                                        engine.max_pages_per_seq)
            if row is None:
                raise RuntimeError(
                    f"the pools cannot hold the check's {len(prompts)} "
                    f"rows ({engine.pool.last_refusal})")
            rows.append(row)
            first, logits = engine.prefill_logits(prompt, row.table)
            got.append([logits])
            toks.append([first])
        for k in range(STEPS):                   # every row in each step
            for row, n in zip(rows, lens):
                row.advance(int(n) + k)
            nxt, logits = engine.decode_logits(
                np.asarray([t[-1] for t in toks], np.int32), lens + k,
                np.stack([row.table for row in rows]))
            for i in range(len(rows)):
                got[i].append(logits[i])
                toks[i].append(int(nxt[i]))
        held = np.asarray(engine.pool.state_slots.ssm)   # (Ls, slots, R, N)
        states = [held[:, row.slot] for row in rows]
    finally:
        for row in rows:
            row.release()
    return got, toks, states


def compare_rows(params, config, prompts, driven, **variant):
    """The reference's half: every row's logits against the plain
    reference's full forward over that row's tokens alone, and every
    state-space layer's state after the last step against the
    reference's after as many tokens.  Returns the worst absolute
    difference of the logits over all rows, and a state-space layer the
    worst relative difference of its state (Frobenius norms) over the
    rows: of the whole state, and of its slow elements (`slow_elements`).
    `variant` goes to the reference (`round_to`, `state_dtype`,
    `lambda0_layer`, `window`): the lower-precision and wrong-constant
    forms the limits have to tell apart."""
    import jax.numpy as jnp
    got, toks, states = driven
    pads = sorted(config["check"]["pads"])
    slow = slow_elements(params, config)
    worst, state_worst = 0.0, np.zeros((2, len(states[0])))
    for i, prompt in enumerate(prompts):
        n = len(prompt)
        pad = next(p for p in pads if p >= n + STEPS)   # few shapes
        seq = np.zeros((pad,), np.int32)
        seq[:n + STEPS] = list(prompt) + toks[i][:STEPS]
        want, state = ref.forward(params, jnp.asarray(seq), np.int32(n - 1),
                                  cfg=config, rows=STEPS + 1, **variant)
        worst = max(worst, float(np.max(np.abs(
            np.stack(got[i]) - np.asarray(want)))))
        # the last decode step took in position n + STEPS - 1
        state = np.transpose(np.asarray(state), (0, 2, 1))   # (Ls, R, N)
        sq, of = (states[i] - state) ** 2, state ** 2
        diff = [np.sqrt((sq * w).sum(axis=(1, 2)) / (of * w).sum(axis=(1, 2)))
                for w in (1.0, slow)]
        state_worst = np.maximum(state_worst, diff)
    return worst, state_worst[0].tolist(), state_worst[1].tolist()


def check_against_reference(engine, params, config, prompts, **variant):
    """Both halves at once (tests, `exp/phi4flash_limits.py`)."""
    return compare_rows(params, config, prompts,
                        drive_rows(engine, prompts), **variant)


def slow_elements(params, config):
    """(Ls, R, N) of 0 / 1: the elements of each state-space layer's
    state whose decay a position, delta x A with delta at its initial
    value softplus(b_dt), is under 1 / SLOW_MEMORY."""
    masks = []
    for i, kind in enumerate(ref.layer_kinds(config["num_hidden_layers"])):
        if kind == "mamba":
            bdt = np.asarray(params[f"h{i}.ssm.bdt"], np.float32)
            a = np.exp(np.asarray(params[f"h{i}.ssm.A_log"], np.float32))
            masks.append(np.logaddexp(bdt, 0.0)[None, :] * a
                         < 1.0 / SLOW_MEMORY)
    return np.stack(masks).astype(np.float32)


def model_facts(spec, cfg):
    """What the readers of `costs_hybrid.py` need to know of the model."""
    return {"layers": spec.layers, "layer_types": list(spec.layer_types),
            "heads": spec.heads, "kv_heads": spec.n_kv_heads,
            "head_dim": spec.head_dim, "hidden": spec.hidden,
            "ffn": spec.hidden * spec.ffn_mult,
            "vocab_size": spec.vocab_size, "window": spec.window,
            "ssm_inner": spec.ssm_inner, "ssm_state": spec.ssm_state,
            "ssm_conv": spec.ssm_conv, "ssm_dt_rank": spec.ssm_dt_rank,
            "page_size": cfg.page_size, "kv_itemsize": 2,
            "weight_itemsize": 2}


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
    # the engine's half of the check before the window, the reference's
    # after it: 25 s of float32 matmuls at "highest" just before a window
    # left the chip's memory side 5-8 % slower for the whole of it in one
    # run of seven (PERF.md section 6, PR 31)
    t_check = time.monotonic()
    from paddle_tpu.serving.engine import aot_build_phase
    with aot_build_phase():
        driven = drive_rows(engine, prompts)
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
    t_check = time.monotonic()
    with aot_build_phase():   # the reference compiles; nothing is in flight
        logit_err, state_err, slow_err = compare_rows(params, config,
                                                      prompts, driven)
    check_s += time.monotonic() - t_check
    decode_rows = [d + seen for d, seen in zip(tap.decode, tap.decode_seen)]
    prefills = [(a, b, len(p)) for a, b, p in tap.prefill]
    red = base.reduce_window(m, tap, requests, closed)
    health = engine.healthz()
    engine.close()
    kv = health["kv"]
    notes = {"logit_err": logit_err, "logit_atol": LOGIT_ATOL,
             "state_err": state_err, "state_err_slow": slow_err,
             "state_rtol": STATE_RTOL,
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
             "kv": {k: kv[k] for k in
                    ("pages", "high_watermark", "reserve_refusals")},
             "kv_window": {k: kv.get("window", {}).get(k) for k in
                           ("pages", "high_watermark", "pages_returned",
                            "row_pages_max")},
             "state_slots": kv.get("state"),
             "refused": {k: health.get(k) for k in
                         ("refused_kv", "refused_state")},
             "program_bytes": health["program_bytes"]}
    correct = (logit_err <= LOGIT_ATOL and slow_err[0] <= STATE_RTOL
               and m["compiles"] == 0
               and (not fell_back or not on_chip)
               and (not on_chip or all(
                   routes.get(k, {}).get("pallas", 0) >= 1
                   for k in ("paged_attention", "ssm_scan")))
               # a rehearsal off the chip is shorter than one answer: it
               # has to have decoded, a chip run to have finished requests
               and (red["attempted"] > 0 or (
                   not on_chip and red["counters"].get("decode_tokens")))
               and health["kv_consistent"])
    red["values"]["first_call_s"] = first_call_s
    red["values"]["state_slots_held_max"] = (kv.get("state") or {}).get(
        "high_watermark")
    return dict(red, correct=correct, t_window=m["t0"], notes=notes,
                trace_dir=m["trace_dir"], trace_window=m["trace_window"],
                decode_rows=decode_rows, prefill_rows=prefills,
                model=model_facts(spec, engine.config))
