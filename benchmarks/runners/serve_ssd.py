"""Runner kind `serve_ssd`: a decoder whose every layer runs a Mamba-2 mixer
in parallel with grouped-query attention (Falcon-H1), whose configuration
file holds its published `config.json` keys, served through the same
`ServingEngine`, scheduler and load loop as runners `serve`, `serve_lm`
and `serve_hybrid`.

From `runners/serve.py` come `Load`, `measure`, `reduce_window` and
`sweep`, from `serve_lm.py` the `Tap` (the positions each decode step's
rows see), from `serve_hybrid.py` `drive_rows` (the engine's half of the
check) as they are.  This file's own: the published keys to a `ModelSpec`,
the engine build, the check's rows, the comparison against
`reference/falconh1_serve.py` (the recurrence one position a step) after
the window with the pools freed, its limits, and the `model` dict the
readers of `costs_ssd.py` take.
"""
from __future__ import annotations

import time

import numpy as np

from reference import falconh1_serve as ref
from runners import serve as base
from runners.serve_hybrid import STEPS, drive_rows
from runners.serve_lm import Tap
from taps import pallas_routes
from traffic import serve_requests

# |engine logits - reference logits| over the compared rows of what the
# window drives: the run's first 64 requests (one cycle of the mix) each
# prefilled, then four decode steps of all 64 rows side by side; the first
# `check.compare` of them (and the longest) against the reference's
# forward over that row alone.  The configuration serves in bfloat16
# (weights, activations, K/V; softmaxes, norms, delta, the recurrence and
# the state float32); the reference computes the same bfloat16 weights in
# float32 at "highest".  The logits are 1/128 of the usual scale
# (lm_head_multiplier).  Readings (my chip runs, PR 38; PERF.md section 2):
# twenty weight seeds 0.00035-0.00040; weights rounded to float8 e4m3
# 0.0024, no key_multiplier 0.0119 (a state kept in bfloat16 0.00034: the
# logits hardly see the state, D x dominates y).
LOGIT_ATOL = 0.001
# Relative difference (Frobenius norms) between a layer's state in a row's
# slot after the last decode step and the reference's after as many
# tokens, over the layer's slow heads (the SLOW_HEADS of 32 whose decay a
# position, delta x a at the initial delta softplus(dt_bias), is least:
# the longest memory), the worst of the compared rows.  Layer 0 sees the
# embedding through one norm, so its state differs from the reference's
# only by the rounding of its own bfloat16 inputs, which averages out over
# a head's memory; a state kept in bfloat16 rounds the state itself every
# position, which adds up over it.  STATE_RTOL holds layer 0 to it: twenty
# seeds 0.0040-0.0041; the bfloat16 state 0.0127, float8 weights 0.046,
# B and C of the two groups swapped 1.44.
STATE_RTOL = 0.007
# The same over layers 1-3, the worst of them: their inputs carry every
# layer before them (twenty seeds 0.0077-0.0080; float8 weights 0.054, no
# key_multiplier 0.25, the bfloat16 state 0.016).  The gated norm over one
# group of 4,096 in place of two reads 0.0083 here against this seed's
# 0.0078, and inside every limit: with these weights the gated input's
# mean square sits under the norm's eps, so its grouping hardly moves M.
STATE_RTOL_DEEP = 0.02
SLOW_HEADS = 8


def spec_from_config(config):
    """The published keys and `serve.max_seq_len` as the serving stack's
    `ModelSpec`; a key whose value the program does not implement is an
    error, not a silent departure."""
    from paddle_tpu.serving import ModelSpec
    c = config
    fixed = {"attention_in_multiplier": 1, "attention_bias": False,
             "mamba_conv_bias": True,
             "mamba_proj_bias": False, "mamba_rms_norm": True,
             "mamba_norm_before_gate": False, "mlp_bias": False,
             "projectors_bias": False, "hidden_act": "silu",
             "rope_scaling": None}
    for key, want in fixed.items():
        if c[key] != want:
            raise ValueError(f"{key}={c[key]!r}: the program implements "
                             f"{want!r}")
    if c["mamba_d_ssm"] != c["mamba_n_heads"] * c["mamba_d_head"]:
        raise ValueError("mamba_d_ssm is not mamba_n_heads x mamba_d_head")
    n = int(c["num_hidden_layers"])
    return ModelSpec(
        vocab_size=c["vocab_size"], hidden=c["hidden_size"], layers=n,
        heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
        head_size=c["head_dim"], max_seq_len=c["serve"]["max_seq_len"],
        ffn_width=c["intermediate_size"], norm="rms",
        norm_eps=c["rms_norm_eps"], positions="rotary",
        rope_theta=float(c["rope_theta"]), layer_types=["parallel"] * n,
        ffn="swiglu", tie_head=bool(c["tie_word_embeddings"]),
        ssm_inner=c["mamba_d_ssm"], ssm_heads=c["mamba_n_heads"],
        ssm_groups=c["mamba_n_groups"], ssm_state=c["mamba_d_state"],
        ssm_conv=c["mamba_d_conv"], ssm_chunk=c["mamba_chunk_size"],
        embed_mult=c["embedding_multiplier"],
        attn_out_mult=c["attention_out_multiplier"],
        key_mult=c["key_multiplier"], ssm_in_mult=c["ssm_in_multiplier"],
        ssm_out_mult=c["ssm_out_multiplier"],
        ssm_mults=tuple(c["ssm_multipliers"]),
        mlp_mults=tuple(c["mlp_multipliers"]),
        head_mult=c["lm_head_multiplier"])


def make_params(spec, precision):
    """A function of the seed that draws every weight in one jitted call,
    on the device, in the precision served."""
    import functools
    import jax
    import jax.numpy as jnp
    from paddle_tpu.serving import init_params
    dtype = {"fp32": jnp.float32, "bf16": jnp.bfloat16}[precision]
    make = jax.jit(functools.partial(init_params, spec, dtype=dtype))
    return lambda seed: make(np.int32(seed % (2 ** 31 - 1)))


def build_engine(config, seed):
    from paddle_tpu.observability.telemetry import get_telemetry
    from paddle_tpu.serving import ServeConfig, ServingEngine
    get_telemetry().enable()     # the compile watcher and dispatch counts
    spec = spec_from_config(config)
    cfg = ServeConfig.from_dict(config["serve"])
    params = make_params(spec, cfg.precision)(seed)
    t0 = time.monotonic()
    engine = ServingEngine(spec, params, cfg)
    return engine, params, spec, time.monotonic() - t0


def pick_rows(requests, config, page_size, rows):
    """The prompts of the check: the run's first `rows` requests (the
    largest decode bucket full; a closed mix's first cycle), the first cut
    to a whole number of the scan's chunks, the second cut so that its
    second decode step writes the last position of a page and its third
    crosses into the next."""
    chunk = config["mamba_chunk_size"]
    prompts = [list(r["prompt"]) for r in requests[:rows]]
    if len(prompts[0]) >= chunk:
        prompts[0] = prompts[0][:len(prompts[0]) // chunk * chunk]
    if len(prompts) > 1:
        n = len(prompts[1]) - (len(prompts[1]) + 2) % page_size
        if n > 0:
            prompts[1] = prompts[1][:n]
    return prompts


def compared(prompts, config):
    """Indices of the rows held to the reference: the first
    `check.compare` (the two cut ones among them) and the longest."""
    first = list(range(min(len(prompts), config["check"]["compare"])))
    longest = int(np.argmax([len(p) for p in prompts]))
    return first + ([longest] if longest not in first else [])


def slow_heads(params, spec):
    """(layers, H) of 0 / 1: each layer's SLOW_HEADS heads whose decay a
    position at the initial delta, softplus(dt_bias) x exp(A_log), is
    least."""
    masks = []
    for i in spec.ssm_layers:
        bias = np.asarray(params[f"h{i}.ssm.dt_bias"], np.float32)
        a = np.exp(np.asarray(params[f"h{i}.ssm.A_log"], np.float32))
        rate = np.logaddexp(bias, 0.0) * a
        mask = np.zeros(rate.shape, np.float32)
        mask[np.argsort(rate)[:SLOW_HEADS]] = 1.0
        masks.append(mask)
    return np.stack(masks)


def compare_rows(params, config, spec, prompts, driven, rows=None,
                 **variant):
    """The reference's half: each compared row's logits against the plain
    reference's full forward over that row's tokens alone, and each
    layer's state after the last step against the reference's after as
    many tokens.  Returns the worst absolute difference of the logits,
    and a layer the worst relative difference of its state (Frobenius
    norms) over the rows: of the whole state and of its slow heads.
    `variant` goes to the reference (`round_to`, `state_dtype`,
    `norm_groups`, `swap_bc`, `no_key_mult`): the lower-precision and
    wrong forms the limits have to tell apart."""
    import jax.numpy as jnp
    got, toks, states = driven
    pads = sorted(config["check"]["pads"])
    slow = slow_heads(params, spec)[:, :, None, None]     # (L, H, 1, 1)
    worst, state_worst = 0.0, np.zeros((2, len(states[0])))
    for i in (compared(prompts, config) if rows is None else rows):
        n = len(prompts[i])
        pad = next(p for p in pads if p >= n + STEPS)   # few shapes
        seq = np.zeros((pad,), np.int32)
        seq[:n + STEPS] = list(prompts[i]) + toks[i][:STEPS]
        want, state = ref.forward(params, jnp.asarray(seq), np.int32(n - 1),
                                  cfg=config, rows=STEPS + 1, **variant)
        worst = max(worst, float(np.max(np.abs(
            np.stack(got[i]) - np.asarray(want)))))
        # the last decode step took in position n + STEPS - 1
        state = np.asarray(state)                        # (L, H, P, N)
        sq, of = (states[i] - state) ** 2, state ** 2
        diff = [np.sqrt((sq * w).sum(axis=(1, 2, 3))
                        / (of * w).sum(axis=(1, 2, 3)))
                for w in (np.ones_like(slow), slow)]
        state_worst = np.maximum(state_worst, diff)
    return worst, state_worst[0].tolist(), state_worst[1].tolist()


def within(logit_err, slow_err):
    """Whether a check's readings are inside every limit."""
    return (logit_err <= LOGIT_ATOL and slow_err[0] <= STATE_RTOL
            and max(slow_err[1:], default=0.0) <= STATE_RTOL_DEEP)


def model_facts(spec, cfg):
    """What the readers of `costs_ssd.py` and `costs_lm.py` need to know of
    the model."""
    return {"layers": spec.layers, "layer_types": list(spec.layer_types),
            "heads": spec.heads, "kv_heads": spec.n_kv_heads,
            "head_dim": spec.head_dim, "hidden": spec.hidden,
            "ffn": spec.ffn_dim, "vocab_size": spec.vocab_size,
            "state_layers": len(spec.ssm_layers),
            "ssm_inner": spec.ssm_inner, "ssm_heads": spec.ssm_heads,
            "ssm_groups": spec.ssm_groups, "ssm_state": spec.ssm_state,
            "ssm_conv": spec.ssm_conv,
            "ssm_conv_channels": spec.ssm_conv_channels,
            "ssm_chunk": spec.ssm_chunk, "page_size": cfg.page_size,
            "kv_itemsize": 2, "weight_itemsize": 2}


def free_pools(engine):
    """Drop the engine's pools and slots from the device: the reference's
    float32 forwards run after the window in the room they leave."""
    for a in engine.pool.state():
        a.delete()


def run(ctx):
    import jax
    config, mix = ctx["config"], ctx["traffic"]
    on_chip = jax.devices()[0].platform == "tpu"
    engine, params, spec, first_call_s = build_engine(config, ctx["seed"])
    tap = Tap(engine, 0)
    requests, closed = serve_requests(mix, ctx["seed"], ctx["seconds"],
                                      spec.vocab_size)
    prompts = pick_rows(requests, config, engine.config.page_size,
                        engine.config.decode_buckets[-1])
    # the engine's half of the check before the window, the reference's
    # after it with the pools freed: float32 forwards at "highest" left the
    # chip's memory side slower for a minute after them (PERF.md, PR 31)
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
    decode_rows = [d + seen for d, seen in zip(tap.decode, tap.decode_seen)]
    prefills = [(a, b, len(p)) for a, b, p in tap.prefill]
    red = base.reduce_window(m, tap, requests, closed)
    health = engine.healthz()
    engine.close()
    free_pools(engine)
    t_check = time.monotonic()
    with aot_build_phase():   # the reference compiles; nothing is in flight
        logit_err, state_err, slow_err = compare_rows(
            params, config, spec, prompts, driven)
    check_s += time.monotonic() - t_check
    kv = health["kv"]
    notes = {"logit_err": logit_err, "logit_atol": LOGIT_ATOL,
             "state_err": state_err, "state_err_slow": slow_err,
             "state_rtol": STATE_RTOL, "state_rtol_deep": STATE_RTOL_DEEP,
             "check_rows": len(prompts),
             "check_compared": compared(prompts, config),
             "check_prompt_lens": [len(p) for p in prompts],
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
             "state_slots": kv.get("state"),
             "refused": {k: health.get(k) for k in
                         ("refused_kv", "refused_state")},
             "ssd_chunks_scanned": red["counters"].get("ssd_chunks_scanned"),
             "program_bytes": health["program_bytes"]}
    correct = (within(logit_err, slow_err)
               and m["compiles"] == 0
               and (not fell_back or not on_chip)
               and (not on_chip or routes.get("paged_attention", {})
                    .get("pallas", 0) >= 1)
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
