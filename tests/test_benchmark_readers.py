"""The per-layer metric readers that rest on what the program itself
puts into a profiler trace and into its counters (PR 25): `pt:` host
spans, `jax.named_scope` names of device operations, the scheduler's
time counters, the compile-stage counters.

Each reader is run as `benchmarks/run.py` runs it, on traces in the
plain form `benchmarks/trace_reduce.py` documents, built here so that
every expected number can be worked out by hand, and on the recorded cut
of a chip trace in `benchmarks/testdata/`.  Where the program leaves no
such mark (an older commit) a reader returns None and the metric is
left out of the result line."""
import importlib.util
import json
import os
import sys

import pytest

import paddle_tpu.observability as obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location(
        "bench_run_under_test", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    path = list(sys.path)
    spec.loader.exec_module(mod)     # puts benchmarks/ on sys.path
    yield mod
    sys.path[:] = path


def read(bench, name, run):
    return bench.load_module("layer_metrics", name).read(run)


DECODE, PREFILL, TRAIN = "111", "222", "333"


def serve_trace(spans=True, scopes=True):
    """20 us of one chip: a prefill run, then two decode runs.
    Times in ns.  Device busy: [1000, 5000) [6000, 9000) [9500, 10000)
    [11000, 14000) [14500, 15000); idle 9000 of the 20000 window."""
    ops = [
        # prefill run [1000, 5000)
        ["fusion:fusion.1", 1000, 1500],     # layer0/attn (score tensor)
        ["copy:copy.5", 1200, 700],          # a pool argument's copy, beside it
        ["copy:copy.2", 2500, 2000],         # kv_write
        ["fusion:fusion.3", 4500, 500],      # lm_head
        # decode run [6000, 10000)
        ["copy:copy.1", 6000, 1000],         # layer0/kv_read
        ["fusion:fusion.2", 7000, 500],      # layer0/kv_write
        ["pallas:paged_attention.3", 7500, 1500],   # layer0/attn
        ["fusion:fusion.4", 9500, 500],      # lm_head
        # decode run [11000, 15000): the same program again
        ["copy:copy.1", 11000, 1000],
        ["fusion:fusion.2", 12000, 500],
        ["pallas:paged_attention.3", 12500, 1500],
        ["fusion:fusion.4", 14500, 500],
    ]
    modules = [[f"jit_serve_prefill({PREFILL})", 1000, 4000],
               [f"jit_serve_decode({DECODE})", 6000, 4000],
               [f"jit_serve_decode({DECODE})", 11000, 4000]]
    host = [["bench:window", 0, 20000]]
    if spans:
        host += [
            ["pt:serve.evict", 0, 200],                 # idle 200
            ["pt:serve.admit", 200, 300],               # idle 300
            ["pt:serve.prefill.prep", 500, 200],        # idle 200
            ["pt:serve.prefill.launch", 700, 500],      # idle 300
            ["pt:serve.prefill.fetch", 1200, 3900],     # idle 100 (5000-5100)
            ["pt:serve.admit", 5100, 400],              # idle 400
            ["pt:serve.decode.prep", 5500, 300],        # idle 300
            ["pt:serve.decode.launch", 5800, 400],      # idle 200
            ["pt:serve.decode.fetch", 6200, 3900],      # idle 500 + 100
            ["pt:serve.book", 10100, 600],              # idle 600
            ["pt:serve.wait", 10700, 100],              # idle 100
            ["pt:serve.decode.launch", 10800, 400],     # idle 200
            ["pt:serve.decode.fetch", 11200, 3900],     # idle 500 + 100
            ["pt:serve.book", 15100, 1900],             # idle 1900
            # [17000, 20000) is under no span: 3000 unspanned
        ]
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": modules}]},
        {"name": "/host:CPU", "lines": [{"name": "python3",
                                         "events": host}]}],
        "text": {}}
    if scopes:
        trace["scopes"] = {
            PREFILL: {"fusion.1": "jit(serve_prefill)/layer0/attn/dot_general:",
                      "copy.2": "jit(serve_prefill)/kv_write/scatter:",
                      "copy.5": "v_flat:",
                      "fusion.3": "jit(serve_prefill)/lm_head/dot_general:"},
            DECODE: {"copy.1": "jit(serve_decode)/layer0/kv_read/slice:",
                     "fusion.2": "jit(serve_decode)/layer0/kv_write/scatter:",
                     "paged_attention.3":
                         "jit(serve_decode)/layer0/attn/pallas_call:",
                     "fusion.4": "jit(serve_decode)/lm_head/dot_general:"}}
    else:
        # an older program: operations have op_names, none of them a scope
        trace["scopes"] = {DECODE: {"copy.1": "jit(serve_decode)/slice:"}}
    return trace


def as_run(bench, trace, counters=None):
    sys.path.insert(0, BENCH)
    import trace_reduce
    return {"trace": trace_reduce.Reduced(trace), "trace_dir": None,
            "counters": counters or {}, "values": {}, "spans": {}}


def test_idle_is_attributed_to_the_programs_spans(bench):
    import program_trace
    run = as_run(bench, serve_trace())
    tr = run["trace"]
    assert tr.window_s == pytest.approx(20e-6)
    assert tr.window_s - tr.busy_s() == pytest.approx(9e-6)
    by = program_trace.idle_by_span(tr)
    assert {k: round(v * 1e9) for k, v in by.items()} == {
        "serve.evict": 200, "serve.admit": 700, "serve.wait": 100,
        "serve.book": 2500, "serve.prefill.prep": 200,
        "serve.prefill.launch": 300, "serve.prefill.fetch": 100,
        "serve.decode.prep": 300, "serve.decode.launch": 400,
        "serve.decode.fetch": 1200, "unspanned": 3000}
    # what no span covers, by the spans on either side: the tail only
    assert program_trace.unspanned_by_neighbours(tr) == [
        ["serve.book -> -", pytest.approx(3000e-9)]]
    sched = 100.0 * (200 + 700 + 100 + 2500) / 20000
    engine = 100.0 * (200 + 300 + 100 + 300 + 400 + 1200) / 20000
    for cell in ("steady", "backlog"):
        assert read(bench, f"idle_sched_pct.{cell}", run) == \
            pytest.approx(sched)
        assert read(bench, f"idle_engine_pct.{cell}", run) == \
            pytest.approx(engine)
    # with the unspanned rest they are the whole idle share
    assert sched + engine + 100.0 * 3000 / 20000 == pytest.approx(
        100.0 * 9000 / 20000)


def test_scoped_operations_are_summed_a_program_run(bench):
    run = as_run(bench, serve_trace())
    # decode: (kv_read 1000 + kv_write 500) ns in each of 2 runs
    assert read(bench, "decode_kv_ms_per_step", run) == \
        pytest.approx(1500 / 1e6)
    # prefill: the scatter, 2000 ns in its one run, and the copy XLA
    # names after the pool argument, 700; the score tensor 1500
    assert read(bench, "prefill_kv_ms_per_run", run) == \
        pytest.approx(2700 / 1e6)
    assert read(bench, "prefill_attn_ms_per_run", run) == \
        pytest.approx(1500 / 1e6)


def test_train_readers_go_by_kernel_name_and_scope(bench):
    ops, at = [], 0
    for name, dur, scope in [
            ("pallas:jvp_flash_fwd_.7", 3000,
             "gpt/layers/0/attn/jvp(flash_fwd):"),
            ("pallas:layer_norm_fwd.8", 400, "gpt/layers/0/norm1/"),
            ("fusion:fusion.9", 1000, "lm_head/dot_general:"),
            ("pallas:softmax_xent_fwd.10", 700, "loss/pallas_call:"),
            ("pallas:softmax_xent_bwd.11", 800,
             "loss/transpose(jvp())/pallas_call:"),
            ("fusion:fusion.12", 900, "lm_head/transpose(jvp())/dot_general:"),
            ("pallas:flash_bwd_dq.13", 5000,
             "gpt/layers/0/attn/transpose(jvp())/pallas_call:"),
            ("pallas:flash_bwd_dkv.14", 6000,
             "gpt/layers/0/attn/transpose(jvp())/pallas_call:"),
            ("fusion:fusion.15", 1200, "optimizer/mul:")]:
        ops.append([name, at, dur, scope])
        at += dur
    step = at
    events = [[n, s + k * step, d] for k in range(2) for n, s, d, _ in ops]
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": events},
        {"name": "XLA Modules", "events": [
            [f"jit_captured_step_captured_({TRAIN})", k * step, step]
            for k in range(2)]}]}],
        "text": {},
        "scopes": {TRAIN: {n.split(":")[1]:
                           "jit(captured_step(captured))/" + sc
                           for n, _, _, sc in ops}}}
    run = as_run(bench, trace)
    assert read(bench, "flash_kernel_ms_per_step", run) == \
        pytest.approx((3000 + 5000 + 6000) / 1e6)
    assert read(bench, "lm_head_loss_ms_per_step", run) == \
        pytest.approx((1000 + 700 + 800 + 900) / 1e6)
    assert read(bench, "optimizer_ms_per_step", run) == \
        pytest.approx(1200 / 1e6)


def test_scheduler_counter_ratios(bench):
    counters = {"lock_wait_s": 1.2, "submitted": 480, "queue_wait_s": 9.6,
                "admitted": 470, "evict_s": 0.01, "admit_host_s": 0.05,
                "decode_prep_s": 0.03, "book_s": 0.11,
                "occupancy_steps": 400}
    run = {"counters": counters, "trace": None}
    assert read(bench, "submit_lock_wait_ms_mean", run) == \
        pytest.approx(1e3 * 1.2 / 480)
    assert read(bench, "queue_wait_ms_mean", run) == \
        pytest.approx(1e3 * 9.6 / 470)
    assert read(bench, "sched_host_ms_per_step", run) == \
        pytest.approx(1e3 * 0.2 / 400)


def test_compile_counters_are_read_from_the_registry(bench):
    obs.reset()
    try:
        tel = obs.get_telemetry().enable(compile_watch=False)
        for stage, s in (("trace", 40.0), ("lower", 9.5),
                         ("backend_compile", 30.0), ("cache_load", 2.5)):
            tel.compile_stage(stage, s)
        for _ in range(3):
            tel.compile_cache("miss")
        tel.compile_cache("hit")
        run = {"trace": None, "counters": {}}
        assert read(bench, "setup_host_trace_s", run) == 49.5
        assert read(bench, "setup_backend_compile_s", run) == 32.5
        assert read(bench, "setup_cache_misses", run) == 3.0
    finally:
        obs.reset()


NEW_METRICS = [
    "submit_lock_wait_ms_mean", "queue_wait_ms_mean",
    "sched_host_ms_per_step", "idle_sched_pct.steady",
    "idle_sched_pct.backlog", "idle_engine_pct.steady",
    "idle_engine_pct.backlog", "decode_kv_ms_per_step",
    "prefill_kv_ms_per_run", "prefill_attn_ms_per_run",
    "flash_kernel_ms_per_step", "lm_head_loss_ms_per_step",
    "optimizer_ms_per_step", "setup_host_trace_s",
    "setup_backend_compile_s", "setup_cache_misses"]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_returns_none_where_the_program_leaves_no_mark(bench, name):
    """The parent commit: no `pt:` span, no scope, no such counter or
    registry metric, kernels called `jvp__.80`.  Traced and untraced."""
    obs.reset()
    old = as_run(bench, serve_trace(spans=False, scopes=False),
                 counters={"submitted": 480, "occupancy_steps": 400})
    assert read(bench, name, old) is None
    assert read(bench, name, {"trace": None, "counters": {}}) is None


def test_every_new_metric_has_a_reader_and_an_entry(bench):
    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entries = {m["name"]: m for m in spec["per_layer"]}
    cells = {w["name"] for w in spec["workloads"]}
    for name in NEW_METRICS:
        assert os.path.exists(
            os.path.join(BENCH, "layer_metrics", name + ".py")), name
        assert set(entries[name].get("workloads", cells)) <= cells


# -- the .xplane.pb itself --------------------------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _entry(key, message):
    return _field(1, key) + _field(2, message)


def test_op_scopes_are_read_from_the_event_metadata(bench, tmp_path):
    """xplane.proto written out by hand: the scope is the stat `tf_op`
    of an operation's XEventMetadata, beside its `program_id`."""
    import program_trace
    stat_names = {1: "hlo_category", 2: "program_id", 3: "tf_op", 4: "flops"}

    def event_metadata(key, line, display, stats):
        body = _field(1, key) + _field(2, line) + _field(4, display)
        for s in stats:
            body += _field(5, s)
        return _field(4, _entry(key, body))

    def str_stat(which, text):
        return _field(1, which) + _field(5, text)

    plane = _field(1, 7) + _field(2, "/device:TPU:0")
    plane += _field(3, _field(2, "XLA Ops"))            # a line, skipped
    for key, name in stat_names.items():
        plane += _field(5, _entry(key, _field(1, key) + _field(2, name)))
    big = 15526790685050851769                          # needs 64 bits
    plane += event_metadata(
        1, "%copy.12 = f32[8]{0} copy(f32[8]{0} %p)", "copy.12",
        [str_stat(1, "data formatting"), _field(1, 2) + _field(3, big),
         str_stat(3, "jit(serve_decode)/layer3/kv_read/slice:"),
         _field(1, 4) + _field(4, 0)])
    plane += event_metadata(
        2, "%paged_attention.29 = f32[32,1,1024] custom-call(...)",
        "paged_attention.29",
        [_field(1, 2) + _field(3, big),
         str_stat(3, "jit(serve_decode)/layer3/attn/pallas_call:")])
    plane += event_metadata(
        3, "%copy.12 = f32[4]{0} copy(f32[4]{0} %q)", "copy.12",
        [_field(1, 2) + _field(3, 42), str_stat(3, "jit(other)/x:")])
    plane += event_metadata(4, "no stats at all", "orphan", [])
    host = _field(2, "/host:CPU") + event_metadata(
        9, "pt:serve.wait", "", [str_stat(3, "not a device plane")])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, host) + _field(1, plane))
    assert program_trace.read_op_scopes(str(path)) == {
        str(big): {"copy.12": "jit(serve_decode)/layer3/kv_read/slice:",
                   "paged_attention.29":
                       "jit(serve_decode)/layer3/attn/pallas_call:"},
        "42": {"copy.12": "jit(other)/x:"}}


# -- the recorded cut of a chip trace -----------------------------------------------

def test_readers_on_the_recorded_chip_cut(bench):
    """`testdata/serve_scoped_trace.json`: one serve_prefill run and the
    serve_decode run after it, cut from this PR's traced run of the
    steady cell on a TPU v5 lite, `pt:` spans and scopes in it.  The
    expected numbers were worked out from the JSON by plain loops."""
    import program_trace
    data = os.path.join(BENCH, "testdata")
    trace = json.load(open(os.path.join(data, "serve_scoped_trace.json")))
    want = json.load(open(os.path.join(data,
                                       "serve_scoped_trace.expect.json")))
    run = as_run(bench, trace)
    tr = run["trace"]
    assert (tr.t0, tr.t1) == (want["t0_ns"], want["t1_ns"])
    names = {n for n, _, _ in program_trace.host_spans(trace)}
    assert names == {"serve.admit", "serve.prefill.prep",
                     "serve.prefill.launch", "serve.prefill.fetch",
                     "serve.decode.prep", "serve.decode.launch",
                     "serve.decode.fetch"}
    assert read(bench, "decode_kv_ms_per_step", run) == \
        pytest.approx(want["decode_kv_ms_per_step"], rel=1e-9)
    assert read(bench, "prefill_kv_ms_per_run", run) == \
        pytest.approx(want["prefill_kv_ms_per_run"], rel=1e-9)
    assert program_trace.scoped_ms_per_run(
        run, r"/layer\d+/attn/", "serve_decode") == \
        pytest.approx(want["decode_attn_scope_ms"], rel=1e-9)
    # at bucket 256 no operation of the score tensor reaches the 5 us
    # the cut keeps, and no train program ran: nothing to read
    assert read(bench, "prefill_attn_ms_per_run", run) is None
    assert read(bench, "flash_kernel_ms_per_step", run) is None
    by = program_trace.idle_by_span(tr)
    assert {k: round(v * 1e9) for k, v in by.items()} == \
        want["idle_by_span_ns"]
    holes = program_trace.unspanned_by_neighbours(tr)
    assert holes[0][0] == "serve.prefill.fetch -> serve.admit"
    assert sum(v for _, v in holes) == pytest.approx(by["unspanned"])
    assert read(bench, "idle_sched_pct.steady", run) == \
        pytest.approx(want["idle_sched_pct"], rel=1e-9)
    assert read(bench, "idle_engine_pct.steady", run) == \
        pytest.approx(want["idle_engine_pct"], rel=1e-9)
    # the kernel is found by the name the program gave it
    assert tr.ops_per_run(r"^pallas:paged_attention\.", "serve_decode")[0] \
        == tr.ops_per_run(r"^pallas:", "serve_decode")[0] > 0.005


# -- PR 27: the readers of the sparse grouped-query decoder's cell ------------

LM_METRICS = ["moe_ms_per_decode_step", "moe_decode_roofline",
              "moe_ms_per_prefill", "moe_prefill_roofline",
              "attn_global_ms_per_step", "attn_window_ms_per_step",
              "paged_attn_gqa_roofline", "expert_load_max_over_mean",
              "serve_mfu_pct.mellum2", "attn_prefill_ms_per_run"]
LM_MODEL = {"layers": 2, "heads": 4, "kv_heads": 2, "head_dim": 128,
            "hidden": 256, "vocab_size": 1024, "layer_types":
            ["sliding", "full"], "window": 8, "page_size": 4,
            "kv_itemsize": 2, "weight_itemsize": 2, "experts": 8,
            "experts_per_token": 2, "expert_width": 128}
LM_PEAK = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11}


def lm_trace():
    """One prefill run [1000, 6000) and two decode runs of one program.
    The prefill's grouped matmul has lost its scope (the chip's expansion
    of a ragged dot); a decode's experts are scoped fusions."""
    ops = [
        ["fusion:fusion.1", 1000, 500],             # layer0/moe_experts sort
        ["pallas:ragged-dot-none.3", 1500, 2000],   # no scope at all
        ["pallas:moe_gmm.7", 3500, 1000],           # layer1/moe_experts
        ["fusion:fusion.9", 4500, 1500],            # layer0/attn_window
    ]
    for t in (7000, 12000):
        ops += [["pallas:paged_attention_window.1", t, 600],
                ["pallas:paged_attention_gqa.2", t + 600, 400],
                ["fusion:fusion.5", t + 1000, 2000],    # layer0/moe_experts
                ["fusion:fusion.6", t + 3000, 100]]     # layer0/moe_route
    modules = [[f"jit_serve_prefill({PREFILL})", 1000, 5000],
               [f"jit_serve_decode({DECODE})", 7000, 4000],
               [f"jit_serve_decode({DECODE})", 12000, 4000]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": modules}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events":
                                         [["bench:window", 0, 20000]]}]}],
        "text": {},
        "scopes": {
            PREFILL: {"fusion.1": "jit(serve_prefill)/layer0/moe_experts/sort:",
                      "moe_gmm.7":
                      "jit(serve_prefill)/layer1/moe_experts/moe_gmm/pallas_call:",
                      "fusion.9": "jit(serve_prefill)/layer0/attn_window/while:"},
            DECODE: {"paged_attention_window.1":
                     "jit(serve_decode)/layer0/attn_window/pallas_call:",
                     "paged_attention_gqa.2":
                     "jit(serve_decode)/layer1/attn_global/pallas_call:",
                     "fusion.5": "jit(serve_decode)/layer0/moe_experts/dot:",
                     "fusion.6": "jit(serve_decode)/layer0/moe_route/top_k:"}}}


def lm_run(bench):
    run = as_run(bench, lm_trace(), counters={
        "occupancy_steps": 10, "moe_decode_experts_touched": 10 * 2 * 6,
        "moe_tokens_routed": 800, "moe_expert_max_tokens": 300,
        "prefill_tokens": 600, "decode_tokens": 400, "admitted": 3})
    run.update(model=LM_MODEL, peak=LM_PEAK, seconds=2.0, chips=1,
               trace_window=(0.0, 100.0),
               # (t0, t1, rows, context, seen full, seen sliding)
               decode_rows=[(1.0, 2.0, 3, 30, 30, 20),
                            (3.0, 4.0, 3, 33, 33, 21),
                            (200.0, 201.0, 9, 99, 99, 99)],   # outside
               prefill_rows=[(5.0, 6.0, 50)])
    return run


def test_lm_scoped_times_count_each_operation_once(bench):
    run = lm_run(bench)
    assert read(bench, "moe_ms_per_decode_step", run) == \
        pytest.approx(2000 / 1e6)
    assert read(bench, "attn_window_ms_per_step", run) == \
        pytest.approx(600 / 1e6)
    assert read(bench, "attn_global_ms_per_step", run) == \
        pytest.approx(400 / 1e6)
    # the scoped sort and kernel, and the ragged dot that has no scope
    assert read(bench, "moe_ms_per_prefill", run) == \
        pytest.approx((500 + 2000 + 1000) / 1e6)
    # the prompt's own attention, scoped by the layer's kind
    assert read(bench, "attn_prefill_ms_per_run", run) == \
        pytest.approx(1500 / 1e6)


def test_lm_rooflines_are_least_time_over_measured_time(bench):
    sys.path.insert(0, BENCH)
    import costs_lm
    from costs import least_seconds
    run = lm_run(bench)
    m = LM_MODEL
    # decode experts: 3 rows, 6 experts touched a step and layer (counter)
    least = 2 * least_seconds(*costs_lm.moe_decode(3, 6.0, 256, 128, 2, 2),
                              LM_PEAK)[0]
    assert read(bench, "moe_decode_roofline", run) == \
        pytest.approx(100 * least * 1e3 / (2000 / 1e6))
    least = 2 * least_seconds(*costs_lm.moe_prefill(50, 8, 256, 128, 2, 2),
                              LM_PEAK)[0]
    assert read(bench, "moe_prefill_roofline", run) == \
        pytest.approx(100 * least * 1e3 / (3500 / 1e6))
    # attention: one full layer on every position, one sliding layer on
    # what its window holds; the mean of the two steps in the window
    def att(seen):
        return least_seconds(*costs_lm.paged_decode_gqa(
            seen, 3, m["heads"], m["kv_heads"], m["head_dim"], 2),
            LM_PEAK)[0]
    least = (att(30) + att(20) + att(33) + att(21)) / 2
    assert read(bench, "paged_attn_gqa_roofline", run) == \
        pytest.approx(100 * least * 1e3 / (1000 / 1e6))


def test_lm_counter_metrics(bench):
    run = lm_run(bench)
    assert read(bench, "expert_load_max_over_mean", run) == \
        pytest.approx(8 * 300 / 800)
    sys.path.insert(0, BENCH)
    import costs_lm
    # the head once a prompt (3) and once a decoded row (400), not once
    # a prompt position
    assert read(bench, "serve_mfu_pct.mellum2", run) == pytest.approx(
        100 * 2 * (costs_lm.layer_params(LM_MODEL) * 1000
                   + costs_lm.head_params(LM_MODEL) * 403) / (2.0 * 1e12))


@pytest.mark.parametrize("name", LM_METRICS)
def test_lm_reader_returns_none_on_a_program_without_the_marks(bench, name):
    """The GPT serve program's trace (no experts, no window scopes, none
    of the counters), traced and untraced: nothing to read, no value."""
    old = as_run(bench, serve_trace(), counters={"occupancy_steps": 400})
    old.update(model={"heads": 16, "head_dim": 64, "layers": 24,
                      "kv_itemsize": 4}, peak=LM_PEAK, seconds=40.0,
               trace_window=(0.0, 100.0), decode_rows=[(1.0, 2.0, 3, 30)])
    assert read(bench, name, old) is None
    assert read(bench, name, {"trace": None, "counters": {}}) is None


def test_lm_metrics_have_entries_for_the_new_cell_only(bench):
    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entries = {m["name"]: m for m in spec["per_layer"]}
    cell = "mellum2-serve-mixedctx-backlog"
    assert cell in {w["name"] for w in spec["workloads"]}
    # the readers that are blind to the model also list PR 31's cell, put
    # at the end of their lists; the experts' and Mellum's own do not
    shared = {"attn_global_ms_per_step", "attn_window_ms_per_step",
              "attn_prefill_ms_per_run", "kv_window_pages_returned"}
    # and the experts' readers PR 34's cell (128 experts of 768), last
    # (not the prefill's two: a 4 s trace of that cell seldom holds one)
    experts = {"moe_ms_per_decode_step", "moe_decode_roofline",
               "expert_load_max_over_mean"}
    for name in LM_METRICS + ["kv_window_pages_returned"]:
        assert entries[name]["workloads"] == [cell] + (
            ["phi4flash-serve-reasoning-backlog"] if name in shared else []
        ) + (["keyevl2-serve-longctx-reasoning-backlog"]
             if name in experts else [])
        assert entries[name]["moves"] == "serve_tokens_per_s"
        assert os.path.exists(os.path.join(
            BENCH, "layer_metrics", name + ".py")) or os.path.exists(
            os.path.join(BENCH, "layer_metrics", name + ".json"))
    tokens = next(m for m in spec["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")
    assert cell in tokens["workloads"]
    # the prefill's KV write is scoped `kv_write` in the new block too:
    # the accepted reader reads it unedited
    assert entries["prefill_kv_ms_per_run"]["workloads"][-1] == cell


HYBRID_METRICS = ["ssm_ms_per_decode_step", "ssm_ms_per_prefill",
                  "gmu_ms_per_decode_step", "attn_cross_ms_per_step",
                  "paged_attn_diff_roofline", "ssm_decode_roofline",
                  "ssm_scan_roofline", "state_slots_held_max",
                  "serve_mfu_pct.phi4flash"]


def test_hybrid_metrics_have_entries_for_their_cell_and_read_nothing_elsewhere(
        bench):
    """PR 31's nine metrics: entries after everything that was there
    (PR 32's one after them), a
    reader each, and nothing read (no exception) from a run of a program
    that has no such scope, counter or model fact: the parent's, or
    another configuration's."""
    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    cell = "phi4flash-serve-reasoning-backlog"
    assert spec["workloads"][-2]["name"] == cell      # PR 34's after it
    assert spec["workloads"][-2]["chips"] == 1
    assert spec["configs"][-2]["name"] == spec["workloads"][-2]["config"]
    assert spec["configs"][-2]["reduced"] == []
    # then PR 32's one, PR 34's 6, PR 35's 2, PR 36's 10
    hybrid = spec["per_layer"][-28:-19]
    assert [m["name"] for m in hybrid] == HYBRID_METRICS
    assert spec["per_layer"][-19]["name"] == "paged_walk_live_pct.grouped"
    for m in hybrid:
        assert m["workloads"] == [cell]
        assert m["moves"] == "serve_tokens_per_s"
    empty = {"trace": None, "values": {}, "counters": {}, "spans": {},
             "peak": None, "model": {}}
    other = dict(empty, counters={"prefill_tokens": 9, "admitted": 1,
                                  "decode_tokens": 3},
                 model=LM_MODEL, seconds=4.0, chips=1,
                 peak={"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9})
    for name in HYBRID_METRICS:
        assert bench.read_layer_metric(name, dict(empty)) is None, name
        assert bench.read_layer_metric(name, dict(other)) is None, name


def test_hybrid_mfu_reads_the_counters(bench):
    """2 x parameters x positions over the window and the peak, at the
    published widths: a window of 1,000 prompt positions in 2 prompts and
    60,000 decoded rows."""
    import importlib
    costs = importlib.import_module("costs_hybrid")
    ref = importlib.import_module("reference.phi4flash_serve")
    m = {"layers": 32, "heads": 40, "kv_heads": 20, "head_dim": 64,
         "hidden": 2560, "ffn": 10240, "vocab_size": 200064,
         "ssm_inner": 5120, "ssm_state": 16, "ssm_conv": 4,
         "ssm_dt_rank": 160, "weight_itemsize": 2, "kv_itemsize": 2,
         "layer_types": [{"mamba": "ssm"}.get(k, k)
                         for k in ref.layer_kinds(32)]}
    run = {"trace": None, "model": m, "seconds": 40.0, "chips": 1,
           "peak": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9},
           "counters": {"prefill_tokens": 1000, "admitted": 2,
                        "decode_tokens": 60000}}
    every, once, row = costs.position_params(m)
    want = 100.0 * 2 * (every * 1000 + once * 2 + row * 60000) / (
        40.0 * 197e12)
    assert read(bench, "serve_mfu_pct.phi4flash", run) == \
        pytest.approx(want)
    assert 5.0 < want < 7.0


# -- the equal-heads kernel's walk (PR 28) -----------------------------------

WALK_METRICS = {"paged_walk_live_pct.steady":
                ("tpot_p50_ms", ["gpt345m-serve-complete-steady"]),
                "paged_walk_live_pct.backlog":
                ("serve_tokens_per_s", ["gpt345m-serve-longprompt-backlog"]),
                # the grouped kernels' two lists (PR 32)
                "paged_walk_live_pct.grouped":
                ("serve_tokens_per_s", ["mellum2-serve-mixedctx-backlog",
                                        "phi4flash-serve-reasoning-backlog"])}


@pytest.mark.parametrize("name", WALK_METRICS)
def test_walk_share_is_chunks_walked_over_grid_steps(bench, name):
    """94 decode steps of the 32-row bucket (160 grid steps each) whose
    rows' contexts filled 13,160 chunks; and a program that keeps no such
    counter, or leaves it zero (the parent of PR 28; for a grouped model
    the parent of PR 32): nothing to read."""
    run = {"trace": None, "counters": {"paged_chunks_walked": 13160,
                                       "paged_grid_steps": 94 * 160}}
    assert read(bench, name, run) == pytest.approx(87.5)
    assert read(bench, name, {"trace": None, "counters": {
        "occupancy_steps": 400}}) is None
    assert read(bench, name, {"trace": None, "counters": {
        "paged_chunks_walked": 0, "paged_grid_steps": 0}}) is None
    assert read(bench, name, {"trace": None}) is None
    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = next(m for m in spec["per_layer"] if m["name"] == name)
    assert (entry["moves"], entry["workloads"]) == WALK_METRICS[name]
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "kernels (ops/paged_attention.py)"


def test_flash_chunks_visited_is_read_from_the_registry(bench):
    """24 causal layers at the train cell's shape book 3 of 4 tiles
    each; a program without the counter (the parent): nothing to read."""
    from paddle_tpu.ops.fused_kernels import record_flash_chunks
    name = "flash_chunks_visited_pct"
    run = {"trace": None, "counters": {"steps": 153}}
    obs.reset()
    try:
        assert read(bench, name, run) is None
        obs.get_telemetry().enable(compile_watch=False)
        assert read(bench, name, run) is None
        for _ in range(24):
            record_flash_chunks(3, 4)
        assert read(bench, name, run) == pytest.approx(75.0)
    finally:
        obs.reset()
    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    assert next(m for m in spec["per_layer"] if m["name"] == name) == {
        "name": name, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "kernels (ops/pallas_ops.py)",
        "moves": "train_tokens_per_s", "workloads": ["gpt345m-train-s1024"]}


AHEAD_METRICS = {
    "decode_ahead_pct.backlog": ("serve_tokens_per_s", [
        "gpt345m-serve-longprompt-backlog", "mellum2-serve-mixedctx-backlog",
        "phi4flash-serve-reasoning-backlog",
        "keyevl2-serve-longctx-reasoning-backlog"]),
    "decode_ahead_pct.steady": ("tpot_p50_ms",
                                ["gpt345m-serve-complete-steady"]),
}


@pytest.mark.parametrize("name", sorted(AHEAD_METRICS))
def test_decode_ahead_pct_with_the_counter(bench, name):
    """2,780 of 2,800 steps launched before the one before was read."""
    run = {"trace": None, "counters": {"decode_steps_ahead": 2780,
                                       "occupancy_steps": 2800}}
    assert read(bench, name, run) == pytest.approx(100 * 2780 / 2800)
    run["counters"]["decode_steps_ahead"] = 0       # every step after a read
    assert read(bench, name, run) == 0.0
    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    moves, cells = AHEAD_METRICS[name]
    assert next(m for m in spec["per_layer"] if m["name"] == name) == {
        "name": name, "unit": "%", "better": "higher",
        "source": "program_counter",
        "layer": "scheduler (serving/scheduler.py)", "moves": moves,
        "workloads": cells}


@pytest.mark.parametrize("name", sorted(AHEAD_METRICS))
def test_decode_ahead_pct_without_the_counter(bench, name):
    """A program that has no such counter (the parent), or a window
    without a decode step: nothing to read, and nothing raised."""
    assert read(bench, name, {"trace": None, "counters": {
        "occupancy_steps": 2800}}) is None
    assert read(bench, name, {"trace": None, "counters": {
        "decode_steps_ahead": 0, "occupancy_steps": 0}}) is None
    assert read(bench, name, {"trace": None}) is None


# -- the serving programs' runs and the step log's counters (PR 36) -----------

STEP_COUNTERS = {
    # a window of 1,000 booked steps, 900 of them timed (100 were read
    # behind a prefill), 18 of those slow; 40 prefills; 50 retirements
    "occupancy_steps": 1000, "steps_timed": 900, "step_period_s": 1.98,
    "steps_slow": 18, "prefill_runs": 40, "prefill_row_stall_s": 3.5,
    "decode_tokens": 7000, "token_gap_max_s": 0.6, "tpot_requests": 50}
STEP_METRICS = {
    # name: (unit, source, layer, moves, cells, value on STEP_COUNTERS
    #        and serve_trace())
    "decode_period_ms_mean": ("ms", "program_counter", "model", 2.2),
    "slow_steps_pct": ("%", "program_counter", "sched", 2.0),
    "decode_device_ms_p50": ("ms", "device_trace", "model", 0.004),
    "token_gap_max_ms_mean": ("ms", "program_counter", "sched", 12.0),
}
LAYERS = {"model": "model step (serving/engine.py, serving/model.py)",
          "sched": "scheduler (serving/scheduler.py)"}
STEADY = ["gpt345m-serve-complete-steady"]
BACKLOG = ["gpt345m-serve-longprompt-backlog",
           "mellum2-serve-mixedctx-backlog",
           "phi4flash-serve-reasoning-backlog",
           "keyevl2-serve-longctx-reasoning-backlog"]
SPLIT = [(f"{name}.{cell}", name, cell) for name in sorted(STEP_METRICS)
         for cell in ("steady", "backlog")]


def windowed(trace, t0, t1):
    """`trace` with the benchmark's window span moved to [t0, t1)."""
    for line in trace["planes"][1]["lines"]:
        line["events"] = [e for e in line["events"]
                          if e[0] != "bench:window"]
        line["events"].append(["bench:window", t0, t1 - t0])
    return trace


def only(trace, kind):
    """`trace` without the runs of the other kind of program."""
    line = trace["planes"][0]["lines"][1]
    line["events"] = [e for e in line["events"] if kind in e[0]]
    return trace


@pytest.mark.parametrize("full, name, cell", SPLIT)
def test_step_metric_on_a_hand_made_run(bench, full, name, cell):
    unit, source, layer, want = STEP_METRICS[name]
    run = as_run(bench, serve_trace(), dict(STEP_COUNTERS))
    assert read(bench, full, run) == pytest.approx(want)
    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    assert next(m for m in spec["per_layer"] if m["name"] == full) == {
        "name": full, "unit": unit, "better": "lower", "source": source,
        "layer": LAYERS[layer],
        "moves": "tpot_p50_ms" if cell == "steady" else "serve_tokens_per_s",
        # a worst gap is summed at retirement: not in the cell whose
        # window may retire nobody, and whose answers outlast the ramp
        "workloads": STEADY if cell == "steady" else
        BACKLOG[:3] if name == "token_gap_max_ms_mean" else BACKLOG}


def test_prefill_share_and_stall_on_a_hand_made_run(bench):
    run = as_run(bench, serve_trace(), dict(STEP_COUNTERS))
    # one prefill run of 4000 ns beside two decode runs of 4000
    assert read(bench, "prefill_device_share_pct", run) == \
        pytest.approx(100 / 3)
    assert read(bench, "prefill_stall_ms_per_token", run) == \
        pytest.approx(0.5)
    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    by = {m["name"]: m for m in spec["per_layer"]}
    assert by["prefill_device_share_pct"] == {
        "name": "prefill_device_share_pct", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": LAYERS["model"],
        "moves": "serve_tokens_per_s", "workloads": BACKLOG[:3]}
    assert by["prefill_stall_ms_per_token"] == {
        "name": "prefill_stall_ms_per_token", "unit": "ms",
        "better": "lower", "source": "program_counter",
        "layer": LAYERS["sched"], "moves": "serve_tokens_per_s",
        "workloads": BACKLOG}
    # every new metric has its reader, and the file only gained them
    assert [m["name"] for m in spec["per_layer"][-10:]] == [
        "decode_period_ms_mean.steady", "decode_period_ms_mean.backlog",
        "slow_steps_pct.steady", "slow_steps_pct.backlog",
        "decode_device_ms_p50.steady", "decode_device_ms_p50.backlog",
        "prefill_device_share_pct", "prefill_stall_ms_per_token",
        "token_gap_max_ms_mean.steady", "token_gap_max_ms_mean.backlog"]


@pytest.mark.parametrize("case, t0, t1, kind, share, p50", [
    # the whole 20 us
    ("whole", 0, 20000, None, 100 / 3, 0.004),
    # prompts only, tokens only
    ("prefill_only", 0, 20000, "prefill", 100.0, None),
    ("decode_only", 0, 20000, "decode", 0.0, 0.004),
    # [2000, 12000) cuts the prefill run to 3000 ns and the second decode
    # run to 1000; the median is over the one decode run that is whole
    ("cut_edges", 2000, 12000, None, 37.5, 0.004),
    # [7000, 9000) lies inside the first decode run: 2000 ns of it
    ("inside_a_run", 7000, 9000, None, 0.0, 0.002),
])
def test_serve_runs_are_cut_to_the_traced_window(bench, case, t0, t1, kind,
                                                 share, p50):
    trace = windowed(serve_trace(), t0, t1)
    if kind:
        trace = only(trace, kind)
    run = as_run(bench, trace, dict(STEP_COUNTERS))
    assert read(bench, "prefill_device_share_pct", run) == \
        pytest.approx(share)
    for cell in ("steady", "backlog"):
        got = read(bench, f"decode_device_ms_p50.{cell}", run)
        assert got == (None if p50 is None else pytest.approx(p50)), case
    import step_trace
    runs = step_trace.serve_runs(run["trace"])
    assert all(t0 <= s < e <= t1 for _, s, e, _ in runs)
    assert [whole for *_, whole in runs] == {
        "cut_edges": [False, True, False], "inside_a_run": [False]}.get(
            case, [True] * len(runs))


NEW_READERS = [full for full, _, _ in SPLIT] + [
    "prefill_device_share_pct", "prefill_stall_ms_per_token"]


@pytest.mark.parametrize("name", NEW_READERS)
def test_step_metric_has_nothing_to_read_without_the_marks(bench, name):
    """The parent's counters (no step log), a train trace, a run that
    was not traced, a window in which nothing was counted: None, and
    nothing raised."""
    parent = {k: v for k, v in STEP_COUNTERS.items()
              if k in ("occupancy_steps", "decode_tokens", "tpot_requests")}
    assert read(bench, name, as_run(bench, serve_trace(), parent)) is None
    assert read(bench, name, as_run(bench, serve_trace())) is None
    assert read(bench, name, {"trace": None}) is None
    # a window in which no step was booked: the counters give nothing,
    # the trace what it holds
    empty = dict.fromkeys(STEP_COUNTERS, 0)
    assert read(bench, name, as_run(bench, serve_trace(), empty)) == (
        pytest.approx(100 / 3) if name == "prefill_device_share_pct"
        else pytest.approx(0.004) if "device" in name else None)
    trace = only(serve_trace(), "no such program")
    trace["planes"][0]["lines"][1]["events"] = [
        ["jit_train_step(333)", 1000, 14000]]
    train = as_run(bench, trace, dict(STEP_COUNTERS))
    if "device" in name:
        assert read(bench, name, train) is None
        assert read(bench, name, {"trace": None,
                                  "counters": dict(STEP_COUNTERS)}) is None


def _feed_periods(periods, untimed=()):
    """The scheduler's own booking of decode steps with the given
    periods (seconds): `_read_locked` on stub steps under a stub clock.
    Steps at the indices `untimed` are read as behind a prefill."""
    import types
    import numpy as np
    from paddle_tpu.serving import ModelSpec
    from paddle_tpu.serving import scheduler as sched_mod
    engine = types.SimpleNamespace(
        spec=ModelSpec(vocab_size=64, hidden=32, layers=2, heads=2,
                       max_seq_len=64),
        pool=types.SimpleNamespace(state_slots=None))
    sched = sched_mod.ContinuousScheduler(engine)
    clock = [100.0]
    real = sched_mod.time
    sched_mod.time = types.SimpleNamespace(monotonic=lambda: clock[0])
    try:
        for k, dt in enumerate(periods):
            step = types.SimpleNamespace(
                seq=k + 1, bucket=8, take_aux=dict,
                read=lambda: np.zeros((0,), np.int32))
            sched._flight = sched_mod._Flight(step, [], clock[0], False)
            clock[0] += dt
            with sched._lock:
                sched._read_locked(timed=k not in untimed)
    finally:
        sched_mod.time = real
    return sched


def test_steps_slow_against_a_hand_fed_sequence_of_periods(bench):
    # ms; the moving average of every period (0.2 new + 0.8 old) as it
    # stood before each step, one compare:
    #   10   -> none yet                      not slow
    #   10   -> 10                            not
    #   16   -> 10       16 > 15              slow
    #   14   -> 11.2     14 < 16.8            not
    #   40   -> 11.76    (behind a prefill: averaged, not timed or judged)
    #   17.7 -> 17.408   17.7 < 26.1          not: the prefill hides it
    #   12   -> 17.4664                       not
    #   30   -> 16.37312 30 > 24.56           slow
    ms = [10, 10, 16, 14, 40, 17.7, 12, 30]
    sched = _feed_periods([m / 1e3 for m in ms], untimed={4})
    s = sched.stats
    assert (s["occupancy_steps"], s["steps_timed"], s["steps_slow"]) == \
        (8, 7, 2)
    assert s["step_period_s"] == pytest.approx((sum(ms) - 40) / 1e3)
    assert not hasattr(sched, "_period_ewma")   # one average, the ETA's
    # the shed ETA's average and the log take all eight as they are
    ewma = None
    for m in ms:
        ewma = m / 1e3 if ewma is None else 0.2 * m / 1e3 + 0.8 * ewma
    assert sched._step_ewma == pytest.approx(ewma)
    assert [r[7] for r in sched.step_log()] == pytest.approx(
        [m / 1e3 for m in ms])
    run = {"trace": None, "counters": dict(s)}
    for cell in ("steady", "backlog"):
        assert read(bench, f"slow_steps_pct.{cell}", run) == \
            pytest.approx(100 * 2 / 7)
        assert read(bench, f"decode_period_ms_mean.{cell}", run) == \
            pytest.approx((sum(ms) - 40) / 7)


def step_spans():
    """`serve_trace()`'s three runs with launches as a scheduler that
    launches ahead would leave them: the second decode step launched
    while the first runs, and fetched after it."""
    trace = serve_trace(spans=False)
    trace["planes"][1]["lines"][0]["events"] += [
        ["pt:serve.prefill.launch", 700, 500],
        ["pt:serve.prefill.fetch", 1200, 3900],
        ["pt:serve.decode.launch", 5800, 400],
        ["pt:serve.decode.launch", 6300, 400],      # ahead of the fetch
        ["pt:serve.decode.fetch", 6800, 3300],
        ["pt:serve.decode.fetch", 10200, 4900]]
    return trace


def test_join_steps_pairs_launches_and_runs_in_order(bench, tmp_path):
    import step_trace
    path = str(tmp_path / "cut.json")
    json.dump(step_spans(), open(path, "w"))
    spans, runs = step_trace.load(path)
    assert [r[0] for r in runs] == ["prefill", "decode", "decode"]
    rows = step_trace.join_steps(spans, runs)
    assert [(r["kind"], r["launch_ns"], r["device_ns"], r["idle_before_ns"],
             r["fetch_end_ns"]) for r in rows] == [
        ("prefill", (700, 1200), (1000, 5000), None, 5100),
        ("decode", (5800, 6200), (6000, 10000), 1000, 10100),
        ("decode", (6300, 6700), (11000, 15000), 1000, 15100)]
    text = step_trace.table(rows)
    assert len(text) == 4 and "prefill" in text[1] and "decode" in text[3]
    # with the arguments a profile keeps, a fetch is found by its number
    numbered = [(k, w, s, e, {"launch": 7 + i // 2} if k == "decode"
                 else {"launch": 3, "request_id": 42})
                for i, (k, w, s, e, _) in enumerate(spans)]
    assert [sp[4].get("launch") for sp in numbered] == [3, 3, 8, 8, 9, 9]
    numbered[3], numbered[4] = (     # launch 8, launch 9, fetch 8, fetch 9
        numbered[3][:4] + ({"launch": 9, "bucket": 8},),
        numbered[4][:4] + ({"launch": 8},))
    numbered[2] = numbered[2][:4] + ({"launch": 8, "bucket": 8},)
    rows = step_trace.join_steps(numbered, runs)
    assert [(r["launch"], r["bucket"], r["request_id"], r["fetch_end_ns"])
            for r in rows] == [(3, None, 42, 5100), (8, 8, None, 10100),
                               (9, 8, None, 15100)]


def test_join_steps_refuses_what_does_not_fit(bench, monkeypatch):
    import step_trace
    # the hand-made trace is 20 us long: its clocks agree to 100 ns
    monkeypatch.setattr(step_trace, "SKEW_NS", 100)
    spans, runs = [], []
    trace = step_spans()
    for name, s, d in trace["planes"][1]["lines"][0]["events"]:
        m = step_trace._SPAN.match(name)
        if m:
            spans.append((m.group(1), m.group(2), s, s + d, {}))
    spans.sort(key=lambda sp: sp[2])
    runs = [("prefill", 1000, 5000), ("decode", 6000, 10000),
            ("decode", 11000, 15000)]
    assert len(step_trace.join_steps(spans, runs)) == 3
    # a run launched before the trace began is dropped, not joined
    assert len(step_trace.join_steps(
        spans, [("decode", 100, 600)] + runs)) == 3
    # a launch whose run the trace no longer holds gets no row
    assert len(step_trace.join_steps(spans, runs[:2])) == 2
    with pytest.raises(ValueError, match="kinds|is a"):
        step_trace.join_steps(spans, [runs[1], runs[0], runs[2]])
    late = [sp if sp[:2] != ("decode", "launch") or sp[2] != 6300
            else ("decode", "launch", 11500, 11900, {}) for sp in spans]
    with pytest.raises(ValueError, match="before its launch"):
        step_trace.join_steps(sorted(late, key=lambda sp: sp[2]), runs)
    with pytest.raises(ValueError, match="no pt:serve"):
        step_trace.join_steps([], runs)
    # inside the clocks' skew a run may seem to start before its launch
    early = [("prefill", 650, 5000)] + runs[1:]
    assert step_trace.join_steps(spans, early)[0]["device_ns"] == (650, 5000)
    # further before the first launch than that, it was launched before
    # the trace began
    rows = step_trace.join_steps(spans[1:], [("decode", 5600, 10000),
                                              runs[2]])
    assert [r["device_ns"] for r in rows] == [(11000, 15000)]
    assert rows[0]["idle_before_ns"] == 1000


@pytest.fixture(scope="module")
def ahead_cut():
    """The recorded cut of a traced run since the scheduler launches
    ahead (GPT backlog cell, my chip run, PR 36), and what plain loops
    over it gave."""
    data = os.path.join(BENCH, "testdata")
    return (json.load(open(os.path.join(data, "serve_ahead_trace.json"))),
            json.load(open(os.path.join(data,
                                        "serve_ahead_trace.expect.json"))))


@pytest.mark.parametrize("name", NEW_READERS)
def test_step_metric_on_the_recorded_cut(bench, ahead_cut, name):
    trace, want = ahead_cut
    run = as_run(bench, trace, dict(want["counters"]))
    assert (run["trace"].t0, run["trace"].t1) == (want["t0_ns"],
                                                  want["t1_ns"])
    assert read(bench, name, run) == pytest.approx(
        want[name.split(".")[0]], rel=1e-9)
    # the parent's counters beside the same trace: nothing to read
    parent = {k: v for k, v in want["counters"].items()
              if k in ("occupancy_steps", "decode_tokens", "tpot_requests",
                       "decode_steps_ahead")}
    assert read(bench, name, as_run(bench, trace, parent)) is None


def test_the_recorded_cut_joins_launch_for_launch(bench, ahead_cut):
    import step_trace
    _, want = ahead_cut
    spans, runs = step_trace.load(os.path.join(
        BENCH, "testdata", "serve_ahead_trace.json"))
    assert len(runs) == want["prefill_device_runs"] + want["decode_device_runs"]
    rows = step_trace.join_steps(spans, runs)
    assert len(rows) == want["launch_spans"] == len(runs)
    assert sum(r["kind"] == "prefill" for r in rows) == want["prefill_device_runs"]
    assert sum(r["device_ns"][1] - r["device_ns"][0] for r in rows
               if r["kind"] == "decode") == want["decode_device_ns"]
    # steps launched ahead (their launch span opens while the step
    # before still runs) and steps launched after a read, both in it
    ahead = [b["launch_ns"][0] < a["device_ns"][1]
             for a, b in zip(rows, rows[1:])
             if a["kind"] == b["kind"] == "decode"]
    assert any(ahead) and not all(ahead)
    # a read comes after its run; every row but the last few has one
    assert all(r["fetch_end_ns"] > r["device_ns"][1] for r in rows
               if r["fetch_end_ns"] is not None)
    assert sum(r["fetch_end_ns"] is None for r in rows) <= 2
    assert len(step_trace.table(rows)) == len(rows) + 1
