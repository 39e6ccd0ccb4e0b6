"""The program's own marks inside a profiler trace, for the per-layer
metric readers that rest on them (PR 25).  `trace_reduce.py` reads what
the profiler and the benchmark put there; this file reads what the
program puts there, and nothing here is needed where the program puts
nothing (an older commit: every function then returns None).

1. **Host spans `pt:<name>`** (`paddle_tpu/observability/trace.py`,
   `span`): `jax.profiler.TraceAnnotation`s on the clock of the device
   trace.  The serving scheduler's are leaves that partition its
   thread's time (`serve.wait`, `.evict`, `.admit`, `.prefill.prep` /
   `.launch` / `.fetch`, `.decode.prep` / `.launch` / `.fetch`,
   `.book`), so `trace_reduce.idle_gaps` attributes the device's idle
   time to them as it stands.  `host_spans(trace)`, `idle_by_span`,
   `idle_pct`.

2. **Scopes of device operations** (`jax.named_scope` in the program).
   The profiler names an operation's event by its HLO line without the
   metadata; the scope (HLO `op_name`, e.g.
   `jit(serve_decode)/layer3/kv_read/slice`) is the stat `tf_op` of the
   event's *metadata* in the `.xplane.pb`, which `jax.profiler.
   ProfileData` does not hand out (my chip run, PR 25).  `op_scopes`
   therefore reads the file's protobuf wire format itself: planes,
   their event metadata, three stats; no dependency.  The plain form of
   `trace_reduce` gains an optional key for it,
   `"scopes": {program fingerprint: {instruction: op_name}}`, where the
   fingerprint is the number in a program's name on the `XLA Modules`
   line (`jit_serve_decode(15526790685050851769)`); a recorded cut
   carries it, and a trace read from a profile gets it on first use.
   `scoped_ms_per_run`.

3. **Counters**: ratios of the serving scheduler's time sums over the
   window (`counter_ratio_ms`), and sums of the program's registry
   metrics, read in-process as `taps.py` does (`registry_sum`: the
   compile-stage seconds and cache misses).

`python3 benchmarks/program_trace.py <trace dir | .xplane.pb>` prints
the idle attribution and the scopes with most device time;
`... <out.json> <start ms> <length ms> [min op us]` writes a recorded
cut like `trace_reduce.py`'s with the `pt:` spans and the scopes in it.
"""
from __future__ import annotations

import re

import trace_reduce

PREFIX = "pt:"
SCHEDULER_SPANS = ("serve.wait", "serve.evict", "serve.admit", "serve.book")
ENGINE_SPANS = ("serve.prefill.prep", "serve.prefill.launch",
                "serve.prefill.fetch", "serve.decode.prep",
                "serve.decode.launch", "serve.decode.fetch")
FINGERPRINT = re.compile(r"\((\d+)\)$")


# -- host spans ---------------------------------------------------------------

def host_spans(trace, prefix=PREFIX):
    """[(name without prefix, start_ns, end_ns)] of the program's spans."""
    return trace_reduce.host_spans(trace, prefix)


def idle_by_span(reduced):
    """{span name: seconds} of the device's idle time in the traced
    window under each of the program's spans, `unspanned` for the rest;
    None where the trace holds no such span."""
    if "idle_by_span" not in reduced.trace:     # two readers share it
        spans = host_spans(reduced.trace)
        ops = trace_reduce.clip(reduced.ops(0), reduced.t0, reduced.t1)
        reduced.trace["idle_by_span"] = dict(trace_reduce.idle_gaps(
            ops, reduced.t0, reduced.t1, spans, n=len(spans) + 1)) \
            if spans else None
    return reduced.trace["idle_by_span"]


def unspanned_by_neighbours(reduced, n=12):
    """The idle time under no span of the program, by the spans on either
    side of it: [["<span before> -> <span after>", seconds]], most first.
    Says what the spans leave out (by hand: the command below)."""
    spans = host_spans(reduced.trace)
    ops = trace_reduce.clip(reduced.ops(0), reduced.t0, reduced.t1)
    at, gaps = reduced.t0, []
    for s, e in trace_reduce.merge_intervals(ops) + [(reduced.t1, reduced.t1)]:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    by, i = {}, 0
    for g0, g1 in gaps:
        while i < len(spans) and spans[i][2] <= g0:
            i += 1
        cur, j = g0, i
        while cur < g1:
            # the next span that covers or follows `cur`
            while j < len(spans) and spans[j][2] <= cur:
                j += 1
            nxt = spans[j] if j < len(spans) else None
            hole_end = min(g1, nxt[1]) if nxt else g1
            if hole_end > cur:
                before = spans[j - 1][0] if j else "-"
                key = f"{before} -> {nxt[0] if nxt else '-'}"
                by[key] = by.get(key, 0) + hole_end - cur
            cur = max(hole_end, min(g1, nxt[2])) if nxt else g1
    best = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return [[k, ns / 1e9] for k, ns in best]


def idle_pct(run, names):
    """Idle under the spans `names`, in % of the traced window."""
    tr = run.get("trace")
    if tr is None or not tr.window_s:
        return None
    by = idle_by_span(tr)
    if by is None:
        return None
    return 100.0 * sum(by.get(n, 0.0) for n in names) / tr.window_s


# -- the .xplane.pb's wire format -----------------------------------------------

def _varint(buf, i):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf, start=0, end=None):
    """(field number, wire type, value) of one protobuf message in
    buf[start:end]; a length-delimited value is its (start, end)."""
    i = start
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        num, kind = key >> 3, key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value = (i, i + n)
            i += n
        elif kind == 1:
            value, i = buf[i:i + 8], i + 8
        elif kind == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {kind} at byte {i}")
        yield num, kind, value


def _map_entry(buf, span):
    key = value = None
    for num, _, v in fields(buf, *span):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def read_op_scopes(path, plane_name="/device:TPU:0"):
    """{program fingerprint: {instruction: op_name}} from the event
    metadata of one device plane.  xplane.proto: XSpace.planes = 1;
    XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5 (maps);
    XEventMetadata.display_name = 4, .stats = 5; XStat.metadata_id = 1,
    .uint64_value = 3, .int64_value = 4, .str_value = 5, .ref_value = 7;
    XStatMetadata.name = 2."""
    buf = open(path, "rb").read()

    def text(span):
        return buf[span[0]:span[1]].decode("utf-8", "replace")

    out = {}
    for num, _, plane in fields(buf):
        if num != 1:
            continue
        name, events, stat_names = None, [], {}
        for num2, _, v in fields(buf, *plane):
            if num2 == 2:
                name = text(v)
            elif num2 == 4:
                events.append(v)
            elif num2 == 5:
                key, meta = _map_entry(buf, v)
                for num3, _, v3 in fields(buf, *meta):
                    if num3 == 2:
                        stat_names[key] = text(v3)
        if name != plane_name:
            continue
        for entry in events:
            _, meta = _map_entry(buf, entry)
            inst = scope = program = None
            for num3, _, v3 in fields(buf, *meta):
                if num3 == 4:
                    inst = text(v3)
                elif num3 == 5:
                    stat = {n: v for n, _, v in fields(buf, *v3)}
                    which = stat_names.get(stat.get(1))
                    if which == "tf_op":
                        scope = (text(stat[5]) if 5 in stat
                                 else stat_names.get(stat.get(7)))
                    elif which == "program_id":
                        program = stat.get(3, stat.get(4))
            if inst and scope and program is not None:
                out.setdefault(str(program), {})[inst] = scope
    return out


def op_scopes(run):
    """The traced run's scopes (see the module docstring), read once."""
    tr = run.get("trace")
    if tr is None:
        return None
    if "scopes" not in tr.trace:
        path = trace_reduce.find_xplane(run.get("trace_dir") or "")
        tr.trace["scopes"] = read_op_scopes(path) if path else {}
    return tr.trace["scopes"]


def instruction(short):
    """`copy:copy.12` -> `copy.12`."""
    return short.split(":", 1)[-1]


def scoped_events(reduced, scopes, program_pattern, chip=0):
    """[(scope, [name, start, dur])] of the operations inside whole runs
    of matching programs in the window, and the number of runs."""
    rx = re.compile(program_pattern)
    modules = []
    for idx, plane in reduced.chips:
        if idx == chip:
            modules = trace_reduce.line_events(plane,
                                               trace_reduce.MODULES_LINE)
    runs_of = {}        # one program's instruction names are its own
    for name, start, dur in modules:
        if rx.search(name) and start >= reduced.t0 \
                and start + dur <= reduced.t1:
            m = FINGERPRINT.search(name)
            runs_of.setdefault(m.group(1) if m else "", []).append(
                (start, start + dur))
    ops = reduced.ops(chip)
    out = []
    for program, runs in runs_of.items():
        of = scopes.get(program, {})
        out += [(of.get(instruction(ev[0]), ""), ev)
                for ev in trace_reduce.within(ops, sorted(runs))]
    return out, sum(len(r) for r in runs_of.values())


def scoped_ms_per_run(run, scope_pattern, program_pattern):
    """Device ms, a run of a matching program, of the operations whose
    scope matches; None where no operation carries such a scope."""
    scopes = op_scopes(run)
    if not scopes:
        return None
    events, runs = scoped_events(run["trace"], scopes, program_pattern)
    rx = re.compile(scope_pattern)
    hit = [ev[2] for scope, ev in events if rx.search(scope)]
    if not runs or not hit:
        return None
    return sum(hit) / 1e6 / runs


def named_ops_ms_per_run(run, op_pattern, program_pattern):
    """`trace_reduce.Reduced.ops_per_run` in ms, None where no operation
    has such a name (a program whose kernels are not named so)."""
    tr = run.get("trace")
    if tr is None:
        return None
    per_run, runs = tr.ops_per_run(op_pattern, program_pattern)
    return per_run * 1e3 if runs and per_run else None


# -- the program's counters -------------------------------------------------------

def counter_ratio_ms(run, seconds_keys, count_key):
    """1e3 * sum of the scheduler's `seconds_keys` / `count_key` over
    the window.  The serve runner publishes the difference of every
    numeric key of `scheduler.stats` between the window's two ends as
    `run["counters"]`; a key the program does not keep is absent there,
    and the ratio is then None."""
    counters = run.get("counters") or {}
    if any(k not in counters for k in (count_key, *seconds_keys)) \
            or not counters[count_key]:
        return None
    return 1e3 * sum(counters[k] for k in seconds_keys) / counters[count_key]



def registry_sum(metric, **labels):
    """Sum of the program's metric `metric` over the series whose labels
    include `labels` (every value of a label given as a tuple); None
    where the program has no such metric."""
    from paddle_tpu.observability.metrics import get_registry
    entry = get_registry().snapshot().get(metric)
    if entry is None:
        return None
    total = 0.0
    for series, value in entry["series"].items():
        have = dict(kv.split("=", 1) for kv in series.split(",") if kv)
        if all(have.get(k) in (v if isinstance(v, tuple) else (v,))
               for k, v in labels.items()):
            total += value
    return total


# -- by hand ------------------------------------------------------------------------

def cut(trace, scopes, start_ms, length_ms, min_op_us=0.0):
    """`trace_reduce.cut` plus the program's spans and the scopes of the
    operations kept; operations shorter than `min_op_us` are left out
    (a program run has thousands) and no HLO text is kept."""
    small = trace_reduce.cut(trace, start_ms, length_ms)
    ops = trace_reduce.line_events(trace_reduce.device_planes(trace)[0][1],
                                   trace_reduce.OPS_LINE)
    t0 = min(e[1] for e in ops) + int(start_ms * 1e6)
    t1 = t0 + int(length_ms * 1e6)
    device, host = small["planes"]
    device["lines"][0]["events"] = [
        e for e in device["lines"][0]["events"] if e[2] >= min_op_us * 1e3]
    host["lines"][0]["events"] += [
        [PREFIX + n, s - t0, e - s]
        for n, s, e in host_spans(trace) if t0 <= s < t1]
    kept = {instruction(e[0]) for e in device["lines"][0]["events"]}
    programs = {m.group(1) for m in (FINGERPRINT.search(e[0])
                                     for e in device["lines"][1]["events"])
                if m}
    small["scopes"] = {p: {i: s for i, s in scopes.get(p, {}).items()
                           if i in kept} for p in programs}
    small["text"] = {}
    return small


if __name__ == "__main__":
    import json
    import sys
    if len(sys.argv) not in (2, 5, 6):
        raise SystemExit(__doc__.split("\n\n")[-1])
    src = sys.argv[1]
    path = src if src.endswith(".pb") else trace_reduce.find_xplane(src)
    raw = trace_reduce.load_xplane(
        path, keep_lines=lambda plane, line:
        plane.startswith(("/device:", "/host:")))
    scopes = read_op_scopes(path)
    if len(sys.argv) >= 5:
        small = cut(raw, scopes, float(sys.argv[3]), float(sys.argv[4]),
                    float(sys.argv[5]) if len(sys.argv) == 6 else 0.0)
        with open(sys.argv[2], "w") as f:
            json.dump(small, f, separators=(",", ":"))
        print(sum(len(l["events"]) for p in small["planes"]
                  for l in p["lines"]), "events kept")
        raise SystemExit(0)
    raw["scopes"] = scopes
    red = trace_reduce.Reduced(raw)
    by = idle_by_span(red) or {}
    print(json.dumps({"window_s": red.window_s, "busy_s": red.busy_s(),
                      "idle_by_span_s": by,
                      "idle_by_span_pct_of_window": {
                          k: 100.0 * v / red.window_s for k, v in by.items()},
                      "unspanned_between": unspanned_by_neighbours(red)},
                     indent=1))
    events, runs = scoped_events(red, scopes, ".")
    total = {}
    for scope, ev in events:
        key = re.sub(r"\d+", "N", "/".join(scope.split("/")[1:3]))
        total[key] = total.get(key, 0) + ev[2]
    for key, ns in sorted(total.items(), key=lambda kv: -kv[1])[:40]:
        print(f"{ns / 1e6:12.3f} ms  {key}")
    print(runs, "program runs in the window")
