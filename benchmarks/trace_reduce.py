"""From a profiler trace to numbers.  `load_xplane` turns jax's
`.xplane.pb` into plain data; everything else works on that plain data,
so `selftest.py` can check it against the small recorded trace in
`testdata/` without a device.

Plain form: {"planes": [{"name", "lines": [{"name", "events":
[[name, start_ns, dur_ns], ...]}]}], "text": {name: HLO text}}.  The
profiler names a device operation by its whole HLO line; `short_name`
cuts that to `<opcode>:<instruction>` (`copy:copy.124`,
`fusion:fusion.7`, and `pallas:<instruction>` for a Mosaic custom
call) and the HLO line is kept once a name under `text`, for readers
that must tell kernels apart by their shapes.  On a TPU each chip is a plane
`/device:TPU:<n>` whose line `XLA Ops` has one event per device
operation and whose line `XLA Modules` has one per program run; host
threads are lines of `/host:CPU`, and the benchmark's own spans are the
events there whose names start with `bench:`."""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


HLO = re.compile(r"^%?(?P<inst>[^ ]+) = (?P<type>\(.*?\)|[^ ]+) (?P<op>[a-z][a-z0-9-]*)\(")


def short_name(name):
    """`%copy.12 = f32[8]{0} copy(...)` -> `copy:copy.12`; a name that
    is not an HLO line stays as it is."""
    m = HLO.match(name)
    if not m:
        return name
    op = "pallas" if 'custom_call_target="tpu_custom_call"' in name \
        else m.group("op")
    return op + ":" + m.group("inst")


def load_xplane(path, keep_lines=None):
    """Read an `.xplane.pb` with jax alone.  `keep_lines(plane, line)`
    may drop lines while reading (host planes are large)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes, text, short = [], {}, {}
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            if keep_lines and not keep_lines(plane.name, line.name):
                continue
            events = []
            for ev in line.events:
                name = ev.name
                if name not in short:
                    short[name] = short_name(name)
                    if short[name] != name:
                        text[short[name]] = name
                events.append([short[name], int(ev.start_ns),
                               int(ev.duration_ns)])
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes, "text": text}


def device_planes(trace):
    """[(chip index, plane)] in chip order."""
    out = []
    for p in trace["planes"]:
        m = DEVICE_PLANE.match(p["name"])
        if m:
            out.append((int(m.group(1)), p))
    return sorted(out, key=lambda t: t[0])


def line_events(plane, line_name):
    for line in plane["lines"]:
        if line["name"] == line_name:
            return line["events"]
    return []


def host_spans(trace, prefix=SPAN_PREFIX):
    """The benchmark's own spans: [(name without prefix, start_ns,
    end_ns)] from every plane that is not a device."""
    out = []
    for p in trace["planes"]:
        if DEVICE_PLANE.match(p["name"]):
            continue
        for line in p["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith(prefix):
                    out.append((name[len(prefix):], start, start + dur))
    return sorted(out, key=lambda t: t[1])


def merge_intervals(events):
    """Union of [start, start+dur) as a sorted list of (start, end)."""
    spans = sorted((s, s + d) for _, s, d in events if d > 0)
    merged = []
    for s, e in spans:
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(events, t0, t1):
    """Events cut to the window [t0, t1)."""
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append([name, a, b - a])
    return out


def busy_seconds(events):
    return sum(e - s for s, e in merge_intervals(events)) / 1e9


def within(events, intervals):
    """Events that start inside one of the sorted (start, end)."""
    out, i = [], 0
    for ev in sorted(events, key=lambda e: e[1]):
        while i < len(intervals) and intervals[i][1] <= ev[1]:
            i += 1
        if i < len(intervals) and intervals[i][0] <= ev[1]:
            out.append(ev)
    return out


def sum_seconds(events, pattern, text_pattern=None, text=None):
    """Seconds of the events whose name matches `pattern` and, if
    given, whose HLO line matches `text_pattern`."""
    rx = re.compile(pattern)
    tx = re.compile(text_pattern) if text_pattern else None
    ok = {}
    total = 0
    for name, _, d in events:
        if name not in ok:
            ok[name] = bool(rx.search(name)) and (
                tx is None or bool(tx.search((text or {}).get(name, ""))))
        if ok[name]:
            total += d
    return total / 1e9


def top_ops(events, n=10):
    """[[name, seconds]] of the n operations with most device time."""
    total = {}
    for name, _, d in events:
        total[name] = total.get(name, 0) + d
    best = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in best]


def idle_gaps(events, t0, t1, spans, n=10):
    """The device's idle time in [t0, t1) by what the host was doing:
    each gap between device operations is split over the host spans it
    overlaps (innermost last wins is not needed: the benchmark's spans
    do not nest) and what is left goes to `unspanned`.
    Returns [[span name, seconds]], longest first."""
    busy = merge_intervals(clip(events, t0, t1))
    gaps, at = [], t0
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if t1 > at:
        gaps.append((at, t1))
    by = {}
    spans = sorted(spans, key=lambda sp: sp[1])
    for g0, g1 in gaps:
        covered = 0
        for name, s0, s1 in spans:
            if s1 <= g0:
                continue
            if s0 >= g1:
                break
            ov = min(g1, s1) - max(g0, s0)
            if ov > 0:
                by[name] = by.get(name, 0) + ov
                covered += ov
        rest = (g1 - g0) - covered
        if rest > 0:
            by["unspanned"] = by.get("unspanned", 0) + rest
    best = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in best]


def summary(trace, n=40):
    """What a trace holds, for reading by hand: per plane and line the
    number of events and the names with most time."""
    out = []
    for p in trace["planes"]:
        for line in p["lines"]:
            ev = line["events"]
            if not ev:
                continue
            out.append({"plane": p["name"], "line": line["name"],
                        "events": len(ev),
                        "first_ns": min(e[1] for e in ev),
                        "last_ns": max(e[1] + e[2] for e in ev),
                        "top": top_ops(ev, n)})
    return out


class Reduced:
    """One run's trace, cut to the traced window, as the per-layer
    metric readers see it.  Device numbers are of chip 0 unless said."""

    def __init__(self, trace):
        self.trace = trace
        self.chips = device_planes(trace)
        self.spans = host_spans(trace)
        # the traced window is the benchmark's own `window` span; a trace
        # without one is taken whole
        window = [sp for sp in self.spans if sp[0] == "window"]
        whole = self.ops(0) + [[None, s, e - s] for s, e in self.programs(".")]
        if window:
            self.t0, self.t1 = window[0][1], window[0][2]
        elif whole:
            self.t0 = min(e[1] for e in whole)
            self.t1 = max(e[1] + e[2] for e in whole)
        else:
            self.t0 = self.t1 = 0

    def ops(self, chip=0):
        for idx, plane in self.chips:
            if idx == chip:
                return line_events(plane, OPS_LINE)
        return []

    def programs(self, pattern, chip=0):
        """(start, end) of each run of a program whose name matches."""
        rx = re.compile(pattern)
        for idx, plane in self.chips:
            if idx == chip:
                return sorted((s, s + d) for name, s, d in
                              line_events(plane, MODULES_LINE)
                              if rx.search(name))
        return []

    @property
    def window_s(self):
        return (self.t1 - self.t0) / 1e9

    def busy_s(self):
        """Mean over the chips of the time an operation ran."""
        if not self.chips:
            return None
        per = [busy_seconds(clip(line_events(p, OPS_LINE), self.t0, self.t1))
               for _, p in self.chips]
        return sum(per) / len(per)

    def ops_per_run(self, op_pattern, program_pattern, chip=0,
                    text_pattern=None):
        """Seconds of matching operations inside matching program runs,
        a run, and the number of runs; (None, 0) where none ran."""
        runs = [r for r in self.programs(program_pattern, chip)
                if r[0] >= self.t0 and r[1] <= self.t1]
        if not runs:
            return None, 0
        inside = within(self.ops(chip), runs)
        return sum_seconds(inside, op_pattern, text_pattern,
                           self.trace.get("text")) / len(runs), len(runs)

    def per_run(self, spec, chip=0):
        """`ops_per_run` for a reader's {"ops", "program", "text"}."""
        return self.ops_per_run(spec["ops"], spec["program"], chip,
                                spec.get("text"))

    def breakdown(self):
        ops = clip(self.ops(0), self.t0, self.t1)
        return {"device_ops": top_ops(ops, 10),
                "idle_gaps": idle_gaps(ops, self.t0, self.t1,
                                       [s for s in self.spans
                                        if s[0] != "window"], 10)}


def cut(trace, start_ms, length_ms):
    """A small piece of a trace to keep as a recorded sample: chip 0's
    two lines and the benchmark's spans, events that start in the piece,
    times rebased to its start."""
    chips = device_planes(trace)
    if not chips:
        raise SystemExit("no device plane in this trace")
    ops = line_events(chips[0][1], OPS_LINE)
    t0 = min(e[1] for e in ops) + int(start_ms * 1e6)
    t1 = t0 + int(length_ms * 1e6)

    def piece(events):
        return [[n, s - t0, d] for n, s, d in events if t0 <= s < t1]
    host = [[SPAN_PREFIX + n, s - t0, e - s]
            for n, s, e in host_spans(trace) if t0 <= s < t1]
    kept = piece(ops)
    text = {n: trace.get("text", {}).get(n, "")[:400] for n, _, _ in kept}
    return {"text": text, "planes": [
        {"name": chips[0][1]["name"], "lines": [
            {"name": OPS_LINE, "events": kept},
            {"name": MODULES_LINE,
             "events": piece(line_events(chips[0][1], MODULES_LINE))}]},
        {"name": "/host:CPU", "lines": [{"name": "bench", "events": host}]}]}


if __name__ == "__main__":
    import json
    import sys
    if len(sys.argv) != 5:
        raise SystemExit("usage: trace_reduce.py <trace dir or .xplane.pb> "
                         "<out.json> <start ms> <length ms>")
    src = sys.argv[1]
    path = src if src.endswith(".pb") else find_xplane(src)
    small = cut(load_xplane(path), float(sys.argv[3]), float(sys.argv[4]))
    with open(sys.argv[2], "w") as f:
        json.dump(small, f, separators=(",", ":"))
    print(sum(len(l["events"]) for p in small["planes"] for l in p["lines"]),
          "events kept")
