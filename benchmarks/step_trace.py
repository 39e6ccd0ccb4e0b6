"""The serving programs' runs, one by one (PR 36): what a launched
program cost the device and the rows that waited for it, for the
per-layer readers `decode_period_ms_mean.*`, `slow_steps_pct.*`,
`decode_device_ms_p50.*`, `prefill_device_share_pct`,
`prefill_stall_ms_per_token` and `token_gap_max_ms_mean.*`.

Two sources, both in the plain form a run already has, and no join
between them (a listed metric that comes out None refuses a run):

1. **The runs of `serve_prefill` / `serve_decode` programs on the `XLA
   Modules` line** of the device trace (`trace_reduce.Reduced.programs`),
   cut to the traced window: `serve_runs`, `device_ms_p50`,
   `prefill_device_share_pct`.
2. **The scheduler's counters over the whole window**
   (`run["counters"]`, traced or not): `step_period_s` over
   `steps_timed` is the mean step period as the program itself books it
   (a step's launch, or the read before it where it was launched ahead,
   to its tokens on the host: no wait for arrivals in it; a step read
   behind a prefill, whose period holds the prefill, is in neither);
   `steps_slow`, `prefill_row_stall_s`, `token_gap_max_s`: `per`.

A program that does not keep `step_period_s` (before PR 36) gives None
everywhere here, the device numbers too: they are read beside the
program's own period or not at all.

By hand, the question PR 35 left (which step stalled, and behind what):

    python3 benchmarks/step_trace.py <trace dir | .xplane.pb | cut .json>

prints the trace's serve programs one a line: launch number, kind, bucket
(a prefill: its request), the `pt:serve.*.launch` span's start and end,
the run's start and end on the device, the device's idle gap before the
run, and the end of the fetch that read it, all in ms from the first
launch.  Launch spans and runs are joined in order; from a profile the
spans carry `launch=` (`jax.profiler.ProfileData` hands out an
annotation's arguments as the event's stats) and a step's fetch is found
by that number, from a recorded cut (the plain form drops arguments) in
order too.  It refuses to print where a run would start before its
launch (by more than the two clocks' skew, `SKEW_NS`) or the kinds
disagree.
"""
from __future__ import annotations

import re

import trace_reduce
from stats import percentile

PROGRAMS = {"prefill": r"serve_prefill\b", "decode": r"serve_decode\b"}
MARK = "step_period_s"      # the counter only a program with a step log keeps
# a run may seem to start this long before its launch span: the profiler's
# host and device clocks are apart by a few tenths of a millisecond in some
# traces (a prefill run 0.188 ms "before" its launch, 0.2 ms after the fetch
# before it; my chip run, PR 36).  A join off by one is off by a step's or a
# launch's length: 1.3 ms in the steady cell, 10 ms in the backlog cells
SKEW_NS = 500_000
_SPAN = re.compile(r"^pt:serve\.(prefill|decode)\.(launch|fetch)$")


# -- the readers ----------------------------------------------------------------

def _marked(run):
    return MARK in (run.get("counters") or {})


def serve_runs(reduced):
    """[(kind, start_ns, end_ns, whole)] of the serve programs' runs that
    touch the traced window, cut to it, in device order; `whole` says the
    run lies inside the window uncut."""
    out = []
    for kind, pattern in PROGRAMS.items():
        for s, e in reduced.programs(pattern):
            a, b = max(s, reduced.t0), min(e, reduced.t1)
            if b > a:
                out.append((kind, a, b, (a, b) == (s, e)))
    return sorted(out, key=lambda r: r[1])


def device_ms_p50(run, kind):
    """Median device ms of a run of a `kind` program in the traced
    window: over the runs that lie whole inside it, or over the cut ones
    where none does."""
    tr = run.get("trace")
    if tr is None or not _marked(run):
        return None
    runs = [r for r in serve_runs(tr) if r[0] == kind]
    whole = [r for r in runs if r[3]] or runs
    return percentile([(e - s) / 1e6 for _, s, e, _ in whole], 50)


def prefill_device_share_pct(run):
    """Of the device time the serve programs' runs took in the traced
    window, the share in % that went to prompts."""
    tr = run.get("trace")
    if tr is None or not _marked(run):
        return None
    runs = serve_runs(tr)
    total = sum(e - s for _, s, e, _ in runs)
    if not total:
        return None
    return 100.0 * sum(e - s for k, s, e, _ in runs
                       if k == "prefill") / total


def per(run, total_key, count_key, scale):
    """`scale` x the window's `total_key` / `count_key` of the
    scheduler's counters; None where the program keeps no such counter
    or counted nothing."""
    c = run.get("counters") or {}
    if total_key not in c or not c.get(count_key):
        return None
    return scale * c[total_key] / c[count_key]


# -- by hand ---------------------------------------------------------------------

def load(path):
    """(spans, runs) of a profile or of a recorded cut: spans
    [(kind, "launch" | "fetch", start_ns, end_ns, arguments)], runs
    [(kind, start_ns, end_ns)] of chip 0, both in time order."""
    spans, runs = [], []
    if path.endswith(".json"):
        import json
        trace = json.load(open(path))
        for name, s, d in (ev for p in trace["planes"]
                           if not trace_reduce.DEVICE_PLANE.match(p["name"])
                           for line in p["lines"] for ev in line["events"]):
            m = _SPAN.match(name)
            if m:
                spans.append((m.group(1), m.group(2), s, s + d, {}))
        modules = trace_reduce.line_events(
            trace_reduce.device_planes(trace)[0][1],
            trace_reduce.MODULES_LINE)
    else:
        from jax.profiler import ProfileData
        if not path.endswith(".pb"):
            path = trace_reduce.find_xplane(path)
        modules, chip = [], None
        for plane in ProfileData.from_file(path).planes:
            m = trace_reduce.DEVICE_PLANE.match(plane.name)
            if m and (chip is None or int(m.group(1)) < chip):
                chip = int(m.group(1))
                modules = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                           for line in plane.lines
                           if line.name == trace_reduce.MODULES_LINE
                           for ev in line.events]
            elif not m:
                for line in plane.lines:
                    for ev in line.events:
                        found = _SPAN.match(ev.name)
                        if found:
                            s = int(ev.start_ns)
                            spans.append((
                                found.group(1), found.group(2), s,
                                s + int(ev.duration_ns),
                                {str(k): v for k, v in ev.stats}))
    for name, s, d in modules:
        for kind, pattern in PROGRAMS.items():
            if re.search(pattern, name):
                runs.append((kind, s, s + d))
    return (sorted(spans, key=lambda sp: sp[2]),
            sorted(runs, key=lambda r: r[1]))


def _number(value):
    try:
        return int(value)
    except (TypeError, ValueError):
        return None


def join_steps(spans, runs):
    """One row a launched program: {"launch", "kind", "bucket",
    "request_id", "launch_ns" (start, end), "device_ns" (start, end),
    "idle_before_ns", "fetch_end_ns"}.  The k-th launch span belongs to
    the k-th run, after the runs whose launch lies before the trace's
    first span are dropped (a run that starts within the clocks' skew of
    that span may be either: kept if the join then holds, else dropped);
    launches at the end whose run the trace no longer holds get no row.
    Raises ValueError where a run would start before its launch or the
    kinds disagree."""
    launches = [sp for sp in spans if sp[1] == "launch"]
    if not launches:
        raise ValueError("no pt:serve.*.launch span in this trace")
    first = launches[0][2]
    if any(first - SKEW_NS <= r[1] < first for r in runs):
        try:
            return _join(spans, launches, runs, first - SKEW_NS)
        except ValueError:
            pass
    return _join(spans, launches, runs, first)


def _join(spans, launches, runs, begin):
    """`join_steps` with the runs that start before `begin` taken as
    launched before the trace began."""
    by_number, in_order = {}, {"prefill": [], "decode": []}
    for sp in spans:
        if sp[1] == "fetch":
            n = _number(sp[4].get("launch"))
            if n is None:
                # a fetch that ended by then read a step of before
                if sp[3] > launches[0][2]:
                    in_order[sp[0]].append(sp)
            else:
                by_number[n] = sp
    busy_until = max([e for _, s, e in runs if s < begin], default=None)
    runs = [r for r in runs if r[1] >= begin]
    rows, taken = [], {"prefill": 0, "decode": 0}
    for k, (lau, run) in enumerate(zip(launches, runs)):
        kind, _, l0, l1, args = lau
        if run[0] != kind:
            raise ValueError(
                f"launch {k} is a {kind} and run {k} a {run[0]}: the "
                f"trace lost a span or a run")
        if run[1] < l0 - SKEW_NS:
            raise ValueError(
                f"run {k} ({kind}) starts {(l0 - run[1]) / 1e3:.1f} us "
                f"before its launch")
        number = _number(args.get("launch"))
        fetch = by_number.get(number) if number is not None else None
        if fetch is None and taken[kind] < len(in_order[kind]):
            fetch = in_order[kind][taken[kind]]
            taken[kind] += 1
        rows.append({
            "launch": number, "kind": kind,
            "bucket": _number(args.get("bucket")),
            "request_id": _number(args.get("request_id")),
            "launch_ns": (l0, l1), "device_ns": (run[1], run[2]),
            "idle_before_ns": (None if busy_until is None
                               else max(0, run[1] - busy_until)),
            "fetch_end_ns": fetch[3] if fetch else None})
        busy_until = max(busy_until or 0, run[2])
    return rows


def table(rows):
    """The rows as lines of text, times in ms from the first launch."""
    if not rows:
        return []
    t0 = rows[0]["launch_ns"][0]

    def ms(ns):
        return "       -" if ns is None else f"{(ns - t0) / 1e6:8.3f}"

    out = ["  launch kind    bucket  launch ms (start end)  device ms "
           "(start end)  idle before  fetch end"]
    for r in rows:
        what = (f"r{r['request_id']}" if r["kind"] == "prefill"
                and r["request_id"] is not None else r["bucket"])
        idle = r["idle_before_ns"]
        out.append(
            f"{'-' if r['launch'] is None else r['launch']:>8} "
            f"{r['kind']:<7} {'-' if what is None else what:>6}  "
            f"{ms(r['launch_ns'][0])} {ms(r['launch_ns'][1])}     "
            f"{ms(r['device_ns'][0])} {ms(r['device_ns'][1])}     "
            f"{'-' if idle is None else format(idle / 1e6, '.3f'):>8}   "
            f"{ms(r['fetch_end_ns'])}")
    return out


if __name__ == "__main__":
    import sys
    if len(sys.argv) != 2:
        raise SystemExit("usage: step_trace.py <trace dir | .xplane.pb | "
                         "recorded cut .json>")
    try:
        print("\n".join(table(join_steps(*load(sys.argv[1])))))
    except ValueError as e:
        raise SystemExit(f"step_trace.py: {e}")
