#!/usr/bin/env python3
"""By hand, on the chip: one traced run of a serving cell, and the engine's
device timeline (``benchmarks/device_timeline.py``) set beside the device
plane of the same run.

    python3 benchmarks/tests/on_chip_timeline.py <cell> <seed> <seconds>

Prints one line ``{"timeline_check": ...}`` after the run's own last line,
and writes it to ``chiprun_out/timeline_<cell>_<seed>.json``:

* ``decode``: each run of the decode program on chip 0's ``XLA Modules``
  line inside the window, matched to the timeline's decode run whose end is
  nearest (the plane moved onto ``perf_counter`` by the middle of the
  readers' own clock bracket, ``program_spans.clock_bracket``, whose width
  is printed), and the mean difference of their
  lengths, over all pairs and over the pairs whose timeline run follows a
  decode (``after == "decode"``, no adoption folded in);
* ``plane_extent``: the part of the window the plane covers, and there the
  timeline's prefill share and idle share, to set beside
  ``engine.prefill_share.sat`` and the sum of the ``sched.idle_*`` shares;
* ``plane_extent`` also gives the plane's own idle share there, between
  operations and between program runs;
* ``window``: the timeline's decode runs in the window, its longest idle gap
  and the scheduler thread's spans under it, and the longest decode runs;
  ``long_decode_runs``: each decode run over three times the median, with
  the plane's busy time inside it where the plane covers it (ROADMAP A8: a
  stall of the host's sits in an idle gap; a run that is long while the
  device idles under it was reported late).

Not collected by pytest; edits no benchmark file (it looks at the trace
before ``run.py`` deletes it).
"""
import bisect
import glob
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def plane(path, programs):
    """``({program: [(start_s, end_s)]}, ops)`` of chip 0 inside the
    harness's window, on the trace's clock in seconds: the runs of the
    cell's programs by kind (``decode``, ``prefill``, ``adopt``), and every
    operation's interval, sorted."""
    from jax.profiler import ProfileData
    from benchmarks import common, trace_reduce
    data = ProfileData.from_file(path)
    lo, hi = next((s, e) for n, s, e in trace_reduce._host_spans(data)
                  if n == common.WINDOW_SPAN)
    runs, ops = {}, []
    tpu0 = next(p for p in data.planes if p.name == "/device:TPU:0")
    for line in tpu0.lines:
        for e in line.events:
            s, t = e.start_ns, e.start_ns + e.duration_ns
            if s < lo or t > hi:
                continue
            if line.name == "XLA Modules":
                name = trace_reduce.program_name(e.name)
                if name in programs:
                    runs.setdefault(programs[name], []).append(
                        (s * 1e-9, t * 1e-9))
            elif line.name == "XLA Ops":
                ops.append((s * 1e-9, t * 1e-9))
    for v in runs.values():
        v.sort()
    ops.sort()
    return runs, ops


def busy_in(ops, lo, hi):
    """Seconds of ``[lo, hi]`` in which an operation ran."""
    from benchmarks import trace_reduce
    first = max(0, bisect.bisect_left(ops, (lo, lo)) - 64)
    inside = [(max(s, lo), min(t, hi)) for s, t in ops[first:
              bisect.bisect_right(ops, (hi, hi))] if t > lo and s < hi]
    return trace_reduce.union_length(inside)[0]


def mean(xs):
    return sum(xs) / len(xs) if xs else None


def match_decode(timeline, plane_runs, offset):
    """Each plane run of the decode program with the timeline's decode run
    whose end is nearest, within half the plane run's length."""
    decode = [r for r in timeline if r.kind == "decode"]
    ends = [r.end for r in decode]
    pairs = []
    for s, t in plane_runs:
        s, t = s - offset, t - offset
        i = bisect.bisect_left(ends, t)
        near = min((j for j in (i - 1, i) if 0 <= j < len(decode)),
                   key=lambda j: abs(ends[j] - t), default=None)
        if near is not None and abs(ends[near] - t) < (t - s) / 2:
            pairs.append((decode[near], s, t))
    return pairs


def decode_check(timeline, plane_runs, offset):
    pairs = match_decode(timeline, plane_runs, offset)
    steady = [(r, s, t) for r, s, t in pairs if r.after == "decode"]

    def ms(rows, f):
        return mean([1e3 * f(*row) for row in rows])
    return {
        "plane_runs": len(plane_runs), "matched": len(pairs),
        "matched_after_decode": len(steady),
        "plane_ms": ms(pairs, lambda r, s, t: t - s),
        "timeline_ms": ms(pairs, lambda r, s, t: r.end - r.start),
        "diff_ms": ms(pairs, lambda r, s, t: (r.end - r.start) - (t - s)),
        "plane_ms_after_decode": ms(steady, lambda r, s, t: t - s),
        "timeline_ms_after_decode": ms(steady,
                                       lambda r, s, t: r.end - r.start),
        "diff_ms_after_decode": ms(
            steady, lambda r, s, t: (r.end - r.start) - (t - s)),
        "end_lag_ms_quartiles": statistics.quantiles(
            [1e3 * (r.end - t) for r, s, t in pairs], n=4) if pairs else None,
    }


def long_runs(timeline, ops, offset, t0):
    """The decode runs over three times the median, and what the plane
    shows inside each: busy seconds (a run the device really took, or a
    pause of the host's in which the watcher could not stamp), and the
    timeline's runs right behind it (near nothing where the device had
    already done them)."""
    decode = [r for r in timeline if r.kind == "decode"]
    median = statistics.median(r.end - r.start for r in decode)
    last = ops[-1][1] - offset if ops else None
    out = []
    for i, r in enumerate(timeline):
        if r.kind != "decode" or r.end - r.start < 3 * median:
            continue
        covered = last is not None and r.end < last
        out.append({
            "at_s": round(r.start - t0, 4),
            "ms": round(1e3 * (r.end - r.start), 3), "after": r.after,
            "plane_busy_ms": round(1e3 * busy_in(
                ops, r.start + offset, r.end + offset), 3)
            if covered else None,
            "next_ms": [round(1e3 * (x.end - x.start), 3)
                        for x in timeline[i + 1:i + 4]]})
    return out


def extent_check(run, timeline, plane_runs, ops, offset, metrics):
    """The timeline's shares over the part of the window the plane covers,
    beside the plane's own: its idle share between operations and between
    program runs."""
    from benchmarks import device_timeline, trace_reduce
    lo, hi = ops[0][0] - offset, ops[-1][1] - offset
    modules = trace_reduce.union_length(
        [iv for v in plane_runs.values() for iv in v])[0]
    inside = [r for r in timeline if r.end > lo and r.start < hi]
    seconds = device_timeline.clipped(inside, (lo, hi))
    busy = sum(seconds)

    def metric(name):
        return metrics.get(name, {}).get("value")
    sched_idle = [metric("sched.idle_" + k + ".sat")
                  for k in ("step_host", "read", "admit", "unattributed")]
    t0, t1 = run.window
    return {
        "from_s": lo - t0, "to_s": hi - t0, "window_s": t1 - t0,
        "timeline_prefill_share": 100.0 * sum(
            s for r, s in zip(inside, seconds) if r.kind == "prefill") / busy,
        "timeline_idle_share": 100.0 * (1.0 - busy / (hi - lo)),
        "plane_idle_between_ops": 100.0 * (
            1.0 - trace_reduce.union_length(ops)[0] / (hi - lo)),
        "plane_idle_between_programs": 100.0 * (1.0 - modules / (hi - lo)),
        "engine.prefill_share.sat": metric("engine.prefill_share.sat"),
        "sched_idle_sum": sum(v or 0.0 for v in sched_idle),
    }


def window_check(run, timeline):
    """ROADMAP A8: the longest idle gap and what the scheduler did in it;
    whether decode runs grew."""
    from benchmarks import program_spans, trace_reduce
    t0, t1 = run.window
    mine = [r for r in timeline if t0 <= r.start < t1]
    decode = [r for r in mine if r.kind == "decode"]
    lengths = sorted(1e3 * (r.end - r.start) for r in decode)
    worst = max(mine, key=lambda r: r.idle_s)
    gap = (worst.start - worst.idle_s, worst.start)
    spans = program_spans.scheduler_thread(program_spans.in_window(run))
    under = trace_reduce.idle_by_span(
        [gap], program_spans.pieces([(s.name, s.start, s.end)
                                     for s in spans]))
    median = statistics.median(lengths)
    return {
        "decode_runs": len(decode), "prefill_runs": len(mine) - len(decode),
        "idle_gap_max_ms": 1e3 * worst.idle_s,
        "idle_gap_at_s": gap[0] - t0, "idle_gap_before": worst.kind,
        "idle_gap_under_ms": {k: 1e3 * v for k, v in under.items()},
        "gaps_over_50ms": sum(r.idle_s > 0.05 for r in mine),
        "decode_ms_median": median, "decode_ms_max": lengths[-1],
        "decode_runs_over_2x_median": sum(x > 2 * median for x in lengths),
        "longest_decode": sorted(
            ((round(1e3 * (r.end - r.start), 3), r.after,
              round(r.start - t0, 3)) for r in decode), reverse=True)[:5],
    }


def main(cell, seed, seconds):
    from benchmarks import common, device_timeline, program_spans, run, \
        trace_reduce
    kept = {}
    reduce_dir = trace_reduce.reduce_dir
    window_close = common.Run.window_close

    def keep_run(self, t0, t1):
        kept["run"] = self
        return window_close(self, t0, t1)

    def look_first(trace_dir):
        found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        programs = {v: k for k, v in kept["run"].cfg["programs"].items()}
        kept["plane"] = plane(found[-1], programs)
        kept["reduced"] = reduce_dir(trace_dir)
        return kept["reduced"]

    common.Run.window_close = keep_run
    trace_reduce.reduce_dir = look_first
    try:
        out = run.main(["--workload", cell, "--seed", seed, "--seconds",
                        seconds, "--trace", "1"])
    finally:
        trace_reduce.reduce_dir = reduce_dir
        common.Run.window_close = window_close
    the_run, reduced = kept["run"], kept["reduced"]
    runs, ops = kept["plane"]
    timeline = device_timeline.runs(the_run)
    # the middle of the readers' bracket, however wide: its width is printed
    bracket = program_spans.clock_bracket(the_run, reduced)
    offset = None if bracket is None else (bracket[0] + bracket[1]) / 2
    check = {"cell": cell, "seed": seed,
             "clock_bracket_us": None if bracket is None
             else 1e6 * abs(bracket[1] - bracket[0]),
             "metrics": {k: v["value"] for k, v in out["metrics"].items()},
             "window": window_check(the_run, timeline)}
    if offset is not None:
        check["decode"] = decode_check(timeline, runs.get("decode", []),
                                       offset)
        check["plane_extent"] = extent_check(the_run, timeline, runs, ops,
                                             offset, out["metrics"])
        check["long_decode_runs"] = long_runs(timeline, ops, offset,
                                              the_run.window[0])
    print(json.dumps({"timeline_check": check}), flush=True)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "timeline_%s_%s.json" % (cell, seed)),
              "w") as f:
        json.dump({"line": out, "timeline_check": check}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:4]))
