#!/usr/bin/env python3
"""By hand, on the chip: one traced run of a cell, and the device's time by
the MXNet operator and node each operation came from.

    python3 benchmarks/tests/on_chip_scopes.py <cell> <seed> <seconds>

``eval_graph`` wraps every node in ``jax.named_scope("<op>/<node name>")``,
so each HLO operation's ``op_name`` carries them, and the TPU's profiler
keeps the ``op_name`` as the stat ``tf_op`` of the operation's *event
metadata* (a fusion's is its root's). ``jax.profiler.ProfileData`` shows an
event's own stats only, so the metadata is read from the ``.xplane.pb`` with
a decoder of the wire format that knows no schema. Prints two tables (by
operator, forward and transposed apart; the heaviest nodes), how many
operations carried a scope, where the core sat idle (between two runs of a
program or inside one), the host's seconds by program span, how many
``mxtpu.`` twins of the program's spans lie on the host plane, how far the
readers' clock match (``program_spans.on_trace_clock``) lands from those
twins, and the readers' idle shares with the seconds they are shares of.
The decoder stays only until ``trace_reduce`` keeps ``tf_op`` itself
(ROADMAP A1b).

Runs against an empty compile cache: JAX leaves metadata out of the cache's
key, so executables cached by a commit without the scopes would be served
without the names. Not collected by pytest; edits no benchmark file (it
looks at the trace before ``run.py`` deletes it).
"""
import collections
import glob
import json
import os
import re
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

TWIN_PREFIX = "mxtpu."
SCOPE_STAT = "tf_op"


# -- the wire format, without a schema ---------------------------------------
def varint(buf, i):
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def fields(buf):
    """``(field number, wire type, value)`` of one message's top level."""
    i, n = 0, len(buf)
    while i < n:
        key, i = varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = varint(buf, i)
        elif wire == 2:
            size, i = varint(buf, i)
            value, i = buf[i:i + size], i + size
        else:                       # 1: eight bytes, 5: four
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        yield key >> 3, wire, value


def scopes_by_event_name(path):
    """``{event name: op_name}`` over the TPU planes of an ``.xplane.pb``, and
    the names of the stats those planes declare. XSpace.planes = 1;
    XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5 (maps: key 1,
    value 2); XEventMetadata.name = 2, .stats = 5; XStatMetadata.name = 2;
    XStat.metadata_id = 1, .str_value = 5, .ref_value = 7."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    found, declared = {}, set()
    for number, _w, plane in fields(space):
        if number != 1:
            continue
        name, events, stats = None, [], {}
        for number, _w, v in fields(plane):
            if number == 2:
                name = bytes(v).decode()
            elif number == 4:
                events.append(v)
            elif number == 5:
                key = text = None
                for n2, _w2, v2 in fields(v):
                    if n2 == 1:
                        key = v2
                    elif n2 == 2:
                        text = next((bytes(v3).decode() for n3, _w3, v3
                                     in fields(v2) if n3 == 2), None)
                stats[key] = text
        if not (name or "").startswith("/device:TPU:"):
            continue
        declared |= set(stats.values())
        for entry in events:
            meta = next((v for n2, _w2, v in fields(entry) if n2 == 2), None)
            if meta is None:
                continue
            event_name = scope = None
            for n3, _w3, v3 in fields(meta):
                if n3 == 2:
                    event_name = bytes(v3).decode()
                elif n3 == 5:
                    stat = {n4: v4 for n4, _w4, v4 in fields(v3)}
                    if stats.get(stat.get(1)) != SCOPE_STAT:
                        continue
                    if 5 in stat:
                        scope = bytes(stat[5]).decode()
                    elif 7 in stat:
                        scope = stats.get(stat[7])
            if event_name is not None and scope:
                found[event_name] = scope
    return found, declared


# -- from an op_name to (operator, node, transposed) --------------------------
# a scope and what follows it; a wrapper (``transpose(``, ``jvp(``) is followed
# by a bracket and a primitive by the end, so neither is taken for a scope
_PART = re.compile(r"(?<![A-Za-z0-9_])([A-Za-z_][A-Za-z0-9_]*)/"
                   r"(?=([A-Za-z0-9_.\-]+))")
_OUTER = re.compile(r"^(?:jit\([^)]*\)/)+([A-Za-z_][A-Za-z0-9_]*)")


def scope_of(op_name, operators):
    """``(operator, node, direction)``: the first ``<op>/<node>`` of the path
    whose ``<op>`` is a registered operator; else the outermost scope under
    the program's name (``optimizer``, ``metric``, ``amp_guard``, ...)."""
    direction = "transposed" if "transpose(" in op_name else "forward"
    for m in _PART.finditer(op_name):
        if m.group(1) in operators:
            return m.group(1), m.group(2), direction
    m = _OUTER.match(op_name)
    return (m.group(1) if m else "(no scope)"), "", direction


def table(path, operators):
    from jax.profiler import ProfileData
    from benchmarks.common import SPAN_PREFIX, WINDOW_SPAN
    from benchmarks.trace_reduce import (idle_by_span, program_name,
                                         union_length)
    scopes, declared = scopes_by_event_name(path)
    data = ProfileData.from_file(path)
    lo = hi = None
    twin_starts = collections.defaultdict(list)
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == SPAN_PREFIX + WINDOW_SPAN:
                    lo, hi = e.start_ns, e.start_ns + e.duration_ns
                elif e.name.startswith(TWIN_PREFIX):
                    twin_starts[e.name[len(TWIN_PREFIX):]].append(e.start_ns)
    by_op, by_node = collections.Counter(), collections.Counter()
    total = scoped = 0.0
    ops, programs, copies = [], [], []

    def clipped(e):
        s, d = e.start_ns, e.duration_ns
        if lo is None:
            return s, s + d
        return max(s, lo), min(s + d, hi)

    for plane in data.planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                programs += [(program_name(e.name),) + clipped(e)
                             for e in line.events]
            elif line.name == "Async XLA Ops":
                copies += [clipped(e) for e in line.events]
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                s, end = clipped(e)
                if end <= s:
                    continue
                d = end - s
                ops.append((s, end))
                total += d
                op_name = scopes.get(e.name)
                if op_name is None:
                    by_op[("(no %s)" % SCOPE_STAT, "")] += d
                    continue
                op, node, direction = scope_of(op_name, operators)
                scoped += d if op in operators else 0
                by_op[(op, direction)] += d
                by_node[(op + "/" + node if node else op, direction)] += d
    # where the core sits idle: between two runs of a program, or inside one
    # (and how much of that an asynchronous copy was in flight)
    busy, gaps = union_length(ops)
    programs = [p for p in programs if p[2] > p[1]]
    inside = idle_by_span(gaps, programs)
    in_flight = merged([c for c in copies if c[1] > c[0]])
    inner = clip_to(gaps, merged([(s, e) for _n, s, e in programs]))
    copying = idle_by_span(inner, [("copy in flight",) + c for c in in_flight])
    runs = collections.Counter(n for n, _s, _e in programs)
    length = collections.Counter()
    for n, s, e in programs:
        length[n] += e - s
    first_op, last_op = min(o[0] for o in ops), max(o[1] for o in ops)
    return {"device_s": total * 1e-9, "scoped_s": scoped * 1e-9,
            "busy_s": busy * 1e-9,
            # the device plane can end before the window does (the
            # profiler's buffer): what lies after its last operation is no
            # idle time, it is no data
            "window_s": (hi - lo) * 1e-9 if lo is not None else None,
            "device_plane_from_to_s": [(first_op - (lo or first_op)) * 1e-9,
                                       (last_op - (lo or first_op)) * 1e-9],
            "stats_declared": sorted(x for x in declared if x),
            "events_with_scope": len(scopes),
            "by_operator": [[op, direction, ns * 1e-9] for (op, direction), ns
                            in by_op.most_common(24)],
            "by_node": [[node, direction, ns * 1e-9] for (node, direction), ns
                        in by_node.most_common(16)],
            "programs": [[n, runs[n], length[n] * 1e-9, inside.get(n, 0) * 1e-9]
                         for n, _c in length.most_common(8)],
            "idle_between_programs_s": inside.get("no_span", 0) * 1e-9,
            "idle_inside_with_copy_in_flight_s":
                copying.get("copy in flight", 0) * 1e-9,
            "host_twins": {TWIN_PREFIX + n: len(v)
                           for n, v in twin_starts.items()}}, twin_starts


def merged(intervals):
    """Overlapping ``(start, end)`` merged into disjoint ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def clip_to(gaps, spans):
    """The parts of sorted ``gaps`` that lie inside sorted, disjoint
    ``spans``."""
    out, first = [], 0
    for g0, g1 in gaps:
        while first < len(spans) and spans[first][1] <= g0:
            first += 1
        i = first
        while i < len(spans) and spans[i][0] < g1:
            out.append((max(g0, spans[i][0]), min(g1, spans[i][1])))
            i += 1
    return out


def clock_check(run, reduced, twin_starts):
    """How far ``on_trace_clock`` lands each ``serve.gen.step`` from its twin
    in the trace, in microseconds (a span whose twin the profiler dropped
    reads the distance to a neighbour's)."""
    from benchmarks import program_spans
    spans = [s for s in program_spans.in_window(run)
             if s.name == "serve.gen.step"]
    shifted = program_spans.on_trace_clock(run, reduced, spans) if spans else None
    if not shifted:
        return None
    theirs = sorted(twin_starts.get("serve.gen.step", ()))
    gaps = []
    for _name, start, _end in shifted:
        near = min(theirs, key=lambda t: abs(t - start), default=None)
        if near is not None:
            gaps.append(abs(near - start) * 1e-3)
    gaps.sort()
    if not gaps:
        return None
    return {"spans": len(gaps), "median_us": gaps[len(gaps) // 2],
            "p99_us": gaps[int(len(gaps) * 0.99)],
            "over_50us": sum(1 for g in gaps if g > 50.0)}


def idle_by_program_span(run, reduced):
    """The readers' idle shares, the seconds of the window they are shares
    of (the plane's first operation to its last), and how far the harness's
    ``submit`` pairs leave the clocks' offset open."""
    from benchmarks import program_spans
    found = program_spans.idle_shares(run, reduced)
    bracket = program_spans.clock_bracket(run, reduced)
    return {"shares": found and found[0], "covered_s": found and found[1],
            "clock_bracket_open_us": bracket and (bracket[1] - bracket[0]) * 1e6,
            "clock_pairs": bracket and bracket[2]}


def host_time_by_span(run):
    """The window's program spans by name: how many, their seconds, and their
    self seconds (each thread's time cut into pieces by innermost span)."""
    from benchmarks import program_spans
    spans = program_spans.in_window(run)
    whole, own, count = (collections.Counter() for _ in range(3))
    for s in spans:
        whole[s.name] += s.end - s.start
        count[s.name] += 1
    for tid in {s.tid for s in spans}:
        mine = [(s.name, s.start, s.end) for s in spans if s.tid == tid]
        for name, start, end in program_spans.pieces(mine):
            own[name] += end - start
    return [[n, count[n], whole[n], own[n]] for n, _c in whole.most_common()]


def main(cell, seed, seconds):
    scratch = os.path.join(ROOT, ".bench_tmp")
    os.makedirs(scratch, exist_ok=True)
    cache = tempfile.mkdtemp(prefix="scopes_cache_", dir=scratch)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    from benchmarks import common, run, trace_reduce
    from mxtpu.ops.registry import list_ops
    operators = set(list_ops())
    kept = {}
    reduce_dir = trace_reduce.reduce_dir
    window_close = common.Run.window_close

    def keep_run(self, t0, t1):
        kept["run"] = self
        return window_close(self, t0, t1)

    def look_first(trace_dir):
        found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        kept["table"], twin_starts = table(found[-1], operators)
        reduced = reduce_dir(trace_dir)
        kept["table"]["clock_check"] = clock_check(kept["run"], reduced,
                                                   twin_starts)
        kept["table"]["host_s_by_span"] = host_time_by_span(kept["run"])
        kept["table"]["idle_by_program_span"] = idle_by_program_span(
            kept["run"], reduced)
        return reduced

    common.Run.window_close = keep_run
    trace_reduce.reduce_dir = look_first
    try:
        run.main(["--workload", cell, "--seed", seed, "--seconds", seconds,
                  "--trace", "1"])
    finally:
        trace_reduce.reduce_dir = reduce_dir
        common.Run.window_close = window_close
        shutil.rmtree(cache, ignore_errors=True)
    print(json.dumps({"scopes": kept["table"]}), flush=True)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "scopes_%s_%s.json" % (cell, seed)),
              "w") as f:
        json.dump(kept["table"], f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:4]))
