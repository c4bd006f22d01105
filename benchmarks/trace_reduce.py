"""From the profiler's ``.xplane.pb`` to numbers: device busy time, device
time by operation and by program, the idle gaps and what the host was doing
in them. Reads the trace with ``jax.profiler.ProfileData`` and nothing else.

A TPU's plane is ``/device:TPU:<n>``. Its line ``XLA Ops`` has one event for
each operation that ran, ``XLA Modules`` one for each run of a compiled
program. The host's plane carries the harness's own spans
(``TraceAnnotation`` named ``bench.<span>``) on the same clock.
"""
from __future__ import annotations

import dataclasses
import functools
import glob
import os
import re

from .common import SPAN_PREFIX, WINDOW_SPAN

_KIND = re.compile(r"^%?([A-Za-z_\-]+(?:\.[A-Za-z_\-]+)*)")


@functools.lru_cache(maxsize=65536)
def op_kind(name):
    """``%fusion.123 = bf16[...] fusion(...)`` -> ``fusion``: the name of the
    operation without its number, so that runs and builds compare."""
    head = name.split(" = ", 1)[0].strip()
    m = _KIND.match(head)
    return m.group(1) if m else head


def program_name(name):
    """``jit_train_step(1017...)`` -> ``jit_train_step``."""
    return name.split("(", 1)[0]


def union_length(intervals):
    """Total length of the union of ``(start, end)`` intervals, and the gaps
    between its pieces as ``(start, end)``."""
    total, gaps = 0, []
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            total += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
    if cur_e is not None:
        total += cur_e - cur_s
    return total, gaps


@dataclasses.dataclass
class Reduced:
    chips: int
    busy_s: float                 # mean over the chips
    window_s: float
    op_s: dict                    # operation kind -> seconds, summed over chips
    programs: dict                # program name -> list of seconds per run
    program_gaps: dict            # program name -> idle seconds between runs
    gaps: list                    # chip 0: (start_ns, end_ns) of idle gaps
    spans: list                   # host: (name, start_ns, end_ns)

    def program_mean_s(self, name):
        runs = self.programs.get(name)
        return sum(runs) / len(runs) if runs else None

    def idle_share(self):
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self):
        """What the ledger keeps of a trace: the operations that took most
        device time, and the idle time by what the host was doing."""
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:10]
        idle = sorted(idle_by_span(self.gaps, self.spans).items(),
                      key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v / self.chips] for k, v in ops],
                "idle_gaps": [[k, v * 1e-9] for k, v in idle]}


def idle_by_span(gaps, spans):
    """Idle nanoseconds by the host span that covered them; what no span
    covered goes to ``no_span``. One sweep over both sorted lists."""
    spans = sorted(spans, key=lambda x: x[1])
    out, first = {}, 0
    for s, e in sorted(gaps):
        while first < len(spans) and spans[first][2] <= s:
            first += 1
        covered, i = 0, first
        while i < len(spans) and spans[i][1] < e:
            ov = min(e, spans[i][2]) - max(s, spans[i][1])
            if ov > 0:
                out[spans[i][0]] = out.get(spans[i][0], 0) + ov
                covered += ov
            i += 1
        out["no_span"] = out.get("no_span", 0) + max(0, (e - s) - covered)
    return out


def _host_spans(data):
    spans = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append((e.name[len(SPAN_PREFIX):], e.start_ns,
                                  e.start_ns + e.duration_ns))
    return spans


def reduce_file(path):
    """Reduce one trace. Where the harness marked its window as a span, all
    is clipped to it; else the window is the extent of the device's events."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    spans = _host_spans(data)
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    lo, hi = win[0] if win else (None, None)
    spans = [x for x in spans if x[0] != WINDOW_SPAN]
    clip = bool(win)
    busy, op_s, programs, program_gaps, gaps0 = [], {}, {}, {}, []
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                ivs = []
                for e in line.events:
                    s, d = e.start_ns, e.duration_ns
                    if clip:
                        if s + d <= lo or s >= hi:
                            continue
                        s, d = max(s, lo), min(s + d, hi) - max(s, lo)
                    ivs.append((s, s + d))
                    k = op_kind(e.name)
                    op_s[k] = op_s.get(k, 0.0) + d * 1e-9
                if not ivs:
                    continue
                total, gaps = union_length(ivs)
                busy.append(total * 1e-9)
                if not clip:
                    a, b = min(i[0] for i in ivs), max(i[1] for i in ivs)
                    lo = a if lo is None else min(lo, a)
                    hi = b if hi is None else max(hi, b)
                if not gaps0:
                    first, last = min(i[0] for i in ivs), max(i[1] for i in ivs)
                    gaps0 = gaps + ([(lo, first), (last, hi)] if clip else [])
                    gaps0 = [g for g in gaps0 if g[1] > g[0]]
            elif line.name == "XLA Modules" and not programs:
                last_end = {}
                for e in sorted(line.events, key=lambda e: e.start_ns):
                    s, d = e.start_ns, e.duration_ns
                    if clip and (s < lo or s + d > hi):
                        continue
                    n = program_name(e.name)
                    programs.setdefault(n, []).append(d * 1e-9)
                    if n in last_end:
                        program_gaps.setdefault(n, []).append(
                            max(0.0, (s - last_end[n]) * 1e-9))
                    last_end[n] = s + d
    if not busy:
        raise RuntimeError("no operation ran on a TPU in trace %s" % path)
    return Reduced(chips=len(busy), busy_s=sum(busy) / len(busy),
                   window_s=(hi - lo) * 1e-9, op_s=op_s, programs=programs,
                   program_gaps=program_gaps, gaps=gaps0, spans=spans)


def reduce_dir(trace_dir):
    """Reduce the newest trace under a ``start_trace`` directory."""
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise RuntimeError("no .xplane.pb under %s" % trace_dir)
    return reduce_file(found[-1])
