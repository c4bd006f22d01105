"""The serving engine's device timeline, for the ``engine.*`` readers.

Since ISSUE 38 the engine hands one output of each prefill and decode run it
dispatches to a watcher thread (``mxtpu.obs.trace.device_run``), which
stamps ``perf_counter`` when the run ends on the device and keeps the run as
an ``'X'`` event ``serve.engine.device.<kind>`` in ``mxtpu.profiler``'s list,
on the clock of the program's spans, for every run of the window: no device
plane, no clock matching. A run lasts from when it was enqueued, or the run
before it ended if that was later, to that stamp; an adoption lands no run
(the one behind it says ``after`` ``adopt``) and its time is idle, or inside
that run where it still ran when that run went out; ``idle_us`` is how long
the device had nothing queued before the run. A program that records no such
event (a commit before ISSUE 38) gives an empty list, and every reader
``None``.
"""
from __future__ import annotations

import collections

from . import program_spans, trace_reduce

PREFIX = "serve.engine.device."
DeviceRun = collections.namedtuple("DeviceRun", "kind start end after idle_s")


def runs(run):
    """The engine's device runs that touch ``Run.window``, in ``perf_counter``
    seconds, in order."""
    from mxtpu import profiler
    offset_us = getattr(profiler, "EPOCH_OFFSET_US", None)
    if offset_us is None:
        return []
    t0, t1 = run.window
    out = []
    for e in profiler.snapshot_events():
        if e.get("ph") != "X" or not e.get("name", "").startswith(PREFIX):
            continue
        start = (e["ts"] - offset_us) * 1e-6
        end = start + e["dur"] * 1e-6
        if end <= t0 or start >= t1:
            continue
        args = e.get("args", {})
        out.append(DeviceRun(e["name"][len(PREFIX):], start, end,
                             args.get("after"),
                             float(args.get("idle_us", 0.0)) * 1e-6))
    out.sort(key=lambda r: r.start)
    return out


def clipped(found, window):
    """Each run's seconds inside the window."""
    t0, t1 = window
    return [min(r.end, t1) - max(r.start, t0) for r in found]


def idle_gaps(found, window):
    """``(busy seconds, gaps)``: the union of the runs (which never overlap)
    inside the window, and the window's stretches with no run as ``(start,
    end)``, its two ends included."""
    t0, t1 = window
    busy, gaps = trace_reduce.union_length(
        [(max(r.start, t0), min(r.end, t1)) for r in found])
    gaps = [(t0, found[0].start)] + gaps + [(found[-1].end, t1)]
    return busy, [(a, b) for a, b in gaps if b > a]


def idle_share(run):
    """The window's idle share of the device, in percent; ``None`` where the
    program records no timeline."""
    found = runs(run)
    if not found:
        return None
    busy, _gaps = idle_gaps(found, run.window)
    t0, t1 = run.window
    return 100.0 * (1.0 - busy / (t1 - t0))


def idle_under(run, share):
    """The window's idle time under the scheduler thread's innermost span of
    ``program_spans.SHARE_OF``'s ``share``, in percent of the window."""
    found = runs(run)
    spans = program_spans.scheduler_thread(program_spans.in_window(run))
    if not found or not spans:
        return None
    _busy, gaps = idle_gaps(found, run.window)
    pieces = program_spans.pieces([(s.name, s.start, s.end) for s in spans])
    idle = trace_reduce.idle_by_span(gaps, pieces)
    t0, t1 = run.window
    return 100.0 * sum(s for name, s in idle.items()
                       if program_spans.SHARE_OF.get(name) == share) / (t1 - t0)
