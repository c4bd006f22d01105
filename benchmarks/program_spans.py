"""The program's own spans (``mxtpu.obs.span``), for the per-layer readers.

``run.py`` deletes the trace before it calls a reader, and a reader gets only
``Run`` and ``Reduced``, so the spans are taken from memory: the program keeps
each one as an ``'X'`` event in ``mxtpu.profiler``'s list while a
``jax.profiler`` session is live, stamped with ``perf_counter`` carried to the
epoch by ``profiler.EPOCH_OFFSET_US``. A program that records no such span (a
commit before the spans were put in) gives an empty list, and every reader
``None``.
"""
from __future__ import annotations

import collections

from . import trace_reduce

Span = collections.namedtuple("Span", "name start end tid sid parent")

CLOCKS_AGREE_S = 50e-6
# the harness's span that both clocks hold: ``Run.spans`` in ``perf_counter``
# seconds, ``Reduced.spans`` in the trace's nanoseconds
ANCHOR = "submit"
# the share of the idle time that a piece of the scheduler thread's time goes
# to, by the span innermost there; under none of these it is unattributed
SHARE_OF = {"serve.gen.step": "step_host",
            "serve.gen.step.dispatch": "step_host",
            "serve.gen.step.emit": "step_host",
            "serve.gen.step.read": "read",
            "serve.gen.admit": "admit",
            "serve.gen.prefill": "admit",
            "serve.gen.first_read": "admit",
            "serve.gen.adopt": "admit"}


def in_window(run):
    """The program's spans that touch ``Run.window``, in ``perf_counter``
    seconds, sorted by start. Needs no trace."""
    from mxtpu import profiler
    offset_us = getattr(profiler, "EPOCH_OFFSET_US", None)
    if offset_us is None:
        return []
    t0, t1 = run.window
    out = []
    for e in profiler.snapshot_events():
        if e.get("cat") != "trace" or e.get("ph") != "X":
            continue
        start = (e["ts"] - offset_us) * 1e-6
        end = start + e["dur"] * 1e-6
        if end <= t0 or start >= t1:
            continue
        args = e.get("args", {})
        out.append(Span(e["name"], start, end, e.get("tid"), args.get("span"),
                        args.get("parent")))
    out.sort(key=lambda s: s.start)
    return out


def clock_bracket(run, trace):
    """``(at least, at most, pairs)``: the seconds to add to a
    ``perf_counter`` reading to land on the trace's clock lie between the
    first two, or ``None`` where the trace holds no ``submit`` span. The
    harness reads ``perf_counter`` just before it opens a ``submit``
    annotation and just after it closes it, so each pair brackets the
    offset: it is at most ``trace start - host start`` and at least ``trace
    end - host end``. The trace holds a contiguous run of the host's spans
    (the warm-up's precede the session), so every alignment is tried and the
    one whose bracket is narrowest, open or missed, is taken."""
    host = sorted((t0, t1) for n, t0, t1 in run.spans if n == ANCHOR)
    traced = sorted((s * 1e-9, e * 1e-9) for n, s, e in trace.spans
                    if n == ANCHOR)
    if not traced or len(traced) > len(host):
        return None
    best = None
    for shift in range(len(host) - len(traced) + 1):
        pairs = list(zip(host[shift:], traced))
        at_most = min(t[0] - h[0] for h, t in pairs)
        at_least = max(t[1] - h[1] for h, t in pairs)
        if best is None or abs(at_most - at_least) < abs(best[1] - best[0]):
            best = (at_least, at_most, len(pairs))
    return best


def clock_offset_s(run, trace):
    """The middle of ``clock_bracket``, or ``None`` where its pairs leave
    more than ``CLOCKS_AGREE_S`` open or miss each other by more."""
    bracket = clock_bracket(run, trace)
    if bracket is None or abs(bracket[1] - bracket[0]) > CLOCKS_AGREE_S:
        return None
    return (bracket[0] + bracket[1]) / 2


def on_trace_clock(run, trace, spans):
    """``spans`` as ``(name, start_ns, end_ns)`` on the trace's clock, the
    form of ``Reduced.spans``; ``None`` where the clocks cannot be matched."""
    offset = clock_offset_s(run, trace)
    if offset is None:
        return None
    return [(s.name, int(round((s.start + offset) * 1e9)),
             int(round((s.end + offset) * 1e9))) for s in spans]


def pieces(spans):
    """One thread's nested ``(name, start, end)`` cut into disjoint pieces,
    each under the name of its innermost span: a parent's self time is a
    piece of its own, so nothing counts twice."""
    out, stack, cursor = [], [], None

    def close(until):
        nonlocal cursor
        while stack and stack[-1][1] <= until:
            name, end = stack.pop()
            if end > cursor:
                out.append((name, cursor, end))
            cursor = max(cursor, end)

    for name, start, end in sorted(spans, key=lambda s: (s[1], -s[2])):
        if stack:
            close(start)
        if stack:
            if start > cursor:
                out.append((stack[-1][0], cursor, start))
            end = min(end, stack[-1][1])
        cursor = start
        stack.append((name, end))
    close(float("inf"))
    return out


def scheduler_thread(spans):
    """The spans of the thread that opened most ``serve.gen`` spans."""
    mine = [s for s in spans if s.name.startswith("serve.gen.")]
    if not mine:
        return []
    tid = collections.Counter(s.tid for s in mine).most_common(1)[0][0]
    return [s for s in mine if s.tid == tid]


def between_operations(gaps, w0_ns, w1_ns):
    """``(gaps, from, to)``: chip 0's gaps that an operation bounds on both
    sides, and the extent they lie in. ``trace_reduce`` adds the time from
    the window's start to the plane's first operation and from its last to
    the window's end as two more gaps, and those are no idle time where the
    plane holds no data: the profiler's buffer can fill long before the
    window closes, and the rest of the window is then one gap. Only a gap
    with an operation on both sides is known to be idle time."""
    gaps = sorted(gaps)
    if gaps and gaps[0][0] <= w0_ns:
        w0_ns = gaps.pop(0)[1]
    if gaps and gaps[-1][1] >= w1_ns:
        w1_ns = gaps.pop()[0]
    return gaps, w0_ns, w1_ns


def idle_shares(run, trace):
    """``(shares, covered seconds)``: chip 0's idle time, in percent of the
    part of the traced window that its plane covers (first operation to
    last), by what the scheduler thread was doing: ``step_host``, ``read``,
    ``admit``, ``unattributed``. They add up to the idle share of that part,
    which is the device's idle share where the plane covers the window.
    ``None`` where the program records no span, the clocks cannot be matched
    or the plane holds no two operations."""
    spans = scheduler_thread(in_window(run))
    if not spans:
        return None
    window = Span("window", *run.window, None, None, None)
    shifted = on_trace_clock(run, trace, spans + [window])
    if shifted is None:
        return None
    gaps, c0, c1 = between_operations(trace.gaps, *shifted.pop()[1:])
    if not gaps or c1 <= c0:
        return None
    shares = dict.fromkeys(("step_host", "read", "admit", "unattributed"), 0)
    for name, ns in trace_reduce.idle_by_span(gaps, pieces(shifted)).items():
        shares[SHARE_OF.get(name, "unattributed")] += ns
    return ({k: 100.0 * v / (c1 - c0) for k, v in shares.items()},
            (c1 - c0) * 1e-9)


def idle_share(run, trace, which):
    """One of ``idle_shares``; a share that would read 0 is left out."""
    found = idle_shares(run, trace)
    return (found[0].get(which) if found else None) or None
