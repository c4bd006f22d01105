"""Cross-process trace spans: one request/step, one timeline (ISSUE 14).

The training step and the serving request both cross 3+ processes
(worker -> PS primary -> backup; client -> replica -> batcher) and the
per-process profiler (:mod:`mxtpu.profiler`) could never show where the
time went between them. This module adds the missing propagation:

* a **trace context** ``(trace_id, span_id)`` lives in a thread-local;
  :func:`span` records a chrome-trace complete event into the profiler
  event list carrying ``args={"trace", "span", "parent"}`` — and a
  chrome flow ``s``/``f`` event pair, so chrome://tracing draws the
  cross-process arrows;
* the context **rides the wire** as an optional third element of the
  existing pickle-5 frame tuple — ``(cid, msg, (trace_id, span_id))``
  — which old receivers never see (senders attach it only when a trace
  is active) and new receivers treat as pure metadata: dropping it can
  never change a reply, so observability stays strictly passive
  (the fault-matrix rows in ``tests/test_observability.py`` pin that);
* **sampling** is deterministic and cheap: ``MXTPU_TRACE_SAMPLE=f``
  samples every round(1/f)-th step/request per :class:`Sampler` —
  counter-based, never wall-clock or randomness, so fault-matrix runs
  replay exactly. With the default 0 every hook is one thread-local
  read that finds nothing.

A span records when a sampled context is open **or** a ``jax.profiler``
session is live. Under a live session every span is also a
``TraceAnnotation`` named ``mxtpu.<span name>``, so it lies on the host
plane of the ``.xplane.pb`` beside the device's operations; with no
sampled context its trace id is the thread's and its parent the
enclosing span on that thread, and no flow pair is written (nothing
crosses a process). With neither, a span is one thread-local read and
one static call that finds nothing.

:func:`device_run` adds the device's side on the same terms: the
serving engine hands it one output of each run it dispatches, and a
watcher thread stamps when the run ends, so
the device's busy and idle time over a whole session lies beside the
scheduler's spans without the profiler's device plane.

Timestamps are the profiler's: ``perf_counter`` carried to **epoch
microseconds** by an offset taken once per process
(``mxtpu.profiler.EPOCH_OFFSET_US``). One process's events are
monotonic on one clock, whichever hook stamped them, and the merged
timeline of a launch lines up without offset solving; on hosts with
NTP the cross-process skew is far below the wire latencies being
measured.

Each process with ``MXTPU_TRACE_DIR`` set dumps its span events at
exit (and on demand via :func:`dump_process_trace`) to
``<dir>/trace-<role>-<pid>.json``; :func:`merge_traces` stitches every
per-process file into ONE chrome://tracing JSON with process_name
metadata — the fleet timeline ``ci/check_observability.py`` and the
E2E launch drill assert on.
"""
from __future__ import annotations

import atexit
import glob
import json
import os
import queue as _queue
import threading
import time
import uuid

from jax.profiler import TraceAnnotation as _Annotation

from .. import profiler as _profiler
from . import metrics as _metrics

__all__ = ["Sampler", "sample_rate", "trace_dir", "start_trace",
           "active_ctx", "wire_ctx", "adopt", "span", "device_run",
           "dump_process_trace", "merge_traces"]

_tls = threading.local()

# the default series are resolved ONCE: a span bump must be one lock
# acquire, not a labels() lookup per event
_spans_recorded = _metrics.counter(
    "trace.spans",
    "chrome-trace span events recorded by this process").default()
_traces_started = _metrics.counter(
    "trace.started",
    "sampled root traces started by this process").default()
_span_drops = _metrics.counter(
    "trace.span_drops",
    "spans dropped past MXTPU_TRACE_EVENTS_MAX").default()

# cheap unique ids: one urandom read per process, then a GIL-atomic
# counter — uuid4 per span is measurable on sub-millisecond steps
import itertools as _it

_ID_PREFIX = uuid.uuid4().hex[:10]
_ID_SEQ = _it.count(1)


def _new_id():
    return "%s%x" % (_ID_PREFIX, next(_ID_SEQ))


_rate_cache = (None, 0.0)


def sample_rate():
    """MXTPU_TRACE_SAMPLE: fraction of steps/requests that carry a
    trace (0 disables, 1 traces everything). Deterministic: a rate f
    samples every round(1/f)-th event of each Sampler. Re-read every
    call (tests toggle it live); the float parse is memoized on the
    raw string so the per-step cost is one dict lookup + compare."""
    global _rate_cache
    raw = os.environ.get("MXTPU_TRACE_SAMPLE", "0") or "0"
    if raw != _rate_cache[0]:
        try:
            v = float(raw)
        except ValueError:
            v = 0.0
        _rate_cache = (raw, v)
    return _rate_cache[1]


def trace_dir():
    """MXTPU_TRACE_DIR: per-process span dumps land here as
    ``trace-<role>-<pid>.json`` (atexit, or dump_process_trace);
    unset disables the dump."""
    return os.environ.get("MXTPU_TRACE_DIR") or None


_events_max_cache = None


def events_max():
    """MXTPU_TRACE_EVENTS_MAX: hard bound on span events one process
    records (default 200000) — a long sampled run plateaus with a
    counted truncation instead of growing the event list forever.
    Read once (it bounds a whole process lifetime; tests reset the
    cache directly)."""
    global _events_max_cache
    if _events_max_cache is None:
        try:
            _events_max_cache = int(os.environ.get(
                "MXTPU_TRACE_EVENTS_MAX", "200000"))
        except ValueError:
            _events_max_cache = 200000
    return _events_max_cache


class Sampler:
    """Deterministic every-Nth sampler for one event stream (a
    trainer's steps, a client's requests). Thread-safe; zero-rate
    short-circuits to False without touching the counter lock."""

    def __init__(self, rate=None):
        self._rate = rate
        self._n = 0
        self._lock = threading.Lock()

    def _period(self):
        rate = sample_rate() if self._rate is None else self._rate
        if rate <= 0:
            return 0
        return max(1, int(round(1.0 / min(rate, 1.0))))

    def sample(self):
        period = self._period()
        if not period:
            return False
        with self._lock:
            self._n += 1
            return self._n % period == 1 or period == 1


# a jax.profiler session is live (a static call, 0.02 us)
_session_live = _Annotation.is_enabled
ANNOTATION_PREFIX = "mxtpu."      # a span's twin in the profiler's trace


def start_trace(name="trace"):
    """Open a sampled root context on this thread; returns a token for
    :func:`end_trace`. The root span itself is recorded by whatever
    :func:`span` scopes the caller opens inside it; opened inside a
    span of a live session (``module.fit.batch``), it hangs from that
    span."""
    prev = getattr(_tls, "ctx", None)
    _tls.ctx = (_new_id(), _enclosing() or name)
    _traces_started.inc()
    return prev


def end_trace(prev=None):
    _tls.ctx = prev


def active_ctx():
    """The thread's (trace_id, parent_span) or None — ONE attribute
    read on the untraced fast path."""
    return getattr(_tls, "ctx", None)


def wire_ctx():
    """The tuple a sender attaches to an outgoing frame (None when no
    trace is active — the frame then stays the classic 2-tuple)."""
    return active_ctx()


class adopt:
    """``with adopt(tctx):`` — server-side scope continuing a trace
    that arrived on the wire; no-op for tctx None."""

    def __init__(self, tctx):
        self._tctx = tctx
        self._prev = None

    def __enter__(self):
        if self._tctx is not None:
            self._prev = getattr(_tls, "ctx", None)
            _tls.ctx = (self._tctx[0], self._tctx[1])
        return self

    def __exit__(self, *exc):
        if self._tctx is not None:
            _tls.ctx = self._prev
        return False


class span:
    """``with span("kv.client.rpc", op="push"):`` — records one
    complete ('X') chrome-trace event with ``args`` ``trace``, ``span``,
    ``parent`` and the keywords given (the identifier of its layer:
    ``rid`` on every span of one request, ``step`` on every span of one
    train step). Under a sampled context the trace id is the context's
    and the flow-event pair that stitches processes rides along; under
    a live ``jax.profiler`` session alone the trace is the thread, and
    the span is also a ``TraceAnnotation`` in the profiler's trace.
    With neither, nothing is recorded. A span never waits for the
    device and never reads a value back."""

    __slots__ = ("name", "args", "_t0", "_at", "_sid", "_ann")

    def __init__(self, name, **args):
        self.name = name
        self.args = args
        self._sid = None

    def __enter__(self):
        at = _recording()
        if at is None:
            return self
        self._ann = None
        if _session_live():
            self._ann = _Annotation(
                ANNOTATION_PREFIX + self.name,
                **{k: str(v) for k, v in self.args.items()})
            self._ann.__enter__()
        # only now is there anything to undo: children opened inside
        # this scope parent onto this span
        self._at = at
        self._sid = _new_id()
        if at[0] is None:
            _tls.open.append(self._sid)
        else:
            _tls.ctx = (at[0], self._sid)
        self._t0 = _profiler._now_us()
        return self

    def __exit__(self, *exc):
        if self._sid is None:
            return False
        t1 = _profiler._now_us()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        trace_id, parent = self._at
        if trace_id is not None:
            _tls.ctx = self._at
        elif self._sid in getattr(_tls, "open", ()):
            # mostly the last; a span closed out of turn (a step whose
            # update never came, closed once the next one begins) goes
            # from wherever it is, and the others keep their places
            _tls.open.remove(self._sid)
        _land(self.name, self._t0, t1, trace_id, self._sid, parent,
              self.args)
        self._sid = None
        return False


def _recording():
    """``(trace id, parent span)`` for a span opened now on this thread,
    or None where it records nothing: the sampled context if one is
    open, else ``(None, the enclosing span)`` under a live session."""
    ctx = getattr(_tls, "ctx", None)
    if ctx is None and _session_live():
        return None, _enclosing()
    return ctx


def _enclosing():
    """The innermost span open on this thread under a live session with
    no sampled context (the thread's stack of them is ``_tls.open``)."""
    stack = _tls.__dict__.setdefault("open", [])
    return stack[-1] if stack else None


def _land(name, t0, t1, trace_id, sid, parent, extra):
    """One 'X' event into the profiler's bounded list; ``trace_id``
    None is a span of a live session with no sampled context."""
    if _spans_recorded.value >= events_max():
        _span_drops.inc()
        return
    pid = os.getpid()
    ident = threading.get_ident()
    thr = ident % 100000
    args = {"trace": trace_id or "thread-%x" % ident, "span": sid,
            "parent": parent}
    for k, v in extra.items():
        args[k] = str(v)
    ev = {"name": name, "cat": "trace", "ph": "X", "ts": t0,
          "dur": max(t1 - t0, 0.01), "pid": pid, "tid": thr,
          "args": args}
    if trace_id is None:
        _profiler._emit(ev)
    else:
        # one lock acquire lands the span AND its chrome flow pair
        # (the 's'/'f' events, id = trace id, are what make
        # chrome://tracing draw arrows between the processes)
        _profiler._emit_many((
            ev,
            {"name": "t:" + trace_id, "cat": "trace", "ph": "s",
             "id": trace_id, "ts": t0, "pid": pid, "tid": thr},
            {"name": "t:" + trace_id, "cat": "trace", "ph": "f",
             "bp": "e", "id": trace_id, "ts": t1, "pid": pid,
             "tid": thr},
        ))
    _spans_recorded.inc(1)
    _maybe_autodump()


_device_queue = None       # the watcher's, made with its thread by the first run
_device_guard = threading.Lock()
_device_unseen = False     # a run went out unrecorded since the last recorded


def device_run(name, out, **args):
    """``device_run("serve.engine.device.decode", nxt, slots=K)`` right after
    a program is dispatched: when the run ends on the device, on the spans'
    clock, for a whole-window device timeline that needs no profiler plane
    (ISSUE 38).

    ``out`` is one output of the run that nothing donates, or ``None`` for
    a run whose outputs are all donated into the next (a mark: it lands no
    event of its own, and the run behind it says ``after`` its kind). One
    daemon watcher, started by the first recorded run, takes them in
    dispatch order, waits for each output to be ready and stamps the clock.
    A run lasts from ``max(enqueued, the previous run's end)`` to that
    stamp and lands as a span does, but on the watcher's thread and with no
    annotation (it is known only after the fact), with ``after`` (the last
    part of the name of what went out before it, ``none`` where unknown)
    and ``idle_us`` (how long the device had nothing of the runs' queued
    before it; a mark's run counts there, or inside the run behind it
    where it still ran when that run went out). It records under the
    condition a span does; with neither a session nor a sampled context it
    is one check a dispatch, no thread and no reference. Neither thread
    ever waits for the other, and the watcher reads no value back."""
    global _device_queue, _device_unseen
    if _recording() is None:
        if _device_queue is not None:
            _device_unseen = True
        return
    item = (name, out, args, _profiler._now_us(), _device_unseen)
    _device_unseen = False
    if _device_queue is None:
        with _device_guard:
            if _device_queue is None:
                q = _queue.SimpleQueue()
                threading.Thread(target=_watch, args=(q,), daemon=True,
                                 name="mxtpu-obs-device-timeline").start()
                _device_queue = q
    _device_queue.put(item)


def _watch(q):
    end = after = None         # the last run's end; what went out last
    while True:
        name, out, args, enqueued, unseen = q.get()   # mxlint: allow(blocking-call) — the daemon watcher idles here between runs
        if unseen:             # the device ran what nobody saw
            end = after = None
        kind = name.rpartition(".")[2]
        if out is None:
            after = kind
            continue
        try:
            out.block_until_ready()
        except Exception:      # a failed run: the scheduler reports it
            out = end = after = None
            continue
        stamp, out = _profiler._now_us(), None
        idle = 0.0 if end is None else max(0.0, enqueued - end)
        _land(name, enqueued if end is None else max(enqueued, end),
              stamp, None, _new_id(), None,
              dict(args, after=after or "none", idle_us=round(idle, 1)))
        end, after = stamp, kind


_dumper_started = [False]
_dumper_guard = threading.Lock()


def _maybe_autodump():
    """First traced span (with MXTPU_TRACE_DIR set) starts ONE daemon
    dumper thread that writes the process timeline every 2 s: a server
    process the launcher SIGTERMs never runs atexit, so its spans must
    already be on disk — and the dump (whose cost grows with the event
    list) runs OFF the traced step's thread. Writes are atomic (tmp +
    rename), so a concurrent merge never reads a torn file."""
    if _dumper_started[0] or trace_dir() is None:
        return
    with _dumper_guard:
        if _dumper_started[0]:
            return
        _dumper_started[0] = True
        threading.Thread(target=_dump_loop, daemon=True,
                         name="mxtpu-obs-trace-dump").start()


def _dump_loop():
    while True:
        time.sleep(2.0)
        try:
            dump_process_trace()
        except OSError:
            pass                 # a full disk must not end tracing


def _process_label():
    role = os.environ.get("DMLC_ROLE", "worker")
    rank = os.environ.get("MXTPU_PROC_ID") \
        or os.environ.get("MXTPU_PS_PORT") \
        or os.environ.get("MXTPU_SERVE_PORT") or ""
    return "%s%s" % (role, ("-" + rank) if rank else "")


def dump_process_trace(path=None):
    """Write this process's trace-cat events (spans + flow pairs) as
    one chrome-trace JSON; returns the path, or None when there is
    nothing to write. Snapshot-and-continue: collection keeps running."""
    events = [e for e in _profiler.snapshot_events()
              if e.get("cat") == "trace"]
    if not events:
        return None
    d = trace_dir()
    if path is None:
        if d is None:
            return None
        path = os.path.join(
            d, "trace-%s-%d.json" % (_process_label(), os.getpid()))
    meta = [{"ph": "M", "name": "process_name", "pid": os.getpid(),
             "args": {"name": _process_label()}}]
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"traceEvents": meta + events,
                   "displayTimeUnit": "ms"}, f)
    os.replace(tmp, path)
    return path


def merge_traces(dir_or_files, out=None):
    """Stitch every per-process ``trace-*.json`` into ONE
    chrome://tracing timeline (distinct pids keep the processes as
    separate tracks; identical trace ids + flow events stitch the
    hops). Returns the merged event list; writes ``out`` when given."""
    if isinstance(dir_or_files, str):
        files = sorted(glob.glob(os.path.join(dir_or_files,
                                              "trace-*.json")))
    else:
        files = list(dir_or_files)
    merged = []
    for fname in files:
        try:
            with open(fname) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue           # a half-written dump is a gap, not fatal
        merged.extend(doc.get("traceEvents", []))
    if out is not None:
        tmp = out + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"traceEvents": merged, "displayTimeUnit": "ms"},
                      f)
        os.replace(tmp, out)
    return merged


if trace_dir():
    atexit.register(dump_process_trace)
