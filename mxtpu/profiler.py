"""Profiler: chrome://tracing dumps + scoped annotations + XLA traces.

Capability parity with ``src/profiler/`` + ``python/mxnet/profiler.py``
(426 LoC): ``set_config`` / ``set_state('run'|'stop')`` / ``pause`` /
``resume`` / ``dump``, custom Domain/Task/Frame/Event/Counter/Marker
objects, env-var autostart (``MXNET_PROFILER_AUTOSTART``), and the
chrome-trace JSON format (``src/profiler/profiler.h:87,429``).

TPU-first rendering: MXNet times each engine op on its worker thread;
here eager op dispatches are timed at the ``invoke`` hook (dispatch wall
time; set ``MXTPU_PROFILE_SYNC=1`` to block per op and capture true device
time, the NaiveEngine-style debugging mode), and compiled regions are
handed to ``jax.profiler`` (XPlane/TensorBoard) via ``start``/``stop``
when ``profile_xla=True`` — the XLA-native equivalent of kernel-level
timelines.
"""
from __future__ import annotations

import atexit
import json
import os
import threading
import time

__all__ = ["set_config", "profiler_set_config", "set_state",
           "profiler_set_state", "pause", "resume", "dump", "dumps",
           "snapshot_events", "reset", "EPOCH_OFFSET_US",
           "Domain", "Task", "Frame", "Event", "Counter", "Marker"]

_lock = threading.Lock()
_state = {
    "running": False,
    "paused": False,
    "filename": "profile.json",
    "events": [],          # chrome trace event dicts
    "profile_xla": False,
    "xla_logdir": None,
    "aggregate": False,
}
_PID = os.getpid()


# ONE clock for every event of this process (scoped objects, the eager
# operator hook, the obs spans): ``perf_counter`` carried to the epoch by
# an offset taken once, here. Monotonic within the process, and aligned
# across the processes of a launch to what their wall clocks agree on, so
# ``obs.merge_traces`` needs no offset solving. A reader that wants a
# ``ts`` back on ``perf_counter`` takes ``EPOCH_OFFSET_US`` off it.
EPOCH_OFFSET_US = (time.time() - time.perf_counter()) * 1e6


def _now_us():
    return time.perf_counter() * 1e6 + EPOCH_OFFSET_US


def set_config(filename="profile.json", profile_all=False,
               profile_symbolic=True, profile_imperative=True,
               profile_memory=False, profile_api=False, aggregate_stats=False,
               continuous_dump=False, dump_period=1, profile_xla=False,
               xla_logdir=None, **kwargs):
    """Configure the profiler (reference profiler.py:set_config)."""
    with _lock:
        _state["filename"] = filename
        _state["aggregate"] = aggregate_stats
        _state["profile_xla"] = profile_xla
        _state["xla_logdir"] = xla_logdir or (filename + ".xplane")


profiler_set_config = set_config


def set_state(state="stop"):
    """'run' starts collection, 'stop' ends it (reference set_state)."""
    with _lock:
        if state == "run":
            _state["running"] = True
            _state["paused"] = False
            if _state["profile_xla"]:
                import jax
                jax.profiler.start_trace(_state["xla_logdir"])
        elif state == "stop":
            if _state["running"] and _state["profile_xla"]:
                import jax
                jax.profiler.stop_trace()
            _state["running"] = False
        else:
            raise ValueError("state must be 'run' or 'stop'")


profiler_set_state = set_state


def pause():
    with _lock:
        _state["paused"] = True


def resume():
    with _lock:
        _state["paused"] = False


def is_active():
    return _state["running"] and not _state["paused"]


def _emit(ev):
    with _lock:
        _state["events"].append(ev)


def _emit_many(evs):
    """Append a batch of events under one lock acquire (the obs trace
    spans land a span + its flow pair per call)."""
    with _lock:
        _state["events"].extend(evs)


def snapshot_events():
    """A consistent copy of the event list while collection keeps
    running — the read every dumper (dump/dumps/the obs trace dump)
    goes through, so none of them ever races a concurrent _emit."""
    with _lock:
        return list(_state["events"])


def reset():
    """Drop collected events (tests and long runs that already dumped);
    collection state is untouched."""
    with _lock:
        _state["events"] = []


def record_span(name, cat, t0_us, t1_us, args=None):
    """Append one complete ('X') chrome trace event."""
    _emit({"name": name, "cat": cat, "ph": "X", "ts": t0_us,
           "dur": max(t1_us - t0_us, 0.01), "pid": _PID,
           "tid": threading.get_ident() % 100000,
           "args": args or {}})


def dumps(reset=False):
    """Return aggregate stats as text (reference dumps)."""
    with _lock:
        events = list(_state["events"])
        if reset:
            _state["events"] = []
    events = [e for e in events if "dur" in e]
    agg = {}
    for e in events:
        k = e["name"]
        tot, cnt = agg.get(k, (0.0, 0))
        agg[k] = (tot + e.get("dur", 0.0), cnt + 1)
    lines = ["%-40s %10s %12s %12s" % ("Name", "Calls", "Total(us)",
                                       "Avg(us)")]
    for k, (tot, cnt) in sorted(agg.items(), key=lambda kv: -kv[1][0]):
        lines.append("%-40s %10d %12.1f %12.1f" % (k, cnt, tot, tot / cnt))
    return "\n".join(lines)


def dump(finished=True, profile_process="worker"):
    """Write the chrome://tracing JSON file (reference DumpProfile,
    src/profiler/profiler.cc:170). Snapshot-and-continue: the event
    list is copied under the lock and collection keeps running — a
    dump mid-run can never race (or clear) concurrent emits. The file
    lands atomically (tmp + rename) so a reader polling it never sees
    a torn JSON."""
    with _lock:
        events = list(_state["events"])
        fname = _state["filename"]
    payload = {"traceEvents": events, "displayTimeUnit": "ms"}
    tmp = "%s.tmp.%d" % (fname, os.getpid())
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, fname)
    return fname


# -- scoped annotation objects (reference c_api_profile.cc objects) --------

class Domain:
    def __init__(self, name):
        self.name = name

    def new_task(self, name):
        return Task(name, self)

    def new_counter(self, name, value=None):
        c = Counter(name, self)
        if value is not None:
            c.set_value(value)
        return c

    def new_marker(self, name):
        return Marker(name, self)


class _Span:
    _cat = "scope"

    def __init__(self, name, domain=None):
        self.name = name
        self.domain = domain
        self._t0 = None

    def start(self):
        self._t0 = _now_us()
        return self

    def stop(self):
        if self._t0 is not None and is_active():
            record_span(self.name, self._cat, self._t0, _now_us(),
                        {"domain": self.domain.name if self.domain else ""})
        self._t0 = None

    def __enter__(self):
        return self.start()

    def __exit__(self, *a):
        self.stop()


class Task(_Span):
    _cat = "task"


class Frame(_Span):
    _cat = "frame"


class Event(_Span):
    _cat = "event"


class Counter:
    def __init__(self, name, domain=None):
        self.name = name
        self.domain = domain
        self._value = 0
        self._vlock = threading.Lock()

    def set_value(self, value):
        with self._vlock:
            self._value = value
        self._emit_value(value)

    def _emit_value(self, value):
        if is_active():
            _emit({"name": self.name, "ph": "C", "ts": _now_us(),
                   "pid": _PID, "args": {"value": value}})

    def increment(self, delta=1):
        # read-modify-write under ONE hold of the lock: releasing between
        # the read and the write loses updates under contention
        with self._vlock:
            self._value += delta
            value = self._value
        self._emit_value(value)

    def decrement(self, delta=1):
        self.increment(-delta)

    def __iadd__(self, v):
        self.increment(v)
        return self

    def __isub__(self, v):
        self.decrement(v)
        return self


class Marker:
    def __init__(self, name, domain=None):
        self.name = name
        self.domain = domain

    def mark(self, scope="process"):
        if is_active():
            _emit({"name": self.name, "ph": "i", "ts": _now_us(),
                   "pid": _PID, "s": "p" if scope == "process" else "t"})


# -- env autostart (reference MXNET_PROFILER_AUTOSTART, env_var.md:105) ----
if os.environ.get("MXNET_PROFILER_AUTOSTART", "0") == "1" or \
        os.environ.get("MXTPU_PROFILER_AUTOSTART", "0") == "1":
    set_state("run")
    atexit.register(dump)
