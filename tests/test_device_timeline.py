"""The device timeline (ISSUE 38): the serving engine hands one output of
each run it dispatches to a watcher that stamps when the run ends on the
device, on the spans' clock. Fake outputs whose readiness the test holds
pin the intervals; a tiny scheduler under a CPU ``jax.profiler`` session
pins that every run lands once and that nothing donated is touched."""
import contextlib
import threading
import time

import numpy as np
import pytest

from mxtpu import obs
from mxtpu import profiler as prof
from mxtpu.obs import trace
from mxtpu.serving import InferenceEngine
from mxtpu.serving.batcher import GenerateScheduler

from test_serving_generate import _lm_params, _lm_symbol


class Held:
    """An output that is ready when the test says so."""

    def __init__(self):
        self.ready = threading.Event()
        self.waited = False

    def block_until_ready(self):
        self.waited = True
        assert self.ready.wait(10.0)
        return self


def runs(prefix="t.dev."):
    return sorted((e for e in prof.snapshot_events()
                   if e.get("ph") == "X" and e["name"].startswith(prefix)),
                  key=lambda e: e["ts"])


def landed(n, prefix="t.dev.", timeout=10.0):
    end = time.monotonic() + timeout
    while len(runs(prefix)) < n and time.monotonic() < end:
        time.sleep(0.005)
    got = runs(prefix)
    assert len(got) == n, [e["name"] for e in got]
    return got


@contextlib.contextmanager
def sampled():
    tok = obs.start_trace()
    try:
        yield
    finally:
        obs.end_trace(tok)


@contextlib.contextmanager
def session(path):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(path), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def end_of(e):
    return e["ts"] + e["dur"]


@pytest.fixture
def fresh(monkeypatch):
    """A watcher of this test's own: the process's may be live already."""
    monkeypatch.setattr(trace, "_device_queue", None)
    monkeypatch.setattr(trace, "_device_unseen", False)
    prof.reset()


def test_intervals_idle_order_and_the_run_behind_an_adopt(fresh):
    a, b, c = Held(), Held(), Held()
    with sampled():
        obs.device_run("t.dev.decode", a, slots=4)
        t_b = prof._now_us()
        obs.device_run("t.dev.prefill", b, rows=8)   # queued behind a
        time.sleep(0.01)
        a.ready.set()
        time.sleep(0.01)
        b.ready.set()
        ev_a, ev_b = landed(2)
        time.sleep(0.01)                  # the device has nothing queued
        obs.device_run("t.dev.adopt", None)
        time.sleep(0.005)
        t0 = prof._now_us()
        obs.device_run("t.dev.decode", c, slots=4)
        t1 = prof._now_us()
        c.ready.set()
        ev_a, ev_b, ev_c = landed(3)
    assert [e["name"] for e in (ev_a, ev_b, ev_c)] == [
        "t.dev.decode", "t.dev.prefill", "t.dev.decode"]
    assert [e["args"]["after"] for e in (ev_a, ev_b, ev_c)] == [
        "none", "decode", "adopt"]
    assert ev_b["args"]["rows"] == "8" and ev_a["args"]["slots"] == "4"
    # b was queued before a ended: it starts where a ends, with no idle
    assert ev_b["ts"] == pytest.approx(end_of(ev_a), abs=1.0)
    assert t_b < end_of(ev_a) and float(ev_b["args"]["idle_us"]) == 0.0
    assert ev_b["dur"] >= 9e3 and ev_a["dur"] >= 9e3
    # the run behind an adopt's mark starts at its own enqueue: the time
    # from the device's last end to there, the adopt's included, is idle
    assert t0 <= ev_c["ts"] <= t1
    idle = float(ev_c["args"]["idle_us"])
    assert idle == pytest.approx(ev_c["ts"] - end_of(ev_b), abs=1.0)
    assert idle >= 14e3
    assert all(end_of(x) <= y["ts"] + 1.0
               for x, y in zip((ev_a, ev_b), (ev_b, ev_c)))
    # one thread of its own
    assert len({e["tid"] for e in (ev_a, ev_b, ev_c)}) == 1
    assert ev_a["tid"] != threading.get_ident() % 100000


def test_runs_are_taken_in_dispatch_order_whatever_ends_first(fresh):
    first, second = Held(), Held()
    with sampled():
        obs.device_run("t.dev.decode", first)
        obs.device_run("t.dev.decode", second)
        second.ready.set()
        time.sleep(0.02)
        assert runs() == []               # still waiting for the first
        first.ready.set()
        one, two = landed(2)
    assert end_of(one) <= two["ts"] + 1.0
    assert two["args"]["after"] == "decode"


def test_nothing_is_recorded_and_no_thread_starts_with_neither(fresh):
    out = Held()
    out.ready.set()
    assert obs.active_ctx() is None
    for _ in range(3):
        obs.device_run("t.dev.decode", out)
        obs.device_run("t.dev.adopt", None)
    assert trace._device_queue is None and not out.waited
    time.sleep(0.02)
    assert runs() == []


def test_a_run_nobody_saw_leaves_the_next_without_a_before(fresh):
    x, y = Held(), Held()
    x.ready.set()
    y.ready.set()
    with sampled():
        obs.device_run("t.dev.decode", x)
        landed(1)
    obs.device_run("t.dev.decode", Held())           # unrecorded
    with sampled():
        obs.device_run("t.dev.prefill", y)
        _, ev = landed(2)
    assert ev["args"]["after"] == "none"
    assert float(ev["args"]["idle_us"]) == 0.0


def test_a_failed_run_lands_nothing_and_the_watcher_goes_on(fresh):
    class Failed:
        def block_until_ready(self):
            raise RuntimeError("the program failed")

    ok = Held()
    ok.ready.set()
    with sampled():
        obs.device_run("t.dev.decode", Failed())
        obs.device_run("t.dev.decode", ok)
        ev, = landed(1)
    assert ev["args"]["after"] == "none"


# ---------------------------------------------------------------------------
# the engine and the scheduler
# ---------------------------------------------------------------------------

@pytest.fixture
def sched(monkeypatch, fresh):
    monkeypatch.setenv("MXTPU_SERVE_GENERATE_SLOTS", "4")
    monkeypatch.setenv("MXTPU_SERVE_GENERATE_PREFILL_BUCKETS", "4,8,16")
    monkeypatch.delenv("MXTPU_TRACE_SAMPLE", raising=False)
    engine = InferenceEngine(_lm_symbol(), _lm_params(), {},
                             data_shapes={"data": (1,)}, buckets=(1,))
    s = GenerateScheduler(engine, 16, slots=4)
    try:
        # every program once, before anything is looked at
        assert s.submit("warm", np.arange(1, 6), 3, None).wait(60)[0] == "ok"
        yield s
    finally:
        s.stop()


def serve(sched, tag):
    rng = np.random.RandomState(5)
    reqs = [sched.submit("%s%d" % (tag, i), rng.randint(0, 17, 2 + 2 * i),
                         3 + i, None) for i in range(6)]
    replies = [r.wait(60) for r in reqs]
    assert all(r[0] == "ok" for r in replies), replies
    return [list(r[1]["tokens"]) for r in replies]


def test_the_engine_records_nothing_with_neither(sched):
    prof.reset()
    serve(sched, "q")
    # the timeline's queue comes with its thread: neither was made
    assert trace._device_queue is None
    assert runs("serve.engine.device.") == []


def test_every_run_lands_once_in_order_and_the_tokens_stay(sched, tmp_path):
    untraced = serve(sched, "u")
    at0 = sched.stats()
    prof.reset()
    with session(tmp_path):
        traced = serve(sched, "t")
    at1 = sched.stats()
    assert traced == untraced
    steps = at1["steps"] - at0["steps"]
    prefills = at1["prefills"] - at0["prefills"]
    got = landed(steps + prefills, "serve.engine.device.")
    kinds = [e["name"].rpartition(".")[2] for e in got]
    assert kinds.count("decode") == steps > 0
    assert kinds.count("prefill") == prefills == 6
    # one interval after the other, never overlapping
    assert all(end_of(x) <= y["ts"] + 1.0 for x, y in zip(got, got[1:]))
    assert all(e["dur"] > 0 for e in got)
    decode = [e for e in got if e["name"].endswith(".decode")]
    assert all(e["args"]["slots"] == "4" for e in decode)
    assert {e["args"]["rows"] for e in got if e["name"].endswith(".prefill")} \
        <= {"4", "8", "16"}
    # a sequence that decodes was adopted: the run behind says so
    assert sum(e["args"]["after"] == "adopt" for e in got) >= 1
    assert got[0]["args"]["after"] == "none"
    assert all(float(e["args"]["idle_us"]) >= 0 for e in got)
    # the watcher's own thread, not the scheduler's
    sched_tids = {e["tid"] for e in prof.snapshot_events()
                  if e.get("ph") == "X" and e["name"].startswith("serve.gen.")}
    assert len({e["tid"] for e in got}) == 1
    assert not sched_tids & {e["tid"] for e in got}
