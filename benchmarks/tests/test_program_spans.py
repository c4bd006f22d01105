"""The readers of the program's spans over made-up spans and gaps: the four
idle shares add up to the idle share of the part of the window that the
device plane covers, nested spans count once, clocks that cannot be matched
and a program without spans give ``None``."""
import importlib

import pytest

from benchmarks import common, program_spans
from benchmarks.trace_reduce import Reduced

T0 = 100.0           # the window on perf_counter, seconds
TRACE_AHEAD = 5.0    # the trace's clock minus perf_counter, seconds


def ns(t):
    return int(round((t + TRACE_AHEAD) * 1e9))


@pytest.fixture
def prof():
    from mxtpu import profiler
    profiler.reset()
    yield profiler
    profiler.reset()


def emit(prof, name, start, end, sid, parent=None, tid=7, **args):
    prof._emit({"name": name, "cat": "trace", "ph": "X",
                "ts": start * 1e6 + prof.EPOCH_OFFSET_US,
                "dur": (end - start) * 1e6, "pid": 1, "tid": tid,
                "args": dict(args, trace="thread-7", span=sid, parent=parent)})


def make_run(submits, window=(T0, T0 + 1.0)):
    run = common.Run(cell={"name": "c", "chips": 1}, cfg={}, traffic={},
                     seed=0, seconds=1.0, trace=True, t_start=0.0, model=None,
                     reference=None, peaks={})
    run.window = window
    run.spans = [("make_weights", 1.0, 2.0)] + [("submit", a, b)
                                                for a, b in submits]
    return run


def make_trace(gaps, submits, window_s=1.0, skew=0.0):
    busy = window_s - sum(b - a for a, b in gaps)
    # the annotation opens a microsecond after the host's reading and closes
    # a microsecond before the next
    return Reduced(chips=1, busy_s=busy, window_s=window_s, op_s={},
                   programs={}, program_gaps={},
                   gaps=[(ns(a), ns(b)) for a, b in gaps],
                   spans=[("submit", ns(a + 1e-6 + skew * i),
                           ns(b - 1e-6 + skew * i))
                          for i, (a, b) in enumerate(submits)]
                   + [("gen_step", ns(T0), ns(T0 + 0.01))])


SUBMITS = [(T0 + 0.05, T0 + 0.0502), (T0 + 0.31, T0 + 0.3103),
           (T0 + 0.77, T0 + 0.7701)]


def one_step(prof, at, sid):
    """admit (with a prefill inside) then a step of three children."""
    emit(prof, "serve.gen.admit", at, at + 0.10, sid + "a", queued=3)
    emit(prof, "serve.gen.prefill", at + 0.02, at + 0.06, sid + "p",
         parent=sid + "a", rid="r")
    emit(prof, "serve.gen.step", at + 0.12, at + 0.30, sid + "s")
    emit(prof, "serve.gen.step.dispatch", at + 0.13, at + 0.15, sid + "d",
         parent=sid + "s")
    emit(prof, "serve.gen.step.read", at + 0.15, at + 0.25, sid + "r",
         parent=sid + "s")
    emit(prof, "serve.gen.step.emit", at + 0.26, at + 0.29, sid + "e",
         parent=sid + "s")


def test_pieces_name_the_innermost_span_and_count_once():
    got = program_spans.pieces([("step", 0, 100), ("read", 20, 60),
                                ("dispatch", 5, 20), ("admit", 120, 130)])
    assert got == [("step", 0, 5), ("dispatch", 5, 20), ("read", 20, 60),
                   ("step", 60, 100), ("admit", 120, 130)]
    assert sum(b - a for _n, a, b in got) == 110
    # a child that overruns its parent by a rounding is held inside it
    assert program_spans.pieces([("a", 0, 10), ("b", 5, 11)]) == \
        [("a", 0, 5), ("b", 5, 10)]


GAPS = [(T0 + 0.01, T0 + 0.04),    # admit's own 0.01, prefill 0.02
        (T0 + 0.10, T0 + 0.14),    # no span 0.02, step's own 0.01, dispatch 0.01
        (T0 + 0.20, T0 + 0.27),    # read 0.05, step's own 0.01, emit 0.01
        (T0 + 0.60, T0 + 0.68),    # no span 0.02, step's own 0.01, dispatch 0.02, read 0.03
        (T0 + 0.94, T0 + 0.99)]    # no span


def test_the_four_shares_add_up_to_the_idle_share(prof):
    one_step(prof, T0 + 0.0, "x")
    one_step(prof, T0 + 0.5, "y")
    # another thread's span never counts
    emit(prof, "serve.gen.step", T0, T0 + 1.0, "other", tid=8)
    # operations at both ends of the window: the plane covers all of it
    run, trace = make_run(SUBMITS), make_trace(GAPS, SUBMITS)
    shares, covered_s = program_spans.idle_shares(run, trace)
    assert covered_s == pytest.approx(1.0)
    assert shares["admit"] == pytest.approx(3.0, abs=1e-3)
    assert shares["step_host"] == pytest.approx(7.0, abs=1e-3)
    assert shares["read"] == pytest.approx(8.0, abs=1e-3)
    assert shares["unattributed"] == pytest.approx(9.0, abs=1e-3)
    assert sum(shares.values()) == pytest.approx(100.0 * trace.idle_share(),
                                                 abs=1e-6)
    for name, which in (("sched.idle_admit.sat", "admit"),
                        ("sched.idle_read.sat", "read"),
                        ("sched.idle_step_host.sat", "step_host"),
                        ("sched.idle_unattributed.sat", "unattributed")):
        reader = importlib.import_module(common.reader_module(name))
        assert reader.read(run, trace) == pytest.approx(shares[which])
    at_least, at_most, pairs = program_spans.clock_bracket(run, trace)
    assert pairs == 3 and 0.0 <= at_most - at_least <= 2.001e-6
    assert at_least <= TRACE_AHEAD <= at_most


def test_where_the_plane_ends_early_the_rest_is_no_idle_time(prof):
    """The profiler's buffer fills 0.4 s into the window: ``trace_reduce``
    hands on the other 0.6 s as one gap, and the first 0.01 s before the
    first operation as another. Neither is known to be idle."""
    one_step(prof, T0 + 0.0, "x")
    one_step(prof, T0 + 0.5, "y")
    gaps = [(T0 - 20e-6, T0 + 0.01),                    # window's start
            (T0 + 0.10, T0 + 0.14), (T0 + 0.20, T0 + 0.27),
            (T0 + 0.40, T0 + 1.0 + 20e-6)]              # to the window's end
    run, trace = make_run(SUBMITS), make_trace(gaps, SUBMITS)
    assert 100.0 * trace.idle_share() == pytest.approx(72.0, abs=0.01)
    shares, covered_s = program_spans.idle_shares(run, trace)
    assert covered_s == pytest.approx(0.39)
    assert shares["read"] == pytest.approx(100 * 0.05 / 0.39, abs=1e-3)
    assert shares["step_host"] == pytest.approx(100 * 0.04 / 0.39, abs=1e-3)
    assert shares["unattributed"] == pytest.approx(100 * 0.02 / 0.39, abs=1e-3)
    assert shares["admit"] == 0
    assert sum(shares.values()) == pytest.approx(100 * 0.11 / 0.39, abs=1e-3)
    # one gap, with no operation on either side: nothing is known
    trace.gaps = [(ns(T0 - 20e-6), ns(T0 + 1.0 + 20e-6))]
    assert program_spans.idle_shares(run, trace) is None


def test_the_trace_holds_a_later_run_of_the_hosts_spans(prof):
    """The warm-up's ``submit`` spans precede the session: the host has
    them, the trace has not."""
    one_step(prof, T0, "x")
    warm = [(T0 - 30.0, T0 - 29.9), (T0 - 20.0, T0 - 19.8)]
    run = make_run(warm + SUBMITS)
    trace = make_trace([(T0 + 0.15, T0 + 0.25)], SUBMITS)
    assert program_spans.idle_shares(run, trace)[0]["read"] == \
        pytest.approx(10.0, abs=1e-3)


def test_a_share_that_would_read_nought_is_left_out(prof):
    one_step(prof, T0, "x")
    run = make_run(SUBMITS)
    trace = make_trace([(T0 + 0.16, T0 + 0.24)], SUBMITS)
    assert program_spans.idle_share(run, trace, "read") == \
        pytest.approx(8.0, abs=1e-3)
    assert program_spans.idle_share(run, trace, "admit") is None


def test_offsets_that_disagree_give_none(prof):
    one_step(prof, T0, "x")
    run = make_run(SUBMITS)
    # the second and third pair sit 80 and 160 us off the first
    trace = make_trace([(T0 + 0.15, T0 + 0.25)], SUBMITS, skew=80e-6)
    assert program_spans.clock_offset_s(run, trace) is None
    assert program_spans.idle_shares(run, trace) is None
    reader = importlib.import_module(common.reader_module("sched.idle_read.sat"))
    assert reader.read(run, trace) is None
    # no anchor in the trace at all
    trace.spans = []
    assert program_spans.idle_shares(run, trace) is None


def test_no_program_span_gives_none(prof, monkeypatch):
    """What a commit without the spans reads: every reader ``None``."""
    run = make_run(SUBMITS)
    trace = make_trace([(T0 + 0.15, T0 + 0.25)], SUBMITS)
    names = ("sched.idle_step_host.sat", "sched.idle_read.sat",
             "sched.idle_admit.sat", "sched.idle_unattributed.sat",
             "sched.step_period_ms.sat", "fit.host_ms_per_step.train")
    readers = [importlib.import_module(common.reader_module(n)) for n in names]
    assert [r.read(run, trace) for r in readers] == [None] * 6
    # and a profiler that publishes no offset (the spans' clock is unknown)
    one_step(prof, T0, "x")
    monkeypatch.delattr(prof, "EPOCH_OFFSET_US")
    assert program_spans.in_window(run) == []
    assert [r.read(run, trace) for r in readers] == [None] * 6


def test_step_period_is_start_to_start_inside_the_window(prof):
    for i, at in enumerate((T0 - 0.03, T0 + 0.01, T0 + 0.05, T0 + 0.08,
                            T0 + 0.13, T0 + 1.02)):
        emit(prof, "serve.gen.step", at, at + 0.035, "s%d" % i)
    reader = importlib.import_module(
        common.reader_module("sched.step_period_ms.sat"))
    assert reader.read(make_run(SUBMITS), None) == pytest.approx(40.0)


def test_fit_host_time_leaves_out_the_wait_for_the_next_batch(prof):
    at = T0 - 0.05
    for i, (whole, waited) in enumerate(((0.06, 0.05), (0.05, 0.046),
                                         (0.05, 0.048), (0.05, 0.047))):
        emit(prof, "module.fit.batch", at, at + whole, "b%d" % i, step=i)
        emit(prof, "module.step", at + 0.0001, at + 0.001, "s%d" % i,
             parent="b%d" % i, step=i)
        emit(prof, "module.fit.next_batch", at + whole - waited - 0.0005,
             at + whole - 0.0005, "n%d" % i, parent="b%d" % i, step=i)
        at += whole
    reader = importlib.import_module(
        common.reader_module("fit.host_ms_per_step.train"))
    # the first turn began before the window: three count
    assert reader.read(make_run(SUBMITS, (T0, T0 + 1.0)), None) == \
        pytest.approx((4.0 + 2.0 + 3.0) / 3)
