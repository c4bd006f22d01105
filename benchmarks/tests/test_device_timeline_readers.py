"""The five ``engine.*`` readers of the engine's device timeline
(``benchmarks/device_timeline.py``) over made-up runs and spans: the whole
window on one clock, the runs clipped to it, idle time under admission by
the scheduler thread's innermost span, and ``None`` from a program that
records no timeline. (The readers of ``program_spans`` have their tests in
``test_program_spans.py``, an accepted file this PR leaves as it is.)"""
import importlib

import pytest

from benchmarks import common, device_timeline

T0 = 100.0           # the window on perf_counter, seconds


@pytest.fixture
def prof():
    from mxtpu import profiler
    profiler.reset()
    yield profiler
    profiler.reset()


def emit(prof, name, start, end, tid=7, **args):
    prof._emit({"name": name, "cat": "trace", "ph": "X",
                "ts": start * 1e6 + prof.EPOCH_OFFSET_US,
                "dur": (end - start) * 1e6, "pid": 1, "tid": tid,
                "args": dict({k: str(v) for k, v in args.items()},
                             trace="thread-%d" % tid, span=name + str(start),
                             parent=None)})


def device(prof, kind, start, end, after, idle_ms=0.0):
    emit(prof, "serve.engine.device." + kind, start, end, tid=9, after=after,
         idle_us=idle_ms * 1e3)


def make_run(window=(T0, T0 + 1.0)):
    run = common.Run(cell={"name": "c", "chips": 1}, cfg={}, traffic={},
                     seed=0, seconds=1.0, trace=True, t_start=0.0, model=None,
                     reference=None, peaks={})
    run.window = window
    return run


def read(metric, run):
    return importlib.import_module(common.reader_module(metric)).read(run, None)


def timeline(prof):
    """0.3 s of decode before the window and into it, a prefill, an idle
    stretch under admission, a decode behind an adopt, steady decodes, and
    one run past the window's end."""
    device(prof, "decode", T0 - 0.2, T0 + 0.1, "none")
    device(prof, "decode", T0 + 0.1, T0 + 0.2, "decode")            # 100 ms
    device(prof, "prefill", T0 + 0.2, T0 + 0.4, "decode")
    # idle 0.4 - 0.5: the scheduler reads the first token back (admission)
    device(prof, "decode", T0 + 0.5, T0 + 0.62, "adopt", idle_ms=100.0)
    device(prof, "decode", T0 + 0.62, T0 + 0.72, "decode")          # 100 ms
    # idle 0.72 - 0.77 under the step's own host time
    device(prof, "decode", T0 + 0.77, T0 + 0.87, "decode", idle_ms=50.0)
    device(prof, "decode", T0 + 0.87, T0 + 1.2, "decode")           # past t1
    emit(prof, "serve.gen.step", T0 + 0.0, T0 + 0.35)
    emit(prof, "serve.gen.admit", T0 + 0.38, T0 + 0.52)
    emit(prof, "serve.gen.first_read", T0 + 0.39, T0 + 0.49)
    emit(prof, "serve.gen.step", T0 + 0.7, T0 + 0.8)


def test_the_five_over_a_whole_window(prof):
    timeline(prof)
    run = make_run()
    # idle: 0.4-0.5 and 0.72-0.77 of a 1 s window
    assert read("engine.device_idle.sat", run) == pytest.approx(15.0)
    # admission: the 0.1 s under first_read and admit; 0.05 s under a step
    assert read("engine.idle_admit.sat", run) == pytest.approx(10.0)
    # prefill 0.2 of 0.85 s busy inside the window
    assert read("engine.prefill_device_share.sat", run) == pytest.approx(
        100 * 0.2 / 0.85)
    # the decodes after a decode, whole inside: 100, 100, 100 ms
    assert read("engine.decode_run_ms.sat", run) == pytest.approx(100.0)
    assert read("engine.idle_gap_max_ms.sat", run) == pytest.approx(100.0)


def test_idle_at_the_windows_ends_counts(prof):
    device(prof, "decode", T0 + 0.25, T0 + 0.5, "decode")
    device(prof, "decode", T0 + 0.5, T0 + 0.75, "decode")
    run = make_run()
    assert read("engine.device_idle.sat", run) == pytest.approx(50.0)
    assert read("engine.decode_run_ms.sat", run) == pytest.approx(250.0)
    assert read("engine.idle_gap_max_ms.sat", run) == 0.0
    assert read("engine.prefill_device_share.sat", run) == 0.0
    # no span of the scheduler's: idle time has nothing to be told by
    assert read("engine.idle_admit.sat", run) is None


def test_runs_outside_the_window_and_other_spans_are_not_read(prof):
    device(prof, "prefill", T0 - 1.0, T0 - 0.5, "decode")
    device(prof, "decode", T0 + 2.0, T0 + 2.1, "decode")
    emit(prof, "serve.gen.step", T0 + 0.1, T0 + 0.2)
    run = make_run()
    assert device_timeline.runs(run) == []
    assert read("engine.device_idle.sat", run) is None


def test_no_timeline_gives_none(prof):
    emit(prof, "serve.gen.step", T0 + 0.1, T0 + 0.2)
    run = make_run()
    for metric in ("engine.device_idle.sat", "engine.idle_admit.sat",
                   "engine.prefill_device_share.sat",
                   "engine.decode_run_ms.sat", "engine.idle_gap_max_ms.sat"):
        assert read(metric, run) is None, metric
