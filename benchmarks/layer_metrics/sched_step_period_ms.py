"""``sched.step_period_ms.<cell tag>``: mean time from the start of one
``serve.gen.step`` span to the start of the next, over the steps that began
inside the window: the scheduler's period, to set beside the gap between two
tokens and the device's time for a decode step. Needs no trace."""
from .. import program_spans


def read(run, trace):
    t0, t1 = run.window
    starts = [s.start for s in program_spans.in_window(run)
              if s.name == "serve.gen.step" and t0 <= s.start < t1]
    if len(starts) < 2:
        return None
    return 1e3 * (starts[-1] - starts[0]) / (len(starts) - 1)
