"""``engine.idle_admit.sat``: the share of the window in which the device had
no run of the engine's (``device_timeline``) while the scheduler thread's
innermost span was admission's (``serve.gen.admit`` and its children, by
``program_spans.SHARE_OF``). One clock, the whole window: the twin of
``sched.idle_admit.sat``, which reads the plane. Needs no trace."""
from .. import device_timeline


def read(run, trace):
    return device_timeline.idle_under(run, "admit")
