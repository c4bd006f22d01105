"""``engine.idle_gap_max_ms.sat``: the longest time the device had nothing of
the engine's queued (``idle_us`` of the runs that start inside the window,
``device_timeline``): the host's longest failure to queue work. A stall of
the host's that holds back dispatch shows here (ROADMAP A8); a completion
the host learns of late does not: the watcher stamps after
``block_until_ready`` returns, so that shows as a long decode run with the
device idle under it, and this metric reads nothing of it. Needs no trace."""
from .. import device_timeline


def read(run, trace):
    t0, t1 = run.window
    idle = [r.idle_s for r in device_timeline.runs(run) if t0 <= r.start < t1]
    return 1e3 * max(idle) if idle else None
