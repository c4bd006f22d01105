"""``engine.device_idle.sat``: 1 - the union of the serving engine's device
runs over the window, in percent (``device_timeline``): the device's idle
share over the whole window, where ``device.idle.sat`` reads the profiler's
plane, which ends before the window does. Needs no trace."""
from .. import device_timeline


def read(run, trace):
    return device_timeline.idle_share(run)
