"""``engine.prefill_device_share.sat``: the prefill runs' seconds over all the
engine's device runs' seconds in the window (``device_timeline``): how much of
the chip admission takes from decoding, over the whole window. The twin of
``engine.prefill_share.sat``, which reads the plane. Needs no trace."""
from .. import device_timeline


def read(run, trace):
    found = device_timeline.runs(run)
    if not found:
        return None
    seconds = device_timeline.clipped(found, run.window)
    total = sum(seconds)
    if not total:
        return None
    return 100.0 * sum(s for r, s in zip(found, seconds)
                       if r.kind == "prefill") / total
