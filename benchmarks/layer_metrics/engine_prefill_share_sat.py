"""``engine.prefill_share.sat``: the prefill programs' device time over the
device's busy time in the traced window: how much of the chip admission
takes from decoding."""


def read(run, trace):
    runs = trace.programs.get(run.cfg["programs"]["prefill"])
    if not runs or not trace.busy_s:
        return None
    return 100.0 * sum(runs) / (trace.busy_s * trace.chips)
