"""``train.dispatch_gap_ms``: mean idle time on the device between the end of
one run of the train step's program and the start of the next, from the
trace. The train step is the program that took most device time."""


def read(run, trace):
    if not trace.programs:
        return None
    step = max(trace.programs, key=lambda n: sum(trace.programs[n]))
    gaps = trace.program_gaps.get(step)
    if not gaps:
        return None
    return 1e3 * sum(gaps) / len(gaps)
