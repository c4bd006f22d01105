"""``decode.hbm_roofline.sat``: the least time the HBM could take to read what
one decode step has to read (every weight once, and the key and value rows of
the live positions of the live slots), over the mean device time of the
decode program in the trace. Bound by bytes: a decode step does about two
operations a byte."""


def read(run, trace):
    c = run.counters
    name = run.cfg["programs"]["decode"]
    mean_s = trace.program_mean_s(name)
    if mean_s is None or not c.get("sched_steps"):
        return None
    live_per_step = c["live_positions"] / c["sched_steps"]
    need = run.reference.decode_step_bytes(run.cfg, live_per_step)
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / mean_s
