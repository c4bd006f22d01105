"""``sched.occupancy.sat``: the share of decode slots that held a sequence,
from the scheduler's own counters over the window: tokens that came from
decode steps, over steps times slots."""


def read(run, trace):
    c = run.counters
    if not c.get("sched_steps"):
        return None
    decoded = c["sched_tokens"] - c["sched_prefills"]
    return 100.0 * decoded / (c["sched_steps"] * c["slots"])
