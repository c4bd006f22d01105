"""``sched.idle_unattributed.<cell tag>``: the share of the traced window, as
far as chip 0's plane covers it (``program_spans.between_operations``), in
which the chip was idle under no span of the scheduler thread that a share
names: between ``_admit_queued`` and ``_step_lanes``, and where the loop
waits on its condition with nothing queued or active. With the three named
shares it adds up to the idle share of the covered part, which is the
device's idle share where the plane covers the whole window."""
from .. import program_spans


def read(run, trace):
    return program_spans.idle_share(run, trace, "unattributed")
