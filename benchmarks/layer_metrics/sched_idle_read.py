"""``sched.idle_read.<cell tag>``: the share of the traced window, as far as
chip 0's plane covers it (``program_spans.between_operations``), in which the
chip was idle under ``serve.gen.step.read``, the step's one ``device_get``:
what is left of it once the step's device time is taken out is the way back
to the host."""
from .. import program_spans


def read(run, trace):
    return program_spans.idle_share(run, trace, "read")
