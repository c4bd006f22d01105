"""``sched.idle_admit.<cell tag>``: the share of the traced window, as far as
chip 0's plane covers it (``program_spans.between_operations``), in which the
chip was idle under ``serve.gen.admit`` and its children (``prefill``,
``first_read``, ``adopt``): expiry checks, lane choice, and the host's side
of a prefill. In a traced run ``serve.gen.prefill`` and ``serve.gen.adopt``
contain the harness's own wrappers, which wait for the device."""
from .. import program_spans


def read(run, trace):
    return program_spans.idle_share(run, trace, "admit")
