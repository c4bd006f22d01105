"""``sched.idle_step_host.<cell tag>``: the share of the traced window, as far
as chip 0's plane covers it (``program_spans.between_operations``), in which
the chip was idle while the scheduler thread was inside a decode step but not
reading the tokens back: under ``serve.gen.step.dispatch`` (the call into
``engine.gen_step``), ``serve.gen.step.emit`` (every live slot's callback,
free and resolve) and ``serve.gen.step``'s own time between them."""
from .. import program_spans


def read(run, trace):
    return program_spans.idle_share(run, trace, "step_host")
