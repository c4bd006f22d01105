"""``fit.host_ms_per_step.<cell tag>``: mean of ``module.fit.batch`` less its
``module.fit.next_batch`` child, over the turns of ``fit``'s loop that lie
whole inside the window: what one step costs the host (dispatch of the fused
step, the metric, the callbacks), without the wait for the next batch, where
an input pipeline or the harness's hold-back makes ``fit`` wait. Needs no
trace."""
from .. import program_spans


def read(run, trace):
    t0, t1 = run.window
    spans = program_spans.in_window(run)
    waited = {s.parent: s.end - s.start for s in spans
              if s.name == "module.fit.next_batch"}
    host = [s.end - s.start - waited.get(s.sid, 0.0) for s in spans
            if s.name == "module.fit.batch" and t0 <= s.start and s.end <= t1]
    if not host:
        return None
    return 1e3 * sum(host) / len(host)
