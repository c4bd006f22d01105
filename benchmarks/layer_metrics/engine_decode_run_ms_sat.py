"""``engine.decode_run_ms.sat``: the mean device time of a decode run that
follows a decode run (``after == "decode"``: no adoption folded in) and lies
whole inside the window, from the engine's device timeline
(``device_timeline``): the steady decode program, launch gap included. Needs
no trace."""
from .. import device_timeline


def read(run, trace):
    t0, t1 = run.window
    ms = [1e3 * (r.end - r.start) for r in device_timeline.runs(run)
          if r.kind == "decode" and r.after == "decode"
          and t0 <= r.start and r.end <= t1]
    return sum(ms) / len(ms) if ms else None
