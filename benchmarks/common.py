"""What both drivers share: the run's context, spans, percentiles."""
from __future__ import annotations

import contextlib
import dataclasses
import shutil
import time

SPAN_PREFIX = "bench."      # the harness's spans in the profiler's trace
WINDOW_SPAN = "window"


@dataclasses.dataclass
class Run:
    """One run of one cell, as the drivers and the readers see it."""
    cell: dict                 # the entry of BENCHMARK.json's workloads
    cfg: dict                  # the configuration file
    traffic: dict              # the traffic file
    seed: int
    seconds: float
    trace: bool
    t_start: float             # perf_counter at process start
    model: object              # benchmarks.models.<family>
    reference: object          # benchmarks.reference.<family>
    peaks: dict                # this device's row of peaks.json
    stand_ins: tuple = ()      # readings with the reference in the program's place
    spans: list = dataclasses.field(default_factory=list)
    counters: dict = dataclasses.field(default_factory=dict)
    looks: dict = dataclasses.field(default_factory=dict)   # side -> the comparison's worst leaves
    marks: list = dataclasses.field(default_factory=list)   # (phase, seconds since start)
    window: tuple = (0.0, 0.0)  # perf_counter at the window's two ends
    trace_dir: str = ""
    setup_s: float = 0.0
    _window_span: object = None
    _tracing: bool = False

    # -- the window, and the profiler around it ---------------------------
    def trace_start(self):
        """Start the profiler (traced runs; at most once). A driver whose
        set-up ends in a ramp calls it before the ramp, so that starting it
        does not stall the loop at the window's opening."""
        if not self.trace or self._tracing:
            return
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._tracing = True

    def window_open(self):
        """Set-up ends here. In a traced run the window is also a span of
        the trace, so that the reduction clips to it on the trace's clock.
        (A ``TraceAnnotation`` starts when it is made, not when entered.)"""
        self.setup_s = time.perf_counter() - self.t_start
        self.trace_start()
        if self.trace:
            import jax
            self._window_span = jax.profiler.TraceAnnotation(
                SPAN_PREFIX + WINDOW_SPAN)
            self._window_span.__enter__()

    def window_close(self, t0, t1):
        self.window = (t0, t1)
        if self.trace:
            import jax
            self._window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()

    def mark(self, phase):
        """Where set-up's time goes: a phase's end, in seconds since start."""
        self.marks.append((phase, round(time.perf_counter() - self.t_start, 3)))

    @contextlib.contextmanager
    def span(self, name):
        """A host span. In a traced run it is also written into the
        profiler's trace, so an idle gap can be named by what the host did."""
        if not self.trace:
            yield
            return
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
            yield
        self.spans.append((name, t0, time.perf_counter()))


def percentile(values, q):
    """The q-th percentile (0-100) by linear interpolation, as numpy's."""
    xs = sorted(values)
    if not xs:
        return None
    k = (len(xs) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def seed_key(seed):
    """A JAX key from any whole number up to 2**62: a seed past 2**31 does
    not fit the int32 that ``PRNGKey`` takes without x64."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def reader_module(metric):
    """The module under ``layer_metrics`` that reads a per-layer metric:
    ``a.b.c`` is read by ``a_b_c.py`` or, where that is absent, by ``a_b.py``,
    so one reader serves a quantity that is split by cell
    (``device.idle.train``, ``device.idle.sat``)."""
    import importlib.util
    name = metric.replace("-", "_")
    for cut in (name, name.rpartition(".")[0]):
        module = "benchmarks.layer_metrics." + cut.replace(".", "_")
        if cut and importlib.util.find_spec(module) is not None:
            return module
    raise ImportError("no reader under layer_metrics for %r" % metric)
