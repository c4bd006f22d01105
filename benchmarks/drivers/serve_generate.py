"""Driver ``serve_generate``: requests through ``GenerateScheduler.submit``
into one ``InferenceEngine``, in a closed or an open loop as the traffic file
says. One generator thread (this one) and the scheduler's own.

Set-up makes the weights, builds the engine, compiles the cell's prefill
buckets and its decode and adopt programs, sends one request through each
bucket, then runs the loop for ``ramp_s`` so that the window opens on a
system in its steady state. Every token is stamped as the scheduler hands it
over (``on_token``). Requests are timed from when they were due.
"""
from __future__ import annotations

import gc
import queue
import time

import numpy as np

from .. import traffic as traffic_mod
from ..common import percentile


class Rec:
    __slots__ = ("rid", "due", "sent", "prompt", "max_new", "times", "reply")

    def __init__(self, rid, due, prompt, max_new):
        self.rid, self.due, self.prompt, self.max_new = rid, due, prompt, max_new
        self.sent, self.times, self.reply = None, [], None

    def ok(self):
        return (self.reply is not None and self.reply[0] == "ok"
                and len(self.times) == int(self.reply[1]["n"]))

    def tokens(self):
        return np.asarray(self.reply[1]["tokens"], np.int64)


class Load:
    """Sends the stream's requests and keeps every record."""

    def __init__(self, run, sched, stream):
        self._run, self.sched, self.stream = run, sched, stream
        self.recs, self.done = [], queue.Queue()
        self.backlog = []          # (time, scheduler's pending count)
        self.at_open = None        # the scheduler's counters at the window's start

    def open_window(self):
        self._run.window_open()
        self.at_open = self.sched.stats()

    def send(self, due, prompt, max_new):
        rec = Rec("r%d" % len(self.recs), due, prompt, max_new)
        self.recs.append(rec)
        with self._run.span("submit"):
            rec.sent = time.perf_counter()
            stamp = rec.times.append
            got = self.sched.submit(
                rec.rid, prompt, max_new, None,
                on_token=lambda _i, _t, _v: stamp(time.perf_counter()))
        if isinstance(got, tuple):                 # shed or refused
            rec.reply = got
            self.done.put(rec)
        else:
            def resolved(reply, rec=rec):
                rec.reply = reply
                self.done.put(rec)
            got.on_resolve(resolved)
        self.backlog.append((rec.sent, self.sched.pending()))
        return rec

    def send_next(self, due=None):
        _gap, prompt, max_new = self.stream.next()
        return self.send(time.perf_counter() if due is None else due,
                         prompt, max_new)

    def wait_all(self, recs, timeout):
        """Wait for every record to resolve; late is late, not wrong."""
        end = time.perf_counter() + timeout
        while any(r.reply is None for r in recs):
            left = end - time.perf_counter()
            if left <= 0:
                break
            try:
                self.done.get(timeout=min(left, 0.25))
            except queue.Empty:
                pass


def closed_loop(run, load, mix):
    """``clients`` requests always outstanding. Returns ``(t0, t1)``."""
    t_begin = time.perf_counter()
    for _ in range(int(mix["clients"])):
        load.send_next()
    t0 = None
    while True:
        now = time.perf_counter()
        if t0 is None and now - t_begin >= mix["ramp_s"]:
            load.open_window()
            t0 = time.perf_counter()
        if t0 is not None and now - t0 >= run.seconds:
            break
        try:
            load.done.get(timeout=0.02)
        except queue.Empty:
            continue
        load.send_next()
    t1 = time.perf_counter()
    run.window_close(t0, t1)
    return t0, t1


def open_loop(run, load, mix):
    """Arrivals on the stream's own schedule, whatever the system does."""
    t_begin = time.perf_counter()
    t0 = t_begin + mix["ramp_s"]
    t1 = t0 + run.seconds
    opened = False
    due = t_begin
    while True:
        gap, prompt, max_new = load.stream.next()
        due += gap
        if not opened and due >= t0:
            time.sleep(max(0.0, t0 - time.perf_counter()))
            load.open_window()
            opened = True
        if due >= t1:
            break
        time.sleep(max(0.0, due - time.perf_counter()))
        load.send(due, prompt, max_new)
    time.sleep(max(0.0, t1 - time.perf_counter()))
    t1 = time.perf_counter()
    run.window_close(t0, t1)
    return t0, t1


def instrument(run, engine, jax):
    """Traced runs only: the harness's own spans around the calls into the
    engine. Prefill and adopt wait for their result inside the span, so the
    span holds the device's time too (the scheduler reads the first token
    back straight after a prefill in any case)."""
    def wrap(name, wait):
        inner = getattr(engine, name)

        def outer(*a, **k):
            with run.span(name):
                out = inner(*a, **k)
                if wait:
                    jax.block_until_ready(out)
            return out
        setattr(engine, name, outer)
    wrap("gen_prefill", True)
    wrap("gen_adopt", True)
    wrap("gen_step", False)


def run(run):
    import jax
    from mxtpu.serving import InferenceEngine
    from mxtpu.serving.batcher import GenerateScheduler

    cfg, mix = run.cfg, run.traffic
    slots = int(cfg["slots"])
    with run.span("make_weights"):
        weights = run.reference.init_weights(cfg, run.seed)
    run.mark("weights_made")
    engine = InferenceEngine(run.model.symbol(cfg), weights, {},
                             {"data": (1,)}, buckets=(1,),
                             dtype=cfg["token_dtype"], warm=False)
    del weights
    run.mark("engine_built")
    menu = engine.gen_prefill_menu()
    for length in menu:
        engine.gen_prefill_program(length)
    engine.gen_decode_program(slots)
    engine.gen_adopt_program(slots)
    run.mark("programs_compiled")
    if run.trace:
        instrument(run, engine, jax)
    sched = GenerateScheduler(engine, int(cfg["queue_depth"]), slots=slots)
    stream = traffic_mod.Stream(mix, cfg["vocab_size"], run.seed)
    load = Load(run, sched, stream)
    # every program once, through the scheduler, before anything is timed
    rng = np.random.default_rng(run.seed)
    warm = [load.send(time.perf_counter(),
                      rng.integers(0, cfg["vocab_size"], size=length - 1)
                      .astype(np.int32), 4) for length in menu]
    load.wait_all(warm, 300.0)
    if not all(r.ok() for r in warm):
        raise RuntimeError("warm-up request failed: %r"
                           % [r.reply for r in warm if not r.ok()][:1])
    run.mark("warmed_up")
    first = len(load.recs)
    compiles0 = engine.cache.stats()["compiles"]
    run.trace_start()
    t0, t1 = {"closed": closed_loop, "open": open_loop}[mix["loop"]](
        run, load, mix)
    s0, s1 = load.at_open, sched.stats()
    compiles1 = engine.cache.stats()["compiles"]
    mine = [r for r in load.recs[first:] if t0 <= r.due < t1]
    load.wait_all(load.recs[first:], 60.0)

    # -- the window's numbers --------------------------------------------
    good = [r for r in mine if r.ok()]
    failed = len(mine) - len(good)
    in_window = sum(1 for r in load.recs[first:] for t in r.times
                    if t0 <= t < t1)
    ttft = [(r.times[0] - r.due) * 1e3 for r in good]
    itl = [(b - a) * 1e3 for r in good for a, b in zip(r.times, r.times[1:])]
    late = [(r.sent - r.due) * 1e3 for r in mine]
    # every end-to-end metric a cell of this driver may report; the manifest
    # says which cell reports which
    metrics = {"output_tokens_per_s": in_window / (t1 - t0),
               "itl_p95_ms": percentile(itl, 95),
               "ttft_p90_ms": percentile(ttft, 90)}
    # live cache positions read by the window's decode steps: a token with
    # index i >= 1 of a request came from a step that attended plen + i rows
    live = sum(len(r.prompt) + i for r in load.recs[first:]
               for i, t in enumerate(r.times) if i and t0 <= t < t1)
    half = (t0 + t1) / 2
    backlog = [b for t, b in load.backlog if t0 <= t < t1]
    run.counters.update(
        requests=len(mine), finished=len(good), tokens_in_window=in_window,
        slots=slots, compiles_in_window=compiles1 - compiles0,
        sched_steps=s1["steps"] - s0["steps"],
        sched_tokens=s1["tokens"] - s0["tokens"],
        sched_prefills=s1["prefills"] - s0["prefills"],
        shed=s1["shed_queue_full"] - s0["shed_queue_full"],
        live_positions=live,
        generator_late_ms_max=max(late) if late else 0.0,
        generator_late_ms_mean=sum(late) / len(late) if late else 0.0,
        ttft_p50_ms=percentile(ttft, 50), ttft_p90_ms=metrics["ttft_p90_ms"],
        itl_p50_ms=percentile(itl, 50), itl_p95_ms=metrics["itl_p95_ms"],
        backlog_first_half=float(np.mean(
            [b for t, b in load.backlog if t0 <= t < half] or [0])),
        backlog_second_half=float(np.mean(
            [b for t, b in load.backlog if half <= t < t1] or [0])),
        backlog_max=max(backlog) if backlog else 0,
        backlog_at_close=sched.pending(),
        mean_output_len=stream.mean_output_len(),
        mean_prompt_len=stream.mean_prompt_len(),
        ops_per_token=run.reference.ops_per_token(cfg))
    if run.counters["compiles_in_window"]:
        failed = max(failed, 1)

    def check():
        """Once the window has closed and the peak has been read: free the
        program's state, then follow a sample of the finished requests."""
        nonlocal engine, sched
        sched.stop()
        engine = sched = load.sched = None
        gc.collect()
        return compare(run, good)

    return {"attempted": len(mine), "failed": failed, "metrics": metrics,
            "check": check}


def compare(run, finished):
    """The widest gap by which a served token's logit lies below the
    reference's best, over a sample of the window's finished requests drawn
    from the seed, the longest among them."""
    import jax.numpy as jnp
    cfg, ref = run.cfg, run.reference
    if not finished:
        return [("logit_gap", float("inf"))], {}
    want = min(int(cfg["check_requests"]), len(finished))
    rng = np.random.default_rng(run.seed + 1)
    longest = max(range(len(finished)),
                  key=lambda i: len(finished[i].prompt) + len(finished[i].times))
    picks = {longest}
    while len(picks) < want:
        picks.add(int(rng.integers(0, len(finished))))
    weights = ref.init_weights(cfg, run.seed)
    total = int(cfg["check_pad_to"])
    max_new = int(cfg["max_new"])
    widest, widest_control, n_tokens = 0.0, None, 0
    for i in sorted(picks):
        rec = finished[i]
        served = rec.tokens()
        plen, n = len(rec.prompt), len(served)
        seq = np.zeros(total, np.int64)
        seq[:plen] = rec.prompt
        seq[plen:plen + n - 1] = served[:-1]
        # the logits at position p choose token p + 1
        at = np.zeros(max_new, np.int64)
        at[:n] = np.arange(plen - 1, plen - 1 + n)
        lg = ref.logits(cfg, weights, seq, at)
        best = jnp.max(lg, axis=-1)
        took = jnp.take_along_axis(lg, jnp.asarray(
            np.pad(served, (0, max_new - n)))[:, None], 1)[:, 0]
        widest = max(widest, float(jnp.max((best - took)[:n])))
        n_tokens += n
        if "fp8" in run.stand_ins:
            # the control need not decode: at each position of the same
            # prompt and tokens, the gap of the token float8 puts first
            low = ref.logits(cfg, weights, seq, at, quant=True)
            first = jnp.argmax(low, axis=-1)
            took = jnp.take_along_axis(lg, first[:, None], 1)[:, 0]
            widest_control = max(widest_control or 0.0,
                                 float(jnp.max((best - took)[:n])))
    run.counters["checked_requests"] = len(picks)
    run.counters["checked_tokens"] = n_tokens
    stood_in = {}
    if widest_control is not None:
        stood_in["fp8"] = [("logit_gap", widest_control)]
    return [("logit_gap", widest)], stood_in
