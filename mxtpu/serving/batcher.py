"""Bounded-latency dynamic batcher: coalesce, flush, shed, drain.

The serving request path in one place, with a hard contract per stage:

* **Admission** (:meth:`DynamicBatcher.submit`, handler threads): a
  bounded queue — at or past ``MXTPU_SERVE_QUEUE_DEPTH`` queued
  requests the submit is REFUSED with the retriable ``overloaded``
  verdict. Nothing is ever silently dropped: every admitted request
  gets exactly one terminal reply.
* **Coalescing** (the flush thread): queued same-signature requests
  pack into one device dispatch, padded into the engine's bucket
  shapes. A batch flushes when the queued rows fill the largest bucket
  or when the OLDEST queued request has waited
  ``MXTPU_SERVE_BATCH_DEADLINE_MS`` — the bounded-latency half: a lone
  request never waits longer than the batch deadline for company.
* **Expiry**: each request carries its deadline (admission time + the
  client's budget). Expired requests are dropped AT DEQUEUE — before
  the batch dispatches, never after: device work already paid for is
  always delivered, and no compute is ever spent on an answer nobody
  is waiting for. The reply is the ``expired`` verdict.
* **Dispatch**: ``fault.fire("serve.batch")`` immediately before the
  engine call makes kill/delay/drop drills land between coalescing and
  compute — the kill-replica-mid-batch point of the failover story.
* **Drain** (:meth:`drain`): stop is a two-phase exit — the server
  first refuses new admissions (``draining`` verdict upstream), then
  this waits until the queue is empty and the in-flight flush
  completed, bounded by its timeout. SIGTERM → drain → exit is the
  graceful path ``tools/launch.py``'s ``_reap`` escalation leans on.

Locking: ONE condition variable guards the queue and counters; it is
never held across an engine dispatch or a reply callback, so the
batcher cannot participate in a lock-order cycle with transport or
engine locks (the mxlint ``lock-order`` pass checks the whole package).
"""
from __future__ import annotations

import collections
import itertools
import os
import threading
import time

import numpy as _np
import jax as _jax

from .. import fault as _fault
from .. import obs as _obs

__all__ = ["DynamicBatcher", "Request", "GenerateScheduler",
           "GenRequest", "RETRIABLE_VERDICTS"]

# batcher instruments (ISSUE 14): every stats() field is a registry
# series labeled by batcher instance — the dict API reads the series
# back, the fleet plane polls the same numbers via the `metrics` op
_SB_COUNTERS = {
    "batches": _obs.counter(
        "serve.batch.batches", "coalesced device dispatches", ("inst",)),
    "batched_rows": _obs.counter(
        "serve.batch.rows", "rows dispatched in batches", ("inst",)),
    "batched_requests": _obs.counter(
        "serve.batch.requests", "requests landed in batches", ("inst",)),
    "shed_queue_full": _obs.counter(
        "serve.batch.shed_queue_full", "submits shed at queue depth",
        ("inst",)),
    "expired": _obs.counter(
        "serve.batch.expired", "requests expired at dequeue", ("inst",)),
    "batch_faults": _obs.counter(
        "serve.batch.faults", "batches lost to injected faults",
        ("inst",)),
}
_SB_GAUGES = {
    "max_batch_rows": _obs.gauge(
        "serve.batch.max_rows", "largest batch dispatched (rows)",
        ("inst",)),
    "max_batch_requests": _obs.gauge(
        "serve.batch.max_requests", "largest batch (requests)",
        ("inst",)),
    "queue_hwm": _obs.gauge(
        "serve.batch.queue_hwm", "queue-depth high-water mark",
        ("inst",)),
}
_SB_QUEUED = _obs.gauge("serve.batch.queued",
                        "requests queued + in the current flush",
                        ("inst",))
_SB_FLUSH_MS = _obs.histogram(
    "serve.batch.flush_ms", "engine dispatch wall time per batch")
_SB_INST = itertools.count(1)

# terminal verdicts a request reply opens with (the wire contract —
# docs/serving.md "Verdicts"): "ok" carries outputs; "overloaded" /
# "draining" are RETRIABLE (another replica, or later); "expired" is
# not (the budget is gone); "err" is a caller bug (bad signature).
RETRIABLE_VERDICTS = ("overloaded", "draining")


class Request:
    """One admitted predict request parked on the queue.

    Two delivery styles, because the two transports need both: the
    in-process shortcut's caller BLOCKS in :meth:`wait`, while the wire
    handler registers an :meth:`on_resolve` callback and keeps reading
    frames — that is what lets one connection's pipelined window carry
    many predicts into the same coalesced batch."""

    __slots__ = ("rid", "arrays", "rows", "deadline", "enq_t",
                 "event", "reply", "wait_bound", "version", "_cbs",
                 "_cb_lock", "tctx")

    def __init__(self, rid, arrays, rows, deadline, wait_bound=60.0,
                 version=None, tctx=None):
        self.rid = rid
        self.arrays = arrays
        self.rows = rows
        self.deadline = deadline
        # sampled trace context that rode the predict frame: pure
        # observability metadata — the batch flush continues the trace
        self.tctx = tctx
        # weight version resolved at ADMISSION (stable or canary):
        # batches never mix versions, so every request is answered by
        # one coherent store even while swaps stream in
        self.version = version
        self.enq_t = time.monotonic()
        self.event = threading.Event()
        self.reply = None
        self.wait_bound = wait_bound
        self._cbs = []
        self._cb_lock = threading.Lock()

    def on_resolve(self, cb):
        """Register ``cb(reply)`` for the terminal reply; fires
        immediately when already resolved (no missed-wakeup window)."""
        with self._cb_lock:
            if self.reply is None:
                self._cbs.append(cb)
                return
        cb(self.reply)

    def resolve(self, reply):
        with self._cb_lock:
            if self.reply is not None:
                return                   # terminal means terminal
            self.reply = reply
            cbs, self._cbs = self._cbs, []
        for cb in cbs:
            cb(reply)
        self.event.set()

    def wait(self, timeout=None):
        """Bounded wait for the terminal reply; a stalled flusher (a
        bug, or an injected kill severing this replica) surfaces as an
        ``err`` verdict instead of a parked handler thread."""
        timeout = self.wait_bound if timeout is None else timeout
        if not self.event.wait(timeout):
            return ("err", "no batch flush within %.1fs for %s"
                    % (timeout, self.rid))
        return self.reply


class DynamicBatcher:
    """Queue + flush thread in front of one :class:`InferenceEngine`."""

    def __init__(self, engine, queue_depth, batch_deadline_ms,
                 server=None):
        self._engine = engine
        self._depth = int(queue_depth)
        self._deadline_s = float(batch_deadline_ms) / 1000.0
        self._server = server          # fault.fire target for kill
        self._cv = threading.Condition()
        self._queue = collections.deque()
        self._queued_rows = 0
        self._inflight = 0             # requests in the current flush
        self._stopped = False
        # every counter IS a registry series (ISSUE 14): stats() reads
        # the instruments back, so the dict and the fleet plane agree
        inst = "b%d" % next(_SB_INST)
        self._c = {f: m.labels(inst) for f, m in _SB_COUNTERS.items()}
        self._g = {f: m.labels(inst) for f, m in _SB_GAUGES.items()}
        self._queued_g = _SB_QUEUED.labels(inst)
        self._thread = threading.Thread(target=self._flush_loop,
                                        daemon=True,
                                        name="mxtpu-serve-batcher")
        self._thread.start()

    # -- admission ---------------------------------------------------------
    def submit(self, rid, arrays, rows, deadline, wait_bound=60.0,
               version=None, tctx=None):
        """Admit one request. Returns the parked :class:`Request`, or
        an ``("overloaded", info)`` verdict tuple when the queue is at
        depth — the caller relays it as the retriable shed reply."""
        with self._cv:
            if self._stopped:
                return ("draining", {"reason": "batcher stopped"})
            if len(self._queue) + self._inflight >= self._depth:
                self._c["shed_queue_full"].inc()
                return ("overloaded",
                        {"queue_depth": self._depth,
                         "queued": len(self._queue) + self._inflight})
            req = Request(rid, arrays, rows, deadline,
                          wait_bound=wait_bound, version=version,
                          tctx=tctx)
            self._queue.append(req)
            self._queued_rows += rows
            self._g["queue_hwm"].set_max(len(self._queue))
            self._queued_g.set(len(self._queue) + self._inflight)
            self._cv.notify_all()
            return req

    # -- the flush loop ----------------------------------------------------
    def _take_batch(self):
        """Wait for work, honor the batch deadline, pop one batch.
        Returns (requests, expired) or (None, None) on stop."""
        max_rows = self._engine.max_bucket
        with self._cv:
            while True:
                if self._stopped and not self._queue:
                    return None, None
                if self._queue:
                    oldest = self._queue[0]
                    flush_at = oldest.enq_t + self._deadline_s
                    now = time.monotonic()
                    if (self._queued_rows >= max_rows
                            or now >= flush_at or self._stopped):
                        break
                    self._cv.wait(timeout=max(0.001, flush_at - now))
                else:
                    # idle tick: bounded, re-checks stop
                    self._cv.wait(timeout=0.1)
            batch, expired, rows = [], [], 0
            now = time.monotonic()
            while self._queue:
                req = self._queue[0]
                if req.deadline is not None and now >= req.deadline:
                    # expiry is decided HERE, at dequeue — an expired
                    # request never reaches the device
                    self._queue.popleft()
                    self._queued_rows -= req.rows
                    expired.append(req)
                    continue
                if rows + req.rows > max_rows:
                    break           # whole requests only; next flush
                if batch and req.version != batch[0].version:
                    break           # one coherent version per batch;
                    #                 the other version flushes next
                self._queue.popleft()
                self._queued_rows -= req.rows
                batch.append(req)
                rows += req.rows
            self._inflight = len(batch)
            return batch, expired

    def _flush_loop(self):
        while True:
            batch, expired = self._take_batch()
            if batch is None:
                return
            for req in expired:
                self._c["expired"].inc()
                req.resolve(("expired",
                             {"rid": req.rid,
                              "late_ms": round((time.monotonic()
                                                - req.deadline) * 1e3,
                                               3)}))
            if batch:
                self._dispatch(batch)
            with self._cv:
                self._inflight = 0
                self._queued_g.set(len(self._queue))
                self._cv.notify_all()

    def _dispatch(self, batch):
        rows = sum(r.rows for r in batch)
        try:
            act = _fault.fire("serve.batch", op="batch",
                              key="rows=%d" % rows, server=self._server)
        except BaseException as e:
            # an injected kill/sever mid-batch: this replica is going
            # down — the batch's clients see their connections die and
            # replay their request ids on the surviving replica
            self._c["batch_faults"].inc()
            for req in batch:
                req.resolve(("err", "replica failed mid-batch: %s" % e))
            return
        if act == "drop":
            self._c["batch_faults"].inc()
            for req in batch:
                req.resolve(("err", "batch dropped (injected)"))
            return
        arrays = [
            _np.concatenate([_np.asarray(r.arrays[i]) for r in batch])
            for i in range(len(self._engine.data_names))]
        # the first traced request of the batch carries the span (a
        # batch mixes traced and untraced requests freely)
        tctx = next((r.tctx for r in batch if r.tctx is not None), None)
        t0 = time.perf_counter()
        try:
            if tctx is None:
                outs, answered = self._engine.predict_versioned(
                    arrays, rows=rows, version=batch[0].version)
            else:
                with _obs.adopt(tctx), \
                        _obs.span("serve.batch.dispatch", rows=rows,
                                  requests=len(batch)):
                    outs, answered = self._engine.predict_versioned(
                        arrays, rows=rows, version=batch[0].version)
        except Exception as e:
            for req in batch:
                req.resolve(("err", "predict failed: %s: %s"
                             % (type(e).__name__, e)))
            return
        _SB_FLUSH_MS.observe((time.perf_counter() - t0) * 1e3)
        self._c["batches"].inc()
        self._c["batched_rows"].inc(rows)
        self._c["batched_requests"].inc(len(batch))
        self._g["max_batch_rows"].set_max(rows)
        self._g["max_batch_requests"].set_max(len(batch))
        lo = 0
        for req in batch:
            hi = lo + req.rows
            req.resolve(("ok", tuple(o[lo:hi] for o in outs),
                         {"batch_rows": rows,
                          "batch_requests": len(batch),
                          "version": answered}))
            lo = hi

    # -- lifecycle ---------------------------------------------------------
    def pending(self):
        with self._cv:
            return len(self._queue) + self._inflight

    def drain(self, timeout=30.0):
        """Flush everything already admitted, then stop the thread.
        The server must have stopped admissions FIRST (its draining
        flag), or this races fresh submits. Bounded: returns False if
        the queue did not empty in time."""
        deadline = time.monotonic() + timeout
        with self._cv:
            self._stopped = True
            self._cv.notify_all()
            while self._queue or self._inflight:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._cv.wait(timeout=min(0.1, left))
        self._thread.join(timeout=max(0.1, deadline - time.monotonic()))
        return True

    def stop(self):
        """Hard stop (crash path): fail everything still queued."""
        with self._cv:
            self._stopped = True
            pend = list(self._queue)
            self._queue.clear()
            self._queued_rows = 0
            self._cv.notify_all()
        for req in pend:
            req.resolve(("err", "server stopped"))
        self._thread.join(timeout=5.0)
        self.release_metrics()

    def stats(self):
        out = {f: s.value for f, s in self._c.items()}
        out.update({f: s.value for f, s in self._g.items()})
        with self._cv:
            out["queued"] = len(self._queue)
        return out

    def release_metrics(self):
        """Return the registry series (replaced/stopped batchers must
        not hold cardinality slots); the local stats() keeps working
        on the detached series."""
        for s in list(self._c.values()) + list(self._g.values()):
            s.drop()
        self._queued_g.drop()


# ---------------------------------------------------------------------------
# Continuous batching for autoregressive generation (ISSUE 17).
#
# Where DynamicBatcher coalesces-flushes-disbands, the scheduler keeps
# ONE in-flight decode batch alive and lets sequences join and leave it
# at every step boundary: a finished sequence frees its slot, a queued
# prefill is adopted into a free slot — the decode batch never drains.
# Per-step cost is constant (the decode program is compiled for a fixed
# slot capacity; inactive slots compute garbage), so aggregate tokens/s
# scales with the number of ACTIVE sequences — the continuous-batching
# throughput story ci/check_generate_perf.py pins.
#
# One step stays in flight per lane: the decode program keeps the next
# step's feed on the device, so a turn of a lane dispatches step n+1
# FIRST and only then reads step n's tokens (the one host read a step),
# emits them and admits. The host's bookkeeping (eos, max_new, deadline,
# freeing a slot) therefore runs one dispatch late, and the device never
# waits for it. At dispatch the lane notes who sat in each slot; a row
# is emitted only to the sequence that sat there then and still does.
# The row a step computed for a sequence that had already left (it
# finished, expired or was dropped in the step before) is a DISCARDED
# row: never emitted, never counted in ``tokens``; it lands at most at
# plen + max_new - 1 <= cache_len - 1, so no cache grows for it. A
# sequence adopted into a freed slot is adopted AFTER the step in flight
# (gen_adopt consumes the state that step returned and overwrites the
# slot's token, position and state rows), so its first decode token
# comes from the step after, and the in-flight step's stale row for
# that slot never reaches it.
#
# Versions: a sequence's weight version resolves ONCE at admission and
# the store tuple rides the sequence's decode LANE — a packed batch of
# slots all on one version. A hot-swap never tears an in-flight
# sequence: its lane keeps the resolved store alive by reference while
# new admissions open a lane on the new version; the old lane drains
# naturally. A replayed sequence that already streamed tokens pins its
# admission version (engine.store_exact) — never a silent rebind.
# ---------------------------------------------------------------------------

_GEN_COUNTERS = {
    "sequences": _obs.counter(
        "serve.gen.sequences", "generate sequences admitted", ("inst",)),
    "finished": _obs.counter(
        "serve.gen.finished", "sequences finished (eos/len)", ("inst",)),
    "expired": _obs.counter(
        "serve.gen.expired", "sequences expired (at dequeue or "
        "mid-generation between decode steps)", ("inst",)),
    "shed_queue_full": _obs.counter(
        "serve.gen.shed_queue_full", "generate submits shed at depth",
        ("inst",)),
    "steps": _obs.counter(
        "serve.gen.steps", "decode steps dispatched", ("inst",)),
    "tokens": _obs.counter(
        "serve.gen.tokens", "tokens generated (decode + prefill first "
        "tokens)", ("inst",)),
    "prefills": _obs.counter(
        "serve.gen.prefills", "prefill dispatches", ("inst",)),
    "step_faults": _obs.counter(
        "serve.gen.step_faults", "decode steps lost to injected faults",
        ("inst",)),
    "steps_ahead": _obs.counter(
        "serve.gen.steps_ahead", "decode steps dispatched while the step "
        "before was still unread", ("inst",)),
    "rows_discarded": _obs.counter(
        "serve.gen.rows_discarded", "slot-steps computed for a sequence "
        "that had left its slot the step before", ("inst",)),
}
# The process's own, not a scheduler's: ``stop()`` drops a scheduler's series,
# and these are read after it (``stats()`` has the scheduler's share).
_GEN_PREFILL_ROWS = _obs.counter(
    "serve.gen.prefill_rows", "prompt rows prefilled (true lengths), by "
    "all schedulers of the process")
_GEN_PREFILL_ROWS_PADDED = _obs.counter(
    "serve.gen.prefill_rows_padded", "rows the prefill programs ran for "
    "them: each prompt's bucket")
_GEN_GAUGES = {
    "slots_active": _obs.gauge(
        "serve.gen.slots_active", "in-flight sequences across lanes",
        ("inst",)),
    "lanes": _obs.gauge(
        "serve.gen.lanes", "live decode lanes (one per weight version)",
        ("inst",)),
    "queue_hwm": _obs.gauge(
        "serve.gen.queue_hwm", "generate queue high-water mark",
        ("inst",)),
}
_GEN_TTFT_MS = _obs.histogram(
    "serve.gen.ttft_ms", "admission -> first token wall time")
_GEN_STEP_MS = _obs.histogram(
    "serve.gen.step_ms", "decode step wall time (one XLA dispatch)")
_GEN_INST = itertools.count(1)


def gen_lanes_max():
    """MXTPU_SERVE_GENERATE_LANES: concurrent decode lanes (one per
    weight version in flight) — 2 covers a hot-swap window: the old
    version drains while the new one serves."""
    return max(1, int(os.environ.get("MXTPU_SERVE_GENERATE_LANES", "2")))


class GenRequest:
    """One admitted generate sequence.

    Same two delivery styles as :class:`Request` (blocking
    :meth:`wait` / :meth:`on_resolve`), plus a PER-TOKEN stream:
    ``on_token(idx, tok, version)`` fires for every generated token, in
    order, from the scheduler thread — the wire handler turns each into
    a partial reply frame riding the pipelined sender. The terminal
    ``ok`` reply repeats the FULL token list, so a dropped token frame
    is recovered from the terminal reply, never re-generated."""

    __slots__ = ("rid", "prompt", "max_new", "eos_id", "deadline",
                 "enq_t", "event", "reply", "wait_bound", "version",
                 "pinned", "tokens_out", "on_token", "_cbs", "_cb_lock",
                 "tctx", "store")

    def __init__(self, rid, prompt, max_new, deadline, wait_bound=120.0,
                 version=None, pinned=False, eos_id=None, on_token=None,
                 tctx=None):
        self.rid = rid
        self.prompt = prompt
        self.max_new = int(max_new)
        self.eos_id = eos_id
        self.deadline = deadline
        self.wait_bound = wait_bound
        self.version = version
        self.pinned = bool(pinned)
        self.on_token = on_token
        self.tctx = tctx
        self.store = None              # (params, aux) resolved at admission
        self.enq_t = time.monotonic()
        self.event = threading.Event()
        self.reply = None
        self.tokens_out = []
        self._cbs = []
        self._cb_lock = threading.Lock()

    def emit(self, tok):
        """Record + stream one generated token (scheduler thread only)."""
        idx = len(self.tokens_out)
        self.tokens_out.append(int(tok))
        cb = self.on_token
        if cb is not None:
            cb(idx, int(tok), self.version)

    def on_resolve(self, cb):
        with self._cb_lock:
            if self.reply is None:
                self._cbs.append(cb)
                return
        cb(self.reply)

    def resolve(self, reply):
        with self._cb_lock:
            if self.reply is not None:
                return
            self.reply = reply
            cbs, self._cbs = self._cbs, []
        for cb in cbs:
            cb(reply)
        self.event.set()

    def wait(self, timeout=None):
        timeout = self.wait_bound if timeout is None else timeout
        if not self.event.wait(timeout):
            return ("err", "no decode progress within %.1fs for %s"
                    % (timeout, self.rid))
        return self.reply

    def _finish(self, reason):
        return ("ok", {"rid": self.rid,
                       "tokens": _np.asarray(self.tokens_out, _np.int32),
                       "n": len(self.tokens_out),
                       "version": self.version,
                       "reason": reason})


class _GenLane:
    """One packed decode batch: every slot on ONE weight version whose
    store tuple is held by reference — a swap or store GC can never
    tear the lane's in-flight sequences."""

    __slots__ = ("version", "store", "state", "slot_req", "active",
                 "flight")

    def __init__(self, version, store, state, capacity):
        self.version = version
        self.store = store             # (param_vals, aux_vals)
        self.state = state             # [tok_feed, pos, states]
        self.slot_req = [None] * capacity
        self.active = 0
        # the step dispatched and not yet read: (its tokens on the
        # device, who sat in each slot when it went out)
        self.flight = None


class GenerateScheduler:
    """Continuous decode scheduler in front of one generative
    :class:`InferenceEngine`."""

    def __init__(self, engine, queue_depth, server=None, slots=None,
                 lanes=None):
        from .engine import gen_slots, gen_max_new
        self._engine = engine
        self._depth = int(queue_depth)
        self._slots = int(slots) if slots else gen_slots()
        self._max_lanes = int(lanes) if lanes else gen_lanes_max()
        self._max_new_cap = gen_max_new()
        self._server = server
        self._cv = threading.Condition()
        self._queue = collections.deque()
        self._lanes = {}               # version -> _GenLane
        self._active = 0
        self._stopped = False
        self._killed = None            # hard-stop error message
        inst = "g%d" % next(_GEN_INST)
        self._c = {f: m.labels(inst) for f, m in _GEN_COUNTERS.items()}
        self._g = {f: m.labels(inst) for f, m in _GEN_GAUGES.items()}
        self._prefill_rows = [0, 0]      # true, padded; under _cv
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="mxtpu-serve-generate")
        self._thread.start()

    # -- admission ---------------------------------------------------------
    def submit(self, rid, prompt, max_new, deadline, wait_bound=120.0,
               version=None, pinned=False, eos_id=None, on_token=None,
               tctx=None):
        """Admit one sequence. Returns the parked :class:`GenRequest`
        or a verdict tuple: ``overloaded`` (queue at depth, retriable),
        ``draining``, or ``err`` (a pinned replay version no longer
        resident — honest refusal beats a torn stream)."""
        prompt = _np.asarray(prompt).reshape(-1)
        plen = int(prompt.shape[0])
        spec = self._engine.generate_spec()
        cache_len = spec["cache_len"]
        if plen < 1 or plen >= cache_len:
            return ("err", "prompt length %d out of range [1, %d)"
                    % (plen, cache_len))
        self._engine.gen_bucket_for(plen)     # raises -> caller's err
        max_new = max(1, min(int(max_new), self._max_new_cap,
                             cache_len - plen))
        if pinned and version is not None:
            store = self._engine.store_exact(version)
            if store is None:
                return ("err", "weight version %r is no longer resident"
                               " — cannot replay a pinned sequence"
                        % (version,))
            answered = int(version)
        else:
            params, aux, answered = self._engine._resolve_store(version)
            store = (params, aux)
        with self._cv:
            if self._stopped:
                return ("draining", {"reason": "scheduler stopped"})
            if len(self._queue) + self._active >= self._depth:
                self._c["shed_queue_full"].inc()
                return ("overloaded",
                        {"queue_depth": self._depth,
                         "queued": len(self._queue) + self._active})
            req = GenRequest(rid, prompt, max_new, deadline,
                             wait_bound=wait_bound, version=answered,
                             pinned=pinned, eos_id=eos_id,
                             on_token=on_token, tctx=tctx)
            req.store = store
            self._queue.append(req)
            self._c["sequences"].inc()
            self._g["queue_hwm"].set_max(len(self._queue))
            self._cv.notify_all()
            return req

    # -- the scheduler thread ----------------------------------------------
    def _run(self):
        # the lane table (self._lanes) is OWNED by this thread: every
        # touch — placement, stepping, retirement, the fail-everything
        # teardown — happens here. stop() never reaches in; it posts
        # _killed and joins, and THIS loop runs the teardown on its way
        # out, so a hard stop can never race a decode step over the
        # lane it is tearing down.
        while True:
            with self._cv:
                if self._killed is not None:
                    break
                if not self._queue and self._active == 0:
                    if self._stopped:
                        return
                    self._cv.wait(timeout=0.05)
                    continue
            try:
                self._admit_queued()
                self._step_lanes()
            except BaseException as e:
                # an injected kill/sever at serve.step: this replica is
                # going down — every in-flight and queued sequence fails
                # fast; clients replay on the surviving replica
                self._c["step_faults"].inc()
                self._fail_all("replica failed mid-batch: %s" % e)
                return
        self._fail_all(self._killed)

    def _admit_queued(self):
        """Move queued sequences into free slots: prefill + adopt at
        the step boundary — the in-flight batch never drains to admit.
        Expiry is ALSO decided here (dequeue) for queued sequences."""
        with self._cv:
            pending = list(self._queue)
            self._queue.clear()
        if pending:
            with _obs.span("serve.gen.admit", queued=len(pending)):
                self._admit(pending)

    def _admit(self, pending):
        keep, expired = [], []
        now = time.monotonic()
        for req in pending:
            if req.deadline is not None and now >= req.deadline:
                expired.append(req)
                continue
            lane = self._lane_for(req)
            if lane is None:
                keep.append(req)       # no lane/slot yet: stays queued
                continue
            slot = lane.slot_req.index(None)
            self._prefill_into(req, lane, slot)
        with self._cv:
            self._queue.extendleft(reversed(keep))
            self._cv.notify_all()
        for req in expired:
            self._c["expired"].inc()
            req.resolve(("expired",
                         {"rid": req.rid, "generated": 0,
                          "late_ms": round((now - req.deadline) * 1e3,
                                           3)}))

    def _lane_for(self, req):
        """The lane answering ``req.version`` with a free slot, created
        on demand (evicting an idle lane when at the lane cap), or None
        when the sequence cannot be placed this step."""
        lane = self._lanes.get(req.version)
        if lane is not None:
            return lane if lane.active < len(lane.slot_req) else None
        if len(self._lanes) >= self._max_lanes:
            idle = [v for v, ln in self._lanes.items() if ln.active == 0]
            if not idle:
                return None
            del self._lanes[idle[0]]
        lane = _GenLane(req.version, req.store,
                        self._engine.gen_state_init(self._slots),
                        self._slots)
        self._lanes[req.version] = lane
        self._g["lanes"].set_max(len(self._lanes))
        return lane

    def _prefill_into(self, req, lane, slot):
        self._c["prefills"].inc()
        plen = int(req.prompt.shape[0])
        padded = self._engine.gen_bucket_for(plen)
        with self._cv:
            self._prefill_rows[0] += plen
            self._prefill_rows[1] += padded
        _GEN_PREFILL_ROWS.default().inc(plen)
        _GEN_PREFILL_ROWS_PADDED.default().inc(padded)
        try:
            with _obs.span("serve.gen.prefill", rid=req.rid, plen=plen):
                first, rows = self._engine.gen_prefill(
                    req.prompt, lane.store[0], lane.store[1])
        except Exception as e:
            req.resolve(("err", "prefill failed: %s: %s"
                         % (type(e).__name__, e)))
            return
        with _obs.span("serve.gen.first_read", rid=req.rid):
            tok0 = int(_jax.device_get(first)[0])
        _GEN_TTFT_MS.observe((time.monotonic() - req.enq_t) * 1e3)
        self._c["tokens"].inc()
        req.emit(tok0)
        if (req.max_new <= 1
                or (req.eos_id is not None and tok0 == req.eos_id)):
            self._c["finished"].inc()
            req.resolve(req._finish(
                "eos" if req.eos_id is not None and tok0 == req.eos_id
                else "len"))
            return
        with _obs.span("serve.gen.adopt", rid=req.rid, slot=slot):
            lane.state = self._engine.gen_adopt(
                lane.state, first, plen, rows, slot)
        lane.slot_req[slot] = req
        lane.active += 1
        with self._cv:
            self._active += 1
        self._g["slots_active"].set(self._active)

    def _step_lanes(self):
        for lane in list(self._lanes.values()):
            if lane.active == 0:
                continue
            act = _fault.fire("serve.step", op="generate",
                              key="active=%d" % lane.active,
                              server=self._server)
            if act == "drop":
                self._c["step_faults"].inc()
                for slot, req in enumerate(lane.slot_req):
                    if req is not None:
                        self._free(lane, slot)
                        req.resolve(("err",
                                     "decode step dropped (injected)"))
                continue
            with _obs.span("serve.gen.step", active=lane.active,
                           version=lane.version):
                self._step(lane)
        self._g["slots_active"].set(self._active)
        # retire empty lanes off the current stable version — a drained
        # hot-swap lane releases its store reference here
        stable = self._engine.version_state()["version"]
        for v in [v for v, ln in self._lanes.items()
                  if ln.active == 0 and v != stable]:
            del self._lanes[v]

    def _step(self, lane):
        """One turn of one lane: dispatch the next decode step from the
        state the device holds, THEN the one host read, of the step
        before, and every token of it out (emit, free, resolve)."""
        t0 = time.perf_counter()
        ahead, lane.flight = lane.flight, None
        if self._owes_a_token(lane, ahead):
            with _obs.span("serve.gen.step.dispatch"):
                nxt, lane.state = self._engine.gen_step(
                    lane.state, lane.store[0], lane.store[1])
                # the copy starts as soon as THIS step has run, whatever
                # is queued behind it by then
                nxt.copy_to_host_async()
            lane.flight = (nxt, tuple(lane.slot_req))
            self._c["steps"].inc()
            if ahead is not None:
                self._c["steps_ahead"].inc()
        if ahead is None:
            return
        # a row belongs to the sequence that sat in the slot at dispatch
        # and still does; any other sequence's row is a discarded one
        nxt, sat = ahead
        live = [slot for slot, req in enumerate(sat)
                if req is not None and lane.slot_req[slot] is req]
        self._c["rows_discarded"].inc(
            len(sat) - sat.count(None) - len(live))
        if not live:
            return                        # nobody's step: never read
        with _obs.span("serve.gen.step.read"):
            toks = _jax.device_get(nxt)   # the ONE per-step host read
        _GEN_STEP_MS.observe((time.perf_counter() - t0) * 1e3)
        self._c["tokens"].inc(len(live))
        now = time.monotonic()
        with _obs.span("serve.gen.step.emit"):
            self._emit_step(lane, live, toks, now)

    @staticmethod
    def _owes_a_token(lane, ahead):
        """Whether a sequence of the lane still lacks a token once the
        step in flight is in. ``max_new`` the host can count ahead; an
        ``eos`` or a deadline it sees only in the tokens, a step late."""
        sat = ahead[1] if ahead is not None else [None] * len(lane.slot_req)
        return any(
            req is not None
            and len(req.tokens_out) + (sat[slot] is req) < req.max_new
            for slot, req in enumerate(lane.slot_req))

    def _emit_step(self, lane, live, toks, now):
        for slot in live:
            req = lane.slot_req[slot]
            tok = int(toks[slot])
            req.emit(tok)
            if ((req.eos_id is not None and tok == req.eos_id)
                    or len(req.tokens_out) >= req.max_new):
                self._free(lane, slot)
                self._c["finished"].inc()
                req.resolve(req._finish(
                    "eos" if req.eos_id is not None
                    and tok == req.eos_id else "len"))
            elif req.deadline is not None and now >= req.deadline:
                # the mid-generation expiry fix (ISSUE 17 satellite):
                # a budget exhausted BETWEEN decode steps frees the
                # slot now instead of decoding forever
                self._free(lane, slot)
                self._c["expired"].inc()
                req.resolve(("expired",
                             {"rid": req.rid,
                              "generated": len(req.tokens_out),
                              "late_ms": round(
                                  (now - req.deadline) * 1e3, 3)}))

    def _free(self, lane, slot):
        lane.slot_req[slot] = None
        lane.active -= 1
        if lane.active == 0 and lane.flight is not None:
            # every row of the step in flight is a discarded one: it is
            # never read and nobody waits for it
            sat = lane.flight[1]
            self._c["rows_discarded"].inc(len(sat) - sat.count(None))
            lane.flight = None
        with self._cv:
            self._active -= 1
            self._cv.notify_all()

    def _fail_all(self, msg):
        with self._cv:
            pend = list(self._queue)
            self._queue.clear()
            self._killed = msg
            self._cv.notify_all()
        for lane in self._lanes.values():
            for slot, req in enumerate(lane.slot_req):
                if req is not None:
                    lane.slot_req[slot] = None
                    req.resolve(("err", msg))
            lane.active = 0
        self._lanes.clear()
        with self._cv:
            self._active = 0
        for req in pend:
            req.resolve(("err", msg))

    # -- lifecycle ---------------------------------------------------------
    def pending(self):
        with self._cv:
            return len(self._queue) + self._active

    def drain(self, timeout=30.0):
        """Finish every admitted sequence, then stop the thread. The
        server must have stopped admissions FIRST. Bounded."""
        deadline = time.monotonic() + timeout
        with self._cv:
            self._stopped = True
            self._cv.notify_all()
            while self._queue or self._active:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._cv.wait(timeout=min(0.1, left))
        self._thread.join(timeout=max(0.1, deadline - time.monotonic()))
        return True

    def stop(self):
        """Hard stop (crash path): fail everything queued + in flight.
        The teardown itself runs ON the scheduler thread (it owns the
        lane table); this just posts the verdict and waits it out. A
        thread that already exited left nothing queued or in flight:
        graceful drain returns only once both are empty, and the
        step-fault path tears everything down on its way out."""
        with self._cv:
            self._stopped = True
            self._killed = "server stopped"
            self._cv.notify_all()
        self._thread.join(timeout=5.0)
        self.release_metrics()

    def stats(self):
        # what the engine summed on the device (per-expert load) is folded
        # into the registry whenever someone asks how things stand: the
        # registry's gauges then say how the time since the last asking went
        self._engine.gen_publish_sums()
        out = {f: s.value for f, s in self._c.items()}
        out.update({f: s.value for f, s in self._g.items()})
        with self._cv:
            out["prefill_rows"], out["prefill_rows_padded"] = \
                self._prefill_rows
            out["queued"] = len(self._queue)
            out["active"] = self._active
        return out

    def release_metrics(self):
        for s in list(self._c.values()) + list(self._g.values()):
            s.drop()
