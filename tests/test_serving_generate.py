"""Continuous-batching generation fast tier (ISSUE 17): the
cached-attention op's prefill/decode bit-compat, the engine's AOT
prefill/adopt/decode programs against a full-recompute oracle, the
continuous scheduler's join/leave semantics, and the wire streaming
protocol under faults — kill -9 mid-generation, dropped token frames,
live hot-swap, and mid-generation expiry (point=serve.step).

The two-process kill -9 drill with a real trainer publishing swaps
lives in tests/test_dist_launch.py; the perf pin (zero retraces, no
host syncs, batching wins) in ci/check_generate_perf.py.
"""
import os
import threading
import time

import numpy as np
import pytest

import mxtpu as mx
from mxtpu import fault
from mxtpu import kvstore_async as ka
from mxtpu.serving import (DeadlineExceeded, InferenceEngine,
                           ModelServer, ServingClient)

V, D, S = 17, 8, 16


@pytest.fixture(autouse=True)
def _serving_knobs(monkeypatch):
    monkeypatch.setenv("MXTPU_PS_HEARTBEAT", "0")
    monkeypatch.setenv("MXTPU_SERVE_GENERATE_SLOTS", "4")
    monkeypatch.setenv("MXTPU_SERVE_GENERATE_PREFILL_BUCKETS", "4,8,16")
    monkeypatch.setattr(ka, "_RETRIES", 1)
    monkeypatch.setattr(ka, "_BACKOFF", 0.01)
    monkeypatch.setattr(ka, "_BACKOFF_MAX", 0.05)
    monkeypatch.setattr(ka, "_RECONNECT_TIMEOUT", 0.2)
    monkeypatch.setattr(ka, "_DEAD_AFTER", 2)
    fault.uninstall()
    yield
    fault.uninstall()


def _lm_symbol(cache_len=S, alibi=False):
    data = mx.sym.Variable("data")
    pos = mx.sym.Variable("pos", shape=(0,), dtype="int32")
    kc = mx.sym.Variable("kc", shape=(0, cache_len, D))
    vc = mx.sym.Variable("vc", shape=(0, cache_len, D))
    emb = mx.sym.Embedding(data=data, input_dim=V, output_dim=D,
                           name="emb")
    q = mx.sym.FullyConnected(data=emb, num_hidden=D, flatten=False,
                              name="q")
    k = mx.sym.FullyConnected(data=emb, num_hidden=D, flatten=False,
                              name="k")
    v = mx.sym.FullyConnected(data=emb, num_hidden=D, flatten=False,
                              name="v")
    att = mx.sym.cached_attention(q, k, v, kc, vc, pos, num_heads=2,
                                  alibi=alibi, name="att")
    out = mx.sym.FullyConnected(data=att[0], num_hidden=V,
                                flatten=False, name="proj")
    return mx.sym.Group([out,
                         mx.sym.identity(att[1], name="kc_next"),
                         mx.sym.identity(att[2], name="vc_next")])


def _lm_params(seed=7):
    rng = np.random.RandomState(seed)
    f = lambda *s: rng.randn(*s).astype(np.float32) * 0.5  # noqa: E731
    return {"emb_weight": f(V, D),
            "q_weight": f(D, D), "q_bias": np.zeros(D, np.float32),
            "k_weight": f(D, D), "k_bias": np.zeros(D, np.float32),
            "v_weight": f(D, D), "v_bias": np.zeros(D, np.float32),
            "proj_weight": f(V, D), "proj_bias": np.zeros(V, np.float32)}


def _engine(seed=7, alibi=False, cache_len=S):
    return InferenceEngine(_lm_symbol(cache_len, alibi=alibi),
                           _lm_params(seed), {},
                           data_shapes={"data": (1,)}, buckets=(1,))


def _oracle(eng, prompt, n):
    """Greedy continuation by FULL RECOMPUTE: re-prefill the growing
    prompt each step — no KV reuse, the independent reference the
    cached decode path must match bit-for-bit."""
    import jax
    store = eng._resolve_store(None)
    cur = list(prompt)
    out = []
    for _ in range(n):
        first, _rows = eng.gen_prefill(np.asarray(cur, np.int32),
                                       store[0], store[1])
        t = int(jax.device_get(first)[0])
        out.append(t)
        cur.append(t)
    return out


# ---------------------------------------------------------------------------
# the op: prefill chunk == token-at-a-time decode chain
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("alibi", [False, True])
def test_cached_attention_prefill_equals_decode_chain(alibi):
    """Attending T tokens in one prefill call is bit-compatible with
    feeding them one at a time through the cache — with and without
    the ALiBi distance bias (absolute cache positions make the bias
    identical across the two schedules)."""
    import jax.numpy as jnp
    from mxtpu.ops.nn import cached_attention
    rng = np.random.RandomState(0)
    B, T, H = 2, 6, 2
    q = rng.randn(B, T, D).astype(np.float32)
    k = rng.randn(B, T, D).astype(np.float32)
    v = rng.randn(B, T, D).astype(np.float32)
    zeros = np.zeros((B, S, D), np.float32)
    full, kn, vn = cached_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(zeros), jnp.asarray(zeros),
        jnp.zeros((B,), jnp.int32), num_heads=H, alibi=alibi)
    full = np.asarray(full)
    assert np.allclose(np.asarray(kn)[:, :T], k, atol=1e-6)
    kc = vc = jnp.asarray(zeros)
    for t in range(T):
        step, kc, vc = cached_attention(
            jnp.asarray(q[:, t:t + 1]), jnp.asarray(k[:, t:t + 1]),
            jnp.asarray(v[:, t:t + 1]), kc, vc,
            jnp.full((B,), t, jnp.int32), num_heads=H, alibi=alibi)
        assert np.allclose(np.asarray(step)[:, 0], full[:, t],
                           atol=1e-5), "diverged at step %d" % t


def test_cached_attention_alibi_changes_the_answer():
    """The bias is actually applied (not silently dropped), and the
    JSON attr round-trip spelling \"True\"/\"False\" is honoured."""
    import jax.numpy as jnp
    from mxtpu.ops.nn import cached_attention
    rng = np.random.RandomState(1)
    q = rng.randn(1, 4, D).astype(np.float32)
    zeros = np.zeros((1, S, D), np.float32)
    run = lambda a: np.asarray(cached_attention(  # noqa: E731
        jnp.asarray(q), jnp.asarray(q), jnp.asarray(q),
        jnp.asarray(zeros), jnp.asarray(zeros),
        jnp.zeros((1,), jnp.int32), num_heads=2, alibi=a)[0])
    assert not np.allclose(run(True), run(False))
    assert np.array_equal(run("True"), run(True))
    assert np.array_equal(run("False"), run(False))


# ---------------------------------------------------------------------------
# the engine: contract detection + decode vs full recompute
# ---------------------------------------------------------------------------

def test_engine_detects_generate_contract():
    eng = _engine()
    assert eng.is_generative
    spec = eng.generate_spec()
    assert spec["token_input"] == "data"
    assert sorted(spec["states"]) == ["kc", "vc"]
    assert spec["cache_len"] == S
    assert spec["prefill_buckets"] == [4, 8, 16]
    plain = mx.sym.FullyConnected(mx.sym.Variable("data"),
                                  num_hidden=3, name="fc")
    eng2 = InferenceEngine(plain, {"fc_weight": np.zeros((3, 4), "f"),
                                   "fc_bias": np.zeros(3, "f")}, {},
                           {"data": (4,)}, buckets=(1,), warm=False)
    assert not eng2.is_generative
    assert eng2.generate_spec() is None


@pytest.mark.parametrize("alibi", [False, True])
def test_decode_matches_full_recompute_zero_retrace(alibi):
    """The served greedy continuation (cached, slot-packed, donated
    decode) equals the full-recompute oracle, and a second sequence
    through the warmed menu compiles NOTHING new."""
    eng = _engine(alibi=alibi)
    ref = _oracle(eng, [3, 1, 4], 10)
    srv = ModelServer(eng, port=0, model_name="lm").start()
    try:
        cli = ServingClient(addrs=[srv.address])
        toks, info = cli.generate2([3, 1, 4], max_new=10, model="lm")
        assert toks == ref, (toks, ref)
        assert info["reason"] == "len" and info["version"] == 0
        before = eng.cache.compiles
        toks2, _ = cli.generate2([3, 1, 4], max_new=10, model="lm")
        assert toks2 == ref
        assert eng.cache.compiles == before, \
            "steady-state decode retraced"
    finally:
        srv.stop()


def test_eos_stops_early():
    eng = _engine()
    ref = _oracle(eng, [3, 1, 4], 10)
    srv = ModelServer(eng, port=0, model_name="lm").start()
    try:
        cli = ServingClient(addrs=[srv.address])
        j = next(i for i in range(1, 10) if ref[i] not in ref[:i])
        toks, info = cli.generate2([3, 1, 4], max_new=10, model="lm",
                                   eos_id=ref[j])
        assert toks == ref[:j + 1], (toks, ref)
        assert info["reason"] == "eos"
    finally:
        srv.stop()


def test_generate_against_oneshot_model_is_an_error():
    plain = mx.sym.FullyConnected(mx.sym.Variable("data"),
                                  num_hidden=3, name="fc")
    eng = InferenceEngine(plain, {"fc_weight": np.zeros((3, 4), "f"),
                                  "fc_bias": np.zeros(3, "f")}, {},
                          {"data": (4,)}, buckets=(1,), warm=False)
    srv = ModelServer(eng, port=0, model_name="t").start()
    try:
        cli = ServingClient(addrs=[srv.address])
        with pytest.raises(RuntimeError, match="not generative"):
            cli.generate2([1, 2], max_new=4, model="t")
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# the scheduler: continuous batching — more sequences than slots
# ---------------------------------------------------------------------------

def test_continuous_batching_joins_and_leaves():
    """7 sequences contend for 4 decode slots: every one finishes with
    the SAME tokens it gets solo (composition independence), and the
    queue high-water mark proves some of them actually waited."""
    eng = _engine()
    refs = {j: _oracle(eng, [1 + (j % 5), 2, 3], 6) for j in range(7)}
    srv = ModelServer(eng, port=0, model_name="lm").start()
    try:
        cli = ServingClient(addrs=[srv.address])
        results, errs = {}, []

        def run(j):
            try:
                results[j] = cli.generate2([1 + (j % 5), 2, 3],
                                           max_new=6, model="lm")[0]
            except Exception as e:   # pragma: no cover - surfaced below
                errs.append((j, e))
        ths = [threading.Thread(target=run, args=(j,)) for j in range(7)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=60)
        assert not errs, errs
        assert results == refs
        st = srv.stats()["models"]["lm"]["scheduler"]
        assert st["sequences"] == 7
        assert st["queue_hwm"] >= 1, \
            "7 sequences on 4 slots never queued?"
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# one step in flight: the host reads a step's tokens one dispatch late
# ---------------------------------------------------------------------------

def _compile_menu(eng, slots):
    """Every generate program of ``slots`` slots built; returns the
    compile count from which retraces are counted."""
    for length in eng.gen_prefill_menu():
        eng.gen_prefill_program(length)
    eng.gen_decode_program(slots)
    eng.gen_adopt_program(slots)
    return eng.cache.stats()["compiles"]


def _first_new_token(eng, prompt, n):
    """A token of the oracle's continuation that has not come before it:
    as ``eos_id`` it stops the sequence there and not earlier."""
    ref = _oracle(eng, prompt, n)
    return next(t for i, t in enumerate(ref) if i and t not in ref[:i])


class _Held:
    """A GenerateScheduler whose thread is parked inside the first
    sequence's first-token callback while the test queues the rest, so
    who is admitted when does not hang on the host's pace: ``first``
    gets one decode step to itself, then every other sequence is
    admitted in one pass, in the order submitted."""

    def __init__(self, eng, slots=4, depth=32):
        from mxtpu.serving.batcher import GenerateScheduler
        self.eng = eng
        self.compiles = _compile_menu(eng, slots)
        self.sched = GenerateScheduler(eng, depth, slots=slots)
        self.seen = {}                 # rid -> [(idx, tok)] as streamed
        self.turns = 0
        step_lanes = self.sched._step_lanes

        def counted():
            self.turns += 1
            step_lanes()
        self.sched._step_lanes = counted

    def submit_all(self, seqs):
        """``seqs``: (rid, prompt, max_new[, eos_id[, deadline]])."""
        gate = threading.Event()
        reqs = []
        for i, (rid, prompt, max_new, *rest) in enumerate(seqs):
            eos_id = rest[0] if rest else None
            deadline = (time.monotonic() + rest[1]
                        if len(rest) > 1 else None)
            got = self.seen.setdefault(rid, [])

            def on_token(idx, tok, _v, got=got, hold=(i == 0)):
                got.append((idx, tok))
                if hold and idx == 0:
                    assert gate.wait(30)
            reqs.append(self.sched.submit(rid, prompt, max_new, deadline,
                                          eos_id=eos_id, on_token=on_token))
            if i == 0:
                until = time.monotonic() + 30
                while not got and time.monotonic() < until:
                    time.sleep(0.001)
        gate.set()
        return reqs

    def retraces(self):
        return self.eng.cache.stats()["compiles"] - self.compiles

    def gone(self):
        self.sched._thread.join(timeout=10)
        return not self.sched._thread.is_alive()


@pytest.fixture
def held():
    made = []

    def make(eng=None, **kw):
        made.append(_Held(eng or _engine(), **kw))
        return made[-1]
    yield make
    for h in made:
        h.sched.stop()


def test_streams_match_recompute_as_sequences_join_and_leave(held):
    """Eleven sequences of unequal prompt and output lengths on 4 slots:
    a slot is freed while the next step is already in flight and is
    adopted again at once. Every stream, as streamed and as replied, is
    the full-recompute oracle's: the in-flight step's stale row for a
    freed slot reaches nobody, and nothing retraces."""
    h = held()
    rng = np.random.RandomState(5)
    seqs = [("r%d" % j, rng.randint(1, V, 1 + (3 * j) % 7).tolist(),
             2 + (5 * j) % 8) for j in range(11)]
    refs = {rid: _oracle(h.eng, prompt, n) for rid, prompt, n in seqs}
    replies = [r.wait(120) for r in h.submit_all(seqs)]
    assert all(r[0] == "ok" for r in replies), replies
    for (rid, _p, n), rep in zip(seqs, replies):
        assert list(rep[1]["tokens"]) == refs[rid], rid
        assert rep[1]["n"] == n and rep[1]["reason"] == "len"
        assert h.seen[rid] == list(enumerate(refs[rid])), rid
    st = h.sched.stats()
    assert st["tokens"] == sum(n for _r, _p, n in seqs)
    assert st["rows_discarded"] >= 1        # somebody left a live lane
    assert h.retraces() == 0


# who leaves, how, and beside whom -> discarded rows. ``r0`` is admitted
# one step before the rest and, where it is there, outlives them.
_LEAVING = {
    # a companion still owes tokens: the step after the leaver's last
    # was dispatched before the host saw it leave
    "len": ([("r1", [3, 1, 4], 5)], 1),
    "eos": ([("r1", [3, 1, 4], 10, "eos")], 1),
    "deadline": ([("r1", [3, 1, 4], 10, None, 0.2)], 1),
    "three_leave": ([("r1", [3, 1, 4], 3), ("r2", [2, 7], 5),
                     ("r3", [5], 7)], 3),
    # alone: ``max_new`` the host counts ahead and dispatches nothing;
    # an ``eos`` it cannot, and drops the one step unread
    "len_alone": ([], 0),
    "eos_alone": ([], 1),
}


@pytest.mark.parametrize("case", sorted(_LEAVING))
def test_leaving_is_seen_one_step_late_on_the_device_only(held, case):
    """``eos``, ``max_new`` and a deadline take effect on the host one
    dispatch after the device produced the token: ``n`` is exact,
    nothing follows an ``eos``, and each sequence that leaves a lane
    with work left costs exactly one discarded row."""
    h = held()
    others, discarded = _LEAVING[case]
    seqs = [("r0", [3, 1, 4], 12, "eos") if case == "eos_alone"
            else ("r0", [1, 2, 3], 12)] + others
    seqs = [(rid, prompt, n, *(_first_new_token(h.eng, prompt, n)
                               if r == "eos" else r for r in rest))
            for rid, prompt, n, *rest in seqs]
    if case == "deadline":
        fault.install("kind=delay,point=serve.step,delay=0.06,nth=1,"
                      "count=1000")
    replies = [r.wait(120) for r in h.submit_all(seqs)]
    fault.uninstall()
    emitted = 0
    for (rid, prompt, n, *rest), rep in zip(seqs, replies):
        eos_id = rest[0] if rest else None
        want = _oracle(h.eng, prompt, n)
        if len(rest) > 1:
            assert rep[0] == "expired", rep
            got = [t for _i, t in h.seen[rid]]
            assert 1 <= rep[1]["generated"] == len(got) < n
            assert got == want[:len(got)]
            emitted += len(got)
            continue
        assert rep[0] == "ok", rep
        if eos_id is not None:
            want = want[:want.index(eos_id) + 1]
        assert list(rep[1]["tokens"]) == want, rid
        assert rep[1]["n"] == len(want) == len(h.seen[rid])
        assert rep[1]["reason"] == ("eos" if eos_id is not None else "len")
        emitted += len(want)
    st = h.sched.stats()
    assert st["rows_discarded"] == discarded
    assert st["tokens"] == emitted
    if case == "len_alone":
        assert st["steps"] == 11 and st["steps_ahead"] == 10
    # the lane emptied: nothing in flight, and the thread sleeps
    assert all(ln.flight is None for ln in h.sched._lanes.values())
    turns = h.turns
    time.sleep(0.2)
    assert h.turns == turns, "the scheduler spins on an empty lane"
    assert turns <= st["steps"] + len(seqs) + 1


def test_the_row_after_the_last_fits_the_cache(held):
    """``plen + max_new == cache_len``: the one step a finished sequence
    runs beyond its last token writes row ``cache_len - 1``, the last
    there is. Its neighbour's tokens and the next occupant's are the
    oracle's."""
    h = held()
    seqs = [("r0", [1, 2, 3], S - 3),
            ("edge", list(range(1, 11)), S - 10),
            ("next", [4, 4, 2, 1, 3, 6, 5, 7], S - 8)]
    # three slots taken for good, so ``next`` waits for ``edge``'s slot
    seqs[1:1] = [("w1", [2, 2], S - 2), ("w2", [6], S - 1)]
    replies = [r.wait(120) for r in h.submit_all(seqs)]
    assert h.sched.stats()["queue_hwm"] >= 1
    for (rid, prompt, n), rep in zip(seqs, replies):
        assert rep[0] == "ok", rep
        assert rep[1]["n"] == n == S - len(prompt)
        assert list(rep[1]["tokens"]) == _oracle(h.eng, prompt, n), rid
    assert h.sched.stats()["rows_discarded"] >= 1


@pytest.mark.parametrize("how", ["drop", "kill", "drain", "stop"])
def test_a_step_in_flight_when_the_lane_goes_down(held, how):
    """``serve.step`` faults, ``drain()`` and ``stop()`` each land while
    a decode step is in flight. drop: the lane's sequences fail, the
    in-flight tokens go with them, and the next occupants get the
    oracle's tokens. kill: everything fails fast, nothing is left
    pending. drain: every sequence finishes with its exact tokens. stop:
    the rest fail. In each the thread is gone (or asleep) afterwards."""
    h = held()
    seqs = [("r0", [1, 2, 3], 12), ("r1", [3, 1, 4], 12), ("r2", [5], 9)]
    refs = {rid: _oracle(h.eng, p, n) for rid, p, n in seqs}
    if how in ("drop", "kill"):
        fault.install("kind=%s,point=serve.step,nth=4,count=1" % how)
    elif how == "stop":
        fault.install("kind=delay,point=serve.step,delay=0.05,nth=1,"
                      "count=1000")
    reqs = h.submit_all(seqs)
    if how == "drain":
        assert h.sched.drain(60)
    elif how == "stop":
        while len(h.seen["r1"]) < 2:
            time.sleep(0.001)
        assert any(ln.flight is not None
                   for ln in h.sched._lanes.values())
        h.sched.stop()
    replies = [r.wait(60) for r in reqs]
    fault.uninstall()
    st = h.sched.stats()
    if how == "drain":
        for (rid, _p, n), rep in zip(seqs, replies):
            assert rep[0] == "ok" and list(rep[1]["tokens"]) == refs[rid]
        assert h.gone()
        assert st["rows_discarded"] == 2     # the last to finish costs none
    elif how == "drop":
        assert all(r == ("err", "decode step dropped (injected)")
                   for r in replies), replies
        assert st["step_faults"] == 1 and st["active"] == 0
        assert all(ln.flight is None for ln in h.sched._lanes.values())
        # every streamed token was the oracle's, up to the drop
        for rid, _p, _n in seqs:
            got = [t for _i, t in h.seen[rid]]
            assert 1 <= len(got) < 9 and got == refs[rid][:len(got)]
        again = h.sched.submit("again", [3, 1, 4], 12, None).wait(60)
        assert list(again[1]["tokens"]) == refs["r1"]
    else:
        assert all(r[0] == "err" for r in replies), replies
        assert h.gone()
        assert h.sched.pending() == 0 and not h.sched._lanes
        if how == "kill":
            assert st["step_faults"] == 1


def test_every_step_but_a_lanes_first_goes_out_ahead(held):
    """Steady load on a lane that never empties (``r0`` holds its slot
    from the first step to the last): each decode step but the lane's
    first is dispatched while the step before is unread, and nothing
    retraces."""
    h = held()
    seqs = [("r0", [1, 2, 3], 13)] + [
        ("r%d" % j, [1 + j % 5, 2], 3 + j % 2) for j in range(1, 7)]
    replies = [r.wait(120) for r in h.submit_all(seqs)]
    assert all(r[0] == "ok" for r in replies), replies
    st = h.sched.stats()
    assert st["steps"] == 12                  # r0's, prefill token aside
    assert st["steps_ahead"] == st["steps"] - 1
    assert st["rows_discarded"] == 6          # each short one left r0 behind
    assert st["tokens"] == sum(n for _r, _p, n in seqs)
    assert h.retraces() == 0


# ---------------------------------------------------------------------------
# the wire: streamed partials, concurrency, plain-request fallback
# ---------------------------------------------------------------------------

def test_wire_streaming_partials_in_order(monkeypatch):
    eng = _engine()
    ref = _oracle(eng, [1, 2, 3], 6)
    srv = ModelServer(eng, port=0, model_name="lm").start()
    try:
        monkeypatch.setattr(ka, "_LOCAL_ON", False)   # real sockets
        cli = ServingClient(addrs=[srv.address])
        seen = []
        toks, info = cli.generate2(
            [1, 2, 3], max_new=6, model="lm",
            on_token=lambda i, t, v: seen.append((i, t)))
        assert toks == ref
        assert seen == list(enumerate(ref)), seen
        results = {}

        def run(j):
            results[j] = cli.generate2([1 + (j % 5), 2, 3], max_new=5,
                                       model="lm")[0]
        ths = [threading.Thread(target=run, args=(j,)) for j in range(8)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=60)
        assert all(len(v) == 5 for v in results.values()), results
    finally:
        srv.stop()


def test_plain_request_fallback_blocks_for_the_full_answer():
    """A client that cannot stream still gets the terminal reply with
    every token — ``generate`` over plain ``request`` is the
    non-streaming fallback, not an error."""
    eng = _engine()
    srv = ModelServer(eng, port=0, model_name="lm").start()
    conn = None
    try:
        conn = ka._ServerConn(srv.address)
        rep = conn.request("generate", "manual:1",
                           np.asarray([1, 2, 3], np.int32),
                           {"max_new": 4, "model": "lm"})
        assert rep[0] == "ok" and rep[1]["n"] == 4, rep
        assert len(list(rep[1]["tokens"])) == 4
    finally:
        if conn is not None:
            conn.close()
        srv.stop()


def test_hello_advertises_generate_signature():
    eng = _engine()
    srv = ModelServer(eng, port=0, model_name="lm").start()
    try:
        cli = ServingClient(addrs=[srv.address])
        cli.hello()
        sig = cli.models["lm"]["signature"]
        assert "generate" in sig
        assert sig["generate"]["cache_len"] == S
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# faults: the three drill rows (docs/serving.md fault matrix)
# ---------------------------------------------------------------------------

def test_kill_mid_generation_replays_exactly_once(monkeypatch):
    """kill() the active replica after 3 streamed tokens: the client
    replays on the peer with the pinned version and already-delivered
    indices deduped — the user-visible stream is exactly-once, in
    order, never torn across versions."""
    srv0 = ModelServer(_engine(), port=0, model_name="lm").start()
    srv1 = ModelServer(_engine(), port=0, model_name="lm").start()
    try:
        ref, _ = ServingClient(addrs=[srv1.address]).generate2(
            [3, 1, 4], max_new=10, model="lm")
        monkeypatch.setattr(ka, "_LOCAL_ON", False)
        cli = ServingClient(addrs=[srv0.address, srv1.address])
        seen = []

        def on_tok(i, t, v):
            seen.append((i, t, v))
            if i == 2:
                srv0.kill()
        # each decode step takes 20 ms, so the replica cannot have
        # streamed all ten tokens before the third one's callback kills
        # it (on a loaded host it sometimes had, and nothing failed over)
        fault.install("kind=delay,point=serve.step,delay=0.02,nth=1,"
                      "count=1000")
        toks, info = cli.generate2([3, 1, 4], max_new=10, model="lm",
                                   on_token=on_tok)
        assert toks == ref, (toks, ref)
        assert [i for i, _, _ in seen] == list(range(10)), seen
        assert [t for _, t, _ in seen] == ref
        assert all(v == info["version"] for _, _, v in seen)
        assert cli.stats()["failovers"] >= 1
    finally:
        srv1.stop()


def test_dropped_token_frame_never_double_emits():
    """Injected drop of one streamed token frame: the client recovers
    the missing token from the terminal reply — no gap, no double
    emit (the idx dedupe is the at-most-once half of exactly-once)."""
    eng = _engine()
    ref = _oracle(eng, [3, 1, 4], 10)
    srv = ModelServer(eng, port=0, model_name="lm").start()
    fault.install("kind=drop,point=server.send,op=generate,nth=3,count=1")
    try:
        cli = ServingClient(addrs=[srv.address])
        seen = []
        toks, _ = cli.generate2([3, 1, 4], max_new=10, model="lm",
                                on_token=lambda i, t, v: seen.append(i))
        assert toks == ref
        assert seen == list(range(10)), seen
    finally:
        fault.uninstall()
        srv.stop()


def test_mid_generation_expiry_returns_expired_verdict():
    """A sequence whose budget runs out MID-generation is evicted at
    the next step boundary with the ``expired`` verdict — it does not
    squat its slot until max_new."""
    eng = _engine()
    srv = ModelServer(eng, port=0, model_name="lm").start()
    fault.install("kind=delay,point=serve.step,delay=0.25,nth=2,count=50")
    try:
        cli = ServingClient(addrs=[srv.address])
        with pytest.raises(DeadlineExceeded, match="expired"):
            cli.generate2([3, 1, 4], max_new=200, budget_ms=400,
                          model="lm")
        st = srv.stats()["models"]["lm"]["scheduler"]
        assert st["expired"] >= 1
    finally:
        fault.uninstall()
        srv.stop()


def test_live_swap_never_tears_an_inflight_sequence():
    """serve.swap lands while a sequence decodes: the sequence keeps
    answering from its admission-time version (every token frame v0,
    final tokens bit-equal to the no-swap run) while the NEXT
    admission answers from v1. Pinned replay of an evicted version is
    refused honestly rather than silently rebound."""
    eng = _engine(seed=7, cache_len=32)
    srv = ModelServer(eng, port=0, model_name="lm").start()
    conn = None
    try:
        cli = ServingClient(addrs=[srv.address])
        ref0, i0 = cli.generate2([3, 1, 4], max_new=12, model="lm")
        assert i0["version"] == 0
        fault.install(
            "kind=delay,point=serve.step,delay=0.05,nth=1,count=1000")
        vers, done = [], []

        def run():
            done.append(cli.generate2(
                [3, 1, 4], max_new=12, model="lm",
                on_token=lambda i, t, v: vers.append(v)))
        th = threading.Thread(target=run)
        th.start()
        time.sleep(0.3)                       # a few tokens in
        srv.swap_weights(_lm_params(8), {}, version=1)
        th.join(timeout=60)
        fault.uninstall()
        toks, info = done[0]
        assert toks == ref0, "in-flight sequence torn by swap"
        assert set(vers) == {0} and info["version"] == 0
        toks1, info1 = cli.generate2([3, 1, 4], max_new=12, model="lm")
        assert info1["version"] == 1
        assert toks1 != ref0
        conn = ka._ServerConn(srv.address)
        with pytest.raises(RuntimeError, match="no longer resident"):
            conn.request("generate", "pin:1",
                         np.asarray([3, 1, 4], np.int32),
                         {"max_new": 4, "model": "lm", "version": 99})
    finally:
        fault.uninstall()
        if conn is not None:
            conn.close()
        srv.stop()


# ---------------------------------------------------------------------------
# heads of 128: the decode program attends through the Pallas decode kernel
# ---------------------------------------------------------------------------

def _wide_lm(n_layer=2, d=256, heads=2, cache_len=256, seed=11):
    """A two-layer decoder whose heads are whole 128-lane slabs, so its
    decode program (and only that one) engages the decode kernel."""
    rng = np.random.RandomState(seed)
    f = lambda *s: rng.randn(*s).astype(np.float32) * 0.08  # noqa: E731
    fc = mx.sym.FullyConnected
    data = mx.sym.Variable("data")
    pos = mx.sym.Variable("pos", shape=(0,), dtype="int32")
    x = mx.sym.Embedding(data=data, input_dim=V, output_dim=d, name="emb")
    params = {"emb_weight": f(V, d) * 6}
    nexts = []
    for i in range(n_layer):
        p = "l%d_" % i
        kc = mx.sym.Variable("kc%d" % i, shape=(0, cache_len, d))
        vc = mx.sym.Variable("vc%d" % i, shape=(0, cache_len, d))
        q, k, v = (fc(data=x, num_hidden=d, flatten=False, name=p + n)
                   for n in "qkv")
        att = mx.sym.cached_attention(q, k, v, kc, vc, pos,
                                      num_heads=heads, alibi=True,
                                      name=p + "att")
        x = x + fc(data=att[0], num_hidden=d, flatten=False, name=p + "o")
        nexts += [mx.sym.identity(att[1], name="kc%d_next" % i),
                  mx.sym.identity(att[2], name="vc%d_next" % i)]
        for n in "qkvo":
            params[p + n + "_weight"] = f(d, d)
            params[p + n + "_bias"] = np.zeros(d, np.float32)
    out = fc(data=x, num_hidden=V, flatten=False, name="proj")
    params.update(proj_weight=f(V, d), proj_bias=np.zeros(V, np.float32))
    return InferenceEngine(mx.sym.Group([out] + nexts), params, {},
                           data_shapes={"data": (1,)}, buckets=(1,),
                           warm=False)


def _generate_all(eng, prompts, max_new):
    """Every prompt through one GenerateScheduler at once (more sequences
    than slots); returns the tokens per prompt and the compiles the run
    added after its programs were built."""
    from mxtpu.serving.batcher import GenerateScheduler
    compiles = _compile_menu(eng, 4)
    sched = GenerateScheduler(eng, 16, slots=4)
    try:
        reqs = [sched.submit("r%d" % j, prompt, max_new, None)
                for j, prompt in enumerate(prompts)]
        replies = [req.wait(120) for req in reqs]
    finally:
        sched.stop()
    assert all(r[0] == "ok" for r in replies), replies
    tokens = [list(r[1]["tokens"]) for r in replies]
    return tokens, eng.cache.stats()["compiles"] - compiles


def test_wide_heads_generate_through_the_decode_kernel(monkeypatch):
    """Prompts of unequal length on 4 slots: the tokens are those of the
    same model traced onto the scatter and the dense formula; the decode
    program counts n_layer nodes on the attention kernel and as many whose
    rows the row-write kernel puts in, the prefill programs none; nothing
    retraces during the run."""
    from mxtpu.ops import nn
    monkeypatch.setenv("MXTPU_SERVE_GENERATE_PREFILL_BUCKETS", "8,32")
    prompts = [[3, 1, 4], [1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9],
               [2], [7, 1, 8, 2, 8, 1, 8], [1, 6, 1, 8, 0, 3],
               list(range(1, 17)) + list(range(16, 0, -1))[:13]]
    eng = _wide_lm()
    got, retraces = _generate_all(eng, prompts, 12)
    assert retraces == 0
    st = eng.stats()
    assert st["gen_decode_attn_path"] == st["gen_decode_row_write"] == 2
    assert st["gen_prefill_attn_path"] == st["gen_prefill_row_write"] == 0
    with monkeypatch.context() as m:
        m.setattr(nn, "_decode_path", lambda *a: False)
        dense_eng = _wide_lm()
        want, _ = _generate_all(dense_eng, prompts, 12)
    st = dense_eng.stats()
    assert st["gen_decode_attn_path"] == st["gen_decode_row_write"] == 0
    assert got == want
    assert len({tuple(t) for t in got}) > 1, "degenerate model"


def test_sharded_engine_keeps_the_dense_formula():
    """The kernel is one device's program: an engine over a mesh makes the
    mesh ambient while it traces its decode program, cached_attention sees
    it and stays on the dense formula (which GSPMD partitions), and the
    tokens are the single-device engine's."""
    from mxtpu.parallel import MeshContext
    prompts = [[3, 1, 4], [1, 5, 9, 2, 6, 5, 3], [2]]
    one = _wide_lm()
    names = one._param_names
    params = {n: np.asarray(v) for n, v in zip(names, one._param_vals)}
    meshed = InferenceEngine(one._symbol, params, {},
                             data_shapes={"data": (1,)}, buckets=(1,),
                             warm=False, mesh=MeshContext({"model": 4}))
    want, _ = _generate_all(one, prompts, 6)
    got, retraces = _generate_all(meshed, prompts, 6)
    assert got == want and retraces == 0
    for eng, nodes in ((one, 2), (meshed, 0)):
        st = eng.stats()
        assert st["gen_decode_attn_path"] == nodes
        assert st["gen_decode_row_write"] == nodes


# ---------------------------------------------------------------------------
# the example: train -> checkpoint -> serve generate, end to end
# ---------------------------------------------------------------------------

def test_char_lm_example_smoke(tmp_path):
    """example/char_lm end to end: the trained char transformer's
    served greedy decode reproduces the memorized corpus and the
    decode loop is retrace-free (the example asserts both)."""
    import importlib.util
    path = os.path.join(os.path.dirname(__file__), os.pardir,
                        "example", "char_lm", "char_lm.py")
    spec = importlib.util.spec_from_file_location("char_lm", path)
    char_lm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(char_lm)
    ppl = char_lm.main(["--model-prefix", str(tmp_path / "char_lm")])
    assert ppl < 1.35
