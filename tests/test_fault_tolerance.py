"""Fault matrix for the dist_async stack (mxtpu/fault.py +
kvstore_async's retry/dedupe/health/auto-resume layers; see the module
docstring's "Fault tolerance" section and docs/fault_tolerance.md).

Every scenario is deterministic: faults come from the injection harness
on exact event schedules (never from timing), servers are loopback
threads in this process, and the only sleeps are sub-second backoffs the
retry layer itself performs. The matrix each test row covers:

fault kind x injection point        -> recovery path proven
---------------------------------------------------------------------
sever  @ worker.send (pre-apply)    -> plain retry, applied once
sever  @ server.send (post-apply)   -> retry + seq dedupe (at-most-once)
truncate @ worker.send              -> garbage frame isolated, retried
drop   @ worker.send                -> per-call timeout fires, retried
delay  @ worker.send                -> transparent (just slower)
kill   @ server.recv                -> snapshot-backed restart, buffered
                                       pushes flushed, workers reconverge
server gone (no injection)          -> pull degrades to cached value,
                                       health() reports the dead shard
kill_worker mid-push-window         -> SIGKILL between pipelined part
                                       pushes: the applied prefix is
                                       consistent (each part <= once),
                                       the dead worker's membership +
                                       dedupe seqs are GC'd, the fleet
                                       continues (worker-liveness rows)
stall  @ worker.send                -> straggler surfaces in the
                                       per-worker push counters /
                                       kv.stats()["stragglers"]
worker dead (no bye)                -> server-side lease expiry GCs its
                                       buffered state; barrier degrades
                                       on its deadline instead of
                                       hanging the survivors
drop   @ stream.append              -> record shed before any byte hits
                                       the segment file: no torn record
                                       is ever tailer-visible
sever  @ stream.tail                -> consumer dies holding a segment
                                       lease; bye requeues it and the
                                       successor resumes exactly-once
                                       from the committed offset
trainer killed post-apply           -> the respawn's bit-identical
                                       stream_push frame (grads +
                                       offset commit) is refused by the
                                       (origin, seq) watermark
partition @ client->primary         -> probe-through-peer, promotion,
                                       fencing epoch minted; the healed
                                       incumbent fences + rejoins
partition @ primary->backup (sync)  -> stream detaches, primary acks
                                       solo + buffers for heal-time
                                       reconciliation; reattach catches
                                       back up
partition @ client->primary ONLY    -> peer_alive says the primary is
  (asymmetric, within grace)           healthy: marked unreachable, NO
                                       promotion — pushes buffer, pulls
                                       degrade, heal flushes
partition full split-brain + heal   -> divergence window reconciled
                                       exactly-once at the new primary,
                                       tables bit-equal, journal clean
stale-epoch cursor_done             -> fenced refusal: a re-granted
                                       shard/lease cannot be retired
                                       under its pre-partition grant
"""
import os

import numpy as np
import pytest

import mxtpu as mx
from mxtpu import fault
from mxtpu import kvstore_async as ka
from mxtpu.devtools import consistency
from mxtpu.kvstore_async import ParameterServer


@pytest.fixture(autouse=True)
def _fast_failure_knobs(monkeypatch):
    """Small retry/backoff windows so every fault path resolves in
    well under a second, heartbeat thread off (tests sweep health
    synchronously via kv._check_health()), and a clean injector."""
    monkeypatch.setattr(ka, "_RETRIES", 2)
    monkeypatch.setattr(ka, "_BACKOFF", 0.01)
    monkeypatch.setattr(ka, "_BACKOFF_MAX", 0.05)
    monkeypatch.setattr(ka, "_RECONNECT_TIMEOUT", 0.2)
    monkeypatch.setattr(ka, "_DEAD_AFTER", 2)
    monkeypatch.setenv("MXTPU_PS_HEARTBEAT", "0")
    # the matrix is about the WIRE: pin the same-process shortcut off so
    # every row exercises real framing (the local-transport rows below
    # flip it back on explicitly)
    monkeypatch.setattr(ka, "_LOCAL_ON", False)
    fault.uninstall()
    yield
    fault.uninstall()


def _store(monkeypatch, addrs, rank=0, nproc=1):
    monkeypatch.setenv("MXTPU_PS_ADDRS", addrs)
    monkeypatch.setenv("MXTPU_PROC_ID", str(rank))
    monkeypatch.setenv("MXTPU_NUM_PROCS", str(nproc))
    return mx.kv.create("dist_async")


# ---------------------------------------------------------------------------
# the injection harness itself
# ---------------------------------------------------------------------------

def test_fault_spec_parsing_and_validation():
    rules = fault.parse_spec(
        "kind=sever,point=server.send,op=push,nth=3,count=2;"
        "kind=delay,point=any,delay=0.25,count=inf")
    assert len(rules) == 2
    assert (rules[0].kind, rules[0].point, rules[0].op,
            rules[0].nth, rules[0].count) == \
        ("sever", "server.send", "push", 3, 2)
    assert rules[1].delay == 0.25 and rules[1].count == float("inf")
    with pytest.raises(ValueError, match="unknown fault kind"):
        fault.parse_spec("kind=explode")
    with pytest.raises(ValueError, match="unknown fault point"):
        fault.parse_spec("kind=sever,point=everywhere")
    with pytest.raises(ValueError, match="kill only applies to server"):
        fault.parse_spec("kind=kill,point=worker.send")
    with pytest.raises(ValueError, match="no kind="):
        fault.parse_spec("point=worker.send")


def test_injector_schedule_is_deterministic():
    inj = fault.FaultInjector("kind=sever,point=worker.send,op=push,"
                              "nth=2,count=2")
    outcomes = []
    for _ in range(5):
        try:
            inj.fire("worker.send", op="push")
            outcomes.append("ok")
        except fault.FaultSever:
            outcomes.append("sever")
    # exactly events 2 and 3 fault, nothing else — replayable schedule
    assert outcomes == ["ok", "sever", "sever", "ok", "ok"]
    inj2 = fault.FaultInjector("kind=sever,point=server.recv,op=pull,"
                               "key=big")
    inj2.fire("server.recv", op="pull", key="other")      # key mismatch
    inj2.fire("worker.send", op="pull", key="big0")       # point mismatch
    with pytest.raises(fault.FaultSever):
        inj2.fire("server.recv", op="pull", key="big0")
    assert inj2.stats()[0][3:] == (1, 1)                  # seen, fired


def test_env_spec_bootstrap(monkeypatch):
    monkeypatch.setenv("MXTPU_FAULT_SPEC",
                       "kind=delay,point=worker.recv,delay=0.01")
    monkeypatch.setattr(fault, "_env_loaded", False)
    monkeypatch.setattr(fault, "_injector", None)
    inj = fault.active()
    assert inj is not None and inj.rules[0].kind == "delay"


# ---------------------------------------------------------------------------
# retry / at-most-once replay
# ---------------------------------------------------------------------------

def test_pre_apply_sever_is_retried(monkeypatch):
    """Connection dies BEFORE the frame reaches the server: the retry
    needs no dedupe help — the replay is the first copy to arrive."""
    srv = ParameterServer().start()
    kv = _store(monkeypatch, srv.address)
    try:
        kv.init("w", mx.nd.zeros((4,)))
        with fault.inject("kind=sever,point=worker.send,op=push,nth=1") \
                as inj:
            kv.push("w", mx.nd.ones((4,)))
        assert inj.stats()[0][4] == 1          # the fault really fired
        out = mx.nd.zeros((4,))
        kv.pull("w", out=out)
        np.testing.assert_allclose(out.asnumpy(), np.ones(4))
        assert srv._clock["w"] == 1 and srv._dup_n == 0
        assert kv.health()["num_dead"] == 0    # one blip != dead
    finally:
        kv.close()
        srv.stop()


def test_lost_ack_push_replay_applied_exactly_once(monkeypatch):
    """Connection dies AFTER the server applied the push but before the
    ack: the blind replay MUST be deduped by the (origin, seq) pair —
    clock-checked, the acceptance-criteria scenario."""
    srv = ParameterServer().start()
    kv = _store(monkeypatch, srv.address)
    try:
        kv.init("w", mx.nd.zeros((4,)))
        with fault.inject("kind=sever,point=server.send,op=push,nth=1") \
                as inj:
            kv.push("w", mx.nd.ones((4,)))     # applied; ack lost; replay
        assert inj.stats()[0][4] == 1
        out = mx.nd.zeros((4,))
        kv.pull("w", out=out)
        np.testing.assert_allclose(out.asnumpy(), np.ones(4))  # not 2.0!
        assert srv._clock["w"] == 1            # applied exactly once
        assert srv._dup_n == 1                 # the replay was refused
    finally:
        kv.close()
        srv.stop()


def test_truncate_fault_recovers(monkeypatch):
    """A torn frame (bogus length then close) must be contained by the
    server's framing guards and recovered by the worker's retry."""
    srv = ParameterServer().start()
    kv = _store(monkeypatch, srv.address)
    try:
        kv.init("w", mx.nd.zeros((2,)))
        with fault.inject(
                "kind=truncate,point=worker.send,op=push,nth=1"):
            kv.push("w", mx.nd.ones((2,)))
        out = mx.nd.zeros((2,))
        kv.pull("w", out=out)
        np.testing.assert_allclose(out.asnumpy(), np.ones(2))
        assert srv._clock["w"] == 1 and srv._dup_n == 0
    finally:
        kv.close()
        srv.stop()


def test_dropped_frame_hits_timeout_then_retries(monkeypatch):
    """kind=drop silently loses the request frame, so ONLY the per-call
    timeout can notice — proves the timeout path, not just the
    connection-error path."""
    monkeypatch.setattr(ka, "_REQUEST_TIMEOUT", 0.3)
    srv = ParameterServer().start()
    kv = _store(monkeypatch, srv.address)
    try:
        kv.init("w", mx.nd.array(np.arange(3, dtype="f")))
        with fault.inject("kind=drop,point=worker.send,op=pull,nth=1") \
                as inj:
            out = mx.nd.zeros((3,))
            kv.pull("w", out=out)
        assert inj.stats()[0][4] == 1
        np.testing.assert_allclose(out.asnumpy(),
                                   np.arange(3, dtype="f"))
    finally:
        kv.close()
        srv.stop()


def test_delay_fault_is_transparent(monkeypatch):
    srv = ParameterServer().start()
    kv = _store(monkeypatch, srv.address)
    try:
        kv.init("w", mx.nd.zeros((2,)))
        with fault.inject("kind=delay,point=worker.send,op=push,"
                          "delay=0.05,count=2") as inj:
            kv.push("w", mx.nd.ones((2,)))
            kv.push("w", mx.nd.ones((2,)))
        assert inj.stats()[0][4] == 2
        out = mx.nd.zeros((2,))
        kv.pull("w", out=out)
        np.testing.assert_allclose(out.asnumpy(), 2 * np.ones(2))
    finally:
        kv.close()
        srv.stop()


def test_barrier_is_never_replayed(monkeypatch):
    """barrier is NOT idempotent (a replayed arrival would double-count
    this worker in the generation), so a barrier fault must surface
    instead of retrying."""
    srv = ParameterServer().start()
    kv = _store(monkeypatch, srv.address)
    try:
        kv.init("w", mx.nd.zeros((2,)))
        with fault.inject("kind=sever,point=worker.send,op=barrier,"
                          "nth=1"):
            with pytest.raises(ConnectionError):
                kv.barrier()
        assert srv._barrier_arrived == 0       # no half-arrived worker
    finally:
        kv.close()
        srv.stop()


# ---------------------------------------------------------------------------
# liveness: dead-shard degradation + recovery
# ---------------------------------------------------------------------------

def test_dead_shard_pull_degrades_to_last_known(monkeypatch):
    """Acceptance scenario: a pull whose shard is dead returns the
    worker's last-known value (staleness-marked) instead of raising,
    health() reports the dead server, and a recovered server clears
    both on the next health sweep + pull."""
    s1, s2 = ParameterServer().start(), ParameterServer().start()
    kv = _store(monkeypatch, s1.address + "," + s2.address)
    try:
        keys = ["k%d" % i for i in range(6)]
        for k in keys:
            kv.init(k, mx.nd.ones((3,)) * float(k[1]))
        out = mx.nd.zeros((3,))
        for k in keys:
            kv.pull(k, out=out)                # warm the last-known cache
        # kill whichever server owns k0; remember its port for revival
        dead = s1 if "k0" in s1._clock else s2
        live = s2 if dead is s1 else s1
        dead_port = int(dead.address.split(":")[1])
        dead_keys = sorted(dead._clock)
        dead.stop()

        kv.pull("k0", out=out)                 # degraded, NOT an error
        np.testing.assert_allclose(out.asnumpy(), np.zeros(3))
        h = kv.health()
        assert h["num_dead"] == 1
        assert "k0" in h["degraded_keys"]
        states = {s["addr"]: s["state"] for s in h["servers"]}
        assert states[dead.address] == "dead"
        assert states[live.address] == "ok"
        assert kv.get_num_dead_node() == 1     # the NumDeadNodes analogue
        # keys on the live shard are untouched by the dead one
        live_key = sorted(live._clock)[0]
        kv.pull(live_key, out=out)
        assert live_key not in kv.health()["degraded_keys"]

        # shard comes back on the same port: the background probe path
        # (run synchronously here) re-marks it ok, and a live pull
        # clears the staleness mark
        revived = ParameterServer(port=dead_port).start()
        try:
            kv._check_health()
            assert kv.health()["num_dead"] == 0
            # revived empty table: the key is gone (no snapshot); a pull
            # still degrades to cache rather than raising mid-training
            kv.pull("k0", out=out)
            assert "k0" in kv.health()["degraded_keys"], \
                "no live value yet -> still staleness-marked"
            for k in dead_keys:                # re-init repopulates
                kv.init(k, mx.nd.ones((3,)) * 7)
            kv.pull("k0", out=out)
            np.testing.assert_allclose(out.asnumpy(), 7 * np.ones(3))
            assert "k0" not in kv.health()["degraded_keys"]
        finally:
            revived.stop()
    finally:
        kv.close()
        s1.stop()
        s2.stop()


def test_pull_without_cache_still_raises(monkeypatch):
    """Degradation never invents data: a key this worker NEVER pulled
    has no last-known value, so a dead shard must still raise."""
    srv = ParameterServer().start()
    kv = _store(monkeypatch, srv.address)
    try:
        kv.init("w", mx.nd.zeros((2,)))        # init warms no pull cache
        srv.stop()
        with pytest.raises(ConnectionError):
            kv.pull("w", out=mx.nd.zeros((2,)))
    finally:
        kv.close()


# ---------------------------------------------------------------------------
# auto-resume: snapshots, buffered pushes, restart
# ---------------------------------------------------------------------------

def test_killed_server_restores_snapshot_and_reconverges(monkeypatch,
                                                         tmp_path):
    """Acceptance scenario: the injector kills the server on schedule
    mid-training; a restart on the same port restores table, clocks,
    optimizer AND the push-dedupe seqs from the snapshot; the worker's
    buffered push flushes with its original seq (at-most-once across
    the crash) and training reconverges with no operator action."""
    snap = str(tmp_path / "snaps")
    srv = ParameterServer(snapshot_dir=snap, snapshot_every=1).start()
    port = int(srv.address.split(":")[1])
    kv = _store(monkeypatch, srv.address)
    try:
        kv.init("w", mx.nd.zeros((4,)))
        kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.5))
        kv.push("w", mx.nd.ones((4,)))         # applied + snapshotted
        # 2nd push: the server is killed on receipt, BEFORE applying
        # (the injector counts from installation, so nth=1 here)
        with fault.inject("kind=kill,point=server.recv,op=push,nth=1"):
            kv.push("w", mx.nd.ones((4,)))     # buffered, not lost
        h = kv.health()
        assert h["num_dead"] == 1 and h["pending_pushes"] == 1

        srv2 = ParameterServer(port=port, snapshot_dir=snap).start()
        try:
            assert srv2._restored_step is not None
            assert srv2._updater is not None, \
                "optimizer must ride the snapshot"
            np.testing.assert_allclose(srv2._table["w"],  # numpy table
                                       -0.5 * np.ones(4))
            assert srv2._clock["w"] == 1

            kv._check_health()                 # probe + flush buffered
            h = kv.health()
            assert h["num_dead"] == 0 and h["pending_pushes"] == 0
            out = mx.nd.zeros((4,))
            kv.pull("w", out=out)              # -0.5 - 0.5 = -1.0
            np.testing.assert_allclose(out.asnumpy(), -np.ones(4))
            assert srv2._clock["w"] == 2 and srv2._dup_n == 0

            # reconvergence: the fleet keeps training as if nothing
            # happened — each further push is applied exactly once
            for _ in range(3):
                kv.push("w", mx.nd.ones((4,)))
            kv.pull("w", out=out)
            np.testing.assert_allclose(out.asnumpy(), -2.5 * np.ones(4))
            assert srv2._clock["w"] == 5
        finally:
            srv2.stop()
    finally:
        kv.close()
        srv.stop()


def test_buffered_push_flush_is_deduped_against_retry(monkeypatch,
                                                      tmp_path):
    """The nastiest interleaving: the push's ack is lost (server DID
    apply it), the server then dies before the worker's replay lands, so
    the replay gets buffered — and after restart the flush replays a seq
    the SNAPSHOT already recorded as applied. The restored dedupe table
    must refuse it."""
    snap = str(tmp_path / "snaps")
    srv = ParameterServer(snapshot_dir=snap, snapshot_every=1).start()
    port = int(srv.address.split(":")[1])
    kv = _store(monkeypatch, srv.address)
    try:
        kv.init("w", mx.nd.zeros((4,)))
        # push 1 applied + snapshotted (seq recorded), then the ack is
        # severed AND the server dies, so every replay attempt fails
        with fault.inject(
                "kind=sever,point=server.send,op=push,nth=1;"
                "kind=kill,point=server.recv,op=push,nth=2"):
            kv.push("w", mx.nd.ones((4,)))
        assert kv.health()["pending_pushes"] == 1
        srv2 = ParameterServer(port=port, snapshot_dir=snap).start()
        try:
            kv._check_health()                 # flush replays seq 1
            assert kv.health()["pending_pushes"] == 0
            out = mx.nd.zeros((4,))
            kv.pull("w", out=out)
            np.testing.assert_allclose(out.asnumpy(), np.ones(4))
            assert srv2._clock["w"] == 1       # exactly once, ever
            assert srv2._dup_n == 1            # the flush was refused
        finally:
            srv2.stop()
    finally:
        kv.close()
        srv.stop()


def test_snapshot_roundtrip_preserves_key_types(tmp_path):
    """Table keys are ints, plain strings, and NUL-separated part
    subkeys — the snapshot's tagged-key encoding must round-trip all
    three exactly."""
    snap = str(tmp_path / "s")
    srv = ParameterServer(snapshot_dir=snap, snapshot_every=0)
    conn = ka._ServerConn(srv.start().address)
    try:
        conn.request("init", 7, np.arange(3, dtype="f"))
        conn.request("init", "name", np.ones((2, 2), "f"))
        conn.request("init", "big\x001", np.zeros(2, "f"))
        conn.request("push", "big\x001", np.ones(2, "f"), 0, "o1", 5)
        assert srv.snapshot()
    finally:
        conn.close()
        srv.stop()
    srv2 = ParameterServer(snapshot_dir=snap)
    try:
        assert set(srv2._table) == {7, "name", "big\x001"}
        assert srv2._clock == {7: 0, "name": 0, "big\x001": 1}
        assert srv2._applied == {("o1", "big\x001"): 5}
        np.testing.assert_allclose(srv2._table[7],        # numpy table
                                   np.arange(3, dtype="f"))
    finally:
        srv2.stop()


def test_local_store_health_is_trivially_ok():
    kv = mx.kv.create("local")
    h = kv.health()
    assert h["num_dead"] == 0 and h["servers"] == []
    assert kv.get_num_dead_node() == 0


# ---------------------------------------------------------------------------
# pipelined-window rows (ISSUE 2): the fast path must keep every fault
# semantic above while many requests ride one socket unacknowledged
# ---------------------------------------------------------------------------

def _eight_part_push(monkeypatch):
    """Shrink the bigarray bound so an (8, 4) array splits into 8
    one-row parts — all of which stream back-to-back inside one
    MXTPU_PS_WINDOW=8 window on the single socket. Coalescing is
    pinned off so each part is its own pipelined frame (op=push on the
    wire), which is what these rows are about."""
    monkeypatch.setattr(ka, "_BIGARRAY_BOUND", 4)
    monkeypatch.setattr(ka, "_COALESCE_BYTES", 0)


def test_window_sever_mid_window_at_most_once(monkeypatch):
    """Sever the connection after the server applied part 3 of an
    8-part pipelined push but before its ack: the whole unacked window
    fails onto the retry layer; the replay of the applied part is
    deduped, the never-dispatched tail applies first-time — the table
    holds each part EXACTLY once and stats() shows the evidence
    (retransmits worker-side, dup_pushes server-side)."""
    _eight_part_push(monkeypatch)
    srv = ParameterServer().start()
    kv = _store(monkeypatch, srv.address)
    try:
        kv.init("w", mx.nd.zeros((8, 4)))
        with fault.inject("kind=sever,point=server.send,op=push,nth=3") \
                as inj:
            kv.push("w", mx.nd.ones((8, 4)))
        assert inj.stats()[0][4] == 1
        out = mx.nd.zeros((8, 4))
        kv.pull("w", out=out)
        np.testing.assert_allclose(out.asnumpy(), np.ones((8, 4)))
        assert all(srv._clock["w\x00%d" % i] == 1 for i in range(8))
        assert srv._dup_n >= 1                 # the applied part replayed
        s = kv.stats()
        assert s["retransmits"] >= 1           # window replay happened
        assert s["dup_pushes"] >= 1            # ...and was deduped
        assert s["inflight_hwm"] >= 2          # requests really pipelined
    finally:
        kv.close()
        srv.stop()


def test_window_truncate_mid_window(monkeypatch):
    """A torn frame in the middle of a streaming window: the channel
    dies, every in-flight part replays, framing guards keep the server
    sane — in-order flush still lands the whole array exactly once."""
    _eight_part_push(monkeypatch)
    srv = ParameterServer().start()
    kv = _store(monkeypatch, srv.address)
    try:
        kv.init("w", mx.nd.zeros((8, 4)))
        with fault.inject(
                "kind=truncate,point=worker.send,op=push,nth=4") as inj:
            kv.push("w", mx.nd.ones((8, 4)))
        assert inj.stats()[0][4] == 1
        out = mx.nd.zeros((8, 4))
        kv.pull("w", out=out)
        np.testing.assert_allclose(out.asnumpy(), np.ones((8, 4)))
        assert all(srv._clock["w\x00%d" % i] == 1 for i in range(8))
    finally:
        kv.close()
        srv.stop()


def test_window_drop_mid_window(monkeypatch):
    """A silently dropped frame mid-window: only the waiter's deadline
    can notice; the channel fails, the unacked window replays, dedupe
    keeps the already-applied prefix at-most-once."""
    _eight_part_push(monkeypatch)
    monkeypatch.setattr(ka, "_REQUEST_TIMEOUT", 0.3)
    srv = ParameterServer().start()
    kv = _store(monkeypatch, srv.address)
    try:
        kv.init("w", mx.nd.zeros((8, 4)))
        with fault.inject("kind=drop,point=worker.send,op=push,nth=5") \
                as inj:
            kv.push("w", mx.nd.ones((8, 4)))
        assert inj.stats()[0][4] == 1
        out = mx.nd.zeros((8, 4))
        kv.pull("w", out=out)
        np.testing.assert_allclose(out.asnumpy(), np.ones((8, 4)))
        assert all(srv._clock["w\x00%d" % i] == 1 for i in range(8))
    finally:
        kv.close()
        srv.stop()


def test_window_inorder_flush_same_key(monkeypatch):
    """Two sequential pushes of ONE key with a sever between their acks:
    replays must neither reorder nor double-apply — the final value is
    the exact two-push sum."""
    srv = ParameterServer().start()
    kv = _store(monkeypatch, srv.address)
    try:
        kv.init("w", mx.nd.zeros((4,)))
        with fault.inject("kind=sever,point=server.send,op=push,nth=1"):
            kv.push("w", mx.nd.ones((4,)))
            kv.push("w", 2 * mx.nd.ones((4,)))
        out = mx.nd.zeros((4,))
        kv.pull("w", out=out)
        np.testing.assert_allclose(out.asnumpy(), 3 * np.ones(4))
        assert srv._clock["w"] == 2 and srv._dup_n == 1
    finally:
        kv.close()
        srv.stop()


def test_coalesced_multi_sever_mid_batch(monkeypatch):
    """Sever inside a coalesced multi-key frame after a prefix of its
    sub-pushes applied: the client replays the WHOLE batch; the seq
    dedupe refuses the prefix and applies only the tail — every key
    lands exactly once."""
    srv = ParameterServer().start()
    kv = _store(monkeypatch, srv.address)
    try:
        keys = ["k%d" % i for i in range(8)]
        vals = [mx.nd.ones((3,)) * (i + 1) for i in range(8)]
        kv.init(keys, [mx.nd.zeros((3,)) for _ in keys])
        # 5th push EVENT at server.recv = sub-push 5 of the multi frame
        # (subs fire their own server.recv), so 4 subs applied first
        with fault.inject("kind=sever,point=server.recv,op=push,nth=5") \
                as inj:
            kv.push(keys, vals)
        assert inj.stats()[0][4] == 1
        for i, k in enumerate(keys):
            out = mx.nd.zeros((3,))
            kv.pull(k, out=out)
            np.testing.assert_allclose(out.asnumpy(),
                                       (i + 1) * np.ones(3))
            assert srv._clock[k] == 1, (k, srv._clock)
        assert srv._dup_n == 4                 # the applied prefix
        s = kv.stats()
        assert s["coalesced_subs"] >= 8        # they really coalesced
    finally:
        kv.close()
        srv.stop()


def test_worker_membership_hello_bye_gc(monkeypatch):
    """Worker-liveness row: a store registers at creation (hello), its
    pushes feed per-worker counters, and a clean close (bye) drops the
    membership AND reclaims the worker's dedupe seqs — the per-origin
    at-most-once table cannot grow one entry per worker incarnation
    forever."""
    srv = ParameterServer().start()
    kv = _store(monkeypatch, srv.address)
    origin = kv._origin
    try:
        assert origin in srv._workers          # hello at creation
        epoch0 = srv._membership_epoch
        kv.init("w", mx.nd.zeros((4,)))
        kv.push("w", mx.nd.ones((4,)))
        kv.push("w", mx.nd.ones((4,)))
        rec = srv._workers[origin]
        assert rec["rank"] == 0 and rec["pushes"] == 2
        assert (origin, "w") in srv._applied
        s = kv.stats()
        assert s["workers"][origin]["pushes"] == 2
        # per-server epochs (the counters are independent per server —
        # an aggregate max would be meaningless); churn is the only
        # cross-server verdict kept
        assert s["membership_epochs"][srv.address] == epoch0
        assert s["membership_churn"] is True   # our own hello counts
        assert s["elastic"]["joins"] == 1
        h = kv.health()
        assert origin in h["workers"] and h["stragglers"] == []
    finally:
        kv.close()                             # sends bye
        assert origin not in srv._workers
        assert (origin, "w") not in srv._applied
        assert srv._membership_epoch == epoch0 + 1
        srv.stop()


def test_dead_worker_lease_expiry_gc(monkeypatch):
    """A worker that vanishes WITHOUT a bye (kill -9): once its lease is
    silent past MXTPU_PS_WORKER_DEAD_AFTER, the next sweep garbage-
    collects its membership and buffered dedupe state."""
    import time
    monkeypatch.setattr(ka, "_WORKER_DEAD_AFTER", 0.05)
    srv = ParameterServer().start()
    conn = ka._ServerConn(srv.address)
    try:
        conn.request("init", "w", np.zeros(4, "f"))
        conn.request("hello", "gone-worker", 3)
        conn.request("push", "w", np.ones(4, "f"), 0, "gone-worker", 1)
        assert "gone-worker" in srv._workers
        assert ("gone-worker", "w") in srv._applied
        time.sleep(0.08)                       # lease expires
        assert srv._gc_workers() == 1          # the lazy sweep reaps it
        assert "gone-worker" not in srv._workers
        assert ("gone-worker", "w") not in srv._applied
        # the table itself is untouched — only the worker's bookkeeping
        np.testing.assert_allclose(srv._table["w"], np.ones(4))
    finally:
        conn.close()
        srv.stop()


def test_barrier_deadline_degrades_instead_of_hanging(monkeypatch):
    """A barrier a dead member can never complete: the server force-
    releases the generation at the deadline, the waiter returns (logged
    + counted), and the NEXT barrier round starts clean."""
    import time
    monkeypatch.setattr(ka, "_BARRIER_TIMEOUT", 0.3)
    srv = ParameterServer().start()
    kv = _store(monkeypatch, srv.address, rank=0, nproc=2)
    try:
        kv.init("w", mx.nd.zeros((2,)))        # init barriers: 2 workers
        # ...which itself would hang forever without the deadline — the
        # second worker never existed. Measure the bound:
        t0 = time.time()
        kv.barrier()
        assert time.time() - t0 < 5
        assert srv._barrier_timeouts >= 1
        assert srv._barrier_arrived == 0       # generation fully reset
        assert kv.stats()["barrier_timeouts"] >= 1
    finally:
        kv.close()
        srv.stop()


def test_stall_fault_surfaces_straggler_counters(monkeypatch):
    """stall row: a stalled worker's push rate falls behind the fleet;
    the per-worker push counters make the straggler observable in
    kv.stats() — push-count based, so the verdict is deterministic."""
    monkeypatch.setattr(ka, "_STRAGGLER_MIN", 10)
    srv = ParameterServer().start()
    kv = _store(monkeypatch, srv.address)
    try:
        kv.init("w", mx.nd.zeros((4,)))
        # the stalled worker: 3 pushes, each through an injected stall
        # (tiny delay — the *counter* is the evidence, not wall time)
        with fault.inject("kind=stall,point=worker.send,op=push,"
                          "delay=0.01,count=3") as inj:
            for _ in range(3):
                kv.push("w", mx.nd.ones((4,)))
        assert inj.stats()[0][4] == 3
        # a healthy peer outruns it 4:1
        conn = ka._ServerConn(srv.address)
        conn.request("hello", "fast-worker", 1)
        for i in range(12):
            conn.request("push", "w", np.ones(4, "f"), 0,
                         "fast-worker", i + 1)
        s = kv.stats()
        assert s["workers"][kv._origin]["pushes"] == 3
        assert s["workers"]["fast-worker"]["pushes"] == 12
        assert kv._origin in s["stragglers"]
        assert "fast-worker" not in s["stragglers"]
        conn.close()
    finally:
        kv.close()
        srv.stop()


# ---------------------------------------------------------------------------
# replication rows (ISSUE 4): primary/backup pairs, hot failover, zero
# acknowledged-update loss. Every row drives promotion/rejoin/catch-up
# through the same injection points as the rest of the matrix.
# ---------------------------------------------------------------------------

def _wait_for(cond, timeout=10.0, what="condition"):
    """Poll an eventual condition with a hard deadline (the condition
    itself is deterministic — only its arrival time is not)."""
    import time
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.01)
    raise AssertionError("timed out waiting for %s" % what)


def _pair(monkeypatch, repl_mode="sync", **srv_kw):
    """A joined (primary, backup) shard pair plus a replicated store
    pointed at the primary. The store learns the backup from hello."""
    pri = ParameterServer(role="primary", repl_mode=repl_mode,
                          **srv_kw).start()
    bak = ParameterServer(role="backup", peer_addr=pri.address,
                          repl_mode=repl_mode).start()
    pri._peer_addr = bak.address
    bak.join_cluster(probe_interval=0)
    _wait_for(lambda: bak._catchup_complete, what="initial catch-up")
    monkeypatch.setenv("MXTPU_PS_REPLICAS", "2")
    kv = _store(monkeypatch, pri.address)
    assert isinstance(kv._conns[0], ka._ReplicatedConn)
    assert kv._conns[0]._addrs[1] == bak.address, \
        "hello must teach the client the shard map"
    return pri, bak, kv


def test_sync_replication_mirrors_every_push(monkeypatch):
    """The baseline invariant everything below builds on: in sync mode
    a push RETURNING means the backup already applied it — no waits,
    no eventually."""
    pri, bak, kv = _pair(monkeypatch)
    try:
        kv.init("w", mx.nd.zeros((4,)))
        for i in range(3):
            kv.push("w", mx.nd.ones((4,)))
            assert bak._clock.get("w") == i + 1, \
                "sync ack returned before the backup applied"
        np.testing.assert_allclose(bak._table["w"], 3 * np.ones(4))
        assert pri._clock["w"] == 3
        srv = kv.stats()
        assert srv["replication"][0]["repl"]["lag"] == 0
    finally:
        kv.close()
        pri.stop()
        bak.stop()


def test_failover_pull_is_fresh_dead_shard_pull_is_stale(monkeypatch):
    """Satellite: a pull served by a just-promoted backup is a LIVE
    pull — no stale marker — while a genuinely dead shard (both
    replicas gone) still degrades to the staleness-marked cache."""
    pri, bak, kv = _pair(monkeypatch)
    try:
        kv.init("w", mx.nd.zeros((4,)))
        kv.push("w", mx.nd.ones((4,)))
        out = mx.nd.zeros((4,))
        kv.pull("w", out=out)                  # warm the cache
        pri.kill()
        _wait_for(lambda: not pri._thread.is_alive(),
                  what="primary teardown")
        kv.pull("w", out=out)                  # failover, not degrade
        np.testing.assert_allclose(out.asnumpy(), np.ones(4))
        h = kv.health()
        assert h["degraded_keys"] == [], \
            "a failover pull must not carry the stale marker"
        assert h["num_dead"] == 0
        assert h["failovers"] == 1 and h["servers"][0]["failed_over"]
        assert bak._role == "primary" and bak._promotions == 1
        # now the shard dies for REAL: both replicas gone — the pull
        # degrades to the last-known value and marks staleness
        bak.stop()
        kv.pull("w", out=out)
        np.testing.assert_allclose(out.asnumpy(), np.ones(4))
        h = kv.health()
        assert "w" in h["degraded_keys"]
        assert h["num_dead"] == 1
    finally:
        kv.close()
        pri.stop()
        bak.stop()


def test_kill_primary_mid_window_zero_acked_loss(monkeypatch):
    """Kill the primary between the pipelined part-pushes of one big
    array (sync mode): the whole unacked window replays against the
    promoted backup; parts the primary forwarded pre-kill are refused
    by the transferred dedupe seqs — every part lands EXACTLY once and
    nothing acked is lost."""
    _eight_part_push(monkeypatch)
    pri, bak, kv = _pair(monkeypatch)
    try:
        kv.init("w", mx.nd.zeros((8, 4)))
        with fault.inject(
                "kind=kill,point=server.recv,op=push,nth=3") as inj:
            kv.push("w", mx.nd.ones((8, 4)))
        assert inj.stats()[0][4] == 1
        assert bak._role == "primary"
        # the promoted table holds each part exactly once, values whole
        for i in range(8):
            sk = "w\x00%d" % i
            assert bak._clock[sk] == 1, (sk, bak._clock)
            assert np.allclose(bak._table[sk], 1.0), bak._table[sk]
        out = mx.nd.zeros((8, 4))
        kv.pull("w", out=out)
        np.testing.assert_allclose(out.asnumpy(), np.ones((8, 4)))
        assert kv.stats()["failovers"] == 1
    finally:
        kv.close()
        pri.stop()
        bak.stop()


def test_kill_primary_mid_coalesced_batch(monkeypatch):
    """Kill the primary inside a coalesced multi-key frame after a
    prefix of its sub-pushes applied (and sync-replicated): the client
    replays the WHOLE batch on the promoted backup, whose transferred
    seqs refuse the prefix — every key exactly once."""
    pri, bak, kv = _pair(monkeypatch)
    try:
        keys = ["k%d" % i for i in range(8)]
        vals = [mx.nd.ones((3,)) * (i + 1) for i in range(8)]
        kv.init(keys, [mx.nd.zeros((3,)) for _ in keys])
        # sub-pushes fire their own server.recv inside the multi frame
        with fault.inject(
                "kind=kill,point=server.recv,op=push,nth=5") as inj:
            kv.push(keys, vals)
        assert inj.stats()[0][4] == 1
        assert bak._role == "primary"
        for i, k in enumerate(keys):
            out = mx.nd.zeros((3,))
            kv.pull(k, out=out)
            np.testing.assert_allclose(out.asnumpy(),
                                       (i + 1) * np.ones(3))
            assert bak._clock[k] == 1, (k, bak._clock)
        assert bak._dup_n >= 1         # the replayed prefix was refused
    finally:
        kv.close()
        pri.stop()
        bak.stop()


def test_sever_repl_stream_sync_mode_acks_after_recovery(monkeypatch):
    """Sever the replication stream itself (sync mode): the push's ack
    is withheld until the stream's retry lands the record — when
    push() returns, the backup must hold the update, sever or no
    sever, applied exactly once."""
    pri, bak, kv = _pair(monkeypatch)
    try:
        kv.init("w", mx.nd.zeros((4,)))
        with fault.inject(
                "kind=sever,point=worker.send,op=repl,nth=1") as inj:
            kv.push("w", mx.nd.ones((4,)))
        assert inj.stats()[0][4] == 1          # the stream really tore
        assert bak._clock.get("w") == 1, \
            "sync ack returned before the re-sent record landed"
        np.testing.assert_allclose(bak._table["w"], np.ones(4))
        assert pri._repl is not None and not pri._repl.dead
    finally:
        kv.close()
        pri.stop()
        bak.stop()


def test_async_repl_mode_bounds_lag_then_drains(monkeypatch):
    """async replication: pushes ack immediately, the stream lags at
    most MXTPU_PS_REPL_LAG_MAX records, and drains to equality."""
    monkeypatch.setattr(ka, "_REPL_LAG_MAX", 2)
    pri, bak, kv = _pair(monkeypatch, repl_mode="async")
    try:
        kv.init("w", mx.nd.zeros((4,)))
        with fault.inject("kind=delay,point=worker.send,op=repl,"
                          "delay=0.02,count=inf"):
            for _ in range(6):
                kv.push("w", mx.nd.ones((4,)))
                assert pri._repl.lag() <= 2, "lag bound violated"
        _wait_for(lambda: bak._clock.get("w") == 6, what="drain")
        np.testing.assert_allclose(bak._table["w"], 6 * np.ones(4))
    finally:
        kv.close()
        pri.stop()
        bak.stop()


def test_kill_backup_during_catchup_primary_detaches(monkeypatch):
    """Kill the backup mid-state-transfer: the stream dies terminally,
    the primary detaches it (redundancy lost, loudly) and keeps
    serving — the fleet never wedges on a dead backup."""
    monkeypatch.setattr(ka, "_REPL_TIMEOUT", 5.0)
    pri = ParameterServer(role="primary").start()
    kv = _store(monkeypatch, pri.address)
    try:
        for i in range(6):
            kv.init("k%d" % i, mx.nd.ones((3,)) * i)
        bak = ParameterServer(role="backup",
                              peer_addr=pri.address).start()
        pri._peer_addr = bak.address
        # the 3rd repl record (an xfer mid-transfer) kills the backup
        with fault.inject(
                "kind=kill,point=server.recv,op=repl,nth=3") as inj:
            bak.join_cluster(probe_interval=0)
            _wait_for(lambda: pri._repl is None,
                      what="primary to detach the dead backup")
        assert inj.stats()[0][4] == 1
        assert not bak._catchup_complete
        # the primary serves on, unreplicated
        kv.push("k0", mx.nd.ones((3,)))
        out = mx.nd.zeros((3,))
        kv.pull("k0", out=out)
        np.testing.assert_allclose(out.asnumpy(), np.ones(3))
        assert kv.health()["num_dead"] == 0
        bak.stop()
    finally:
        kv.close()
        pri.stop()


def test_respawned_primary_rejoins_and_catches_up(monkeypatch):
    """The full repair loop in-process: primary dies mid-training, the
    backup promotes and serves, a fresh server on the old port demotes
    itself against the promoted peer and catches up (table + clocks +
    dedupe seqs + optimizer + ACCUMULATED updater state) — after which
    new pushes replicate to it and the pair is redundant again.
    Momentum SGD on purpose: a catch-up that transferred the table but
    not the momentum buffers would diverge on the very next forwarded
    push (the bug the public-API verify drive caught)."""
    pri, bak, kv = _pair(monkeypatch)
    port = int(pri.address.split(":")[1])
    # momentum-SGD ground truth for grad=1 pushes: m += 0.9m+1,
    # w -= 0.5m  ->  w1=-0.5, w2=-1.45, w3=-2.805
    try:
        kv.init("w", mx.nd.zeros((4,)))
        kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.5,
                                          momentum=0.9))
        kv.push("w", mx.nd.ones((4,)))
        pri.kill()
        _wait_for(lambda: not pri._thread.is_alive(),
                  what="primary teardown")
        kv.push("w", mx.nd.ones((4,)))         # fails over mid-stream
        assert bak._role == "primary"
        np.testing.assert_allclose(bak._table["w"], -1.45 * np.ones(4),
                                   rtol=1e-6)
        pri2 = ParameterServer(port=port, role="primary",
                               peer_addr=bak.address).start()
        try:
            pri2.join_cluster(probe_interval=0)
            assert pri2._role == "backup", \
                "a respawn facing a promoted peer must demote"
            _wait_for(lambda: pri2._catchup_complete, what="catch-up")
            np.testing.assert_allclose(pri2._table["w"],
                                       -1.45 * np.ones(4), rtol=1e-6)
            assert pri2._clock["w"] == 2
            assert pri2._updater is not None, \
                "the optimizer must ride the state transfer"
            assert any(k == "w" for (_, k) in pri2._applied), \
                "push-dedupe seqs must ride the state transfer"
            kv.push("w", mx.nd.ones((4,)))     # replicates to pri2 now
            assert pri2._clock["w"] == 3
            np.testing.assert_allclose(
                pri2._table["w"], -2.805 * np.ones(4), rtol=1e-6,
                err_msg="rejoined backup diverged — the accumulated "
                        "momentum state did not ride the catch-up")
            row = kv.health()["replication"][0]
            assert row["role"] == "primary"
            assert row["promotions"] == 1
            assert row["repl"]["catchup"]["done"]
            assert row["repl"]["lag"] == 0
        finally:
            pri2.stop()
    finally:
        kv.close()
        pri.stop()
        bak.stop()


def test_backup_refuses_client_ops_until_promoted(monkeypatch):
    """Routing safety: a store (mis)pointed at a live backup gets the
    not_serving verdict and swaps to the real primary instead of
    reading a possibly-stale table."""
    pri, bak, kv0 = _pair(monkeypatch)
    kv0.init("w", mx.nd.zeros((4,)))
    kv0.push("w", mx.nd.ones((4,)))
    try:
        # a second store whose 'primary' entry is actually the backup
        monkeypatch.setenv("MXTPU_PS_BACKUP_ADDRS", pri.address)
        kv = _store(monkeypatch, bak.address)
        try:
            out = mx.nd.zeros((4,))
            kv.pull("w", out=out)              # refused, re-routed
            np.testing.assert_allclose(out.asnumpy(), np.ones(4))
            assert kv._conns[0].failovers == 1
            assert pri._role == "primary"      # promote was a no-op
        finally:
            kv.close()
    finally:
        kv0.close()
        pri.stop()
        bak.stop()


def test_kill_worker_mid_push_window(monkeypatch, tmp_path):
    """kill_worker row: a child worker is SIGKILLed by the fault
    harness between the pipelined part-pushes of one big array. The
    server must be left consistent — every part applied at most once,
    no torn values — and a successor worker (fresh origin, the
    launcher-respawn situation) completes the same push cleanly."""
    import json
    import subprocess
    import sys
    srv = ParameterServer().start()
    child = r"""
import os, numpy as np
import jax; jax.config.update("jax_platforms", "cpu")
import mxtpu as mx
from mxtpu import kvstore_async as ka
ka._BIGARRAY_BOUND = 4            # (8, 4) splits into 8 one-row parts
ka._COALESCE_BYTES = 0
kv = mx.kv.create("dist_async")
kv.init("w", mx.nd.zeros((8, 4)))
print("READY", flush=True)
# SIGKILL fires on the 5th wire event after init's frames drain —
# mid-window, with a prefix of the 8 part-pushes applied
import mxtpu.fault as fault
fault.install("kind=kill_worker,point=any,op=push,nth=5")
kv.push("w", mx.nd.ones((8, 4)))
print("UNREACHABLE", flush=True)
"""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update({"JAX_PLATFORMS": "cpu", "MXTPU_PS_ADDRS": srv.address,
                "MXTPU_PS_HEARTBEAT": "0", "MXTPU_PS_LOCAL": "0",
                "MXTPU_PROC_ID": "0", "MXTPU_NUM_PROCS": "1"})
    try:
        proc = subprocess.run([sys.executable, "-c", child], env=env,
                              capture_output=True, text=True, timeout=120)
        assert "READY" in proc.stdout, proc.stdout + proc.stderr
        assert "UNREACHABLE" not in proc.stdout
        assert proc.returncode == -9           # really SIGKILLed
        # applied prefix is consistent: each part 0 or 1 times, values
        # whole (the zero-copy receive can never tear a row)
        for i in range(8):
            sk = "w\x00%d" % i
            assert srv._clock[sk] in (0, 1)
            row = srv._table[sk]
            assert np.allclose(row, 0.0) or np.allclose(row, 1.0)
        applied = sum(srv._clock["w\x00%d" % i] for i in range(8))
        assert applied < 8                     # it really died mid-push
        # the successor (fresh origin = respawned worker) finishes the
        # job: its push is NOT deduped against the dead origin's seqs
        monkeypatch.setattr(ka, "_BIGARRAY_BOUND", 4)
        monkeypatch.setattr(ka, "_COALESCE_BYTES", 0)
        kv = _store(monkeypatch, srv.address)
        try:
            kv.push("w", mx.nd.ones((8, 4)))
            out = mx.nd.zeros((8, 4))
            kv.pull("w", out=out)
            got = out.asnumpy()
            # every row = prefix (0/1) + successor's 1
            for i in range(8):
                expect = 1.0 + (1.0 if srv._clock["w\x00%d" % i] == 2
                                else 0.0)
                assert np.allclose(got[i], expect), (i, got[i])
        finally:
            kv.close()
    finally:
        srv.stop()
    """The same-process shortcut must keep the matrix semantics: a
    post-apply sever replays through the same retry layer and the
    replay is seq-deduped — at-most-once holds with zero wire."""
    monkeypatch.setattr(ka, "_LOCAL_ON", True)
    srv = ParameterServer().start()
    kv = _store(monkeypatch, srv.address)
    try:
        kv.init("w", mx.nd.zeros((4,)))
        with fault.inject("kind=sever,point=server.send,op=push,nth=1") \
                as inj:
            kv.push("w", mx.nd.ones((4,)))
        assert inj.stats()[0][4] == 1
        out = mx.nd.zeros((4,))
        kv.pull("w", out=out)
        np.testing.assert_allclose(out.asnumpy(), np.ones(4))
        assert srv._clock["w"] == 1 and srv._dup_n == 1
        s = kv.stats()
        assert s["local_reqs"] > 0             # it really went local
        assert s["retransmits"] >= 1
    finally:
        kv.close()
        srv.stop()


# ---------------------------------------------------------------------------
# serving rows: the model-serving request path through the same harness
# (mxtpu/serving; the full behavior matrix lives in tests/test_serving.py,
# these are the two wire-level rows of the fault matrix —
# sever @ server.send (op=predict)  -> lost ack AFTER compute: replay
#                                      with the ORIGINAL request id,
#                                      answered exactly once client-side
# kill  @ serve.batch               -> replica dies mid-batch: clients
#                                      fail over, replays answered by
#                                      the surviving replica)
# ---------------------------------------------------------------------------

def _serving_pair(batch_deadline_ms=10):
    from mxtpu.serving import InferenceEngine, ModelServer
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    net = mx.sym.FullyConnected(net, num_hidden=3, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, data_names=("data",),
                        label_names=("softmax_label",))
    mod.bind(data_shapes=[("data", (4, 6))],
             label_shapes=[("softmax_label", (4,))])
    mod.init_params(mx.init.Uniform(0.1))
    ap, xp = mod.get_params()

    def mkeng():
        return InferenceEngine(net, ap, xp, {"data": (6,)},
                               buckets=(4,), warm=False)

    s1 = ModelServer(mkeng(), model_name="fm",
                     batch_deadline_ms_=batch_deadline_ms).start()
    s2 = ModelServer(mkeng(), model_name="fm",
                     batch_deadline_ms_=batch_deadline_ms,
                     replicas=[s1.address]).start()
    s1._replicas.append(s2.address)
    return s1, s2, mkeng


def test_serving_spec_points_validate():
    rules = fault.parse_spec(
        "kind=drop,point=serve.request,op=predict,nth=2;"
        "kind=kill,point=serve.batch;"
        "kind=kill,point=serve.swap;"
        "kind=sever,point=publish.snapshot")
    assert rules[0].point == "serve.request"
    assert rules[1].point == "serve.batch"
    assert rules[2].point == "serve.swap"
    assert rules[3].point == "publish.snapshot"
    # signal kinds stay training-loop-only; transport kinds are free
    with pytest.raises(ValueError, match="worker.step"):
        fault.parse_spec("kind=nan_grad,point=serve.request")
    with pytest.raises(ValueError, match="worker.step"):
        fault.parse_spec("kind=join_worker,point=serve.batch")
    with pytest.raises(ValueError, match="worker.step"):
        fault.parse_spec("kind=split_shard,point=serve.swap")


def test_serving_sever_mid_predict_window(monkeypatch):
    """Lost predict ack (sever @ server.send, post-compute): the
    client's window fails, the health probe finds the replica alive,
    and the replay carries the ORIGINAL request id — the server sees
    the duplicate, the client delivers exactly one answer."""
    from mxtpu.serving import ServingClient
    s1, s2, mkeng = _serving_pair()
    try:
        cli = ServingClient(addrs=[s1.address], budget_ms=5000)
        cli.hello()
        x = np.ones((1, 6), "f")
        warm = cli.predict(x)[0]                    # fault-free baseline
        with fault.inject(
                "kind=sever,point=server.send,op=predict,nth=1") as inj:
            out = cli.predict(x)[0]
        assert inj.stats()[0][4] == 1, "the sever never fired"
        np.testing.assert_array_equal(out, warm)    # same bits, once
        assert cli.stats()["replays"] >= 1
        dups = (s1.stats()["counters"]["dup_requests"]
                + s2.stats()["counters"]["dup_requests"])
        assert dups == 1, "replay did not carry the original rid"
    finally:
        s2.stop()
        s1.stop()


def test_serving_kill_replica_mid_batch(monkeypatch):
    """kind=kill @ serve.batch: the active replica crashes between
    coalescing and compute. Every in-flight client fails over and
    replays on the survivor; each request is answered exactly once,
    bit-identical to the fault-free engine."""
    import threading as _threading
    from mxtpu.serving import ServingClient
    s1, s2, mkeng = _serving_pair(batch_deadline_ms=20)
    try:
        cli = ServingClient(addrs=[s1.address], budget_ms=5000)
        cli.hello()
        oracle = mkeng()
        rng = np.random.RandomState(5)
        xs = [rng.rand(1, 6).astype("f") for _ in range(4)]
        want = [oracle.predict([x])[0] for x in xs]
        outs, errs = {}, {}
        lock = _threading.Lock()

        def one(i):
            try:
                r = cli.predict(xs[i])[0]
                with lock:
                    outs[i] = r
            except Exception as e:
                with lock:
                    errs[i] = e

        with fault.inject("kind=kill,point=serve.batch,nth=1") as inj:
            ts = [_threading.Thread(target=one, args=(i,))
                  for i in range(4)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=60)
        assert inj.stats()[0][4] == 1, "the kill never fired"
        assert not errs, errs
        assert len(outs) == 4
        for i, out in outs.items():
            np.testing.assert_array_equal(out, want[i][:1])
        assert cli.stats()["failovers"] >= 1
        alive = [s for s in (s1, s2) if not s._tcp.dying]
        assert len(alive) == 1
        assert alive[0].stats()["counters"]["responses"] >= 1
    finally:
        s2.stop()
        s1.stop()


# ---------------------------------------------------------------------------
# weight-rollout rows (ISSUE 11): the train→serve stream through the same
# harness (full behavior matrix in tests/test_rollout.py) —
# drop  @ serve.swap        -> version record lost; the replica keeps
#                              answering from the last COMPLETE version
#                              and the stream's watermark re-delivers
# sever @ serve.swap        -> weight stream severed mid-record: the
#                              sync round fails, serving is unaffected,
#                              the retry is an exact catch-up
# kill  @ serve.swap        -> replica dies mid-swap: clients fail over,
#                              the peer swaps the same version and
#                              answers the replays exactly once
# drop/sever @ publish.snapshot -> the trainer's publish is lost BEFORE
#                              any byte lands; subscribers never see a
#                              torn version
# kill  @ publish.snapshot  -> the parameter server crashes mid-publish;
#                              subscribers keep the last complete
#                              version
# ---------------------------------------------------------------------------

def test_weight_swap_drop_keeps_last_complete_version():
    """kind=drop @ serve.swap: the version record is lost at the swap
    choke point — never a half-swapped table, the replica answers from
    the last complete version; the next delivery of the SAME version
    (the watermark was not advanced) applies cleanly."""
    from mxtpu.serving import ServingClient
    s1, s2, mkeng = _serving_pair()
    try:
        cli = ServingClient(addrs=[s1.address], budget_ms=5000)
        x = np.ones((1, 6), "f")
        _, ri = cli.predict2(x)
        assert ri["version"] == 0
        p1 = {n: v * 1.25
              for n, v in s1._engine.current_params().items()}
        with fault.inject(
                "kind=drop,point=serve.swap,nth=1,count=1") as inj:
            assert s1.swap_weights(p1, version=1) is None
        assert inj.stats()[0][4] == 1, "the drop never fired"
        assert s1.stats()["counters"]["swaps_dropped"] == 1
        _, ri = cli.predict2(x)
        assert ri["version"] == 0          # last complete version
        # re-delivery (stream catch-up) lands the same version
        assert s1.swap_weights(p1, version=1) == 1
        _, ri = cli.predict2(x)
        assert ri["version"] == 1
    finally:
        s2.stop()
        s1.stop()


def test_weight_stream_sever_mid_record_catches_up(tmp_path):
    """kind=sever @ serve.swap: the weight stream dies mid-record. The
    sync round surfaces the ConnectionError (counted), serving keeps
    the old version, and the NEXT round re-delivers from the watermark
    — the _ReplStream catch-up discipline on weights."""
    from mxtpu.serving import ServingClient, WeightPublisher, WeightSync
    s1, s2, mkeng = _serving_pair()
    sync = None
    try:
        cli = ServingClient(addrs=[s1.address], budget_ms=5000)
        pub = WeightPublisher(str(tmp_path / "w"))
        pub.publish({n: v * 2.0
                     for n, v in s1._engine.current_params().items()})
        sync = WeightSync(s1, weight_dir=str(tmp_path / "w"), poll=0.05)
        with fault.inject(
                "kind=sever,point=serve.swap,nth=1,count=1") as inj:
            with pytest.raises(ConnectionError):
                sync.poll_once()
        assert inj.stats()[0][4] == 1, "the sever never fired"
        x = np.ones((1, 6), "f")
        _, ri = cli.predict2(x)
        assert ri["version"] == 0          # unaffected mid-sever
        assert sync.poll_once() == 1       # exact catch-up, fault gone
        _, ri = cli.predict2(x)
        assert ri["version"] == 1
    finally:
        if sync is not None:
            sync.stop()
        s2.stop()
        s1.stop()


def test_weight_swap_kill_mid_swap_fails_over_exactly_once():
    """kind=kill @ serve.swap: the active replica dies mid-swap. Its
    clients fail over with their ORIGINAL request ids; the peer (which
    received the same version record) answers every replay exactly
    once from the NEW version — zero acknowledged loss across the
    kill."""
    import threading as _threading
    from mxtpu.serving import ServingClient
    s1, s2, mkeng = _serving_pair(batch_deadline_ms=20)
    try:
        cli = ServingClient(addrs=[s1.address], budget_ms=5000)
        cli.hello()
        p1 = {n: v * 1.5
              for n, v in s1._engine.current_params().items()}
        oracle = mkeng()
        oracle.swap_weights(p1, version=1)
        rng = np.random.RandomState(6)
        xs = [rng.rand(1, 6).astype("f") for _ in range(4)]
        want = [oracle.predict([x])[0] for x in xs]
        with fault.inject("kind=kill,point=serve.swap,nth=1") as inj:
            with pytest.raises((ConnectionError, RuntimeError)):
                s1.swap_weights(p1, version=1)   # dies mid-swap
            assert s2.swap_weights(p1, version=1) == 1
        assert inj.stats()[0][4] == 1, "the kill never fired"
        assert s1._tcp.dying and not s2._tcp.dying
        outs, errs = {}, {}
        lock = _threading.Lock()

        def one(i):
            try:
                r, ri = cli.predict2(xs[i])
                with lock:
                    outs[i] = (r[0], ri["version"])
            except Exception as e:
                with lock:
                    errs[i] = e

        ts = [_threading.Thread(target=one, args=(i,)) for i in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert not errs, errs
        assert len(outs) == 4              # exactly one answer each
        for i, (out, v) in outs.items():
            assert v == 1
            np.testing.assert_array_equal(out, want[i][:1])
        assert cli.stats()["failovers"] >= 1
    finally:
        s2.stop()
        s1.stop()


def test_publish_snapshot_drop_loses_publish_cleanly(tmp_path):
    """kind=drop @ publish.snapshot: the publish is lost BEFORE any
    byte is written — no torn snapshot, no version bump; the next
    publish lands normally with the next version number."""
    from mxtpu.serving import WeightPublisher
    pub = WeightPublisher(str(tmp_path / "w"))
    params = {"w": np.arange(4, dtype="f")}
    with fault.inject(
            "kind=drop,point=publish.snapshot,nth=1,count=1") as inj:
        assert pub.publish(params) is None
    assert inj.stats()[0][4] == 1, "the drop never fired"
    assert pub.versions() == [] and pub.version == 0
    assert pub.stats()["dropped"] == 1
    out = pub.publish(params)
    assert out["version"] == 1 and pub.versions() == [1]


def test_publish_snapshot_sever_crashes_trainer_mid_publish(tmp_path):
    """kind=sever @ publish.snapshot: the trainer-side publish dies
    mid-flight. The fault fires BEFORE the snapshot write, so
    subscribers can never observe a half-published version — the dir
    still holds only complete, digest-verified versions."""
    from mxtpu.serving import WeightPublisher
    pub = WeightPublisher(str(tmp_path / "w"))
    pub.publish({"w": np.zeros(4, "f")})
    with fault.inject(
            "kind=sever,point=publish.snapshot,nth=1,count=1") as inj:
        with pytest.raises(ConnectionError):
            pub.publish({"w": np.ones(4, "f")})
    assert inj.stats()[0][4] == 1, "the sever never fired"
    assert pub.versions() == [1]           # v2 never became visible
    out = pub.publish({"w": np.ones(4, "f")})
    assert out["version"] == 2 and pub.versions() == [1, 2]


def test_publish_snapshot_kill_takes_down_ps_mid_publish():
    """kind=kill @ publish.snapshot on the parameter server: the shard
    crashes mid-publish. The publishing client sees the connection
    die; the weight stream's published version never advances, so
    subscribers keep the last complete version."""
    srv = ka.ParameterServer()
    srv.start()
    conn = ka._ServerConn(srv.address, n_socks=1)
    try:
        conn.request("init", "w", np.ones(4, "f"))
        reply = conn.request("publish", None, None, False)
        assert reply[1]["version"] == 1
        with fault.inject(
                "kind=kill,point=publish.snapshot,nth=1") as inj:
            with pytest.raises((ConnectionError, RuntimeError)):
                conn.request("publish", None, None, False,
                             retries=0, timeout=5.0)
        assert inj.stats()[0][4] == 1, "the kill never fired"
        assert srv._tcp.dying
        assert srv._pub_version == 1       # v2 never became visible
    finally:
        conn.close()
        srv.stop()


# ---------------------------------------------------------------------------
# fused Module dist path (ISSUE 10): faults mid-grad-push-window
# ---------------------------------------------------------------------------

def _fused_dist_module(monkeypatch, kv, batches=4):
    """A Module on the fused dist fast path (async window) driven for
    ``batches`` fit-loop steps against ``kv``. Returns (module, number
    of trainable params)."""
    monkeypatch.setenv("MXTPU_MODULE_FUSED", "1")
    monkeypatch.setenv("MXTPU_MODULE_FUSED_DIST", "1")
    monkeypatch.setenv("MXTPU_MODULE_DIST_MODE", "async")
    rng = np.random.RandomState(3)
    x = rng.rand(64, 8).astype("f")
    y = (rng.rand(64) * 4).astype("f")
    it = mx.io.NDArrayIter(x, y, batch_size=16,
                           label_name="softmax_label")
    sym = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.var("data"), num_hidden=4,
                              name="ffd"), name="softmax")
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.bind(it.provide_data, it.provide_label)
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(kvstore=kv, optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    assert mod._fused is not None and mod._fused.mode == "dist"
    pool = list(it)
    for i in range(batches):
        b = pool[i % len(pool)]
        mod.forward_backward(b)
        mod.update()
    mod._fused.flush()
    return mod, 2


def test_fused_dist_sever_mid_grad_push_window(monkeypatch):
    """Sever the connection after the server applied a fused-step
    pushpull but before its ack (the grad-push window is in flight):
    the window fails onto the retry layer, the replay of the applied
    sub-pushes is REFUSED by seq dedupe while still answering with the
    current value — each step's gradient lands exactly once and
    training completes."""
    srv = ParameterServer().start()
    kv = _store(monkeypatch, srv.address)
    try:
        with fault.inject(
                "kind=sever,point=server.send,op=multi,nth=2") as inj:
            mod, n_params = _fused_dist_module(monkeypatch, kv,
                                               batches=4)
        assert inj.stats()[0][4] == 1, "the sever never fired"
        # exactly-once: every key's clock counts each step's push once
        for k, c in srv._clock.items():
            assert c == 4, (k, c)
        assert srv._dup_n >= 1          # the applied batch replayed
        s = kv.stats()
        assert s["retransmits"] >= 1    # window replay happened
        assert s["dup_pushes"] >= 1     # ...and was deduped
        args, _ = mod.get_params()
        for v in args.values():
            assert np.isfinite(v.asnumpy()).all()
    finally:
        kv.close()
        srv.stop()


def test_fused_dist_kill_primary_mid_grad_push_window(monkeypatch):
    """SIGKILL the primary inside a fused-step pushpull frame after a
    prefix of the step's sub-pushes applied (and sync-replicated): the
    client fails over IN PLACE, replays the whole window on the
    promoted backup, whose transferred dedupe seqs refuse the prefix —
    every gradient exactly once, zero acknowledged loss, and the fused
    path keeps training through the failover."""
    pri, bak, kv = _pair(monkeypatch)
    try:
        # 2 sub-pushes per step frame: nth=6 lands on the SECOND sub of
        # the third step, so the frame dies with a one-sub applied (and
        # sync-replicated) prefix for the replay to be refused on
        with fault.inject(
                "kind=kill,point=server.recv,op=pushpull,nth=6") as inj:
            mod, n_params = _fused_dist_module(monkeypatch, kv,
                                               batches=4)
        assert inj.stats()[0][4] == 1, "the kill never fired"
        assert bak._role == "primary"
        for k, c in bak._clock.items():
            assert c == 4, (k, c)
        assert bak._dup_n >= 1, "the replayed prefix must be refused"
        assert kv.stats()["failovers"] == 1
        assert mod._fused is not None and mod._fused.mode == "dist"
        args, _ = mod.get_params()
        for v in args.values():
            assert np.isfinite(v.asnumpy()).all()
    finally:
        kv.close()
        pri.stop()
        bak.stop()


# ---------------------------------------------------------------------------
# AMP half-width wire rows (ISSUE 12): the push payload's dtype IS the
# wire tag — replay/dedupe must be dtype-stable, the server table stays
# the fp32 master, and pushpull replies ride bf16 in kind.
# ---------------------------------------------------------------------------

def test_pushpull_bf16_wire_dtype_tag_replay_dedupe(monkeypatch):
    """A bf16 pushpull severed at server.send (applied; ack lost): the
    blind replay carries the SAME bf16 payload, the (origin, seq)
    dedupe refuses the re-apply, the retry still answers with the
    current value — and both the reply dtype (bf16, in kind) and the
    server table dtype (fp32 master) survive the replay."""
    import ml_dtypes
    srv = ParameterServer().start()
    kv = _store(monkeypatch, srv.address)
    try:
        kv.init("w", mx.nd.zeros((4,)))
        g = np.ones(4, ml_dtypes.bfloat16)
        out = mx.nd.zeros((4,))
        with fault.inject(
                "kind=sever,point=server.send,op=pushpull,nth=1") as inj:
            kv.push_pull("w", g, out=out)
        assert inj.stats()[0][4] == 1
        # applied exactly once into the fp32 master, replay refused
        assert srv._clock["w"] == 1
        assert srv._dup_n == 1
        assert srv._table["w"].dtype == np.float32
        np.testing.assert_allclose(srv._table["w"], np.ones(4))
        # the pull target got the post-update value, upcast to fp32
        assert out.dtype == np.float32
        np.testing.assert_allclose(out.asnumpy(), np.ones(4))
        # the raw wire reply is bf16 — the in-kind half of the tag
        reply = kv._conn("w").request(
            "pushpull", "w", np.ones(4, ml_dtypes.bfloat16), 0,
            kv._origin, next(kv._seq))
        assert reply[0] == "ok"
        assert reply[1].dtype == ml_dtypes.bfloat16
        assert srv._table["w"].dtype == np.float32
    finally:
        kv.close()
        srv.stop()


def test_push_bf16_payload_upcasts_into_fp32_table(monkeypatch):
    """A plain bf16 push (the ShardedTrainer attach_kvstore wire, or a
    buffered replay): _wire_decode upcasts before the in-place apply,
    so the accumulate math never runs half-precision."""
    srv = ParameterServer().start()
    kv = _store(monkeypatch, srv.address)
    try:
        import ml_dtypes
        kv.init("w", mx.nd.zeros((4,)))
        for _ in range(3):
            kv.push("w", np.full(4, 0.5, ml_dtypes.bfloat16))
        assert srv._table["w"].dtype == np.float32
        np.testing.assert_allclose(srv._table["w"], np.full(4, 1.5))
        assert srv._clock["w"] == 3
    finally:
        kv.close()
        srv.stop()


def test_module_step_fault_point_validates():
    """The module.step grammar row: nan_grad is valid there (the AMP
    loss-scale overflow drill), the elastic signal kinds are not (the
    guard owns the fleet callbacks)."""
    rules = fault.parse_spec("kind=nan_grad,point=module.step,nth=2")
    assert rules[0].point == "module.step"
    with pytest.raises(ValueError, match="join_worker"):
        fault.parse_spec("kind=join_worker,point=module.step")


# ---------------------------------------------------------------------------
# row-sparse pushpull (ISSUE 13): faults mid-sparse-wire. The matrix rows:
#   sever @ server.send op=spushpull -> replay refused by seq dedupe,
#       reply still carries the CURRENT row values (exactly-once apply)
#   kill primary mid-sparse-push     -> promoted backup holds the
#       forwarded prefix and REFUSES its replay; rows land exactly once
#   online split of an embedding shard -> row-range value + clock +
#       dedupe seqs + row-wise optimizer state move exactly-once
# ---------------------------------------------------------------------------

def test_sparse_pushpull_sever_replays_exactly_once(monkeypatch):
    """Sever after the server applied a sparse pushpull but before its
    ack: the blind replay carries the same (row_ids, rows) payload,
    the (origin, seq) watermark refuses the re-apply, and the retry's
    reply still gathers the current row values — rows land exactly
    once, the pull half stays fresh."""
    srv = ParameterServer().start()
    kv = _store(monkeypatch, srv.address)
    try:
        kv.init("emb", mx.nd.zeros((6, 3)))
        kv.set_optimizer(mx.optimizer.SGD(learning_rate=1.0,
                                          momentum=0.9,
                                          rescale_grad=1.0))
        ids = np.array([1, 4], "int64")
        out = mx.nd.zeros((6, 3))
        with fault.inject(
                "kind=sever,point=server.send,op=spushpull,nth=1") as inj:
            kv.sparse_push_pull("emb", ids, np.ones((2, 3), "f"),
                                out=out)
        assert inj.stats()[0][4] == 1, "the sever never fired"
        assert srv._clock["emb"] == 1          # applied exactly once
        assert srv._dup_n == 1                 # the replay was refused
        assert kv.stats()["retransmits"] >= 1
        got = out.asnumpy()
        np.testing.assert_allclose(got[ids], -np.ones((2, 3)))
        assert np.all(got[[0, 2, 3, 5]] == 0)  # untouched rows intact
        # momentum applied once, not twice: second push continues it
        kv.sparse_push_pull("emb", ids, np.ones((2, 3), "f"), out=out)
        np.testing.assert_allclose(out.asnumpy()[ids],
                                   np.full((2, 3), -2.9))
    finally:
        kv.close()
        srv.stop()


def test_sparse_push_kill_primary_refuses_replayed_prefix(monkeypatch):
    """SIGKILL the primary AFTER a sparse pushpull applied and
    sync-replicated but before its ack: the client fails over in
    place and replays the frame at the promoted backup, whose
    forwarded watermark REFUSES the re-apply — every row update
    exactly once, zero acknowledged loss, row values bit-intact."""
    pri, bak, kv = _pair(monkeypatch)
    try:
        kv.init("emb", mx.nd.zeros((6, 3)))
        kv.set_optimizer(mx.optimizer.SGD(learning_rate=1.0,
                                          rescale_grad=1.0))
        ids = np.array([2, 5], "int64")
        out = mx.nd.zeros((6, 3))
        kv.sparse_push_pull("emb", ids, np.ones((2, 3), "f"), out=out)
        with fault.inject(
                "kind=kill,point=server.send,op=spushpull,nth=1") as inj:
            kv.sparse_push_pull("emb", ids, np.ones((2, 3), "f"),
                                out=out)
        assert inj.stats()[0][4] == 1, "the kill never fired"
        assert bak._role == "primary"
        assert kv.stats()["failovers"] == 1
        # first frame refused (forwarded prefix), second applied fresh
        assert bak._clock["emb"] == 2
        assert bak._dup_n >= 1
        got = out.asnumpy()
        np.testing.assert_allclose(got[ids], -2 * np.ones((2, 3)))
        np.testing.assert_allclose(
            np.asarray(bak._table["emb"])[np.asarray(ids)],
            -2 * np.ones((2, 3)))
    finally:
        kv.close()
        pri.stop()
        bak.stop()


def test_split_moves_sparse_embedding_state_exactly_once(monkeypatch):
    """Online split of a hot embedding shard: the sparse key moves with
    its value, clock, push-dedupe seqs and ROW-WISE optimizer state
    (numpy momentum table) — a replayed pre-split seq is refused at the
    new home, and the next fresh push continues the momentum sequence
    bit-for-bit with an unsplit control run."""
    src = ParameterServer().start()
    dst = ParameterServer().start()
    ctl = ParameterServer().start()
    kv = _store(monkeypatch, src.address)
    monkeypatch.setenv("MXTPU_PS_ADDRS", ctl.address)
    kv_ctl = mx.kv.create("dist_async")
    try:
        opt = dict(learning_rate=0.5, momentum=0.9, rescale_grad=1.0)
        ids = np.array([1, 4], "int64")
        for store in (kv, kv_ctl):
            store.init("emb", mx.nd.zeros((6, 3)))
            store.set_optimizer(mx.optimizer.SGD(**opt))
        out, out_ctl = mx.nd.zeros((6, 3)), mx.nd.zeros((6, 3))
        kv.sparse_push_pull("emb", ids, np.ones((2, 3), "f"), out=out)
        kv_ctl.sparse_push_pull("emb", ids, np.ones((2, 3), "f"),
                                out=out_ctl)
        reply = kv._conn("emb").request("split", dst.address, ["emb"])
        assert reply[0] == "ok" and reply[1]["moved"] == ["emb"]
        assert "emb" not in src._table
        # replay a PRE-SPLIT seq at the new home: the transferred
        # dedupe seqs refuse it (nothing double-applies)
        dst_conn = kv._conn_for_addr(dst.address)
        r = dst_conn.request("spush", "emb", ids, np.ones((2, 3), "f"),
                             0, kv._origin, 1)
        assert r == ("ok", "dup")
        assert dst._clock["emb"] == 1
        # fresh push routes via map_stale to dst and CONTINUES the
        # moved momentum state exactly like the unsplit control
        kv.sparse_push_pull("emb", ids, np.ones((2, 3), "f"), out=out)
        kv_ctl.sparse_push_pull("emb", ids, np.ones((2, 3), "f"),
                                out=out_ctl)
        assert dst._clock["emb"] == 2
        np.testing.assert_array_equal(out.asnumpy(), out_ctl.asnumpy())
        assert kv.stats()["map_reroutes"] >= 1
    finally:
        kv.close()
        kv_ctl.close()
        src.stop()
        dst.stop()
        ctl.stop()


# ---------------------------------------------------------------------------
# streaming data plane rows (ISSUE 18; full drills in test_streaming.py
# and the serve->train loop in test_dist_launch.py)
# ---------------------------------------------------------------------------

def test_stream_append_drop_no_torn_record(tmp_path):
    """drop @ stream.append: the injected loss sheds the record BEFORE
    any byte reaches the segment file — a concurrent tailer can never
    observe a torn record, only a clean gap the producer re-sends."""
    from mxtpu.streaming import StreamReader, StreamWriter
    w = StreamWriter(str(tmp_path), shard=0)
    w.append(b"first")
    with fault.inject("kind=drop,point=stream.append,nth=1") as inj:
        assert w.append(b"lost") is None
        assert inj.stats()[0][4] == 1
    seg, _ = w.append(b"second")
    records, _end, _sealed = StreamReader(str(tmp_path), 0).read(seg)
    assert [p for p, _ in records] == [b"first", b"second"]
    w.close()


def test_stream_sever_mid_tail_requeues_lease(monkeypatch, tmp_path):
    """sever @ stream.tail: the consumer dies mid-tail holding the
    segment lease; its bye requeues the lease and a successor replays
    the segment from the committed offset — exactly once (the clock
    totals in test_streaming.py's twin prove the arithmetic)."""
    from mxtpu.kvstore_async import stream_origin
    from mxtpu.streaming import StreamingIter, StreamWriter
    w = StreamWriter(str(tmp_path), shard=0)
    w.append(b"rec")
    w.close()
    srv = ParameterServer().start()
    kv = _store(monkeypatch, srv.address)
    try:
        it = StreamingIter(kv, str(tmp_path), group="m", batch_size=1,
                           decode=None, idle_timeout=0.3, poll=0.01)
        with fault.inject("kind=sever,point=stream.tail,nth=1"):
            with pytest.raises(ConnectionError):
                it.iter_next()
        assert srv._cursors[stream_origin("m", 0, 0)]["outstanding"]
        kv.close()                          # bye -> lease requeues
        kv2 = _store(monkeypatch, srv.address)
        it2 = StreamingIter(kv2, str(tmp_path), group="m",
                            batch_size=1, decode=None,
                            idle_timeout=0.3, poll=0.01)
        assert it2.iter_next() is True      # successor owns the lease
        assert it2.getdata() == [b"rec"]
        kv2.stream_push([], it2.pending_commit())
        it2.commit_done()
        assert kv2.stream_offsets("m")[(0, 0)][1] is True
        kv2.close()
    finally:
        srv.stop()


def test_stream_killed_trainer_replay_refused(monkeypatch, tmp_path):
    """Trainer killed between the server durably applying a frame
    (grads + offset commit) and recording its success locally: the
    respawn re-derives the SAME (origin, seq) frame from the log and
    the server refuses the double — grads AND commit."""
    srv = ParameterServer().start()
    kv = _store(monkeypatch, srv.address)
    try:
        kv.init("w", mx.nd.zeros((2,)))
        frame_parts = [("w", np.ones((2,), "f"))]
        commit = ("m", 0, 0, 64, False)
        assert kv.stream_push(frame_parts, commit) is False  # applied
        # the respawn's bit-identical replay
        assert kv.stream_push(frame_parts, commit) is True   # refused
        out = mx.nd.zeros((2,))
        kv.pull("w", out=out)
        np.testing.assert_allclose(out.asnumpy(), 1.0)       # once
        assert srv._stream_dup == 1 and srv._clock["w"] == 1
        assert kv.stream_offsets("m")[(0, 0)] == (64, False)
    finally:
        kv.close()
        srv.stop()


# ---------------------------------------------------------------------------
# partition rows (ISSUE 19): epoch-fenced replication, split-brain
# prevention, probe-through-peer unreachable verdicts, heal-time
# reconciliation. The 10k-op acceptance drill with a control run and
# the full journal checker lives in ci/check_partition.py; these rows
# pin each mechanism in isolation.
# ---------------------------------------------------------------------------

# the whole client command surface toward one address — what a real
# network partition cuts (peer_info/join_backup/promote/repl ride other
# links or other addrs and are scoped by their own rules)
_CLIENT_OPS = "push|pull|pushpull|spushpull|multi|init|hello|ping" \
              "|barrier|shard_map"


def _split_pair(monkeypatch, repl_mode="sync"):
    """_pair, but with addresses guaranteed substring-free of each
    other (partition rules match addr by substring)."""
    pri = ParameterServer(role="primary", repl_mode=repl_mode).start()
    bak = None
    for _ in range(4):
        bak = ParameterServer(role="backup", peer_addr=pri.address,
                              repl_mode=repl_mode).start()
        if pri.address not in bak.address \
                and bak.address not in pri.address:
            break
        bak.stop()
    pri._peer_addr = bak.address
    bak.join_cluster(probe_interval=0)
    _wait_for(lambda: bak._catchup_complete, what="initial catch-up")
    monkeypatch.setenv("MXTPU_PS_REPLICAS", "2")
    kv = _store(monkeypatch, pri.address)
    return pri, bak, kv


def test_partition_primary_from_clients_promotes_and_fences(monkeypatch):
    """partition @ client->primary mid-push-window: the failover probe
    finds the standby CAN still reach the primary, but the grace window
    is spent (grace=0) so availability wins — the backup is promoted
    and mints fencing epoch 2 while the cut-off incumbent still thinks
    it is primary at epoch 1. On heal the incumbent's own peer probe is
    the fencing trigger: it demotes, rejoins as backup and catches up;
    no acked push is lost and the pair reconverges bit-for-bit."""
    monkeypatch.setattr(ka, "_PARTITION_GRACE", 0.0)
    pri, bak, kv = _split_pair(monkeypatch)
    try:
        kv.init("w", mx.nd.zeros((4,)))
        for _ in range(3):
            kv.push("w", mx.nd.ones((4,)))
        with fault.inject("kind=partition,point=worker.send,"
                          "addr=%s,op=%s"
                          % (pri.address, _CLIENT_OPS)) as inj:
            for _ in range(3):
                kv.push("w", mx.nd.ones((4,)))
            assert inj.stats()[0][4] >= 1
            assert bak._role == "primary" and bak._epoch == 2
            assert bak._promotions == 1
            # the cut-off incumbent never heard the promotion: still
            # primary at epoch 1 — but no client can reach it, so no
            # two servers ack the same key in the same epoch
            assert pri._role == "primary" and pri._epoch == 1
            out = mx.nd.zeros((4,))
            kv.pull("w", out=out)     # served LIVE by the new primary
            np.testing.assert_allclose(out.asnumpy(), 6.0)
            h = kv.health()
            assert h["failovers"] == 1 and h["fence_epoch"] == 2
        # heal: one incumbent monitor tick fences + rejoins
        assert pri._probe_peer()
        assert pri._role == "backup" and pri._epoch == 2
        assert not pri._fenced        # rejoin completed
        _wait_for(lambda: pri._catchup_complete,
                  what="post-heal catch-up")
        for _ in range(2):            # sync acks mirror on pri again
            kv.push("w", mx.nd.ones((4,)))
        _wait_for(lambda: pri._clock.get("w") == 8,
                  what="replication to the rejoined backup")
        assert np.asarray(pri._table["w"]).tobytes() \
            == np.asarray(bak._table["w"]).tobytes()
        np.testing.assert_allclose(np.asarray(bak._table["w"]), 8.0)
    finally:
        kv.close()
        pri.stop()
        bak.stop()


def test_partition_repl_link_sync_acks_solo_and_buffers(monkeypatch):
    """partition @ primary->backup in sync mode: an ack blocks only
    until the send failure kills the stream, then the primary acks
    solo — loudly unreplicated — and keeps the cut records for
    heal-time reconciliation. Reattach streams the whole table back
    (reconciliation window included) and redundancy returns."""
    pri, bak, kv = _split_pair(monkeypatch)
    try:
        kv.init("w", mx.nd.zeros((4,)))
        kv.push("w", mx.nd.ones((4,)))
        assert bak._clock.get("w") == 1     # sync ack == mirrored
        with fault.inject("kind=partition,point=worker.send,"
                          "addr=%s,op=repl" % bak.address) as inj:
            # the push STILL acks (liveness): the dead stream is
            # detected within the sync budget and the record kept
            kv.push("w", mx.nd.ones((4,)))
            assert inj.stats()[0][4] >= 1
            _wait_for(lambda: pri._repl_lost, what="stream detach")
            kv.push("w", mx.nd.ones((4,)))  # solo from the start
            assert pri._clock["w"] == 3
            assert bak._clock.get("w") == 1  # frozen mid-cut
            with pri._ctr_lock:
                kept = len(pri._unreplicated)
            assert kept == 2
            assert kv.stats()["replication"][0]["repl"] is None
        # heal: the backup's own monitor tick reattaches it
        assert bak._probe_peer()
        assert not pri._repl_lost and pri._unreplicated == []
        _wait_for(lambda: bak._clock.get("w") == 3,
                  what="post-heal catch-up")
        assert np.asarray(bak._table["w"]).tobytes() \
            == np.asarray(pri._table["w"]).tobytes()
    finally:
        kv.close()
        pri.stop()
        bak.stop()


def test_asymmetric_cut_unreachable_not_dead_no_promotion(monkeypatch):
    """Only the CLIENT's link to the primary is cut; the standby can
    still reach it (peer_alive). Within MXTPU_PS_PARTITION_GRACE the
    verdict is 'unreachable', NOT 'dead': no promotion, pushes buffer
    with their original seqs, pulls degrade to the cached value, and
    the heal-time health sweep flushes everything — zero loss, zero
    failovers (satellite: health() tells the two states apart)."""
    monkeypatch.setattr(ka, "_PARTITION_GRACE", 60.0)
    pri, bak, kv = _split_pair(monkeypatch)
    try:
        kv.init("w", mx.nd.zeros((4,)))
        kv.push("w", mx.nd.ones((4,)))
        out = mx.nd.zeros((4,))
        kv.pull("w", out=out)               # warm the pull cache
        with fault.inject("kind=partition,point=worker.send,"
                          "addr=%s,op=%s" % (pri.address, _CLIENT_OPS)):
            for _ in range(2):
                kv.push("w", mx.nd.ones((4,)))   # buffered, not lost
            h = kv.health()
            assert h["num_unreachable"] == 1 and h["num_dead"] == 0
            assert h["failovers"] == 0
            assert h["pending_pushes"] == 2
            assert h["servers"][0]["state"] == "unreachable"
            assert bak._role == "backup" and bak._epoch == 1, \
                "a healthy-but-unreachable primary must not be deposed"
            kv.pull("w", out=out)           # degraded cached value
            np.testing.assert_allclose(out.asnumpy(), 1.0)
            assert "w" in kv.health()["degraded_keys"]
        # heal: one health sweep re-registers and flushes the buffer
        kv._check_health()
        assert kv.health()["pending_pushes"] == 0
        _wait_for(lambda: bak._clock.get("w") == 3,
                  what="flushed pushes to replicate")
        np.testing.assert_allclose(np.asarray(pri._table["w"]), 3.0)
        kv.pull("w", out=out)               # live again: marker clears
        np.testing.assert_allclose(out.asnumpy(), 3.0)
        h = kv.health()
        assert h["failovers"] == 0 and h["num_unreachable"] == 0
        assert h["degraded_keys"] == []
    finally:
        kv.close()
        pri.stop()
        bak.stop()


def test_split_brain_heal_reconciles_bit_equal(monkeypatch, tmp_path):
    """The full lifecycle in miniature (ci/check_partition.py is the
    10k-op version): async-mode divergence window buffered at the
    cut-off primary, backup promoted under epoch 2, heal-time
    reconciliation replays the window at the new primary EXACTLY once
    — the client's post-failover seqs sit ABOVE the window's, so the
    (origin, key) watermarks alone could not dedupe the replay (the
    regression this row pins) — and the journal checker proves no
    acked write was lost."""
    monkeypatch.setattr(ka, "_PARTITION_GRACE", 0.0)
    monkeypatch.setenv("MXTPU_HISTORY_DIR", str(tmp_path))
    consistency.reset()
    try:
        pri, bak, kv = _split_pair(monkeypatch, repl_mode="async")
        try:
            kv.init("w", mx.nd.zeros((4,)))
            for _ in range(2):
                kv.push("w", mx.nd.ones((4,)))
            _wait_for(lambda: bak._clock.get("w") == 2,
                      what="warm-up replication")
            # divergence: repl link cut, the primary acks + buffers
            with fault.inject("kind=partition,point=worker.send,"
                              "addr=%s,op=repl" % bak.address):
                for _ in range(3):
                    kv.push("w", mx.nd.ones((4,)))
                _wait_for(lambda: pri._repl_lost, what="stream detach")
                _wait_for(lambda: pri._clock.get("w") == 5,
                          what="solo acks")
            with pri._ctr_lock:
                assert len(pri._unreplicated) == 3
            # split: clients lose the primary, the backup is promoted
            with fault.inject("kind=partition,point=worker.send,"
                              "addr=%s,op=%s"
                              % (pri.address, _CLIENT_OPS)):
                for _ in range(3):
                    kv.push("w", mx.nd.ones((4,)))
                assert bak._role == "primary" and bak._epoch == 2
            # heal: fence via the peer probe, reconcile, demote
            assert pri._probe_peer()
            assert pri._role == "backup" and pri._epoch == 2
            _wait_for(lambda: bak._clock.get("w") == 8,
                      what="reconciled divergence window")
            _wait_for(lambda: pri._catchup_complete,
                      what="post-heal catch-up")
            for _ in range(2):
                kv.push("w", mx.nd.ones((4,)))
            _wait_for(lambda: bak._clock.get("w") == 10
                      and pri._clock.get("w") == 10,
                      what="post-heal convergence")
            np.testing.assert_allclose(
                np.asarray(bak._table["w"]), 10.0)
            assert np.asarray(pri._table["w"]).tobytes() \
                == np.asarray(bak._table["w"]).tobytes()
            assert kv.health()["failovers"] == 1
        finally:
            kv.close()
            pri.stop()
            bak.stop()
        consistency.reset()       # close the writer before reading
        report = consistency.check(str(tmp_path))
        assert report["ok"], report["violations"]
        assert sorted(report["epochs"]) == [1, 2]
        assert report["acked"] >= 10
    finally:
        consistency.reset()


def test_stale_epoch_cursor_done_is_fenced():
    """Epoch discipline on the server-owned cursor (tentpole b): a
    segment lease granted before a partition cannot be retired under
    its stale grant epoch once the shard was re-granted after the heal
    — the late completion gets the ``fenced`` verdict, so two tailers
    can never both retire one segment."""
    srv = ParameterServer(role="primary").start()
    conn = ka._ServerConn(srv.address)
    try:
        conn.request("hello", "tailer-a", 0)
        r = conn.request("cursor_next", "tailer-a", "seg", 1, "r1")
        assert r[1] == 0 and r[3] == 1     # granted under epoch 1
        # the fleet moves on: a promotion elsewhere minted epoch 2 and
        # this server adopted it at the rejoin handshake (white-box
        # stand-in — the full adoption path runs in the rows above)
        with srv._repl_guard:
            srv._epoch = 2
        conn.request("bye", "tailer-a")    # death requeues the lease
        conn.request("hello", "tailer-b", 0)
        r2 = conn.request("cursor_next", "tailer-b", "seg", 1, "r2")
        assert r2[1] == 0 and r2[3] == 2   # re-granted under epoch 2
        # the partitioned ex-holder's late completion: refused
        with pytest.raises(RuntimeError, match="fenced"):
            conn.request("cursor_done", "tailer-a", "seg", 0, 1,
                         retries=0)
        assert 0 not in srv._cursors["seg"]["done"]
        # the current holder retires it fine
        conn.request("cursor_done", "tailer-b", "seg", 0, 2)
        assert 0 in srv._cursors["seg"]["done"]
    finally:
        conn.close()
        srv.stop()


def test_stream_lease_lost_across_heal_is_yielded(monkeypatch):
    """Client half of the cursor fencing: stream_lease_done meeting a
    ``fenced`` refusal treats the lease as LOST — the new holder owns
    the segment — instead of raising into the consumer loop, and the
    witnessed epoch is adopted."""
    from mxtpu.kvstore_async import stream_origin
    srv = ParameterServer(role="primary").start()
    kv = _store(monkeypatch, srv.address)
    kv2 = None
    try:
        lease = stream_origin("g", 0, 0)
        assert kv.stream_lease(lease) == "owned"
        with srv._repl_guard:
            srv._epoch = 2
        srv._drop_worker(kv._origin)   # requeue, as a GC'd death would
        kv2 = _store(monkeypatch, srv.address)
        assert kv2.stream_lease(lease) == "owned"
        kv.stream_lease_done(lease)        # fenced -> lease yielded
        assert kv._fleet_epoch == 2
        assert srv._cursors[lease]["outstanding"] == {0: kv2._origin}
        assert 0 not in srv._cursors[lease]["done"]
        kv2.stream_lease_done(lease)       # the real holder retires it
        assert 0 in srv._cursors[lease]["done"]
    finally:
        kv.close()
        if kv2 is not None:
            kv2.close()
        srv.stop()
