"""Observability plane tests (ISSUE 14): registry semantics, sampled
cross-process tracing, the telemetry surface, and the fault-matrix
rows pinning that observability is STRICTLY PASSIVE — drop/sever on
the ``metrics`` op or on a trace-carrying frame never affects
training results (exactly-once and bit-parity unaffected), and a dead
shard's telemetry gap is reported, not fatal.
"""
import json
import os
import threading
import time

import numpy as np
import pytest

import mxtpu as mx
from mxtpu import fault
from mxtpu import obs
from mxtpu import profiler as prof
from mxtpu import kvstore_async as ka
from mxtpu.obs.metrics import Registry


@pytest.fixture(autouse=True)
def _no_sampling(monkeypatch):
    monkeypatch.delenv("MXTPU_TRACE_SAMPLE", raising=False)
    monkeypatch.delenv("MXTPU_TRACE_DIR", raising=False)
    yield


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_counter_gauge_histogram():
    r = Registry()
    c = r.counter("t.reqs", "x", ("inst",)).labels("a")
    c.inc()
    c.inc(4)
    assert c.value == 5
    g = r.gauge("t.depth").default()
    g.set(7)
    g.dec(2)
    g.set_max(3)        # below current: no-op
    g.set_max(11)
    assert g.value == 11
    h = r.histogram("t.lat_ms").default()
    for v in (0.2, 1.0, 9.0, 90.0):
        h.observe(v)
    assert h.count == 4 and h.sum == pytest.approx(100.2)
    assert 0.2 <= h.percentile(0.5) <= 9.0
    assert h.percentile(0.99) >= h.percentile(0.5)
    snap = r.snapshot()
    assert snap["metrics"]["t.reqs"]["series"]["a"] == 5
    hs = snap["metrics"]["t.lat_ms"]["series"][""]
    assert hs["count"] == 4 and hs["p99"] >= hs["p50"]
    assert snap["series"] == 3


def test_registry_idempotent_and_kind_clash():
    r = Registry()
    a = r.counter("t.x", "one")
    b = r.counter("t.x", "two")
    assert a is b
    with pytest.raises(ValueError):
        r.gauge("t.x")


def test_registry_cardinality_bound(monkeypatch):
    monkeypatch.setenv("MXTPU_METRICS_MAX_SERIES", "3")
    r = Registry()
    m = r.counter("t.many", labels=("k",))
    kept = [m.labels(str(i)) for i in range(3)]
    spilled = m.labels("overflow-a")
    assert spilled.detached
    spilled.inc(9)
    assert spilled.value == 9          # exact for its local holder
    snap = r.snapshot()
    fam = snap["metrics"]["t.many"]
    assert len(fam["series"]) == 3 and fam["overflowed"] == 1
    assert snap["overflowed_series"] == 1
    # dropping a series frees its slot for a new label
    kept[0].drop()
    fresh = m.labels("later")
    assert not fresh.detached
    # the same label tuple resolves to the same series object
    assert m.labels("1") is kept[1]


def test_registry_views_and_snapshot_isolation():
    r = Registry()
    k1 = r.view("t.view", lambda: {"a": 1})
    k2 = r.view("t.view", lambda: {"a": 2})
    assert k1 == "t.view" and k2 != k1

    def boom():
        raise RuntimeError("dying component")
    r.view("t.bad", boom)
    snap = r.snapshot()
    assert snap["views"][k1] == {"a": 1}
    assert snap["views"][k2] == {"a": 2}
    assert "error" in snap["views"]["t.bad"]   # never kills the poll
    r.unview(k2)
    assert k2 not in r.snapshot()["views"]
    r.unview(None)                             # capped-out handle: no-op


# ---------------------------------------------------------------------------
# sampling + spans
# ---------------------------------------------------------------------------

def test_sampler_deterministic(monkeypatch):
    s = obs.Sampler(rate=0.25)
    got = [s.sample() for _ in range(8)]
    assert got == [True, False, False, False, True, False, False,
                   False]
    assert all(obs.Sampler(rate=1.0).sample() for _ in range(5))
    z = obs.Sampler(rate=0.0)
    assert not any(z.sample() for _ in range(5))
    # env-driven rate re-read live
    monkeypatch.setenv("MXTPU_TRACE_SAMPLE", "1")
    env_s = obs.Sampler()
    assert env_s.sample()
    monkeypatch.setenv("MXTPU_TRACE_SAMPLE", "0")
    assert not env_s.sample()


def test_spans_record_nesting_and_flow_events():
    prof.reset()
    tok = obs.start_trace()
    with obs.span("t.outer", op="o"):
        with obs.span("t.inner"):
            pass
    obs.end_trace(tok)
    assert obs.active_ctx() is None
    evs = [e for e in prof.snapshot_events() if e.get("cat") == "trace"]
    spans = {e["name"]: e for e in evs if e["ph"] == "X"}
    outer, inner = spans["t.outer"], spans["t.inner"]
    assert outer["args"]["trace"] == inner["args"]["trace"]
    assert inner["args"]["parent"] == outer["args"]["span"]
    assert outer["args"]["op"] == "o"
    # the chrome flow pair rides along, id = trace id
    flows = [e for e in evs if e["ph"] in ("s", "f")]
    assert len(flows) == 4
    assert {f["id"] for f in flows} == {outer["args"]["trace"]}


def test_span_without_context_records_nothing():
    prof.reset()
    with obs.span("t.orphan"):
        pass
    assert [e for e in prof.snapshot_events()
            if e.get("cat") == "trace"] == []


def test_trace_rides_wire_and_merges(tmp_path, monkeypatch):
    """A traced request over REAL framing: the server-side apply span
    lands in the same trace, per-process dumps merge into one
    timeline carrying the flow events."""
    monkeypatch.setattr(ka, "_LOCAL_ON", False)
    monkeypatch.setenv("MXTPU_TRACE_DIR", str(tmp_path))
    prof.reset()
    srv = ka.ParameterServer().start()
    conn = ka._ServerConn(srv.address)
    try:
        tok = obs.start_trace()
        with obs.span("t.root"):
            conn.request("ping")
        obs.end_trace(tok)
        spans = [e for e in prof.snapshot_events()
                 if e.get("cat") == "trace" and e["ph"] == "X"]
        names = {e["name"] for e in spans}
        assert {"t.root", "kv.client.rpc", "kv.server.apply"} <= names
        tids = {e["args"]["trace"] for e in spans}
        assert len(tids) == 1, "one trace stitches every hop"
        path = obs.dump_process_trace()
        assert path and os.path.basename(path).startswith("trace-")
        merged = obs.merge_traces(str(tmp_path),
                                  out=str(tmp_path / "merged.json"))
        doc = json.load(open(tmp_path / "merged.json"))
        assert doc["traceEvents"] == merged
        assert any(e.get("ph") == "M" for e in merged), "process_name"
        assert any(e.get("ph") == "s" for e in merged), "flow events"
    finally:
        conn.close()
        srv.stop()


def test_trace_events_bounded(monkeypatch):
    import mxtpu.obs.trace as trace_mod
    monkeypatch.setattr(trace_mod, "_events_max_cache", 0)
    before_drops = trace_mod._span_drops.value
    tok = obs.start_trace()
    with obs.span("t.capped"):
        pass
    obs.end_trace(tok)
    assert trace_mod._span_drops.value == before_drops + 1


# ---------------------------------------------------------------------------
# the telemetry surface
# ---------------------------------------------------------------------------

def test_metrics_op_on_parameter_server_and_backup():
    srv = ka.ParameterServer().start()
    conn = ka._ServerConn(srv.address)
    try:
        reply = conn.request("metrics")
        snap = reply[1]
        assert "kv.server" in {k.split("#")[0] for k in snap["views"]}
        assert snap["pid"] == os.getpid()
        # a backup answers metrics too (no not_serving refusal):
        # telemetry must not require a promotion
        srv._role = "backup"
        assert conn.request("metrics")[0] == "ok"
    finally:
        conn.close()
        srv.stop()


def test_exporter_announce_and_aggregator_discovery(tmp_path):
    exp = obs.TelemetryExporter().start()
    try:
        ep = exp.announce(str(tmp_path))
        assert open(ep).read() == exp.address
        agg = obs.TelemetryAggregator(
            endpoints_dir=str(tmp_path / "endpoints"),
            out=str(tmp_path / "fleet.json"))
        doc = agg.sweep()
        snap = doc["fleet"][exp.address]
        assert not snap.get("gap")
        assert "metrics" in snap
        assert json.load(open(tmp_path / "fleet.json"))["sweeps"] == 1
        agg.stop()
    finally:
        exp.stop()


def test_aggregator_history_ring_bounded(tmp_path):
    exp = obs.TelemetryExporter().start()
    try:
        agg = obs.TelemetryAggregator(targets=[exp.address], history=3)
        for _ in range(6):
            doc = agg.sweep()
        assert len(doc["history"]) == 3
        assert doc["sweeps"] == 6
        agg.stop()
    finally:
        exp.stop()


def test_mxtop_renders_fleet_table(tmp_path):
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import mxtop
    exp = obs.TelemetryExporter().start()
    try:
        agg = obs.TelemetryAggregator(
            targets=[exp.address, "127.0.0.1:1"])
        out = mxtop.render(agg.sweep())
        assert exp.address in out
        assert "gap:" in out            # the dead target's row
        assert "PROC" in out and "P99MS" in out
        agg.stop()
    finally:
        exp.stop()


# ---------------------------------------------------------------------------
# stats() dicts are registry-backed (identical keys, same numbers)
# ---------------------------------------------------------------------------

def test_kv_stats_keys_unchanged_and_registry_backed():
    kv = mx.kv.create("dist_async")
    try:
        kv.init("w", mx.nd.array(np.ones((4, 3), "f")))
        kv.push("w", mx.nd.array(np.ones((4, 3), "f")))
        s = kv.stats()
        for key in ("bytes_sent", "bytes_recv", "frames_sent",
                    "frames_recv", "coalesced_frames",
                    "coalesced_subs", "retransmits", "inflight_hwm",
                    "local_reqs", "map_reroutes", "sparse_frames",
                    "sparse_rows_sent", "pending_pushes", "failovers",
                    "dup_pushes", "server_pushes", "workers",
                    "stragglers", "elastic"):
            assert key in s, key
        # the dict reads the registry series back: a later stats()
        # value can only be at or past what the snapshot held
        snap = obs.REGISTRY.snapshot()
        fam = snap["metrics"]["kv.client.local_reqs"]["series"]
        assert fam, "the store's comms series must be registered"
        # this store's series is the newest instance's: a store that an
        # earlier test of this worker left open keeps a series of its own
        own = fam["c%d" % max(int(inst[1:]) for inst in fam)]
        assert kv.stats()["local_reqs"] >= own > 0
        assert snap["metrics"]["kv.server.pushes"]["series"]
    finally:
        kv.close()


def test_fused_fit_populates_step_metrics():
    x = np.random.RandomState(0).randn(64, 8).astype("f")
    y = (np.random.RandomState(1).rand(64) * 2).astype("f")
    it = mx.io.NDArrayIter(x, y, batch_size=16,
                           label_name="softmax_label")
    sym = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.var("data"), num_hidden=2),
        name="softmax")
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.bind(it.provide_data, it.provide_label)
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd")
    before = obs.REGISTRY.snapshot()["metrics"]["module.steps"][
        "series"].get("", 0)
    for b in it:
        mod.forward_backward(b)
        mod.update()
    snap = obs.REGISTRY.snapshot()
    assert snap["metrics"]["module.steps"]["series"][""] >= before + 4
    hist = snap["metrics"]["module.step_ms"]["series"][""]
    assert hist["count"] >= 3 and hist["p50"] > 0
    assert "module.fused" in {k.split("#")[0] for k in snap["views"]}


# ---------------------------------------------------------------------------
# fault-matrix rows: observability is strictly passive
# ---------------------------------------------------------------------------

def _short_dist_fit(seed=7, on_ready=None):
    """A deterministic fused-dist fit over REAL framing; returns the
    final param bytes (the bit-parity evidence) and the kv handle's
    final stats. ``on_ready(kv)`` runs after the optimizer attaches —
    where a drill hangs its concurrent pollers — and its return value
    (a cleanup thunk) is called before the stats read."""
    r = np.random.RandomState(seed)
    x = r.rand(64, 8).astype("f")
    y = (r.rand(64) * 2).astype("f")
    it = mx.io.NDArrayIter(x, y, batch_size=16,
                           label_name="softmax_label")
    sym = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.var("data"), num_hidden=4,
                              name="fc"),
        name="softmax")
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.bind(it.provide_data, it.provide_label)
    mx.random.seed(seed)       # the initializer draws jax keys from
    np.random.seed(seed)       # mx.random; fused state from numpy
    mod.init_params(mx.init.Uniform(0.1))
    kv = mx.kv.create("dist_async")
    mod.init_optimizer(kvstore=kv, optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    cleanup = on_ready(kv) if on_ready is not None else None
    for _epoch in range(2):
        it.reset()
        for b in it:
            mod.forward_backward(b)
            mod.update()
    mod._fused.flush()
    if cleanup is not None:
        cleanup()
    arg, _aux = mod.get_params()
    blob = {n: v.asnumpy().tobytes() for n, v in arg.items()}
    stats = kv.stats()
    kv.close()
    return blob, stats


def test_fault_drop_metrics_op_never_touches_training(monkeypatch):
    """drop/sever on the `metrics` op: concurrent telemetry polls lose
    their answers, the training result stays bit-for-bit identical to
    the fault-free control run."""
    monkeypatch.setattr(ka, "_LOCAL_ON", False)
    control, _ = _short_dist_fit()
    gaps = [0]
    stop = threading.Event()

    def poller(addr):
        conn = None
        while not stop.is_set():
            try:
                if conn is None:
                    conn = ka._ServerConn(addr, n_socks=1,
                                          connect_timeout=2.0)
                conn.request("metrics", retries=0, timeout=1.0)
            except (ConnectionError, RuntimeError, OSError):
                gaps[0] += 1
                if conn is not None:
                    conn.close()
                    conn = None
            time.sleep(0.01)
        if conn is not None:
            conn.close()

    def on_ready(kv):
        t = threading.Thread(
            target=poller, args=(kv._own_server.address,), daemon=True)
        t.start()

        def cleanup():
            stop.set()
            t.join(timeout=10)
        return cleanup

    # drop at worker.send: the poll frame never leaves the poller (the
    # wire rendering of a lost metrics request); training frames are
    # untouched (op=metrics matches only the telemetry op)
    with fault.inject("kind=drop,point=worker.send,op=metrics,"
                      "nth=1,count=inf"):
        faulted, _stats = _short_dist_fit(on_ready=on_ready)
    assert gaps[0] > 0, "the injected drops must have hit the polls"
    assert faulted == control, \
        "a dropped metrics reply changed training results"


def test_fault_sever_on_trace_carrying_frame_keeps_bit_parity(
        monkeypatch):
    """Full tracing on + an injected sever mid-run: the trace-carrying
    pushpull frame is replayed by the retry layer, seq dedupe keeps it
    exactly-once, and the result is bit-identical to the untraced
    fault-free control."""
    monkeypatch.setattr(ka, "_LOCAL_ON", False)
    # individual pushpull frames (coalescing would tag them op=multi
    # on the wire, and the rule must land on a trace-carrying frame)
    monkeypatch.setattr(ka, "_COALESCE_BYTES", -1)
    control, _ = _short_dist_fit()
    monkeypatch.setenv("MXTPU_TRACE_SAMPLE", "1")
    with fault.inject("kind=sever,point=server.send,op=pushpull,"
                      "nth=3"):
        traced, stats = _short_dist_fit()
    assert traced == control, \
        "tracing + sever changed the training bits"
    assert stats["retransmits"] >= 1, "the sever must have fired"
    assert stats["dup_pushes"] >= 1, \
        "the replayed trace-carrying frame must dedupe exactly-once"


def test_dead_shard_telemetry_gap_is_reported_not_fatal():
    srv = ka.ParameterServer().start()
    addr = srv.address
    agg = obs.TelemetryAggregator(targets=[addr])
    try:
        assert not agg.sweep()["fleet"][addr].get("gap")
        srv.stop()                      # the shard dies
        doc = agg.sweep()               # ...and the sweep still returns
        snap = doc["fleet"][addr]
        assert snap["gap"] and snap["error"]
        assert doc["gaps"] >= 1
    finally:
        agg.stop()


def test_gapped_endpoint_parked_not_pruned_and_resumes(tmp_path):
    """Staleness semantics (ISSUE 16): a gapped worker endpoint is
    PARKED after 3 gapped sweeps (probed every 4th sweep, so exited
    workers stop taxing every sweep with a connect timeout) but its row
    and endpoint file survive — the document ``seq`` advances while the
    row's ``age_sweeps`` grows, which is how a consumer tells "this row
    is dead" from "the aggregator is behind". A paused-then-RESUMED
    exporter comes back as live capacity on the next probe sweep;
    pruning (the old behavior) conflated it with dead capacity
    forever."""
    epd = tmp_path / "endpoints"
    epd.mkdir()
    exp = obs.TelemetryExporter().start()
    addr = exp.address
    port = int(addr.rsplit(":", 1)[1])
    ep = epd / "worker-1.ep"
    ep.write_text(addr)
    agg = obs.TelemetryAggregator(targets=["127.0.0.1:2"],
                                  endpoints_dir=str(epd),
                                  connect_timeout=0.2)
    try:
        doc = agg.sweep()                       # sweep 1: live
        row = doc["fleet"][addr]
        assert not row.get("gap")
        assert row["seq"] == 1 and row["age_sweeps"] == 0
        exp.stop()                              # the PAUSE
        for i in range(2, 8):                   # sweeps 2..7: gapped
            doc = agg.sweep()
            row = doc["fleet"][addr]
            assert row["gap"], "row must persist while gapped"
            assert row["seq"] == 1              # last sweep that heard it
            assert row["age_sweeps"] == i - 1   # grows with doc seq
            assert doc["seq"] == i              # ...which ADVANCES
        assert row.get("parked"), "reduced-rate probing by now"
        assert ep.exists(), "endpoint file must never be pruned"
        # the RESUME: same port, fresh exporter (sweep 8 is a probe)
        exp = obs.TelemetryExporter(port=port).start()
        doc = agg.sweep()
        row = doc["fleet"][addr]
        assert not row.get("gap"), \
            "a paused-then-resumed exporter is live capacity again"
        assert row["seq"] == 8 and row["age_sweeps"] == 0
        # explicit targets are never parked: their gap IS the signal
        assert doc["fleet"]["127.0.0.1:2"]["gap"]
        assert not doc["fleet"]["127.0.0.1:2"].get("parked")
    finally:
        agg.stop()
        exp.stop()


def test_spec_validates_metrics_fault_rules():
    """op=metrics rules parse through the standard grammar — the
    telemetry path is targetable like any other wire op."""
    rules = fault.parse_spec(
        "kind=drop,point=server.send,op=metrics;"
        "kind=sever,point=server.recv,op=metrics,nth=2")
    assert [r.op for r in rules] == ["metrics", "metrics"]
