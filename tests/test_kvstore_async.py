"""dist_async parameter service (reference async mode,
src/kvstore/kvstore_dist_server.h:339,462: pushes applied immediately
server-side, no merge barrier — staleness traded for straggler
tolerance). Fast in-process tier; the multi-process straggler
demonstration is tests/nightly/async_worker.py via the local launcher."""
import os
import threading
import time

import numpy as np
import pytest

import mxtpu as mx
from mxtpu.kvstore_async import AsyncDistKVStore, ParameterServer


def test_create_returns_async_store():
    kv = mx.kv.create("dist_async")
    try:
        assert isinstance(kv, AsyncDistKVStore)
        assert kv.type == "dist_async"
    finally:
        kv.close()


def test_server_side_optimizer_applies_each_push():
    kv = mx.kv.create("dist_async")
    try:
        kv.init(3, mx.nd.zeros((2, 3)))
        kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.5))
        kv.push(3, mx.nd.ones((2, 3)))
        kv.push(3, mx.nd.ones((2, 3)))
        out = mx.nd.zeros((2, 3))
        kv.pull(3, out=out)
        # two sequential updates, each applied on arrival: w = 0 - .5 - .5
        np.testing.assert_allclose(out.asnumpy(), -np.ones((2, 3)))
        assert kv.staleness_stats()["pushes"] == 2
    finally:
        kv.close()


def test_push_without_updater_accumulates():
    kv = mx.kv.create("dist_async")
    try:
        kv.init("a", mx.nd.array(np.arange(4, dtype="f")))
        kv.push("a", mx.nd.ones((4,)))
        out = mx.nd.zeros((4,))
        kv.pull("a", out=out)
        np.testing.assert_allclose(out.asnumpy(),
                                   np.arange(4, dtype="f") + 1)
    finally:
        kv.close()


def test_list_push_merges_locally_before_send():
    kv = mx.kv.create("dist_async")
    try:
        kv.init("k", mx.nd.zeros((3,)))
        kv.push("k", [mx.nd.ones((3,)), mx.nd.ones((3,)) * 2])
        out = mx.nd.zeros((3,))
        kv.pull("k", out=out)
        np.testing.assert_allclose(out.asnumpy(), 3 * np.ones(3))
        # one wire push for the merged device shards
        assert kv.staleness_stats()["clocks"]["k"] == 1
    finally:
        kv.close()


def test_uninitialized_key_errors():
    kv = mx.kv.create("dist_async")
    try:
        with pytest.raises(RuntimeError, match="uninitialized"):
            kv.push("missing", mx.nd.ones((2,)))
        with pytest.raises(RuntimeError, match="uninitialized"):
            kv.pull("missing", out=mx.nd.zeros((2,)))
        with pytest.raises((RuntimeError, KeyError), match="uninitialized"):
            kv.row_sparse_pull("absent", out=mx.nd.zeros((2,)),
                               row_ids=mx.nd.array([0]))
    finally:
        kv.close()


def _worker_env(addr, rank, nproc):
    return {"MXTPU_PS_ADDRS": addr, "MXTPU_PROC_ID": str(rank),
            "MXTPU_NUM_PROCS": str(nproc)}


def _patched_env(env):
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    return saved


def _restore_env(saved):
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def test_concurrent_workers_interleave_with_staleness():
    """Two 'workers' (threads with their own stores/clocks) against one
    shared server: pushes interleave without any barrier and the server
    observes staleness > 0 — the async property itself."""
    server = ParameterServer().start()
    stores = []
    try:
        saved = _patched_env(_worker_env(server.address, 0, 2))
        try:
            kv0 = mx.kv.create("dist_async")
            stores.append(kv0)
            os.environ["MXTPU_PROC_ID"] = "1"
            kv1 = mx.kv.create("dist_async")
            stores.append(kv1)
        finally:
            _restore_env(saved)
        # manual init: barrier needs both workers, run init concurrently
        t = threading.Thread(
            target=lambda: kv1.init("w", mx.nd.zeros((4,))))
        t.start()
        kv0.init("w", mx.nd.zeros((4,)))
        t.join()

        n_steps = {0: 40, 1: 40}
        def run(kv, rank):
            w = mx.nd.zeros((4,))
            for _ in range(n_steps[rank]):
                kv.pull("w", out=w)
                kv.push("w", mx.nd.ones((4,)) * 0.01)
        th = [threading.Thread(target=run, args=(kv, r))
              for r, kv in enumerate(stores)]
        for x in th:
            x.start()
        for x in th:
            x.join()
        stats = stores[0].staleness_stats()
        assert stats["pushes"] == 80
        assert stats["staleness_max"] > 0, stats
        out = mx.nd.zeros((4,))
        stores[0].pull("w", out=out)
        # no updater: every push accumulated exactly once, stale or not
        np.testing.assert_allclose(out.asnumpy(), 0.01 * 80 * np.ones(4),
                                   rtol=1e-5)
    finally:
        for kv in stores:
            kv.close()
        server.stop()


def test_key_sharding_across_servers():
    s1, s2 = ParameterServer().start(), ParameterServer().start()
    saved = _patched_env(_worker_env(
        s1.address + "," + s2.address, 0, 1))
    try:
        kv = mx.kv.create("dist_async")
        keys = ["k%d" % i for i in range(8)]
        for k in keys:
            kv.init(k, mx.nd.ones((2,)))
            kv.push(k, mx.nd.ones((2,)))
        # every key landed on exactly one server; union covers all keys
        c1 = s1._clock
        c2 = s2._clock
        assert not (set(c1) & set(c2))
        assert set(c1) | set(c2) == set(keys)
        out = mx.nd.zeros((2,))
        for k in keys:
            kv.pull(k, out=out)
            np.testing.assert_allclose(out.asnumpy(), 2 * np.ones(2))
        kv.close()
    finally:
        _restore_env(saved)
        s1.stop()
        s2.stop()


def test_module_fit_through_dist_async():
    """Module.fit with kvstore='dist_async': grads push to the parameter
    service, SGD runs server-side (update_on_kvstore), weights pull back
    — the reference's async training loop shape, single-process."""
    r = np.random.RandomState(5)
    y = (r.rand(192) * 4).astype("f")
    x = r.rand(192, 16).astype("f") * 0.1
    for i in range(192):
        x[i, int(y[i]) * 4:int(y[i]) * 4 + 4] += 1.0
    it = mx.io.NDArrayIter(x, y, batch_size=32, shuffle=True,
                           label_name="softmax_label")
    sym = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.var("data"), num_hidden=4),
        name="softmax")
    mod = mx.mod.Module(sym)
    mod.fit(it, num_epoch=4, kvstore="dist_async", optimizer="sgd",
            optimizer_params={"learning_rate": 0.5})
    acc = dict(mod.score(it, "acc"))["accuracy"]
    assert acc > 0.9, acc
    # the optimizer really ran server-side: pushes were counted there
    kv = mod._kvstore
    stats = kv.staleness_stats()
    assert stats["pushes"] >= 4 * 6 * 2  # epochs * batches * params
    kv.close()


def test_bigarray_parts_roundtrip():
    """Arrays above MXTPU_KVSTORE_BIGARRAY_BOUND split into row parts,
    each an independent subkey (reference BIGARRAY_BOUND key splits) —
    init/push/pull must reassemble exactly."""
    from mxtpu import kvstore_async as ka
    old = ka._BIGARRAY_BOUND
    ka._BIGARRAY_BOUND = 1000
    try:
        kv = mx.kv.create("dist_async")
        r = np.random.RandomState(0)
        w = r.rand(40, 100).astype("f")      # 4000 elems -> 4 parts
        kv.init("big", mx.nd.array(w))
        assert len(kv._parts["big"]) == 4
        out = mx.nd.zeros(w.shape)
        kv.pull("big", out=out)
        np.testing.assert_allclose(out.asnumpy(), w, rtol=1e-6)
        kv.push("big", mx.nd.ones(w.shape))
        kv.pull("big", out=out)
        np.testing.assert_allclose(out.asnumpy(), w + 1, rtol=1e-6)
        kv.close()
    finally:
        ka._BIGARRAY_BOUND = old


def test_row_sparse_pull_async():
    """Only requested rows travel (server-side pull_rows); targets may be
    row_sparse or exactly the gathered shape."""
    from mxtpu import kvstore_async as ka
    from mxtpu.ndarray.sparse import row_sparse_array
    old = ka._BIGARRAY_BOUND
    ka._BIGARRAY_BOUND = 60          # force parts: 20x6=120 elems -> 3+
    try:
        kv = mx.kv.create("dist_async")
        r = np.random.RandomState(1)
        w = r.rand(20, 6).astype("f")
        kv.init("emb", mx.nd.array(w))
        assert len(kv._parts["emb"]) > 1
        ids = np.array([0, 3, 7, 19], "int64")
        dense_tgt = mx.nd.zeros((4, 6))
        kv.row_sparse_pull("emb", out=dense_tgt, row_ids=mx.nd.array(ids))
        np.testing.assert_allclose(dense_tgt.asnumpy(), w[ids], rtol=1e-6)
        rsp = row_sparse_array((np.zeros((1, 6), "f"), [0]), shape=(20, 6))
        kv.row_sparse_pull("emb", out=rsp, row_ids=mx.nd.array(ids))
        np.testing.assert_allclose(rsp.asnumpy()[ids], w[ids], rtol=1e-6)
        # rows outside ids are zero in the pulled row_sparse view
        mask = np.ones(20, bool)
        mask[ids] = False
        assert np.all(rsp.asnumpy()[mask] == 0)
        # dense FULL-shape target (Module.prepare pulls into full
        # executor buffers): ONLY the requested rows refresh — the
        # server slices row-wise, the whole table never rides the wire
        # for a row pull (ISSUE 13 fixed the old whole-table re-fetch)
        sentinel = np.full((20, 6), -7.0, "f")
        full = mx.nd.array(sentinel)
        kv.row_sparse_pull("emb", out=full, row_ids=mx.nd.array(ids))
        got = full.asnumpy()
        np.testing.assert_allclose(got[ids], w[ids], rtol=1e-6)
        np.testing.assert_allclose(got[mask], sentinel[mask])
        # out-of-range ids are refused before any wire traffic
        with pytest.raises(IndexError, match="out of range"):
            kv.row_sparse_pull("emb", out=mx.nd.zeros((1, 6)),
                               row_ids=mx.nd.array([20]))
        with pytest.raises(IndexError, match="out of range"):
            kv.row_sparse_pull("emb", out=mx.nd.zeros((1, 6)),
                               row_ids=mx.nd.array([-1]))
        kv.close()
    finally:
        ka._BIGARRAY_BOUND = old


def test_async_wire_compression():
    """2-bit compression on the push wire: server dequantizes before its
    update; error feedback makes repeated pushes converge to the true
    accumulated gradient."""
    kv = mx.kv.create("dist_async")
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    w = np.zeros((4, 8), "f")
    kv.init("w", mx.nd.array(w))
    g = np.full((4, 8), 0.7, "f")
    # no updater: server accumulates pushes. Each push emits exactly one
    # +0.5 code per element (2-bit wire), so 5 pushes of 0.7 land 2.5 on
    # the table with 1.0 carried in the worker-side residual — the
    # reference's error-feedback semantics, not lossless transfer.
    for _ in range(5):
        kv.push("w", mx.nd.array(g))
    out = mx.nd.zeros(w.shape)
    kv.pull("w", out=out)
    np.testing.assert_allclose(out.asnumpy(), np.full((4, 8), 2.5),
                               rtol=1e-6)
    res = kv.gradient_compression._residuals["w"]
    np.testing.assert_allclose(np.asarray(res), np.full((4, 8), 1.0),
                               rtol=1e-6)
    kv.close()


def test_ps_token_auth():
    """With MXTPU_PS_TOKEN set, the server reads a raw fixed-length
    preamble and closes unauthenticated sockets WITHOUT unpickling
    anything — the auth check must never feed attacker bytes to pickle."""
    import socket as _socket
    from mxtpu.kvstore_async import (_send_frame, _recv_frame,
                                     _ServerConn, _auth_blob)
    srv = ParameterServer(token="sekrit").start()
    host, _, port = srv.address.partition(":")
    try:
        # no preamble, straight to a (pickle) frame: the server consumes
        # it as a failed raw compare and closes — no reply, no unpickle
        s = _socket.create_connection((host, int(port)), timeout=10)
        _send_frame(s, ("pull", "w"))
        try:
            s.shutdown(_socket.SHUT_WR)  # EOF: the server stops reading
            s.settimeout(10)             # the would-be preamble, closes
            assert s.recv(1) == b""      # orderly close, nothing served
        except OSError:
            # the server may close with our frame's tail unread, which
            # RSTs instead of FINs — equally "closed without serving"
            pass
        s.close()
        # wrong token: closed the same way
        s = _socket.create_connection((host, int(port)), timeout=10)
        s.sendall(_auth_blob("wrong"))
        try:
            s.settimeout(10)
            assert s.recv(1) == b""
        except OSError:
            pass
        s.close()
        # right token: full init/pull roundtrip works
        conn = _ServerConn(srv.address, token="sekrit")
        conn.request("init", "w", np.ones(3, "f"))
        reply = conn.request("pull", "w")
        np.testing.assert_allclose(reply[1], np.ones(3, "f"))
        conn.close()
    finally:
        srv.stop()


def test_scalar_and_edge_row_ids():
    """Rank-0 params round-trip (regression: part slicing must not index
    a 0-d array); out-of-range row_ids raise; empty row_ids are a valid
    no-rows pull."""
    from mxtpu.ndarray.sparse import row_sparse_array
    kv = mx.kv.create("dist_async")
    try:
        kv.init("s", mx.nd.array(3.0))
        kv.push("s", mx.nd.array(1.0))
        out = mx.nd.array(0.0)
        kv.pull("s", out=out)
        assert float(out.asnumpy()) == 4.0
        kv.init("t", mx.nd.array(np.arange(12, dtype="f").reshape(4, 3)))
        with pytest.raises(IndexError, match="out of range"):
            kv.row_sparse_pull("t", out=mx.nd.zeros((1, 3)),
                               row_ids=mx.nd.array([7]))
        rsp = row_sparse_array((np.zeros((1, 3), "f"), [0]), shape=(4, 3))
        kv.row_sparse_pull("t", out=rsp, row_ids=mx.nd.array([], dtype="f"))
        assert np.all(rsp.asnumpy() == 0)
    finally:
        kv.close()


def test_realistic_volume_straggler():
    """The async property at real parameter scale (round-4 verdict: the
    service's throughput at ~100 MB/step was unmeasured): one worker
    streams a 33 MB parameter's push/pull rounds flat out while a
    straggler sleeps each step. Big parted pushes must not serialize the
    fleet — the fast worker completes several times more rounds, the
    server observes staleness, and every push still lands exactly once."""
    server = ParameterServer().start()
    stores = []
    try:
        saved = _patched_env(_worker_env(server.address, 0, 2))
        try:
            kv0 = mx.kv.create("dist_async")
            stores.append(kv0)
            os.environ["MXTPU_PROC_ID"] = "1"
            kv1 = mx.kv.create("dist_async")
            stores.append(kv1)
        finally:
            _restore_env(saved)
        shape = (1792, 4608)           # ~33 MB fp32, parts at the 1e6 bound
        t = threading.Thread(
            target=lambda: kv1.init("wbig", mx.nd.zeros(shape)))
        t.start()
        kv0.init("wbig", mx.nd.zeros(shape))
        t.join()
        assert len(kv0._parts["wbig"]) >= 8

        g = mx.nd.ones(shape)
        counts = {}

        # calibrate: one uncontended round, so the straggler's sleep
        # dominates per-round time whatever this host's speed is
        w0 = mx.nd.zeros(shape)
        t0 = time.time()
        kv0.pull("wbig", out=w0)
        kv0.push("wbig", g)
        round_s = time.time() - t0
        sleep_s = max(0.5, 4 * round_s)
        budget = max(6.0, 6 * sleep_s)

        def run(kv, rank, sleep):
            w = mx.nd.zeros(shape)
            n = 0
            deadline = time.time() + budget
            while time.time() < deadline:
                kv.pull("wbig", out=w)
                kv.push("wbig", g)
                n += 1
                if sleep:
                    time.sleep(sleep)
            counts[rank] = n

        th = [threading.Thread(target=run, args=(kv, r, sleep_s * r))
              for r, kv in enumerate(stores)]
        for x in th:
            x.start()
        for x in th:
            x.join()
        assert counts[0] >= 2 * counts[1], counts
        stats = stores[0].staleness_stats()
        assert stats["staleness_max"] > 0, stats
        # accumulate-only server: the table holds exactly
        # (total pushes) * 1.0 in every element — big parted pushes
        # neither dropped nor double-applied
        out = mx.nd.zeros(shape)
        stores[0].pull("wbig", out=out)
        total = counts[0] + counts[1] + 1   # +1: the calibration round
        got = out.asnumpy()
        assert got[0, 0] == total and got[-1, -1] == total, \
            (got[0, 0], got[-1, -1], total)
    finally:
        for kv in stores:
            kv.close()
        server.stop()


def test_push_pull_one_round_trip():
    """kv.push_pull (the fused pushpull wire op, ISSUE 10): apply +
    read-back in one request — accumulate server: the returned value
    is the post-apply table, the clock advances exactly once."""
    kv = mx.kv.create("dist_async")
    try:
        kv.init("w", mx.nd.ones((3,)))
        out = mx.nd.zeros((3,))
        kv.push_pull("w", mx.nd.ones((3,)), out=out)
        np.testing.assert_allclose(out.asnumpy(), 2 * np.ones(3))
        kv.push_pull("w", mx.nd.ones((3,)) * 3, out=out)
        np.testing.assert_allclose(out.asnumpy(), 5 * np.ones(3))
        srv = kv._own_server
        assert srv._clock["w"] == 2
    finally:
        kv.close()


def test_push_pull_big_array_parts():
    """push_pull splits big arrays into the same row parts as
    push/pull and reassembles the returned post-update value exactly."""
    from mxtpu import kvstore_async as ka
    old = ka._BIGARRAY_BOUND
    ka._BIGARRAY_BOUND = 1000
    try:
        kv = mx.kv.create("dist_async")
        r = np.random.RandomState(1)
        w = r.rand(40, 100).astype("f")
        g = r.rand(40, 100).astype("f")
        kv.init("big", mx.nd.array(w))
        assert len(kv._parts["big"]) == 4
        out = mx.nd.zeros(w.shape)
        kv.push_pull("big", mx.nd.array(g), out=out)
        np.testing.assert_allclose(out.asnumpy(), w + g, rtol=1e-6)
        kv.close()
    finally:
        ka._BIGARRAY_BOUND = old


def test_push_pull_server_side_optimizer():
    """With a server-side updater, push_pull returns the POST-UPDATE
    weights (what the fused Module dist step rebinds its parameter
    store with) — matching a separate push-then-pull bit-for-bit."""
    from mxtpu import optimizer as opt
    kv = mx.kv.create("dist_async")
    kv2 = mx.kv.create("dist_async")
    try:
        for k in (kv, kv2):
            k.set_optimizer(opt.SGD(learning_rate=0.5, momentum=0.9,
                                    rescale_grad=1.0))
        w0 = np.arange(6, dtype="f").reshape(2, 3)
        g = np.ones((2, 3), "f")
        kv.init("w", mx.nd.array(w0))
        kv2.init("w", mx.nd.array(w0))
        a, b = mx.nd.zeros((2, 3)), mx.nd.zeros((2, 3))
        for _ in range(3):
            kv.push_pull("w", mx.nd.array(g), out=a)
            kv2.push("w", mx.nd.array(g))
            kv2.pull("w", out=b)
            np.testing.assert_array_equal(a.asnumpy(), b.asnumpy())
    finally:
        kv.close()
        kv2.close()
