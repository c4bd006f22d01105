#!/usr/bin/env python
"""Loopback microbench for the Module fused train step (ISSUE 5).

Measures steady-state ``Module.fit`` throughput — the exact hot loop
``fit`` runs per batch (``forward_backward`` → ``update`` →
``update_metric``) — for the two bundled CPU-runnable models:

* **mlp**  — 256→128→64→10 softmax MLP at batch 64
* **lenet** — LeNet-style conv/pool/conv/pool/fc on 1x28x28 at batch 4

Batch sizes are per-model: the fused step removes PER-STEP dispatch
overhead (python updater loop, per-batch metric sync, extra program
launches), so each model runs in the regime where the Module path — not
raw conv arithmetic on this 1-core CI host — is what's being measured:
the MLP is overhead-dominated even at batch 64; the conv net only below
~batch 8 (at batch 32+ its conv FLOPs bound a single core and the fused
win shrinks to ~1.2x — the full scan is in docs/perf_analysis.md).

Each model runs twice: ``MXTPU_MODULE_FUSED=1`` (one donated XLA program
per step: forward + backward + whole optimizer update + device-side
metric accumulation) and ``=0`` (the eager path: speculated fwd+bwd
program, per-parameter Python optimizer dispatches, per-batch
``asnumpy()`` metric sync). The warmup batches (compiles + metric
registration) are excluded; the metric is drained once at the end so the
async path's deferred work is counted.

``--dist`` (ISSUE 10) switches to the loopback-PS fit microbench: the
same hot loop driven through ``kvstore='dist_async'`` (in-process
server, local transport), measured three ways — the eager dist path
(per-param push/pull loop), the fused-dist SYNC mode (one grad-emitting
program + one coalesced push + one pull per batch, bit-for-bit with
eager) and the fused-dist ASYNC mode (push+pull pipelined on the
store's pool under the bounded-inflight window).

``--amp`` (ISSUE 12) sweeps mixed precision: the fp32 fused path vs
``MXTPU_AMP=bf16`` — single-host fit throughput, plus the dist sync
loop over REAL wire framing with pushpull bytes/step (bf16 frames
carry the dtype in the payload; the half-width-wire contract is
bytes ratio <= 0.55, also pinned structurally by
``ci/check_module_perf.py --amp``).

``--mesh`` (ISSUE 20) sweeps the pjit-sharded fused step: the fused
single-device fit loop vs the same loop compiled as an SPMD program
over an 8-way emulated mesh (``Module.set_sharding``), plus single vs
sharded AOT serving (``InferenceEngine(mesh=...)``) request rates. On
emulated CPU devices the mesh legs pay partitioning overhead instead
of banking real-chip speedup, so the row carries the structural
evidence alongside the rates: per-device store bytes (~1/N of total)
and a zero-recompile steady serve state (the hard pins live in
``ci/check_mesh_perf.py``).

Prints exactly ONE JSON line (tests/test_bench_contract.py parses it)
and mirrors it to docs/module_bench.json unless --no-write (the file
keeps one line per bench kind: ``module_fit``, ``module_fit_dist``,
``module_fit_amp`` and ``module_fit_mesh``). CPU-only.
MXTPU_BENCH_TINY shrinks the models/batch counts for the contract
test.

Run: JAX_PLATFORMS=cpu python tools/bench_module.py [--dist|--amp]
     [--batches 100]
     XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
       JAX_PLATFORMS=cpu python tools/bench_module.py --mesh
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

TINY = os.environ.get("MXTPU_BENCH_TINY", "0") not in ("", "0")


def _mlp(mx, hidden=(128, 64), classes=10):
    net = mx.sym.var("data")
    for i, h in enumerate(hidden):
        net = mx.sym.FullyConnected(net, num_hidden=h, name="fc%d" % i)
        net = mx.sym.Activation(net, act_type="relu", name="act%d" % i)
    net = mx.sym.FullyConnected(net, num_hidden=classes, name="fc_out")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _lenet(mx, classes=10):
    data = mx.sym.var("data")
    net = mx.sym.Convolution(data, kernel=(5, 5), num_filter=4,
                             name="conv1")
    net = mx.sym.Activation(net, act_type="tanh", name="tanh1")
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2),
                         pool_type="max", name="pool1")
    net = mx.sym.Convolution(net, kernel=(5, 5), num_filter=8,
                             name="conv2")
    net = mx.sym.Activation(net, act_type="tanh", name="tanh2")
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2),
                         pool_type="max", name="pool2")
    net = mx.sym.Flatten(net, name="flat")
    net = mx.sym.FullyConnected(net, num_hidden=32, name="fc1")
    net = mx.sym.Activation(net, act_type="tanh", name="tanh3")
    net = mx.sym.FullyConnected(net, num_hidden=classes, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _data(model, n, batch_size):
    rng = np.random.RandomState(0)
    if model == "mlp":
        x = rng.randn(n, 256).astype("float32")
    else:
        x = rng.randn(n, 1, 28, 28).astype("float32")
    y = rng.randint(0, 10, n).astype("float32")
    return x, y


def _steady_state_rate(mx, sym, x, y, batch_size, batches, warmup,
                       mesh=None):
    """img/sec of the fit() hot loop after warmup, current env."""
    it = mx.io.NDArrayIter(x, y, batch_size=batch_size,
                           label_name="softmax_label")
    mod = mx.mod.Module(sym)
    if mesh is not None:
        mod.set_sharding(mesh)
    mod.bind(it.provide_data, it.provide_label)
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.01,
                                         "momentum": 0.9})
    metric = mx.metric.create("acc")
    pool = list(it)

    def one(batch):
        mod.forward_backward(batch)
        mod.update()
        mod.update_metric(metric, batch.label)

    for i in range(warmup):
        one(pool[i % len(pool)])
    metric.get()   # mxlint: allow(blocking-call) — drain any device accumulation; a value getter, not a wait
    metric.reset()

    t0 = time.perf_counter()
    for i in range(batches):
        one(pool[i % len(pool)])
    metric.get()   # mxlint: allow(blocking-call) — epoch-end read (value getter), both paths
    # flush async dispatch: the step's outputs must actually exist
    mod._exec_group.execs[0].arg_dict[
        mod._exec_group.param_names[0]].wait_to_read()
    dt = time.perf_counter() - t0
    fused = mod._fused is not None
    return batch_size * batches / dt, fused


DEFAULT_BS = {"mlp": 8, "lenet": 2} if TINY else {"mlp": 64, "lenet": 4}


def _dist_rate(mx, sym, x, y, batch_size, batches, warmup):
    """img/sec of the fit() hot loop against an in-process dist_async
    parameter service, current env (MXTPU_MODULE_FUSED[_DIST] /
    MXTPU_MODULE_DIST_MODE select the path)."""
    it = mx.io.NDArrayIter(x, y, batch_size=batch_size,
                           label_name="softmax_label")
    mod = mx.mod.Module(sym)
    mod.bind(it.provide_data, it.provide_label)
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(kvstore="dist_async", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.01})
    metric = mx.metric.create("acc")
    pool = list(it)

    def one(batch):
        mod.forward_backward(batch)
        mod.update()
        mod.update_metric(metric, batch.label)

    try:
        for i in range(warmup):
            one(pool[i % len(pool)])
        if mod._fused is not None:
            mod._fused.flush()
        metric.get()   # mxlint: allow(blocking-call) — drain any device accumulation; a value getter, not a wait
        metric.reset()

        t0 = time.perf_counter()
        for i in range(batches):
            one(pool[i % len(pool)])
        if mod._fused is not None:
            mod._fused.flush()   # outstanding async windows count
        metric.get()   # mxlint: allow(blocking-call) — epoch-end read (value getter), both paths
        mod._exec_group.execs[0].arg_dict[
            mod._exec_group.param_names[0]].wait_to_read()
        dt = time.perf_counter() - t0
        fused = mod._fused is not None
    finally:
        mod._kvstore.close()
    return batch_size * batches / dt, fused


def run_dist(batches, warmup, batch_size=None):
    """The --dist sweep: eager vs fused-sync vs fused-async, loopback
    PS, mlp model (the dispatch-bound regime the dist fast path
    targets)."""
    import mxtpu as mx

    os.environ.setdefault("MXTPU_PS_HEARTBEAT", "0")
    bs = batch_size or DEFAULT_BS["mlp"]
    n = max(4 * bs, 64)
    x, y = _data("mlp", n, bs)
    sym = _mlp(mx)
    saved = {k: os.environ.get(k) for k in
             ("MXTPU_MODULE_FUSED", "MXTPU_MODULE_FUSED_DIST",
              "MXTPU_MODULE_DIST_MODE")}
    rates = {}
    try:
        for name, env in (
                ("eager", {"MXTPU_MODULE_FUSED": "1",
                           "MXTPU_MODULE_FUSED_DIST": "0"}),
                ("fused_sync", {"MXTPU_MODULE_FUSED": "1",
                                "MXTPU_MODULE_FUSED_DIST": "1",
                                "MXTPU_MODULE_DIST_MODE": "sync"}),
                ("fused_async", {"MXTPU_MODULE_FUSED": "1",
                                 "MXTPU_MODULE_FUSED_DIST": "1",
                                 "MXTPU_MODULE_DIST_MODE": "async"})):
            os.environ.update(env)
            rate, fused = _dist_rate(mx, sym, x, y, bs, batches, warmup)
            assert fused == (name != "eager"), \
                "%s path engagement mismatch" % name
            rates[name] = rate
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    row = {"batch_size": bs,
           "eager_img_s": round(rates["eager"], 1),
           "fused_sync_img_s": round(rates["fused_sync"], 1),
           "fused_async_img_s": round(rates["fused_async"], 1),
           "speedup_sync": round(rates["fused_sync"] / rates["eager"], 2),
           "speedup_async": round(rates["fused_async"] / rates["eager"],
                                  2)}
    return {"bench": "module_fit_dist", "tiny": TINY,
            "batches": batches, "warmup": warmup,
            "host_cores": os.cpu_count(), "models": {"mlp": row}}


def _amp_dist_rate(mx, sym, x, y, batch_size, batches, warmup):
    """img/sec + wire bytes/step of the fused-sync dist fit hot loop
    over the REAL framing (local transport off so the byte counters
    tick), current MXTPU_AMP env."""
    from mxtpu import kvstore_async as ka
    it = mx.io.NDArrayIter(x, y, batch_size=batch_size,
                           label_name="softmax_label")
    mod = mx.mod.Module(sym)
    mod.bind(it.provide_data, it.provide_label)
    mod.init_params(mx.initializer.Xavier())
    saved_local = ka._LOCAL_ON
    ka._LOCAL_ON = False
    try:
        mod.init_optimizer(kvstore="dist_async", optimizer="sgd",
                           optimizer_params={"learning_rate": 0.01})
        kv = mod._kvstore
        pool = list(it)

        def one(batch):
            mod.forward_backward(batch)
            mod.update()

        for i in range(warmup):
            one(pool[i % len(pool)])
        mod._fused.flush()
        before = kv._stats.snapshot()
        t0 = time.perf_counter()
        for i in range(batches):
            one(pool[i % len(pool)])
        mod._fused.flush()
        mod._exec_group.execs[0].arg_dict[
            mod._exec_group.param_names[0]].wait_to_read()
        dt = time.perf_counter() - t0
        after = kv._stats.snapshot()
        sent = (after["bytes_sent"] - before["bytes_sent"]) / batches
        recv = (after["bytes_recv"] - before["bytes_recv"]) / batches
        assert mod._fused is not None and mod._fused.mode == "dist"
        kv.close()
    finally:
        ka._LOCAL_ON = saved_local
    return batch_size * batches / dt, sent, recv


def run_amp(batches, warmup, batch_size=None):
    """The --amp sweep (ISSUE 12): fp32 fused vs bf16 fused, single-host
    AND dist sync over the wire — throughput plus pushpull bytes/step
    (the <= 0.55x half-width-wire contract ci/check_module_perf.py
    --amp pins structurally)."""
    import mxtpu as mx

    os.environ.setdefault("MXTPU_PS_HEARTBEAT", "0")
    bs = batch_size or DEFAULT_BS["mlp"]
    # dist leg runs the wire-bound regime (small batch: compute per
    # step shrinks, the ~335KB/step pushpull stays) — that is where
    # the half-width wire pays on a CPU host whose bf16 matmuls are
    # EMULATED; on real hardware bf16 also wins the compute leg
    dist_bs = batch_size or (DEFAULT_BS["mlp"] if TINY else 16)
    sym = _mlp(mx)
    saved = {k: os.environ.get(k) for k in
             ("MXTPU_AMP", "MXTPU_MODULE_FUSED", "MXTPU_MODULE_FUSED_DIST",
              "MXTPU_MODULE_DIST_MODE")}
    os.environ.update({"MXTPU_MODULE_FUSED": "1",
                       "MXTPU_MODULE_FUSED_DIST": "1",
                       "MXTPU_MODULE_DIST_MODE": "sync"})
    local, dist = {}, {}
    try:
        for name in ("fp32", "bf16"):
            os.environ["MXTPU_AMP"] = "" if name == "fp32" else "bf16"
            x, y = _data("mlp", max(4 * bs, 64), bs)
            rate, fused = _steady_state_rate(mx, sym, x, y, bs, batches,
                                             warmup)
            assert fused, "%s local path did not engage" % name
            local[name] = rate
            xd, yd = _data("mlp", max(4 * dist_bs, 64), dist_bs)
            dist[name] = _amp_dist_rate(mx, sym, xd, yd, dist_bs,
                                        batches, warmup)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    wire_ratio = (dist["bf16"][1] + dist["bf16"][2]) / max(
        1.0, dist["fp32"][1] + dist["fp32"][2])
    return {"bench": "module_fit_amp", "tiny": TINY,
            "batches": batches, "warmup": warmup,
            "host_cores": os.cpu_count(),
            "models": {"mlp": {
                "batch_size": bs,
                "fp32_img_s": round(local["fp32"], 1),
                "bf16_img_s": round(local["bf16"], 1),
                "speedup": round(local["bf16"] / local["fp32"], 2)}},
            "dist": {
                "batch_size": dist_bs,
                "fp32_img_s": round(dist["fp32"][0], 1),
                "bf16_img_s": round(dist["bf16"][0], 1),
                "speedup": round(dist["bf16"][0] / dist["fp32"][0], 2),
                "fp32_bytes_per_step": round(dist["fp32"][1]
                                             + dist["fp32"][2]),
                "bf16_bytes_per_step": round(dist["bf16"][1]
                                             + dist["bf16"][2]),
                "wire_bytes_ratio": round(wire_ratio, 3)}}


def _mesh_store_stats(mx, jax, sym, x, y, batch_size, mesh):
    """One mesh-mode train step, then the structural numbers the row
    carries: host params for the serve leg + the donated store's
    (total, worst-per-device, devices-occupied) byte split across
    params AND optimizer-state leaves."""
    it = mx.io.NDArrayIter(x, y, batch_size=batch_size,
                           label_name="softmax_label")
    mod = mx.mod.Module(sym)
    mod.set_sharding(mesh)
    mod.bind(it.provide_data, it.provide_label)
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.01,
                                         "momentum": 0.9})
    batch = next(iter(it))
    mod.forward_backward(batch)
    mod.update()
    leaves = [a._data for a in mod._fused._group.param_store.values()]
    for state in getattr(mod._updater, "states", {}).values():
        for leaf in jax.tree_util.tree_leaves(state):
            leaf = getattr(leaf, "_data", leaf)
            if hasattr(leaf, "addressable_shards"):
                leaves.append(leaf)
    per_dev, total = {}, 0
    for arr in leaves:
        total += arr.nbytes
        for s in arr.addressable_shards:
            per_dev[s.device.id] = per_dev.get(s.device.id, 0) \
                + s.data.nbytes
    args_, _ = mod.get_params()
    host = {k: v.asnumpy() for k, v in args_.items()}
    return host, total, max(per_dev.values()), len(per_dev)


def _serve_rate(mx, sym, host, batches, mesh=None):
    """req/sec of the AOT predict menu on repeat batch-8 requests,
    plus the recompile count across the timed window (must be 0)."""
    from mxtpu.serving import InferenceEngine
    eng = InferenceEngine(sym, host, {}, {"data": (256,)},
                          buckets=(8,), warm=True, mesh=mesh)
    q = np.random.RandomState(1).randn(8, 256).astype("float32")
    eng.predict([q])                      # any residual placement work
    before = eng.stats()["compiles"]
    t0 = time.perf_counter()
    for _ in range(batches):
        eng.predict([q])
    dt = time.perf_counter() - t0
    return batches / dt, eng.stats()["compiles"] - before


def run_mesh(batches, warmup, batch_size=None):
    """The --mesh sweep (ISSUE 20): fused single-device vs pjit-sharded
    fused train loop, and single vs sharded serving, on the emulated
    8-way mesh. Every param dim 0 divides the mesh so the FSDP default
    rule shards the whole store."""
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    os.environ.setdefault("MXTPU_PS_HEARTBEAT", "0")
    import jax
    import mxtpu as mx
    from mxtpu.parallel import MeshContext

    n_dev = len(jax.devices())
    mesh = MeshContext({"model": n_dev})
    hidden = (64, 32) if TINY else (256, 64)
    sym = _mlp(mx, hidden=hidden, classes=8)
    bs = batch_size or DEFAULT_BS["mlp"]
    n = max(4 * bs, 64)
    rng = np.random.RandomState(0)
    x = rng.randn(n, 256).astype("float32")
    y = rng.randint(0, 8, n).astype("float32")

    saved = {k: os.environ.get(k)
             for k in ("MXTPU_MODULE_FUSED", "MXTPU_MESH")}
    os.environ.pop("MXTPU_MESH", None)     # explicit mesh only: the
    os.environ["MXTPU_MODULE_FUSED"] = "1"  # single leg must stay single
    try:
        single_rate, f1 = _steady_state_rate(mx, sym, x, y, bs,
                                             batches, warmup)
        mesh_rate, f2 = _steady_state_rate(mx, sym, x, y, bs,
                                           batches, warmup, mesh=mesh)
        assert f1 and f2, "fused path did not engage"
        host, store_total, store_worst, store_devs = _mesh_store_stats(
            mx, jax, sym, x, y, bs, mesh)
        serve_single, rc0 = _serve_rate(mx, sym, host, batches)
        serve_mesh, rc1 = _serve_rate(mx, sym, host, batches, mesh=mesh)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return {"bench": "module_fit_mesh", "tiny": TINY,
            "batches": batches, "warmup": warmup,
            "host_cores": os.cpu_count(), "devices": n_dev,
            "train": {
                "batch_size": bs,
                "fused_img_s": round(single_rate, 1),
                "mesh_img_s": round(mesh_rate, 1),
                "mesh_vs_single": round(mesh_rate / single_rate, 2),
                "store_bytes": store_total,
                "store_bytes_worst_device": store_worst,
                "store_devices": store_devs},
            "serve": {
                "batch_size": 8,
                "single_req_s": round(serve_single, 1),
                "mesh_req_s": round(serve_mesh, 1),
                "mesh_vs_single": round(serve_mesh / serve_single, 2),
                "recompiles": rc0 + rc1}}


def run(batches, warmup, batch_size=None):
    import mxtpu as mx

    models = {}
    for name, sym_fn in (("mlp", _mlp), ("lenet", _lenet)):
        bs = batch_size or DEFAULT_BS[name]
        n = max(4 * bs, 64)
        x, y = _data(name, n, bs)
        sym = sym_fn(mx)
        saved = os.environ.get("MXTPU_MODULE_FUSED")
        try:
            os.environ["MXTPU_MODULE_FUSED"] = "1"
            fused_rate, was_fused = _steady_state_rate(
                mx, sym, x, y, bs, batches, warmup)
            assert was_fused, "fused path did not engage"
            os.environ["MXTPU_MODULE_FUSED"] = "0"
            eager_rate, was_fused = _steady_state_rate(
                mx, sym, x, y, bs, batches, warmup)
            assert not was_fused
        finally:
            if saved is None:
                os.environ.pop("MXTPU_MODULE_FUSED", None)
            else:
                os.environ["MXTPU_MODULE_FUSED"] = saved
        models[name] = {"batch_size": bs,
                        "fused_img_s": round(fused_rate, 1),
                        "eager_img_s": round(eager_rate, 1),
                        "speedup": round(fused_rate / eager_rate, 2)}
    return {"bench": "module_fit", "tiny": TINY,
            "batches": batches, "warmup": warmup,
            "host_cores": os.cpu_count(), "models": models}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", type=int, default=4 if TINY else 100,
                    help="steady-state batches per timing run")
    ap.add_argument("--warmup", type=int, default=2 if TINY else 8)
    ap.add_argument("--batch-size", type=int, default=None,
                    help="override the per-model defaults (%r)"
                    % (DEFAULT_BS,))
    ap.add_argument("--dist", action="store_true",
                    help="loopback-PS fit microbench: eager vs fused "
                         "sync vs fused async over kvstore='dist_async'")
    ap.add_argument("--amp", action="store_true",
                    help="mixed-precision microbench: fp32 vs bf16 fused "
                         "(single-host + dist sync over the wire, with "
                         "pushpull bytes/step)")
    ap.add_argument("--mesh", action="store_true",
                    help="pjit-sharded microbench: fused single-device "
                         "vs 8-way mesh train + serve (set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8)")
    ap.add_argument("--no-write", action="store_true",
                    help="do not mirror the line to docs/module_bench.json")
    args = ap.parse_args()

    if args.mesh:
        result = run_mesh(args.batches, args.warmup, args.batch_size)
    elif args.amp:
        result = run_amp(args.batches, args.warmup, args.batch_size)
    elif args.dist:
        result = run_dist(args.batches, args.warmup, args.batch_size)
    else:
        result = run(args.batches, args.warmup, args.batch_size)
    line = json.dumps(result)
    print(line, flush=True)
    if not args.no_write:
        # the file keeps one line per bench kind (module_fit,
        # module_fit_dist, module_fit_amp, module_fit_mesh): replace
        # this kind's line, keep the others
        path = os.path.join(ROOT, "docs", "module_bench.json")
        kept = []
        if os.path.exists(path):
            with open(path) as f:
                for existing in f:
                    existing = existing.strip()
                    if not existing:
                        continue
                    try:
                        if json.loads(existing).get("bench") == \
                                result["bench"]:
                            continue
                    except ValueError:
                        continue
                    kept.append(existing)
        with open(path, "w") as f:
            for existing in kept:
                f.write(existing + "\n")
            f.write(line + "\n")


if __name__ == "__main__":
    main()
