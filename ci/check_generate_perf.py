#!/usr/bin/env python
"""Fast-tier generation perf pin (ISSUE 17): the four properties that
make continuous-batching decode cheap, demonstrated on a loopback
replica in this process and pinned so a regression fails CI:

  1. **Zero retraces after warmup**: once one sequence has been served
     per prefill bucket, a sustained 64-way load compiles NOTHING new
     — the engine's compile counter is bit-pinned across the load.
  2. **Zero hidden host syncs**: the whole sustained load runs with
     JAX's device-to-host transfer guard set to ``disallow`` — the
     decode loop's ONE explicit per-step ``device_get`` (the token
     read; since ISSUE 31 it reads the step dispatched the turn
     BEFORE, whose copy the scheduler started explicitly with
     ``copy_to_host_async`` when it went out) is allowed, any
     implicit ``np.asarray`` on device state would raise and fail
     the run.
  3. **Batching wins**: tokens/s at 64 concurrent sequences must be at
     least ``SPEEDUP_PIN``x tokens/s at 8 — the fixed-capacity packed
     decode step amortises dispatch across active slots, so throughput
     scales with occupancy, not sequence count.
  4. **The generation menu prewarms**: ``export_programs`` after the
     load carries the gen_prefill/gen_decode/gen_adopt programs
     (they ride the same shared ProgramCache as the predict buckets —
     MXTPU_SERVE_PREWARM_DIR needs no new machinery), and a FRESH
     engine that imports the file serves generate with ZERO compiles.

Run: ``JAX_PLATFORMS=cpu python ci/check_generate_perf.py`` (wired
into ``ci/run_ci.sh fast``). Exit 0 = contract holds.
"""
from __future__ import annotations

import os
import sys
import tempfile
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["MXTPU_PS_HEARTBEAT"] = "0"
os.environ["MXTPU_SERVE_GENERATE_SLOTS"] = "32"
os.environ["MXTPU_SERVE_GENERATE_PREFILL_BUCKETS"] = "4,8"

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np                                    # noqa: E402
import jax                                            # noqa: E402

import mxtpu as mx                                    # noqa: E402
from mxtpu.serving import (                           # noqa: E402
    InferenceEngine, ModelServer, ServingClient)

V, D, S = 17, 128, 64
MAX_NEW = 48
SPEEDUP_PIN = 2.0          # tokens/s @64 concurrent vs @8


def fail(msg):
    print("generate perf check FAILED: %s" % msg)
    return 1


def build_lm():
    data = mx.sym.Variable("data")
    pos = mx.sym.Variable("pos", shape=(0,), dtype="int32")
    kc = mx.sym.Variable("kc", shape=(0, S, D))
    vc = mx.sym.Variable("vc", shape=(0, S, D))
    emb = mx.sym.Embedding(data=data, input_dim=V, output_dim=D,
                           name="emb")
    q = mx.sym.FullyConnected(data=emb, num_hidden=D, flatten=False,
                              name="q")
    k = mx.sym.FullyConnected(data=emb, num_hidden=D, flatten=False,
                              name="k")
    v = mx.sym.FullyConnected(data=emb, num_hidden=D, flatten=False,
                              name="v")
    att = mx.sym.cached_attention(q, k, v, kc, vc, pos, num_heads=2,
                                  name="att")
    out = mx.sym.FullyConnected(data=att[0], num_hidden=V,
                                flatten=False, name="proj")
    return mx.sym.Group([out,
                         mx.sym.identity(att[1], name="kc_next"),
                         mx.sym.identity(att[2], name="vc_next")])


def build_params(seed=3):
    rng = np.random.RandomState(seed)
    f = lambda *s: rng.randn(*s).astype(np.float32) * 0.4  # noqa: E731
    return {"emb_weight": f(V, D),
            "q_weight": f(D, D), "q_bias": np.zeros(D, np.float32),
            "k_weight": f(D, D), "k_bias": np.zeros(D, np.float32),
            "v_weight": f(D, D), "v_bias": np.zeros(D, np.float32),
            "proj_weight": f(V, D), "proj_bias": np.zeros(V, np.float32)}


def make_engine(warm=True):
    return InferenceEngine(build_lm(), build_params(), {},
                           data_shapes={"data": (1,)}, buckets=(1,),
                           warm=warm)


def sweep(cli, n, max_new=MAX_NEW):
    """n concurrent greedy sequences; returns (tokens/s, total toks)."""
    total = [0] * n
    errs = []

    def run(j):
        try:
            toks, _ = cli.generate2([1 + (j % 5), 2, 3 + (j % 7)],
                                    max_new=max_new, model="lm")
            total[j] = len(toks)
        except Exception as e:
            errs.append("seq %d: %s: %s" % (j, type(e).__name__, e))
    ths = [threading.Thread(target=run, args=(j,)) for j in range(n)]
    t0 = time.perf_counter()
    for t in ths:
        t.start()
    for t in ths:
        t.join(timeout=300)
    wall = time.perf_counter() - t0
    if errs:
        raise RuntimeError("; ".join(errs[:3]))
    if any(c != max_new for c in total):
        raise RuntimeError("short sequence: %r" % (total,))
    return n * max_new / wall, n * max_new


def main():
    engine = make_engine()
    srv = ModelServer(engine, port=0, model_name="lm").start()
    try:
        cli = ServingClient(addrs=[srv.address])

        # -- warmup: one sequence per prefill bucket builds the menu --
        for plen in (3, 8):
            cli.generate2(list(range(1, plen + 1)), max_new=4,
                          model="lm")
        pinned = engine.cache.compiles
        if pinned <= 0:
            return fail("warmup compiled nothing?")

        # -- contracts 1+2+3: sustained load, guarded + pinned ---------
        jax.config.update("jax_transfer_guard_device_to_host",
                          "disallow")
        try:
            # best-of-2 per level: the contract is about dispatch
            # amortisation, not this host's worst scheduling hiccup
            tps8 = max(sweep(cli, 8)[0] for _ in range(2))
            tps64 = max(sweep(cli, 64)[0] for _ in range(2))
        finally:
            jax.config.update("jax_transfer_guard_device_to_host",
                              "allow")
        if engine.cache.compiles != pinned:
            return fail("sustained load retraced (%d -> %d compiles)"
                        % (pinned, engine.cache.compiles))
        print("tokens/s: %.0f @8  %.0f @64  (%.2fx, pin >= %.1fx; "
              "%d programs, 0 retraces, d2h guard clean)"
              % (tps8, tps64, tps64 / tps8, SPEEDUP_PIN, pinned))
        if tps64 < SPEEDUP_PIN * tps8:
            return fail(
                "batching win regressed: %.0f tok/s @64 < %.1fx * "
                "%.0f tok/s @8" % (tps64, SPEEDUP_PIN, tps8))

        # -- contract 4: the gen menu rides the prewarm file -----------
        with tempfile.TemporaryDirectory(prefix="genmenu_") as d:
            path = os.path.join(d, "lm-e0000.programs")
            n = engine.export_programs(path)
            if n <= 0:
                return fail("export_programs wrote nothing")
            fresh = make_engine(warm=False)
            imported = fresh.prewarm_from(path)
            if imported < n:
                return fail("prewarm imported %d of %d programs"
                            % (imported, n))
            srv2 = ModelServer(fresh, port=0, model_name="lm").start()
            try:
                cli2 = ServingClient(addrs=[srv2.address])
                toks, _ = cli2.generate2([1, 2, 3], max_new=8,
                                         model="lm")
                if len(toks) != 8:
                    return fail("prewarmed engine generated %d/8"
                                % len(toks))
                if fresh.cache.compiles != 0:
                    return fail(
                        "prewarmed engine cold-compiled %d program(s) "
                        "for generate" % fresh.cache.compiles)
            finally:
                srv2.stop()
            print("prewarm: %d program(s) exported, %d imported, "
                  "generate served with 0 compiles" % (n, imported))
    finally:
        srv.stop()
    print("generate perf contract OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
