#!/usr/bin/env python
"""Measure dist_async parameter-service push/pull throughput at realistic
parameter volume (reference scale: ResNet-50 is ~25.5M fp32 params ≈
102 MB/step each way).

Round-4 verdict finding: each push shipped the full dense gradient as one
pickled frame through one socket — correctness was proven but throughput
at real sizes was unmeasured. This tool measures it, across the levers
that changed in round 5:

* part splitting (MXTPU_KVSTORE_BIGARRAY_BOUND row chunks, reference
  BIGARRAY_BOUND splits) — parts move concurrently over the worker pool;
* server count (parts of one array spread over servers);
* 2-bit wire compression (16x payload cut, worker-side residual).

Writes docs/ps_throughput.json and prints it. CPU-only — no TPU needed.

Run: JAX_PLATFORMS=cpu python tools/bench_ps.py [--mb 100] [--iters 5]
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


def measure(n_servers, bound, compress, total_mb, iters):
    """Time init+push+pull of a ResNet-50-shaped parameter set; returns
    MB/s for push and pull (payload MB counted pre-compression — the
    useful-gradient rate, matching how the reference reports it)."""
    import mxtpu as mx
    from mxtpu import kvstore_async as ka

    servers = [ka.ParameterServer().start() for _ in range(n_servers)]
    saved = {k: os.environ.get(k) for k in ("MXTPU_PS_ADDRS",)}
    os.environ["MXTPU_PS_ADDRS"] = ",".join(s.address for s in servers)
    old_bound = ka._BIGARRAY_BOUND
    ka._BIGARRAY_BOUND = bound
    try:
        kv = mx.kv.create("dist_async")
        if compress:
            kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
        # ResNet-50-ish split: one fat fc-like matrix plus conv-sized
        # blocks, padded to the requested volume
        total_elems = int(total_mb * 1e6 / 4)
        shapes = [(2048, 1000)]
        left = total_elems - 2048 * 1000
        while left > 0:
            n = min(left, 2359296)   # a 3x3x512x512 conv worth
            rows = max(1, n // 4608)
            shapes.append((rows, 4608))
            left -= rows * 4608
        arrs = [mx.nd.array(np.random.RandomState(i).rand(*s)
                            .astype("f")) for i, s in enumerate(shapes)]
        outs = [mx.nd.zeros(s) for s in shapes]
        for i, a in enumerate(arrs):
            kv.init(i, a)
        payload_mb = sum(a.size for a in arrs) * 4 / 1e6

        t0 = time.perf_counter()
        for _ in range(iters):
            for i, a in enumerate(arrs):
                kv.push(i, a)
        push_s = (time.perf_counter() - t0) / iters
        t0 = time.perf_counter()
        for _ in range(iters):
            for i, o in enumerate(outs):
                kv.pull(i, out=o)
        pull_s = (time.perf_counter() - t0) / iters
        n_parts = sum(len(p) for p in kv._parts.values())
        kv.close()
        return {"payload_mb": round(payload_mb, 1),
                "n_parts": n_parts,
                "push_mb_s": round(payload_mb / push_s, 1),
                "pull_mb_s": round(payload_mb / pull_s, 1),
                "push_s": round(push_s, 3), "pull_s": round(pull_s, 3)}
    finally:
        ka._BIGARRAY_BOUND = old_bound
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        for s in servers:
            s.stop()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mb", type=float, default=100.0,
                    help="parameter volume (ResNet-50 fp32 ~= 102)")
    ap.add_argument("--iters", type=int, default=3)
    args = ap.parse_args()

    grid = [
        # label, n_servers, bound(elems), compress
        ("1srv_whole", 1, 1 << 62, False),   # round-4 behavior
        ("1srv_parts", 1, 1000000, False),
        ("2srv_parts", 2, 1000000, False),
        ("4srv_parts", 4, 1000000, False),
        ("1srv_parts_2bit", 1, 1000000, True),
        ("2srv_parts_2bit", 2, 1000000, True),
    ]
    report = {"volume_mb": args.mb, "iters": args.iters,
              "host_cores": os.cpu_count(), "timestamp":
              time.strftime("%F %T")}
    for label, n_srv, bound, comp in grid:
        report[label] = measure(n_srv, bound, comp, args.mb, args.iters)
        print(label, report[label], flush=True)
    out = os.path.join(ROOT, "docs", "ps_throughput.json")
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    print("wrote", out)


if __name__ == "__main__":
    main()
