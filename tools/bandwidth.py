#!/usr/bin/env python
"""Collective-bandwidth diagnostic (reference tools/bandwidth/measure.py,
cited by docs/faq/perf.md:194-196 for weighing compute vs communication).

The reference measures KVStore push+pull bytes/sec across GPUs for a
given network's gradient sizes. Here the comm fabric is XLA collectives
over the device mesh, so we time psum (the gradient all-reduce),
all_gather (the weight broadcast analogue) and ppermute (the ring/
pipeline primitive) for a sweep of sizes, and per-network gradient
totals for the model-zoo names the reference script takes via --network.

Run on TPU hardware, or locally with
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
  python tools/bandwidth.py --sizes 1e6 --iters 5
"""
from __future__ import annotations

import argparse
import functools
import time


def measure(fn, x, iters):
    """Per-collective seconds, with dispatch/transfer overhead cancelled:
    time an iters-loop and a 2*iters-loop (both ending in the same scalar
    round-trip) and difference them, so the fixed cost of the final
    reduction + host sync drops out of the reported number."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames="n")
    def loop(x, n):
        def body(_, acc):
            return acc + fn(x)
        return jnp.sum(jax.lax.fori_loop(0, n, body, jnp.zeros_like(x)))

    float(loop(x, iters))                     # compile both variants
    float(loop(x, 2 * iters))

    def timed(n):
        best = float("inf")
        for _ in range(3):
            t = time.perf_counter()
            float(loop(x, n))
            best = min(best, time.perf_counter() - t)
        return best

    t_short, t_long = timed(iters), timed(2 * iters)
    if t_long > t_short:
        return (t_long - t_short) / iters
    return t_long / (2 * iters)               # jitter floor: raw estimate


def main():
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    import numpy as np

    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", type=float, nargs="+",
                    default=[1e5, 1e6, 1e7],
                    help="elements (fp32) per collective")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--network", type=str, default=None,
                    help="model-zoo name: also report that net's total "
                         "gradient bytes per step")
    args = ap.parse_args()

    devs = jax.devices()
    n = len(devs)
    mesh = Mesh(np.array(devs), ("x",))
    print("devices: %d x %s" % (n, devs[0].platform))

    def run(name, fn, size):
        x = jnp.ones((n, int(size)), jnp.float32)
        sm = shard_map(fn, mesh=mesh, in_specs=P("x"), out_specs=P("x"),
                       check_vma=False)
        dt = measure(sm, x, args.iters)
        nbytes = int(size) * 4
        # ring all-reduce moves 2(n-1)/n of the payload per device
        print("%-12s %10d B  %8.3f ms  %8.2f GB/s (algo)"
              % (name, nbytes, dt * 1e3, nbytes / dt / 1e9))

    perm = [(i, (i + 1) % n) for i in range(n)]
    for size in args.sizes:
        run("psum", lambda v: jax.lax.psum(v, "x"), size)
        run("all_gather",
            lambda v: jax.lax.all_gather(v, "x").reshape(v.shape[0] * n,
                                                         -1)[:v.shape[0]],
            size)
        run("ppermute",
            functools.partial(jax.lax.ppermute, axis_name="x", perm=perm),
            size)

    if args.network:
        import mxtpu as mx
        from mxtpu.gluon.model_zoo import vision
        net = getattr(vision, args.network)()
        net.initialize(mx.init.Xavier())
        net(mx.nd.zeros((1, 3, 224, 224)))
        total = sum(int(np.prod(p.shape)) * 4
                    for p in net.collect_params().values())
        print("%s gradient payload per step: %.1f MB"
              % (args.network, total / 1e6))


if __name__ == "__main__":
    main()
