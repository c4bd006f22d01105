"""Device timing: warm-up, N iterations, one ``jax.block_until_ready``.

JAX dispatch is asynchronous, so a timing that does not wait for the last
result measures the enqueue. ``timed_steps`` waits once, inside the timed
region, on everything the last iteration returned.

That ``block_until_ready`` is an honest barrier was checked on the machine
these numbers come from (TPU v5 lite, jax 0.9.0, libtpu 0.0.34; PR 21,
2026-09-26): a bf16 4096^2 matmul loop took 765.0 us an iteration timed
this way and 764.5 us timed by a chained-input, difference-of-two-lengths
loop ending in a device-to-host read — 0.07% apart, 179.7 TFLOP/s either
way — so the heavier method this module used to carry is gone.
"""
from __future__ import annotations

import time

__all__ = ["timed_steps"]


def _device_arrays(value):
    import jax
    leaves = []
    for leaf in jax.tree_util.tree_leaves(value):
        leaf = getattr(leaf, "_data", leaf)       # mxtpu NDArray
        if isinstance(leaf, jax.Array):
            leaves.append(leaf)
    if not leaves:
        # nothing to wait on means no barrier happened — and a silent
        # no-op here would turn the timing into a dispatch-rate measurement
        raise TypeError(
            "timed_steps needs the step to RETURN a device array to wait "
            "on (got %r), not to mutate in place" % (value,))
    return leaves


def timed_steps(step, state=None, warmup=3, iters=20):
    """Seconds per iteration of ``step``.

    ``step(state) -> state`` runs one unit of work and returns a jax
    array, an mxtpu NDArray or a pytree of them; whatever it returns is
    passed back in. ``warmup`` untimed iterations cover compilation, then
    ``iters`` iterations are timed up to the moment the last result is
    ready. Returns ``(seconds_per_iter, state)``.
    """
    import jax
    for _ in range(warmup):
        state = step(state)
    jax.block_until_ready(_device_arrays(state))
    t0 = time.perf_counter()
    for _ in range(iters):
        state = step(state)
    jax.block_until_ready(_device_arrays(state))
    return (time.perf_counter() - t0) / iters, state
