"""mxtpu/benchmarking.py: warm-up + N iterations + one
``jax.block_until_ready``. What is checkable on the CPU is the contract —
the barrier waits on what the step returned, a step that returns nothing
to wait on is refused, and the figure agrees with a naive wall clock."""
import time

import numpy as np
import pytest

import jax.numpy as jnp

import mxtpu as mx
from mxtpu.benchmarking import timed_steps


def test_refuses_a_step_with_nothing_to_wait_on():
    # a step that mutates in place and returns None would silently skip
    # the barrier and time the dispatch rate instead
    with pytest.raises(TypeError):
        timed_steps(lambda s: None, warmup=1, iters=1)
    with pytest.raises(TypeError):
        timed_steps(lambda s: [], warmup=1, iters=1)


def test_counts_warmup_and_iters_and_threads_state():
    calls = []

    def step(s):
        calls.append(s)
        return jnp.asarray(0 if s is None else int(s) + 1)

    sec, state = timed_steps(step, warmup=2, iters=5)
    assert len(calls) == 7 and int(state) == 6
    assert np.isfinite(sec) and sec > 0


def test_accepts_ndarray_and_pytree_states():
    x = mx.nd.array(np.ones((4, 4), "f"))
    sec, out = timed_steps(lambda s: x * 2, warmup=1, iters=2)
    assert sec > 0 and float(out.asnumpy()[0, 0]) == 2.0
    sec, out = timed_steps(lambda s: {"a": jnp.ones(3), "n": 1},
                           warmup=1, iters=2)
    assert sec > 0 and out["n"] == 1


def test_agrees_with_a_wall_clock_on_real_work():
    a = jnp.ones((256, 256))

    def step(s):
        return (a if s is None else s) @ a / 256.0

    def naive():
        s = step(None)
        s.block_until_ready()
        t0 = time.perf_counter()
        for _ in range(20):
            s = step(s)
        s.block_until_ready()
        return (time.perf_counter() - t0) / 20

    # the best of three of each: one loop of 20 steps of 0.3 ms that loses
    # its core to another test worker reads several times too long
    sec = min(timed_steps(step, warmup=2, iters=20)[0] for _ in range(3))
    wall = min(naive() for _ in range(3))
    assert 0.2 < sec / wall < 5.0, (sec, wall)
