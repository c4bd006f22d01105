"""``hyper_mix`` / ``hyper_merge`` (``ops/nn.py``): ``n`` residual streams
around a sub-layer, read weights, write weights and a stream matrix that
Sinkhorn iterations make doubly stochastic, all in float32 whatever the
streams' dtype."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxtpu.ops import nn

N, D = 4, 24


def leaves(seed, B=2, T=3, dtype=jnp.float32, base_diag=4.0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    streams = jax.random.normal(k[0], (B, T, N, D), dtype)
    phi = jax.random.normal(k[1], (N * D, N * (N + 2)), jnp.float32) / np.sqrt(N * D)
    alpha = jnp.asarray([0.7, 1.3, 1.1], jnp.float32)
    base = jnp.concatenate([0.3 * jax.random.normal(k[2], (2 * N,)),
                            base_diag * jnp.eye(N).ravel()]).astype(jnp.float32)
    return streams, phi, alpha, base


def definition(streams, phi, alpha, base, iters=20, eps=1e-6, lo=-30.0,
               hi=30.0):
    """The equations in float64 numpy. Returns ``(a, b, R, read, carried)``."""
    X, phi, alpha, base = (np.asarray(a, np.float64)
                           for a in (streams, phi, alpha, base))
    B, T, n, d = X.shape
    flat = X.reshape(B, T, n * d)
    flat = flat / np.sqrt(np.mean(flat * flat, -1, keepdims=True) + eps)
    m = flat @ phi
    sig = lambda z: 1.0 / (1.0 + np.exp(-z))  # noqa: E731
    a = sig(alpha[0] * m[..., :n] + base[:n])
    b = 2.0 * sig(alpha[1] * m[..., n:2 * n] + base[n:2 * n])
    M = np.exp(np.clip(alpha[2] * m[..., 2 * n:] + base[2 * n:], lo, hi)
               ).reshape(B, T, n, n)
    for _ in range(iters):
        M = M / (M.sum(-1, keepdims=True) + eps)
        M = M / (M.sum(-2, keepdims=True) + eps)
    return (a, b, M, np.einsum("btj,btjd->btd", a, X),
            np.einsum("btij,btjd->btid", M, X))


def test_hyper_mix_is_the_definition():
    args = leaves(1)
    before = nn.hyper_mix_nodes()
    read, carried, write = jax.jit(nn.hyper_mix)(*args)
    assert nn.hyper_mix_nodes() == before + 1
    _a, b, _R, want_read, want_carried = definition(*args)
    assert np.max(np.abs(np.asarray(read) - want_read)) < 1e-5
    assert np.max(np.abs(np.asarray(carried) - want_carried)) < 1e-5
    assert np.max(np.abs(np.asarray(write) - b)) < 1e-6
    assert 0.0 < np.min(b) and np.max(b) < 2.0


def test_sinkhorn_rows_and_columns_sum_to_one():
    """After 20 iterations the matrix is doubly stochastic to float32's
    rounding, with streams as unit vectors so that ``carried`` IS ``R``; and
    every iteration runs: 3 of them leave the rows visibly off."""
    streams, phi, alpha, base = leaves(2, B=1, T=5, base_diag=2.0)
    unit = jnp.broadcast_to(jnp.eye(N, D), (1, 5, N, D)) * 1.0
    _read, carried, _w = jax.jit(nn.hyper_mix)(unit, phi, 3.0 * alpha, base)
    R = np.asarray(carried)[..., :N]                    # [1, 5, n, n]
    assert np.all(R > 0)
    assert np.max(np.abs(R.sum(-1) - 1)) < 1e-5         # rows
    assert np.max(np.abs(R.sum(-2) - 1)) < 1e-5         # columns
    assert np.max(np.abs(R - definition(unit, phi, 3.0 * alpha, base)[2])) < 1e-5
    few = np.asarray(nn.sinkhorn(jnp.log(jnp.asarray(R) + 1e-3)
                                 + jnp.arange(N) * 2.0, 3, 1e-6))
    assert np.max(np.abs(few.sum(-1) - 1)) > 1e-4
    assert np.max(np.abs(few.sum(-2) - 1)) < 1e-5       # columns come last


def test_clamp_engages():
    """Logits of +-200 would overflow ``exp`` in float32; clamped to +-30
    the matrix stays finite and still sums to one, and it is the clamped
    definition's."""
    streams, phi, alpha, base = leaves(3, B=1, T=2)
    wild = base.at[2 * N:].set(jnp.asarray(
        [200.0, -200.0, 0.0, 50.0] * N, jnp.float32))
    read, carried, write = jax.jit(nn.hyper_mix)(streams, phi, alpha, wild)
    assert np.all(np.isfinite(np.asarray(carried)))
    want = definition(streams, phi, alpha, wild)
    assert np.max(np.abs(np.asarray(carried) - want[4])) < 1e-4
    unclamped = jax.jit(lambda *a: nn.hyper_mix(
        *a, clamp_min=-1e9, clamp_max=1e9))(streams, phi, alpha, wild)[1]
    assert not np.all(np.isfinite(np.asarray(unclamped)))
    # a narrower clamp is another matrix: the limits are read
    narrow = jax.jit(lambda *a: nn.hyper_mix(
        *a, clamp_min=-1.0, clamp_max=1.0))(streams, phi, alpha, wild)[1]
    assert np.max(np.abs(np.asarray(narrow) - np.asarray(carried))) > 1e-2


def test_the_arithmetic_is_float32_whatever_the_streams_dtype():
    """bfloat16 streams: the read comes back in bfloat16, the carried
    streams and the write weights in float32, and they are the float32
    definition of the bfloat16 values (a product with ``phi`` rounded to
    bfloat16 would be off by 1e-2)."""
    args = leaves(4, dtype=jnp.bfloat16)
    read, carried, write = jax.jit(nn.hyper_mix)(*args)
    assert read.dtype == jnp.bfloat16
    assert carried.dtype == write.dtype == jnp.float32
    _a, b, _R, _read, want = definition(*args)
    assert np.max(np.abs(np.asarray(carried) - want)) < 2e-5
    assert np.max(np.abs(np.asarray(write) - b)) < 2e-6


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_hyper_merge_adds_the_sublayers_output_by_the_write_weights(dtype):
    k = jax.random.split(jax.random.PRNGKey(9), 3)
    carried = jax.random.normal(k[0], (2, 3, N, D), jnp.float32)
    write = 2.0 * jax.random.uniform(k[1], (2, 3, N), jnp.float32)
    y = jax.random.normal(k[2], (2, 3, D), dtype)
    out = jax.jit(nn.hyper_merge)(carried, write, y)
    assert out.dtype == dtype and out.shape == (2, 3, N, D)
    want = (np.asarray(carried, np.float64)
            + np.asarray(write, np.float64)[..., None]
            * np.asarray(y, np.float64)[:, :, None, :])
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    assert np.max(np.abs(np.asarray(out, np.float64) - want)) < tol


def test_a_sublayer_between_mix_and_merge_keeps_the_streams_sum():
    """``R``'s columns sum to one, so the sum over streams moves by exactly
    ``(sum_i b_i) y``: what the model's last norm sees of a sub-layer."""
    args = leaves(5)
    _read, carried, write = jax.jit(nn.hyper_mix)(*args)
    y = jax.random.normal(jax.random.PRNGKey(1), (2, 3, D), jnp.float32)
    out = np.asarray(nn.hyper_merge(carried, write, y), np.float64)
    want = (np.asarray(args[0], np.float64).sum(2)
            + np.asarray(write, np.float64).sum(-1, keepdims=True)
            * np.asarray(y, np.float64))
    assert np.max(np.abs(out.sum(2) - want)) < 1e-4
