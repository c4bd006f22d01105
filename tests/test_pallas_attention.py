"""Pallas flash-attention kernels vs the pure-XLA reference.

Runs through the Pallas interpreter on the CPU test mesh; on TPU the
same code compiles to Mosaic. Checks forward + backward, causal masks,
sequence-shard offsets, padding (non-block-multiple T), bf16 inputs,
and integration via local_attention / the op registry.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from mxtpu.ops.pallas_attention import (flash_attention,
                                        flash_attention_reference)


def _rand(shape, dtype=np.float32, seed=0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.standard_normal(shape).astype(dtype))


def _check(q, k, v, causal=False, q_offset=0, k_offset=0, tol=2e-5,
           block_q=64, block_k=64):
    out = flash_attention(q, k, v, causal=causal, q_offset=q_offset,
                          k_offset=k_offset, block_q=block_q,
                          block_k=block_k)
    ref = flash_attention_reference(q, k, v, causal=causal,
                                    q_offset=q_offset, k_offset=k_offset)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=tol, rtol=tol)


def test_forward_matches_reference():
    q, k, v = (_rand((2, 3, 128, 64), seed=i) for i in range(3))
    _check(q, k, v)


def test_forward_causal():
    q, k, v = (_rand((1, 2, 128, 32), seed=i + 7) for i in range(3))
    _check(q, k, v, causal=True)


def test_forward_multi_block():
    q, k, v = (_rand((1, 2, 256, 32), seed=i + 3) for i in range(3))
    _check(q, k, v, causal=True, block_q=64, block_k=64)


def test_forward_unpadded_lengths():
    # T not a multiple of the block size: wrapper pads, kernel masks.
    q = _rand((1, 2, 100, 32), seed=1)
    k = _rand((1, 2, 72, 32), seed=2)
    v = _rand((1, 2, 72, 32), seed=3)
    _check(q, k, v, block_q=64, block_k=64)
    _check(q, k, v, causal=True, block_q=64, block_k=64)


def test_sequence_shard_offsets():
    # Causal mask with sharded sequence: device holding rows [64, 128)
    # attending a K/V block holding rows [0, 64) must be fully visible;
    # the reverse fully masked.
    q, k, v = (_rand((1, 1, 64, 32), seed=i + 11) for i in range(3))
    _check(q, k, v, causal=True, q_offset=64, k_offset=0)
    # fully-masked rows must produce zeros, not NaNs
    out = flash_attention(q, k, v, causal=True, q_offset=0, k_offset=64,
                          block_q=64, block_k=64)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), 0.0, atol=1e-6)


def test_traced_offsets():
    q, k, v = (_rand((1, 1, 64, 32), seed=i + 5) for i in range(3))

    @jax.jit
    def f(qo):
        return flash_attention(q, k, v, causal=True, q_offset=qo,
                               k_offset=0, block_q=64, block_k=64)

    out = f(jnp.int32(64))
    ref = flash_attention_reference(q, k, v, causal=True, q_offset=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_reference(causal):
    q, k, v = (_rand((1, 2, 128, 32), seed=i + 21) for i in range(3))

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        o = flash_attention_reference(q, k, v, causal=causal)
        return jnp.sum(jnp.sin(o))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-5, rtol=3e-5)


def test_gradients_unpadded():
    q = _rand((1, 1, 96, 32), seed=31)
    k = _rand((1, 1, 80, 32), seed=32)
    v = _rand((1, 1, 80, 32), seed=33)

    def loss(fn, *args):
        return jnp.sum(fn(*args) ** 2)

    gf = jax.grad(lambda a, b, c: loss(
        lambda *x: flash_attention(*x, causal=True, block_q=64, block_k=64),
        a, b, c), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda a, b, c: loss(
        lambda *x: flash_attention_reference(*x, causal=True),
        a, b, c), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-5, rtol=3e-5)


def test_bf16_inputs():
    q, k, v = (_rand((1, 2, 128, 64), seed=i).astype(jnp.bfloat16)
               for i in range(3))
    out = flash_attention(q, k, v, block_q=64, block_k=64)
    ref = flash_attention_reference(q, k, v)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref, dtype=np.float32),
                               atol=3e-2, rtol=3e-2)


def test_local_attention_flash_impl():
    from mxtpu.parallel.ring_attention import local_attention
    q, k, v = (_rand((1, 2, 128, 32), seed=i + 41) for i in range(3))
    out = local_attention(q, k, v, causal=True, impl="flash")
    ref = local_attention(q, k, v, causal=True, impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_registered_as_op():
    from mxtpu.ops import get_op
    assert get_op("_contrib_flash_attention") is not None
    assert get_op("flash_attention") is not None


def test_nd_namespace():
    import mxtpu as mx
    q, k, v = (_rand((1, 1, 64, 32), seed=i + 51) for i in range(3))
    out = mx.nd.flash_attention(mx.nd.array(np.asarray(q)),
                                mx.nd.array(np.asarray(k)),
                                mx.nd.array(np.asarray(v)))
    ref = flash_attention_reference(q, k, v)
    np.testing.assert_allclose(out.asnumpy(), np.asarray(ref), atol=2e-5)


def test_with_lse_matches_logsumexp():
    from mxtpu.ops.pallas_attention import flash_attention_with_lse
    q, k, v = (_rand((1, 2, 128, 32), seed=i + 61) for i in range(3))
    o, lse = flash_attention_with_lse(q, k, v, block_q=64, block_k=64)
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / d ** 0.5
    ref_lse = jax.scipy.special.logsumexp(s, axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               atol=2e-5, rtol=2e-5)


def test_lse_merge_rule():
    # attention over [K1; K2] == lse-merge of attention over K1 and K2
    from mxtpu.ops.pallas_attention import flash_attention_with_lse
    q = _rand((1, 1, 64, 32), seed=71)
    k = _rand((1, 1, 128, 32), seed=72)
    v = _rand((1, 1, 128, 32), seed=73)
    o1, l1 = flash_attention_with_lse(q, k[:, :, :64], v[:, :, :64],
                                      block_q=64, block_k=64)
    o2, l2 = flash_attention_with_lse(q, k[:, :, 64:], v[:, :, 64:],
                                      block_q=64, block_k=64)
    lm = jnp.logaddexp(l1, l2)
    om = o1 * jnp.exp(l1 - lm)[..., None] + o2 * jnp.exp(l2 - lm)[..., None]
    full = flash_attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(om), np.asarray(full),
                               atol=2e-5, rtol=2e-5)


def test_with_lse_gradients():
    # d(lse)/d(q,k) path through the custom VJP
    from mxtpu.ops.pallas_attention import flash_attention_with_lse
    q, k, v = (_rand((1, 1, 64, 16), seed=i + 81) for i in range(3))

    def loss_flash(q, k, v):
        o, lse = flash_attention_with_lse(q, k, v, block_q=64, block_k=64)
        return jnp.sum(o ** 2) + jnp.sum(jnp.sin(lse))

    def loss_ref(q, k, v):
        d = q.shape[-1]
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / d ** 0.5
        o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
        lse = jax.scipy.special.logsumexp(s, axis=-1)
        return jnp.sum(o ** 2) + jnp.sum(jnp.sin(lse))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-5, rtol=3e-5)


def test_traced_scale():
    q, k, v = (_rand((1, 1, 64, 32), seed=i + 91) for i in range(3))

    @jax.jit
    def f(s):
        return flash_attention(q, k, v, scale=s, block_q=64, block_k=64)

    out = f(jnp.float32(0.1))
    ref = flash_attention_reference(q, k, v, scale=0.1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_flash_impl(causal):
    from mxtpu.parallel import MeshContext
    from mxtpu.parallel.ring_attention import ring_attention_sharded
    mc = MeshContext(jax.devices(), data=1, seq=8)
    rng = np.random.RandomState(5)
    qq, kk, vv = (jnp.asarray(
        rng.standard_normal((1, 2, 128, 16)).astype(np.float32))
        for _ in range(3))
    out = ring_attention_sharded(qq, kk, vv, mc, causal=causal,
                                 impl="flash")
    ref = flash_attention_reference(qq, kk, vv, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)


# slow: over 30 s alone (28.8 and 35.6 s in PR 32's two runs: the ring's
# backward through the interpreter on 8 devices)
@pytest.mark.slow
def test_ring_attention_flash_grad():
    from mxtpu.parallel import MeshContext
    from mxtpu.parallel.ring_attention import ring_attention_sharded
    mc = MeshContext(jax.devices(), data=1, seq=4)
    rng = np.random.RandomState(6)
    qq, kk, vv = (jnp.asarray(
        rng.standard_normal((1, 1, 64, 16)).astype(np.float32))
        for _ in range(3))

    def loss(impl, q, k, v):
        o = ring_attention_sharded(q, k, v, mc, causal=True, impl=impl)
        return jnp.sum(o ** 2)

    gf = jax.grad(lambda *a: loss("flash", *a), argnums=(0, 1, 2))(qq, kk, vv)
    gx = jax.grad(lambda *a: loss("xla", *a), argnums=(0, 1, 2))(qq, kk, vv)
    for a, b in zip(gf, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-5, rtol=3e-5)


def test_traced_scale_gradient():
    # a learnable attention temperature must receive a real gradient
    q, k, v = (_rand((1, 1, 64, 16), seed=i + 101) for i in range(3))

    def loss_flash(s):
        return jnp.sum(flash_attention(q, k, v, scale=s,
                                       block_q=64, block_k=64) ** 2)

    def loss_ref(s):
        return jnp.sum(flash_attention_reference(q, k, v, scale=s) ** 2)

    g = jax.grad(loss_flash)(jnp.float32(0.2))
    gr = jax.grad(loss_ref)(jnp.float32(0.2))
    assert float(jnp.abs(g)) > 0
    np.testing.assert_allclose(float(g), float(gr), rtol=1e-4)
