"""Pallas fused LSTM time loop vs the lax.scan formulation
(ops/pallas_rnn.py; interpret mode on the CPU test mesh)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import mxtpu as mx
from mxtpu import nd
from mxtpu.ops import rnn as rnn_ops
from mxtpu.ops.pallas_rnn import lstm_scan, _scan_reference


def _inputs(T=6, N=4, H=8, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.standard_normal((T, N, 4 * H)).astype(np.float32)),
            jnp.asarray(rng.standard_normal((N, H)).astype(np.float32)),
            jnp.asarray(rng.standard_normal((N, H)).astype(np.float32)),
            jnp.asarray(rng.standard_normal((H, 4 * H)).astype(np.float32)
                        * 0.3))


def test_forward_matches_scan():
    xp, h0, c0, wh = _inputs()
    ys_p, ht_p, ct_p = lstm_scan(xp, h0, c0, wh)
    ys_s, ht_s, ct_s = _scan_reference(xp, h0, c0, wh)
    np.testing.assert_allclose(np.asarray(ys_p), np.asarray(ys_s),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(ht_p), np.asarray(ht_s),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(ct_p), np.asarray(ct_s),
                               atol=1e-5, rtol=1e-5)


def test_gradients_match_scan():
    xp, h0, c0, wh = _inputs(T=4, N=2, H=4, seed=3)

    def loss(fn, *args):
        ys, ht, ct = fn(*args)
        return jnp.sum(ys ** 2) + jnp.sum(jnp.sin(ht)) + jnp.sum(ct)

    gp = jax.grad(lambda *a: loss(lstm_scan, *a),
                  argnums=(0, 1, 2, 3))(xp, h0, c0, wh)
    gs = jax.grad(lambda *a: loss(_scan_reference, *a),
                  argnums=(0, 1, 2, 3))(xp, h0, c0, wh)
    for a, b in zip(gp, gs):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("bidirectional", [False, True])
def test_rnn_op_pallas_path(bidirectional):
    """Full fused RNN op: pallas LSTM path == scan path, fwd and grads."""
    T, N, I, H, L = 5, 3, 6, 4, 2
    rng = np.random.RandomState(7)
    x = rng.standard_normal((T, N, I)).astype(np.float32)
    ndir = 2 if bidirectional else 1
    psize = rnn_ops.rnn_param_size("lstm", I, H, L, bidirectional)
    params = (rng.standard_normal(psize) * 0.2).astype(np.float32)
    h0 = np.zeros((L * ndir, N, H), np.float32)
    c0 = np.zeros((L * ndir, N, H), np.float32)

    def run():
        return mx.nd.RNN(nd.array(x), nd.array(params), nd.array(h0),
                         nd.array(c0), state_size=H, num_layers=L,
                         mode="lstm", bidirectional=bidirectional,
                         state_outputs=True)

    try:
        rnn_ops.USE_PALLAS_RNN = False
        ref = [o.asnumpy() for o in run()]
        rnn_ops.USE_PALLAS_RNN = True
        got = [o.asnumpy() for o in run()]
    finally:
        rnn_ops.USE_PALLAS_RNN = False
    for a, b in zip(got, ref):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


def test_gluon_lstm_layer_pallas_path():
    from mxtpu.gluon import rnn as grnn
    T, N, I, H = 4, 2, 5, 3
    rng = np.random.RandomState(1)
    x = nd.array(rng.standard_normal((T, N, I)).astype(np.float32))
    mx.random.seed(0)
    layer = grnn.LSTM(H, num_layers=1)
    layer.initialize(mx.init.Xavier())

    def fwd_and_grad():
        with mx.autograd.record():
            out = layer(x)
            loss = (out * out).sum()
        loss.backward()
        w = next(iter(layer.collect_params().values()))
        return out.asnumpy(), w.grad().asnumpy()

    try:
        rnn_ops.USE_PALLAS_RNN = False
        out_ref, g_ref = fwd_and_grad()
        rnn_ops.USE_PALLAS_RNN = True
        out_p, g_p = fwd_and_grad()
    finally:
        rnn_ops.USE_PALLAS_RNN = False
    np.testing.assert_allclose(out_p, out_ref, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(g_p, g_ref, atol=1e-5, rtol=1e-5)


def test_bf16_forward_backward_consistent():
    # bf16 inputs: backward recompute must mirror the kernel's f32-carry
    # precision so gradients belong to the same function as the forward
    xp, h0, c0, wh = _inputs(T=5, N=2, H=4, seed=9)
    xp = xp.astype(jnp.bfloat16)
    h0 = h0.astype(jnp.bfloat16)
    c0 = c0.astype(jnp.bfloat16)
    wh = wh.astype(jnp.bfloat16)
    ys_p, ht_p, ct_p = lstm_scan(xp, h0, c0, wh)
    ys_s, ht_s, ct_s = _scan_reference(xp, h0, c0, wh)
    assert ys_p.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(ys_p, np.float32),
                               np.asarray(ys_s, np.float32),
                               atol=2e-2, rtol=2e-2)

    def loss(fn, *a):
        ys, ht, ct = fn(*a)
        return jnp.sum(ys.astype(jnp.float32) ** 2)

    gp = jax.grad(lambda *a: loss(lstm_scan, *a), argnums=(0, 3))(
        xp, h0, c0, wh)
    gs = jax.grad(lambda *a: loss(_scan_reference, *a), argnums=(0, 3))(
        xp, h0, c0, wh)
    for a, b in zip(gp, gs):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5e-2, rtol=5e-2)


def test_gru_forward_and_grads_match_scan():
    from mxtpu.ops.pallas_rnn import gru_scan, _gru_scan_reference
    rng = np.random.RandomState(11)
    T, N, H = 5, 3, 4
    xp = jnp.asarray(rng.standard_normal((T, N, 3 * H)).astype(np.float32))
    h0 = jnp.asarray(rng.standard_normal((N, H)).astype(np.float32))
    whrz = jnp.asarray(rng.standard_normal((H, 2 * H)).astype(np.float32)
                       * 0.3)
    whn = jnp.asarray(rng.standard_normal((H, H)).astype(np.float32) * 0.3)
    bhn = jnp.asarray(rng.standard_normal((H,)).astype(np.float32) * 0.1)
    ys_p, ht_p = gru_scan(xp, h0, whrz, whn, bhn)
    ys_s, ht_s = _gru_scan_reference(xp, h0, whrz, whn, bhn)
    np.testing.assert_allclose(np.asarray(ys_p), np.asarray(ys_s),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(ht_p), np.asarray(ht_s),
                               atol=1e-5, rtol=1e-5)

    def loss(fn, *a):
        ys, ht = fn(*a)
        return jnp.sum(ys ** 2) + jnp.sum(jnp.sin(ht))

    gp = jax.grad(lambda *a: loss(gru_scan, *a),
                  argnums=(0, 1, 2, 3, 4))(xp, h0, whrz, whn, bhn)
    gs = jax.grad(lambda *a: loss(_gru_scan_reference, *a),
                  argnums=(0, 1, 2, 3, 4))(xp, h0, whrz, whn, bhn)
    for a, b in zip(gp, gs):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("bidirectional", [False, True])
def test_rnn_op_gru_pallas_path(bidirectional):
    T, N, I, H, L = 5, 3, 6, 4, 2
    rng = np.random.RandomState(7)
    x = rng.standard_normal((T, N, I)).astype(np.float32)
    ndir = 2 if bidirectional else 1
    psize = rnn_ops.rnn_param_size("gru", I, H, L, bidirectional)
    params = (rng.standard_normal(psize) * 0.2).astype(np.float32)
    h0 = np.zeros((L * ndir, N, H), np.float32)

    def run():
        return mx.nd.RNN(nd.array(x), nd.array(params), nd.array(h0),
                         state_size=H, num_layers=L, mode="gru",
                         bidirectional=bidirectional, state_outputs=True)

    try:
        rnn_ops.USE_PALLAS_RNN = False
        ref = [o.asnumpy() for o in run()]
        rnn_ops.USE_PALLAS_RNN = True
        got = [o.asnumpy() for o in run()]
    finally:
        rnn_ops.USE_PALLAS_RNN = False
    for a, b in zip(got, ref):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)
