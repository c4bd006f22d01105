"""Pallas fused LSTM/GRU time loops (cuDNN-RNN parity, the second hot op).

The fused RNN op (ops/rnn.py) hoists the input projection into one big
MXU matmul and scans the recurrence with ``lax.scan``. This module lowers
the scan body itself to a Pallas kernel: the grid walks time steps while
h/c live in VMEM scratch across the whole sequence — no per-step HBM
round-trip for the carry, and the gate pointwise math fuses with the
h @ Wh matmul in one kernel (the reference gets this from cuDNN's fused
LSTM, ``src/operator/cudnn_rnn-inl.h``).

Differentiation: custom VJP whose backward recomputes through the
mathematically identical ``lax.scan`` formulation — residuals stay tiny
(the inputs), matching the rematerialization discipline used elsewhere.

Lowered for any platform but TPU the same kernel runs through the Pallas
interpreter, so tests cover it everywhere (``pallas_util``). ``ops.rnn``
takes the ``lax.scan`` route unless ``mxtpu.ops.rnn.USE_PALLAS_RNN`` is
set: the kernels keep the whole recurrent weight in VMEM, so they only
hold hidden sizes whose ``wh`` fits the scoped-VMEM limit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..base import MXNetError
from .pallas_util import SCOPED_VMEM_LIMIT

__all__ = ["lstm_scan", "gru_scan"]


def _require_fit(cell, x_proj, *weights):
    """The kernels keep every recurrent weight resident in VMEM (upcast
    to f32 for the matmul) next to the double-buffered x_proj/ys blocks,
    the f32 gates and the carries. The estimate is an upper bound checked
    against Mosaic's own refusals for v5e in
    tests/test_kernels_compile_v5e.py."""
    _, N, G = x_proj.shape
    H = weights[0].shape[0]
    need = (4 * sum(w.size for w in weights)
            + 2 * N * (G + H) * x_proj.dtype.itemsize
            + 4 * N * G + 24 * N * H)
    if need > SCOPED_VMEM_LIMIT:
        raise MXNetError(
            "The Pallas %s kernel at hidden size %d, batch %d needs about "
            "%.1f MiB of VMEM; the scoped-VMEM limit is %d MiB. Use the "
            "lax.scan route (mxtpu.ops.rnn.USE_PALLAS_RNN = False, the "
            "default), which has no such limit."
            % (cell, H, N, need / 2 ** 20, SCOPED_VMEM_LIMIT >> 20))


@functools.cache
def _fwd_call():
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from .pallas_util import per_platform

    def kernel(xp_ref, wh_ref, h0_ref, c0_ref, ys_ref, ht_ref, ct_ref,
               h_s, c_s, *, T, H):
        t = pl.program_id(0)

        @pl.when(t == 0)
        def _init():
            h_s[:] = h0_ref[:].astype(jnp.float32)
            c_s[:] = c0_ref[:].astype(jnp.float32)

        h, c = h_s[:], c_s[:]
        gates = xp_ref[0].astype(jnp.float32) + jax.lax.dot_general(
            h, wh_ref[:].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        i = jax.nn.sigmoid(gates[:, 0 * H:1 * H])
        f = jax.nn.sigmoid(gates[:, 1 * H:2 * H])
        g = jnp.tanh(gates[:, 2 * H:3 * H])
        o = jax.nn.sigmoid(gates[:, 3 * H:4 * H])
        c = f * c + i * g
        h = o * jnp.tanh(c)
        h_s[:], c_s[:] = h, c
        ys_ref[0] = h.astype(ys_ref.dtype)

        @pl.when(t == T - 1)
        def _fin():
            ht_ref[:] = h.astype(ht_ref.dtype)
            ct_ref[:] = c.astype(ct_ref.dtype)

    @jax.jit
    def call(x_proj, h0, c0, wh_t):
        T, N, G = x_proj.shape
        H = h0.shape[-1]
        _require_fit("LSTM", x_proj, wh_t)
        return per_platform(functools.partial(
            pl.pallas_call,
            functools.partial(kernel, T=T, H=H),
            grid=(T,),
            in_specs=[
                pl.BlockSpec((1, N, G), lambda t: (t, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
            ],
            out_specs=[
                pl.BlockSpec((1, N, H), lambda t: (t, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((T, N, H), x_proj.dtype),
                jax.ShapeDtypeStruct((N, H), h0.dtype),
                jax.ShapeDtypeStruct((N, H), c0.dtype),
            ],
            scratch_shapes=[pltpu.VMEM((N, H), jnp.float32),
                            pltpu.VMEM((N, H), jnp.float32)],
        ), x_proj, wh_t, h0, c0)

    return call


@functools.cache
def _gru_fwd_call():
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from .pallas_util import per_platform

    def kernel(xp_ref, whrz_ref, whn_ref, bhn_ref, h0_ref, ys_ref, ht_ref,
               h_s, *, T, H):
        t = pl.program_id(0)

        @pl.when(t == 0)
        def _init():
            h_s[:] = h0_ref[:].astype(jnp.float32)

        h = h_s[:]
        xp = xp_ref[0].astype(jnp.float32)            # [N, 3H], order r,z,n
        rz = jax.nn.sigmoid(xp[:, :2 * H] + jax.lax.dot_general(
            h, whrz_ref[:].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))
        r, z = rz[:, :H], rz[:, H:]
        hn = jax.lax.dot_general(
            h, whn_ref[:].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) \
            + bhn_ref[:].astype(jnp.float32)
        n = jnp.tanh(xp[:, 2 * H:] + r * hn)
        h = (1 - z) * n + z * h
        h_s[:] = h
        ys_ref[0] = h.astype(ys_ref.dtype)

        @pl.when(t == T - 1)
        def _fin():
            ht_ref[:] = h.astype(ht_ref.dtype)

    @jax.jit
    def call(x_proj, h0, whrz_t, whn_t, bhn):
        T, N, G = x_proj.shape
        H = h0.shape[-1]
        _require_fit("GRU", x_proj, whrz_t, whn_t)
        return per_platform(functools.partial(
            pl.pallas_call,
            functools.partial(kernel, T=T, H=H),
            grid=(T,),
            in_specs=[
                pl.BlockSpec((1, N, G), lambda t: (t, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
            ],
            out_specs=[
                pl.BlockSpec((1, N, H), lambda t: (t, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((T, N, H), x_proj.dtype),
                jax.ShapeDtypeStruct((N, H), h0.dtype),
            ],
            scratch_shapes=[pltpu.VMEM((N, H), jnp.float32)],
        ), x_proj, whrz_t, whn_t, bhn, h0)

    return call


def _gru_scan_reference(x_proj, h0, whrz_t, whn_t, bhn):
    """lax.scan formulation mirroring the GRU kernel's f32 precision."""
    H = h0.shape[-1]
    whrz32 = whrz_t.astype(jnp.float32)
    whn32 = whn_t.astype(jnp.float32)
    bhn32 = bhn.astype(jnp.float32)

    def step(h, xp):
        xp = xp.astype(jnp.float32)
        rz = jax.nn.sigmoid(xp[:, :2 * H] + h @ whrz32)
        r, z = rz[:, :H], rz[:, H:]
        n = jnp.tanh(xp[:, 2 * H:] + r * (h @ whn32 + bhn32))
        h = (1 - z) * n + z * h
        return h, h.astype(x_proj.dtype)

    hT, ys = jax.lax.scan(step, h0.astype(jnp.float32), x_proj)
    return ys, hT.astype(h0.dtype)


@jax.custom_vjp
def gru_scan(x_proj, h0, whrz_t, whn_t, bhn):
    """Fused GRU over time. x_proj: (T, N, 3H) pre-projected inputs
    (x @ Wx + bi, gate order [r, z, n]), h0: (N, H), whrz_t: (H, 2H)
    transposed r/z recurrent weights, whn_t: (H, H) candidate weights,
    bhn: (H,) candidate recurrent bias (kept separate because the
    candidate gate sees r * (h @ Whn + bhn)). Returns (ys, hT)."""
    return _gru_fwd_call()(x_proj, h0, whrz_t, whn_t, bhn)


def _gru_vjp_fwd(x_proj, h0, whrz_t, whn_t, bhn):
    out = _gru_fwd_call()(x_proj, h0, whrz_t, whn_t, bhn)
    return out, (x_proj, h0, whrz_t, whn_t, bhn)


def _gru_vjp_bwd(res, cot):
    _, vjp = jax.vjp(_gru_scan_reference, *res)
    return vjp(cot)


gru_scan.defvjp(_gru_vjp_fwd, _gru_vjp_bwd)


def _scan_reference(x_proj, h0, c0, wh_t):
    """The mathematically identical lax.scan formulation (used for the
    backward recompute and as the numeric cross-check in tests). Must
    mirror the kernel's precision EXACTLY — carry and gate math in f32,
    outputs cast back — or bf16 gradients would belong to a different
    function than the forward that ran."""
    H = h0.shape[-1]
    wh32 = wh_t.astype(jnp.float32)

    def step(carry, xp):
        h, c = carry
        gates = xp.astype(jnp.float32) + h @ wh32
        i = jax.nn.sigmoid(gates[:, 0 * H:1 * H])
        f = jax.nn.sigmoid(gates[:, 1 * H:2 * H])
        g = jnp.tanh(gates[:, 2 * H:3 * H])
        o = jax.nn.sigmoid(gates[:, 3 * H:4 * H])
        c = f * c + i * g
        h = o * jnp.tanh(c)
        return (h, c), h.astype(x_proj.dtype)

    (hT, cT), ys = jax.lax.scan(
        step, (h0.astype(jnp.float32), c0.astype(jnp.float32)), x_proj)
    return ys, hT.astype(h0.dtype), cT.astype(c0.dtype)


@jax.custom_vjp
def lstm_scan(x_proj, h0, c0, wh_t):
    """Fused LSTM over time. x_proj: (T, N, 4H) pre-projected inputs
    (x @ Wx + biases), h0/c0: (N, H), wh_t: (H, 4H) transposed recurrent
    weights, gate order [i, f, g, o]. Returns (ys (T,N,H), hT, cT)."""
    return _fwd_call()(x_proj, h0, c0, wh_t)


def _vjp_fwd(x_proj, h0, c0, wh_t):
    out = _fwd_call()(x_proj, h0, c0, wh_t)
    return out, (x_proj, h0, c0, wh_t)


def _vjp_bwd(res, cot):
    # recompute-based backward through the identical scan math
    _, vjp = jax.vjp(_scan_reference, *res)
    return vjp(cot)


lstm_scan.defvjp(_vjp_fwd, _vjp_bwd)
