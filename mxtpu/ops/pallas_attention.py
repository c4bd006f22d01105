"""Pallas TPU flash attention (forward + backward kernels).

The reference framework has no attention op at all (MXNet 1.1 predates
it; its sequence tooling is bucketing + fused cuDNN RNN, SURVEY §5.7).
mxtpu treats long-context attention as a first-class hot op and lowers
it to hand-written Pallas TPU kernels:

* tiled online-softmax forward (flash attention): Q blocks stream over
  K/V blocks in VMEM, running max / denominator carried in VMEM scratch
  across the innermost grid dimension — one HBM pass over K/V,
  O(block_q * block_k) VMEM instead of O(T^2) HBM for the scores;
* recompute-based backward split into a dQ kernel (grid over Q blocks)
  and a dK/dV kernel (grid over K/V blocks), the flash-attention-2
  decomposition — residuals are just (q, k, v, out, lse);
* causal masking under *sequence sharding*: the global positions of the
  local Q/K rows ride along as SMEM scalars (``q_offset``/``k_offset``,
  static ints or traced values), and ``flash_attention_with_lse``
  additionally returns the log-sum-exp so partial results merge online —
  this is what each step of the ppermute ring in
  ``mxtpu.parallel.ring_attention`` (impl="flash") calls;
* fully-masked tiles (above the causal diagonal) are skipped outright.

* one-token decode over the serving engine's packed ``[B, S, D]`` cache
  (:func:`decode_attention`): the cache is read in the tiling it is
  stored in, a block of rows with all heads' columns at a time, the
  heads kept apart by a block-diagonal query matrix; the grid is the
  list of live blocks (:func:`live_blocks`, from ``pos``: whole blocks,
  then tail blocks of a quarter), so a block past a slot's live length
  is no grid step. ``ops.nn.cached_attention`` takes it whenever a
  call has one query row a sample, heads of whole 128-lane slabs, a
  block that divides ``S`` and no ambient mesh; every other shape keeps
  the dense formula.

* one-token decode over a LATENT cache
  (:func:`latent_decode_attention`, PR 34): one ``[c ; k_rope]`` row a
  position shared by all heads; a block of rows is fetched once and serves
  as every head's key (all its columns) and value (the first ``rank``).
  ``ops.nn.latent_attention`` takes it for one row a sample.

Lowered for TPU the kernels compile through Mosaic; lowered for any
other platform the same kernels run through the Pallas interpreter
(tests), so numerics are identical everywhere (``pallas_util``). Timed
on one TPU v5e (PERF.md section 6, PR 28): 24 chained layers at 16 slots
of 40-1200 live rows on a 2048-row bfloat16 cache, 16 heads of 128, take
6.1 ms with :func:`decode_attention` (2.9 ms of it XLA's cache write)
where the dense formula takes 31.9 ms; inside BLOOM-1b7's decode program
the kernel runs 2.9 ms a step. The flash kernels have not been timed on
the current machine.

Pallas itself is imported lazily on first use — `import mxtpu` stays
cheap; the op registry registration in ops/__init__ binds a thin
wrapper, not this module.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..base import MXNetError
from .pallas_util import SCOPED_VMEM_LIMIT

__all__ = ["flash_attention", "flash_attention_with_lse",
           "flash_attention_reference", "decode_attention",
           "latent_decode_attention"]

_NEG = -1e30  # large-negative instead of finfo.min: exp() underflows to 0
              # without inf - inf = nan hazards in the running-max rescale


@functools.cache
def _kernels():
    """Build the pallas_call wrappers on first use (lazy: pallas/mosaic
    imports cost ~2s, which `import mxtpu` must not pay)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from .pallas_util import per_platform

    def vspec(shape, index_map):
        return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)

    # offs = [q_offset, k_offset, kv_len, scale] as float32 SMEM scalars
    # (float so the array flows through custom_vjp as one differentiable-
    # signature operand and scale may be traced; exact for offsets < 2^24).
    def block_live(offs_ref, qb, kb, block_q, block_k, causal):
        """False iff every (qi, ki) pair in this tile is causally masked —
        lets the kernels skip whole tiles above the diagonal."""
        if not causal:
            return True
        q_off = offs_ref[0].astype(jnp.int32)
        k_off = offs_ref[1].astype(jnp.int32)
        return q_off + (qb + 1) * block_q - 1 >= k_off + kb * block_k

    def tile_mask(offs_ref, qb, kb, block_q, block_k, causal):
        q_off = offs_ref[0].astype(jnp.int32)
        k_off = offs_ref[1].astype(jnp.int32)
        kv_len = offs_ref[2].astype(jnp.int32)
        qi = q_off + qb * block_q + \
            jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        ki = k_off + kb * block_k + \
            jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = (ki - k_off) < kv_len          # pad keys masked out
        if causal:
            mask = mask & (qi >= ki)
        return mask

    def dot(a, b, dims):
        return jax.lax.dot_general(a, b, (dims, ((), ())),
                                   preferred_element_type=jnp.float32)

    # -- forward ------------------------------------------------------------

    def fwd_kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                   acc_ref, m_ref, l_ref, *, causal, block_q, block_k, nk):
        qb, kb = pl.program_id(1), pl.program_id(2)

        @pl.when(kb == 0)
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)
            m_ref[:] = jnp.full_like(m_ref, _NEG)
            l_ref[:] = jnp.zeros_like(l_ref)

        @pl.when(block_live(offs_ref, qb, kb, block_q, block_k, causal))
        def _compute():
            q = q_ref[0].astype(jnp.float32)      # [bq, d]
            k = k_ref[0].astype(jnp.float32)      # [bk, d]
            v = v_ref[0].astype(jnp.float32)      # [bk, d]
            s = dot(q, k, ((1,), (1,))) * offs_ref[3]
            mask = tile_mask(offs_ref, qb, kb, block_q, block_k, causal)
            s = jnp.where(mask, s, _NEG)

            m_prev, l_prev = m_ref[:], l_ref[:]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1)[:, None])
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_ref[:] = l_prev * corr + jnp.sum(p, axis=-1)[:, None]
            m_ref[:] = m_new
            acc_ref[:] = acc_ref[:] * corr + dot(p, v, ((1,), (0,)))

        @pl.when(kb == nk - 1)
        def _fin():
            l_safe = jnp.where(l_ref[:] == 0.0, 1.0, l_ref[:])
            o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
            # fully-masked rows keep lse = _NEG so online merges ignore them
            lse_ref[0] = jnp.where(l_ref[:] == 0.0, _NEG,
                                   m_ref[:] + jnp.log(l_safe))

    @functools.partial(jax.jit, static_argnums=(4, 5, 6))
    def fwd(q, k, v, offs, causal, block_q, block_k):
        bh, tq, d = q.shape
        tk = k.shape[1]
        nq, nk = tq // block_q, tk // block_k
        kern = functools.partial(fwd_kernel, causal=causal,
                                 block_q=block_q, block_k=block_k, nk=nk)
        return per_platform(functools.partial(
            pl.pallas_call,
            kern,
            grid=(bh, nq, nk),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                vspec((1, block_q, d), lambda b, i, j: (b, i, 0)),
                vspec((1, block_k, d), lambda b, i, j: (b, j, 0)),
                vspec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            ],
            out_specs=[
                vspec((1, block_q, d), lambda b, i, j: (b, i, 0)),
                vspec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
                jax.ShapeDtypeStruct((bh, tq, 1), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
            ],
        ), offs, q, k, v)

    # -- backward -----------------------------------------------------------
    # Gradient w.r.t. the scaled scores s̃: dL/ds̃ = p*(dp - delta + dlse)
    # where p = exp(s̃ - lse) (normalized), dp = do·v, delta = rowsum(do*o),
    # and dlse is the cotangent of the lse output (zero when only the
    # attention output is differentiated).

    def bwd_dq_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                      delta_ref, dq_ref, acc_ref, *, causal, block_q,
                      block_k, nk):
        qb, kb = pl.program_id(1), pl.program_id(2)

        @pl.when(kb == 0)
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        @pl.when(block_live(offs_ref, qb, kb, block_q, block_k, causal))
        def _compute():
            q = q_ref[0].astype(jnp.float32)
            k = k_ref[0].astype(jnp.float32)
            v = v_ref[0].astype(jnp.float32)
            do = do_ref[0].astype(jnp.float32)
            s = dot(q, k, ((1,), (1,))) * offs_ref[3]
            mask = tile_mask(offs_ref, qb, kb, block_q, block_k, causal)
            p = jnp.where(mask, jnp.exp(s - lse_ref[0]), 0.0)
            dp = dot(do, v, ((1,), (1,)))
            ds = p * (dp - delta_ref[0]) * offs_ref[3]
            acc_ref[:] = acc_ref[:] + dot(ds, k, ((1,), (0,)))

        @pl.when(kb == nk - 1)
        def _fin():
            dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)

    def bwd_dkv_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                       delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                       causal, block_q, block_k, nq):
        kb, qb = pl.program_id(1), pl.program_id(2)

        @pl.when(qb == 0)
        def _init():
            dk_acc[:] = jnp.zeros_like(dk_acc)
            dv_acc[:] = jnp.zeros_like(dv_acc)

        @pl.when(block_live(offs_ref, qb, kb, block_q, block_k, causal))
        def _compute():
            q = q_ref[0].astype(jnp.float32)
            k = k_ref[0].astype(jnp.float32)
            v = v_ref[0].astype(jnp.float32)
            do = do_ref[0].astype(jnp.float32)
            s = dot(q, k, ((1,), (1,))) * offs_ref[3]
            mask = tile_mask(offs_ref, qb, kb, block_q, block_k, causal)
            p = jnp.where(mask, jnp.exp(s - lse_ref[0]), 0.0)
            dv_acc[:] = dv_acc[:] + dot(p, do, ((0,), (0,)))
            dp = dot(do, v, ((1,), (1,)))
            ds = p * (dp - delta_ref[0]) * offs_ref[3]
            dk_acc[:] = dk_acc[:] + dot(ds, q, ((0,), (0,)))

        @pl.when(qb == nq - 1)
        def _fin():
            dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    @functools.partial(jax.jit, static_argnums=(8, 9, 10))
    def bwd(q, k, v, o, lse, do, dlse, offs, causal, block_q, block_k):
        bh, tq, d = q.shape
        tk = k.shape[1]
        nq, nk = tq // block_q, tk // block_k
        # fold the lse cotangent into delta: ds = p*(dp - (delta - dlse))
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1, keepdims=True) - dlse

        dq = per_platform(functools.partial(
            pl.pallas_call,
            functools.partial(bwd_dq_kernel, causal=causal,
                              block_q=block_q, block_k=block_k, nk=nk),
            grid=(bh, nq, nk),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                vspec((1, block_q, d), lambda b, i, j: (b, i, 0)),
                vspec((1, block_k, d), lambda b, i, j: (b, j, 0)),
                vspec((1, block_k, d), lambda b, i, j: (b, j, 0)),
                vspec((1, block_q, d), lambda b, i, j: (b, i, 0)),
                vspec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
                vspec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            ],
            out_specs=vspec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            out_shape=jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        ), offs, q, k, v, do, lse, delta)

        dk, dv = per_platform(functools.partial(
            pl.pallas_call,
            functools.partial(bwd_dkv_kernel, causal=causal,
                              block_q=block_q, block_k=block_k, nq=nq),
            grid=(bh, nk, nq),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                vspec((1, block_q, d), lambda b, j, i: (b, i, 0)),
                vspec((1, block_k, d), lambda b, j, i: (b, j, 0)),
                vspec((1, block_k, d), lambda b, j, i: (b, j, 0)),
                vspec((1, block_q, d), lambda b, j, i: (b, i, 0)),
                vspec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
                vspec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
            ],
            out_specs=[
                vspec((1, block_k, d), lambda b, j, i: (b, j, 0)),
                vspec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, tk, d), k.dtype),
                jax.ShapeDtypeStruct((bh, tk, d), v.dtype),
            ],
            scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                            pltpu.VMEM((block_k, d), jnp.float32)],
        ), offs, q, k, v, do, lse, delta)
        return dq, dk, dv

    return fwd, bwd


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def _pad_t(x, block):
    pad = (-x.shape[2]) % block
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 3))
    return x


def _flatten(q, k, v, block_q, block_k):
    b, h, tq, d = q.shape
    qf = _pad_t(q, block_q).reshape(b * h, -1, d)
    kf = _pad_t(k, block_k).reshape(b * h, -1, d)
    vf = _pad_t(v, block_k).reshape(b * h, -1, d)
    return qf, kf, vf


def _flash_fwd(q, k, v, offs, causal, block_q, block_k):
    b, h, tq, d = q.shape
    fwd, _ = _kernels()
    qf, kf, vf = _flatten(q, k, v, block_q, block_k)
    o, lse = fwd(qf, kf, vf, offs, causal, block_q, block_k)
    o = o[:, :tq].reshape(b, h, tq, d)
    lse = lse[:, :tq, 0].reshape(b, h, tq)
    return (o, lse), (q, k, v, offs, o, lse)


def _flash_bwd(causal, block_q, block_k, res, cot):
    q, k, v, offs, o, lse = res
    do, dlse = cot
    b, h, tq, d = q.shape
    tk = k.shape[2]
    _, bwd = _kernels()
    qf, kf, vf = _flatten(q, k, v, block_q, block_k)
    of = _pad_t(o, block_q).reshape(b * h, -1, d)
    dof = _pad_t(do, block_q).reshape(b * h, -1, d)
    lsef = _pad_t(lse[..., None], block_q).reshape(b * h, -1, 1)
    dlsef = _pad_t(dlse.astype(jnp.float32)[..., None],
                   block_q).reshape(b * h, -1, 1)
    dq, dk, dv = bwd(qf, kf, vf, of, lsef, dof, dlsef, offs, causal,
                     block_q, block_k)
    dq = dq[:, :tq].reshape(b, h, tq, d).astype(q.dtype)
    dk = dk[:, :tk].reshape(b, h, tk, d).astype(k.dtype)
    dv = dv[:, :tk].reshape(b, h, tk, d).astype(v.dtype)
    return dq, dk, dv, jnp.zeros_like(offs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_with_lse(q, k, v, offs, causal, block_q, block_k):
    return _flash_fwd(q, k, v, offs, causal, block_q, block_k)[0]


_flash_with_lse.defvjp(_flash_fwd, _flash_bwd)


def _max_block(d, dtype):
    """Largest block edge whose (edge, edge) tile pair compiles inside
    Mosaic's 16 MiB scoped-VMEM limit, forward and backward, for rows of
    ``d`` elements of ``dtype`` — read off AOT compiles against v5e
    (tests/test_kernels_compile_v5e.py keeps the table honest): the f32
    score-tile temporaries cost ~10 bytes per (q, k) pair, the rest is
    the double-buffered q/k/v/do blocks."""
    row_bytes = d * jnp.dtype(dtype).itemsize
    if row_bytes <= 512:        # bf16 d<=256, f32 d<=128
        return 1024
    if row_bytes <= 2048:       # up to f32 d=512
        return 512
    raise MXNetError(
        "flash_attention: head dim %d in %s (%d bytes a row) has no block "
        "size known to fit the %d MiB scoped-VMEM limit; use the XLA "
        "attention path" % (d, jnp.dtype(dtype).name, row_bytes,
                            SCOPED_VMEM_LIMIT >> 20))


def _prep(q, k, v, causal, scale, q_offset, k_offset, block_q, block_k):
    d = q.shape[-1]
    limit = _max_block(d, q.dtype)
    if block_q is None:
        block_q = min(512, limit)
    if block_k is None:
        block_k = limit
    if max(block_q, block_k) > limit:
        raise MXNetError(
            "flash_attention: blocks (%d, %d) for head dim %d in %s exceed "
            "the %d MiB scoped-VMEM limit (Mosaic refuses e.g. (1024, "
            "2048) at 19-25 MiB); the largest block edge that compiles "
            "is %d" % (block_q, block_k, d, jnp.dtype(q.dtype).name,
                       SCOPED_VMEM_LIMIT >> 20, limit))
    if scale is None:
        scale = 1.0 / float(d) ** 0.5
    if not isinstance(scale, (int, float)):
        # Traced scale: fold it into Q (s = (q*scale)·k) so its gradient
        # flows through ordinary AD of the multiply — the custom VJP
        # returns zeros for the offs operand, which would otherwise
        # silently drop d(loss)/d(scale).
        q = q * jnp.asarray(scale).astype(q.dtype)
        scale = 1.0

    def blk(req, t):  # round up to the 8-sublane tile multiple
        return int(min(req, -(-max(t, 1) // 8) * 8))

    tq, tk = q.shape[2], k.shape[2]
    block_q = blk(block_q, tq)
    block_k = blk(block_k, tk)
    offs = jnp.stack([jnp.asarray(q_offset, jnp.float32),
                      jnp.asarray(k_offset, jnp.float32),
                      jnp.asarray(tk, jnp.float32),
                      jnp.asarray(scale, jnp.float32)])
    return q, offs, bool(causal), block_q, block_k


def flash_attention(q, k, v, causal=False, scale=None, q_offset=0,
                    k_offset=0, block_q=None, block_k=None):
    """Flash attention via Pallas TPU kernels. q,k,v: [B, H, T, D].

    ``q_offset``/``k_offset`` are the global sequence positions of the
    first local Q/K row (static ints or traced scalars) — causal masks
    stay correct when T is a shard of a longer sequence (ring/Ulysses
    sequence parallelism). ``scale`` may also be traced. Differentiable
    (custom VJP, flash-attention-2 style recompute backward); one HBM
    pass per tensor per kernel. The default blocks are (512, 1024)
    shrunk to what :func:`_max_block` says fits VMEM for this head dim
    and dtype; an explicit pair over that limit raises.
    """
    q, offs, causal, block_q, block_k = _prep(q, k, v, causal, scale,
                                              q_offset, k_offset,
                                              block_q, block_k)
    # dropping lse via [0] makes AD deliver a zero dlse cotangent — no
    # separate VJP wrapper needed, and the kernel computes lse anyway
    return _flash_with_lse(q, k, v, offs, causal, block_q, block_k)[0]


def flash_attention_with_lse(q, k, v, causal=False, scale=None, q_offset=0,
                             k_offset=0, block_q=None, block_k=None):
    """Like :func:`flash_attention` but also returns the per-row
    log-sum-exp ``lse`` [B, H, T] (float32; ``-1e30`` for fully-masked
    rows). Partial attention results over disjoint K/V shards combine
    exactly via ``lse' = logaddexp(lse1, lse2); o' = o1*exp(lse1 - lse')
    + o2*exp(lse2 - lse')`` — the merge rule ring attention
    (impl="flash") applies across ppermute steps. Both outputs are
    differentiable."""
    q, offs, causal, block_q, block_k = _prep(q, k, v, causal, scale,
                                              q_offset, k_offset,
                                              block_q, block_k)
    return _flash_with_lse(q, k, v, offs, causal, block_q, block_k)


def flash_attention_reference(q, k, v, causal=False, scale=None,
                              q_offset=0, k_offset=0):
    """Pure-XLA reference (used in tests to cross-check the kernels)."""
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / float(d) ** 0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        qi = q_offset + jnp.arange(q.shape[2])
        ki = k_offset + jnp.arange(k.shape[2])
        mask = qi[:, None] >= ki[None, :]
        s = jnp.where(mask[None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


# ---------------------------------------------------------------------------
# one-token decode attention over the packed [B, S, D] cache
# ---------------------------------------------------------------------------

# Bytes of K (and as many of V) a whole block holds: 128 rows of BLOOM-1b7's
# 4 KiB, 256 of K-EXAONE's 2 KiB. Every grid step is a live block
# (:func:`live_blocks`), so the block only has to be long enough for a step's
# DMA to cover the step's own cost (timed on the v5e, PERF.md PR 35).
DECODE_BLOCK_BYTES = 512 * 1024
# A slot's rows past its last whole block are read in tail blocks, this many
# to a block: a quarter of a block is the most a slot fetches past ``pos``.
TAIL_BLOCKS = 4
_FIRST, _LAST, _TAIL = 1, 2, 4      # what a grid step is, in ``flags``


def tail_rows(block_s):
    """The rows of a tail block: ``block_s / TAIL_BLOCKS`` where that is whole
    16-row tiles, else the block itself (no finer tail)."""
    rows = block_s // TAIL_BLOCKS
    return rows if rows and rows % 16 == 0 else block_s


def live_blocks(pos, S, block_s, tail_s):
    """The grid of both decode kernels: the blocks that hold a live row,
    slot after slot. ``pos [B]`` int32; slot ``b`` has ``n = clip(pos[b], 0,
    S - 1) + 1`` live rows (a full cache's position, already inside it, or
    all of a ring once it has turned), read as its ``n // block_s`` whole
    blocks and then, in blocks of ``tail_s`` rows (which divides ``block_s``),
    what is left: at least one step, and at most ``tail_s - 1`` rows past
    ``pos``.

    Returns ``(slot, block, tail, flags, total)``: four int32 tables of ``B x
    (S // block_s - 1 + block_s // tail_s) + 1`` entries and the number of
    grid steps. Step ``i`` belongs to slot ``slot[i]``; ``flags[i]`` says
    whether it is the slot's first (1), its last (2) and a tail block (4).
    ``block[i]`` and ``tail[i]`` name the whole block and the tail block the
    step's two windows on the cache show, as ``slot x blocks a slot + block``:
    the one the step works on, and for the other window the last one a step
    before it worked on, so a window moves (and fetches) only when it is
    used. Entries from ``total`` on repeat step ``total - 1``, so the entry a
    pipeline reads one step ahead of the last lies inside the arrays and
    names blocks that are already there.

    Comparisons against the running sum only: no gather, no sort, no
    ``while`` in the compiled step (``jnp.searchsorted``'s default lowers
    to one), and one expression for every layer of a program that shares
    ``pos``, ``S`` and the blocks, which XLA merges."""
    p = pos.astype(jnp.int32).reshape(-1)
    B = p.shape[0]
    nblk, ntail, ratio = S // block_s, S // tail_s, block_s // tail_s
    rows = jnp.clip(p, 0, S - 1) + 1
    whole = rows // block_s
    tails = (rows - whole * block_s + tail_s - 1) // tail_s
    steps = whole + tails
    before = jnp.arange(B)[:, None] <= jnp.arange(B)[None, :]
    ends = jnp.sum(jnp.where(before, steps[:, None], 0), axis=0)  # cumsum
    starts = ends - steps
    total = jnp.sum(steps)
    i = jnp.minimum(jnp.arange(B * (nblk - 1 + ratio) + 1, dtype=jnp.int32),
                    total - 1)[:, None]
    # [steps, B]: the slots that have begun by step i, those wholly behind it
    begun, done = starts[None, :] <= i, ends[None, :] <= i

    def of_slot(x):     # x[slot[i]], without a gather
        return jnp.sum(jnp.where(begun & ~done, x[None, :], 0), axis=1)

    def newest(x, seen):    # the largest x of the slots seen (x grows with b)
        return jnp.max(jnp.where(seen, x[None, :], 0), axis=1)

    slot = jnp.sum(done, axis=1, dtype=jnp.int32)
    k = i[:, 0] - of_slot(starts)           # the step's place in its slot
    w = of_slot(whole)
    in_tail = k >= w
    ids = jnp.arange(B, dtype=jnp.int32)
    last_block = jnp.where(whole > 0, ids * nblk + whole - 1, 0)
    last_tail = jnp.where(tails > 0,
                          ids * ntail + whole * ratio + tails - 1, 0)
    block = jnp.where(in_tail, newest(last_block, begun), slot * nblk + k)
    tail = jnp.where(in_tail, slot * ntail + w * (ratio - 1) + k,
                     newest(last_tail, done))
    flags = (_FIRST * (k == 0) + _LAST * (i[:, 0] == of_slot(ends) - 1)
             + _TAIL * in_tail)
    return (slot, block.astype(jnp.int32), tail.astype(jnp.int32),
            flags.astype(jnp.int32), total)


@functools.cache
def _decode_call():
    """Build the decode kernel's pallas_call wrapper on first use."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from .pallas_util import per_platform

    def kernel(slot_ref, block_ref, tail_ref, flag_ref, pos_ref, q_ref,
               slope_ref, k_ref, v_ref, kt_ref, vt_ref, o_ref, qbd_ref,
               acc_ref, m_ref, l_ref, *, hd, S, scale, group, window):
        i = pl.program_id(0)
        flags = flag_ref[i]
        p = pos_ref[slot_ref[i]]
        rows, d = qbd_ref.shape

        def own_lanes():
            """[rows, D]: True where a lane belongs to its row's key/value
            head (``group`` query heads share one)."""
            head = jax.lax.broadcasted_iota(jnp.int32, (rows, d), 0)
            if group > 1:
                head = head // group
            lane = jax.lax.broadcasted_iota(jnp.int32, (rows, d), 1)
            return (lane >= head * hd) & (lane < (head + 1) * hd)

        @pl.when(flags & _FIRST != 0)
        def _init():
            # the query as a block-diagonal [heads, D] matrix: row h
            # holds head h's 128-lane slab and zeros elsewhere, so ONE
            # matrix product against the cache block as it is stored
            # gives every head's score row
            # (selected in float32: a mask over packed bfloat16 rows is a
            # relayout Mosaic refuses)
            if group == 1:
                q = jnp.broadcast_to(q_ref[0].astype(jnp.float32), (rows, d))
            else:       # [heads, hd] rows, each laid over every slab
                q = jnp.concatenate(
                    [q_ref[0].astype(jnp.float32)] * (d // hd), axis=1)
            qbd_ref[:] = jnp.where(own_lanes(), q, 0.0).astype(qbd_ref.dtype)
            acc_ref[:] = jnp.zeros_like(acc_ref)
            m_ref[:] = jnp.full_like(m_ref, _NEG)
            l_ref[:] = jnp.zeros_like(l_ref)

        def attend(k_ref, v_ref, index):
            """One live block of the slot's rows, whole or tail: block
            ``index`` of the slot's, as long as its window on the cache."""
            cdt = qbd_ref.dtype
            s = jax.lax.dot_general(
                qbd_ref[:], k_ref[0].astype(cdt), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            at = index * k_ref.shape[1] + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            if window:
                # row r of a ring holds the newest position that is r
                # modulo its length: how far back from pos that lies
                at_p = jax.lax.rem(p, S)
                dist = jnp.where(at <= at_p, at_p - at, at_p - at + S)
                live = (dist < window) & (dist <= p)
            else:
                dist = p - at
                live = at <= p
            s = s - slope_ref[:] * dist.astype(jnp.float32)
            s = jnp.where(live, s, _NEG)
            m_prev = m_ref[:]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            # a live block holds a live row (its first without a window;
            # in a ring, row pos mod ring or block 0's row 0), so m_new is
            # a real score once the live block has been seen and a masked
            # column's exp underflows to exactly 0
            pr = jnp.where(live, jnp.exp(s - m_new), 0.0) if window \
                else jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[:] = l_ref[:] * corr + jnp.sum(pr, axis=-1, keepdims=True)
            m_ref[:] = m_new
            acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
                pr.astype(cdt), v_ref[0].astype(cdt),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        # every grid step is a live block (:func:`live_blocks`)
        @pl.when(flags & _TAIL == 0)
        def _whole():
            attend(k_ref, v_ref, block_ref[i] % (S // k_ref.shape[1]))

        @pl.when(flags & _TAIL != 0)
        def _tail():
            attend(kt_ref, vt_ref, tail_ref[i] % (S // kt_ref.shape[1]))

        @pl.when(flags & _LAST != 0)
        def _fin():
            # row h of acc is head h's weights times ALL of V's columns;
            # its own slab is the head's output
            out = jnp.where(own_lanes(), acc_ref[:] / l_ref[:], 0.0)
            if group == 1:
                o_ref[0] = jnp.sum(out, axis=0,
                                   keepdims=True).astype(o_ref.dtype)
            else:       # fold the slabs: all but a row's own are zero
                o_ref[0] = sum(out[:, c * hd:(c + 1) * hd]
                               for c in range(d // hd)).astype(o_ref.dtype)

    @functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
    def call(q, k_cache, v_cache, pos, slopes, hd, block_s, group=1,
             window=0):
        B, S, D = k_cache.shape
        rows = slopes.shape[0]
        tail_s = tail_rows(block_s)
        *tables, total = live_blocks(pos, S, block_s, tail_s)
        # grouped: the query and the output ride as [B, heads, hd], a
        # head a row, which is how the kernel's matrices want them
        q_block = (1, 1, D) if group == 1 else (1, rows, hd)
        nblk, ntail = S // block_s, S // tail_s

        def slot_map(i, slot_ref, *_):
            return (slot_ref[i], 0, 0)

        # the cache's two windows, of whole blocks and of tail blocks
        def block_map(i, _slot_ref, block_ref, *_):
            return (block_ref[i] // nblk, block_ref[i] % nblk, 0)

        def tail_map(i, _slot_ref, _block_ref, tail_ref, *_):
            return (tail_ref[i] // ntail, tail_ref[i] % ntail, 0)

        kern = functools.partial(kernel, hd=hd, S=S, scale=hd ** -0.5,
                                 group=group, window=window)
        return per_platform(functools.partial(
            pl.pallas_call,
            kern,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=5,
                grid=(total,),
                in_specs=[
                    pl.BlockSpec(q_block, slot_map),
                    pl.BlockSpec((rows, 1), lambda i, *_: (0, 0)),
                    pl.BlockSpec((1, block_s, D), block_map),
                    pl.BlockSpec((1, block_s, D), block_map),
                    pl.BlockSpec((1, tail_s, D), tail_map),
                    pl.BlockSpec((1, tail_s, D), tail_map),
                ],
                out_specs=pl.BlockSpec(q_block, slot_map),
                scratch_shapes=[
                    pltpu.VMEM((rows, D), q.dtype),
                    pltpu.VMEM((rows, D), jnp.float32),
                    pltpu.VMEM((rows, 1), jnp.float32),
                    pltpu.VMEM((rows, 1), jnp.float32),
                ]),
            out_shape=jax.ShapeDtypeStruct((B,) + q_block[1:], q.dtype),
            name="decode_attention",
        ), *tables, pos, q, slopes, k_cache, v_cache, k_cache, v_cache)

    return call


WRITE_ROWS = 16     # rows of the block a row write carries (a bf16 tile)


@functools.cache
def _write_call():
    """Build the row-write kernel's pallas_call wrapper on first use."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from .pallas_util import per_platform

    def kernel(at_ref, row_ref, cache_ref, out_ref):
        r = at_ref[pl.program_id(0)] % WRITE_ROWS
        shape = out_ref.shape[1:]
        # selected in float32, as the decode kernel's query matrix is
        old = cache_ref[0].astype(jnp.float32)
        new = jnp.broadcast_to(row_ref[0].astype(jnp.float32), shape)
        here = jax.lax.broadcasted_iota(jnp.int32, shape, 0) == r
        out_ref[0] = jnp.where(here, new, old).astype(out_ref.dtype)

    @jax.jit
    def call(cache, rows, at):
        B, _S, D = cache.shape

        def block(b, at_ref):
            return (b, at_ref[b] // WRITE_ROWS, 0)

        return per_platform(functools.partial(
            pl.pallas_call,
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(B,),
                in_specs=[pl.BlockSpec((1, 1, D), lambda b, at_ref: (b, 0, 0)),
                          pl.BlockSpec((1, WRITE_ROWS, D), block)],
                out_specs=pl.BlockSpec((1, WRITE_ROWS, D), block)),
            out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
            input_output_aliases={2: 0},       # the cache, in place
            name="cache_write_row",
        ), at, rows.astype(cache.dtype), cache)

    return call


def cache_write_row(cache, rows, at):
    """``cache [B, S, D]`` with row ``at[b]`` of slot ``b`` replaced by
    ``rows[b, 0]``, in place when the cache is donated: one kernel over the
    slots, each moving the block of ``WRITE_ROWS`` rows that holds its row.
    The row is selected in float32 and cast back, which is exact for
    bfloat16 and float32 caches. XLA's own scatter of one row a slot is a
    ``while`` of a trip a slot (4.3 us a trip on the v5e, and several trace
    events each: 64 slots and 16 caches of them fill a profile before its
    window opens, PERF.md PR 30; 16 slots and 48 caches are 3.3 ms of a 9.3
    ms decode program, PR 33). Both halves of ``ops.nn.cached_attention``
    write a decode step's rows through it (``ops.nn._decode_step``).
    Needs ``S`` in whole blocks; ``at`` inside the cache."""
    if cache.shape[1] % WRITE_ROWS:
        raise MXNetError("cache_write_row: %d rows are not whole blocks of "
                         "%d" % (cache.shape[1], WRITE_ROWS))
    return _write_call()(cache, rows, at.astype(jnp.int32).reshape(-1))


def decode_block(S, D, cache_dtype, block_s=None):
    """The rows of cache one grid step of :func:`decode_attention`
    reads, or None when no block divides ``S``: the power of two whose rows
    fill ``DECODE_BLOCK_BYTES`` (the whole cache where it is shorter). An
    explicit ``block_s`` whose K and V blocks, double-buffered, pass half of
    the scoped-VMEM limit raises (512 rows of 4 KiB compile, 1024 Mosaic
    refuses)."""
    row_bytes = D * jnp.dtype(cache_dtype).itemsize
    if block_s is None:
        rows = max(DECODE_BLOCK_BYTES // row_bytes, 8)
        block_s = min(1 << (rows.bit_length() - 1), S)
    elif 4 * block_s * row_bytes > SCOPED_VMEM_LIMIT // 2:
        raise MXNetError(
            "decode_attention: a block of %d rows of %d bytes, K and V "
            "double-buffered (%d MiB), does not fit the %d MiB "
            "scoped-VMEM limit" % (block_s, row_bytes,
                                   (4 * block_s * row_bytes) >> 20,
                                   SCOPED_VMEM_LIMIT >> 20))
    if S % block_s or block_s % 8:
        return None
    return block_s


def decode_attention(q, k_cache, v_cache, pos, num_heads, alibi=False,
                     block_s=None, num_kv_heads=None, window=0):
    """One query row a sample against the packed cache where it lies.

    ``q [B, 1, heads x hd]``, caches ``[B, S, kv_heads x hd]`` (row
    ``pos[b]`` already written), ``pos [B]`` int32. Slot ``b`` attends
    rows ``s <= pos[b]`` of its own cache; ``alibi`` subtracts
    ``2^(-8(h+1)/H) * (pos[b] - s)`` from head ``h``'s scores. Returns
    ``[B, 1, heads x hd]`` in ``q``'s dtype.

    ``num_kv_heads`` under ``num_heads``: query head ``h`` attends
    key/value head ``h // (heads / kv_heads)``; the group's query heads
    are rows of one matrix against the shared column block. ``window >
    0``: the cache is a ring (position ``s`` in row ``s mod S``, the row
    of ``pos[b]`` already written) and a slot attends the ring's live rows
    within ``window`` positions of ``pos[b]``.

    The cache is read in the layout it is stored in: a grid step is one
    block of one slot's rows, a block holds ALL heads' columns, and the
    heads are kept apart by a block-diagonal query matrix, not by
    re-tiling K and V to heads-minor. The grid is ONE dimension over the
    live blocks of all slots (:func:`live_blocks`: its length and the slot
    and block of each step are reckoned from ``pos`` and scalar-prefetched),
    so a block past a slot's live length is no grid step; the rows past a
    slot's last whole block are read in tail blocks of a quarter of a block
    (:func:`tail_rows`), through a second, shorter window on the same cache,
    so under a quarter of a block is fetched past ``pos``.
    Scores, the running maximum and sum and the output accumulate in
    float32. Needs a head of whole 128-lane slabs and ``block_s``
    dividing ``S``; forward only (``ops.nn.cached_attention`` gives the
    multi-head step, row write and all, the dense formula's gradient;
    nothing differentiates a grouped one)."""
    B, S, D = k_cache.shape
    H = int(num_heads)
    K = int(num_kv_heads or H)
    hd = D // K
    blk = decode_block(S, D, k_cache.dtype, block_s)
    if hd % 128 or blk is None or H % K or (K != H and H % 8):
        raise MXNetError(
            "decode_attention: head dim %d must be a multiple of 128, the "
            "block must divide the cache length %d, and %d query heads "
            "over %d key/value heads must group into whole rows of 8"
            % (hd, S, H, K))
    rows = -(-H // 8) * 8
    slopes = [2.0 ** (-8.0 * (i + 1) / H) if alibi and i < H else 0.0
              for i in range(rows)]
    p = pos.astype(jnp.int32).reshape(-1)
    if not window:
        # a slot the scheduler left idle may count past the cache: it holds
        # nothing anyone reads, only keep its block index inside the array
        p = jnp.clip(p, 0, S - 1)
    slopes = jnp.asarray(slopes, jnp.float32)[:, None]
    if K == H:
        return _decode_call()(q, k_cache, v_cache, p, slopes, hd, blk, 1,
                              int(window))
    out = _decode_call()(q.reshape(B, H, hd), k_cache, v_cache, p, slopes,
                         hd, blk, H // K, int(window))
    return out.reshape(B, 1, H * hd)


# ---------------------------------------------------------------------------
# one-token decode attention over a LATENT cache: one [c_kv ; k_rope] row a
# position, shared by all heads, key (all columns) and value (the first
# ``rank``) at once
# ---------------------------------------------------------------------------

# Bytes of cache rows a grid step reads, as the power of two of rows that fits:
# 512 rows of 640 columns (bfloat16), 1024 of 384. 32 query rows a slot keep a
# step bound by the MXU's tile loads, not by its rows' bytes: at 640 columns
# 256 rows take as long a row and twice the steps, and tail blocks of a
# quarter, which :func:`decode_attention` gains by, cost Xing4.0's cell 2.4%
# (timed on the v5e, PERF.md PR 35); at 384 columns, 96 slots of 3,600 live
# rows, six calls take 3.59 ms at 512 rows, 2.84 at 1024 and 2.90 at 2048,
# and at 640 columns 1024 rows (2.30 ms) gain nothing on 512 (2.25; PR 36)
LATENT_BLOCK_BYTES = 768 * 1024


@functools.cache
def _latent_call():
    """Build the latent decode kernel's pallas_call wrapper on first use."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from .pallas_util import per_platform

    def kernel(slot_ref, block_ref, flag_ref, pos_ref, q_ref, c_ref, o_ref,
               acc_ref, m_ref, l_ref, *, scale, rank):
        i = pl.program_id(0)
        flags = flag_ref[i]
        p = pos_ref[slot_ref[i]]

        @pl.when(flags & _FIRST != 0)
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)
            m_ref[:] = jnp.full_like(m_ref, _NEG)
            l_ref[:] = jnp.zeros_like(l_ref)

        # every grid step is a live block (:func:`live_blocks`), and it
        # serves twice from one fetch: every column is the key of all
        # heads, the first ``rank`` are their value
        rows = c_ref[0]
        s = jax.lax.dot_general(
            q_ref[0], rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        at = block_ref[i] * rows.shape[0] + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(at <= p, s, _NEG)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # the first row of a live block is live, so m_new is a real
        # score and a masked column's exp underflows to exactly 0
        pr = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * corr + jnp.sum(pr, axis=-1, keepdims=True)
        m_ref[:] = m_new
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            pr.astype(rows.dtype), rows[:, :rank],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(flags & _LAST != 0)
        def _fin():
            o_ref[0] = (acc_ref[:] / l_ref[:]).astype(o_ref.dtype)

    @functools.partial(jax.jit, static_argnums=(3, 4, 5))
    def call(q, cache, pos, rank, block_s, scale):
        B, S, W = cache.shape
        H = q.shape[1]
        # no finer tail (LATENT_BLOCK_BYTES says why): a slot's last block is a
        # block like the others, and the one window shows whichever it is
        slot, block, tail, flags, total = live_blocks(pos, S, block_s,
                                                      block_s)
        block = jnp.where(flags & _TAIL != 0, tail, block) % (S // block_s)

        def slot_map(i, slot_ref, *_):
            return (slot_ref[i], 0, 0)

        def rows_map(i, slot_ref, block_ref, *_):
            return (slot_ref[i], block_ref[i], 0)

        kern = functools.partial(kernel, scale=scale, rank=rank)
        return per_platform(functools.partial(
            pl.pallas_call,
            kern,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4,
                grid=(total,),
                in_specs=[
                    pl.BlockSpec((1, H, W), slot_map),
                    pl.BlockSpec((1, block_s, W), rows_map),
                ],
                out_specs=pl.BlockSpec((1, H, rank), slot_map),
                scratch_shapes=[
                    pltpu.VMEM((H, rank), jnp.float32),
                    pltpu.VMEM((H, 1), jnp.float32),
                    pltpu.VMEM((H, 1), jnp.float32),
                ]),
            out_shape=jax.ShapeDtypeStruct((B, H, rank), q.dtype),
            name="latent_decode_attention",
        ), slot, block, flags, pos, q, cache)

    return call


def latent_block(S, row_bytes, block_s=None):
    """The rows of cache one grid step of :func:`latent_decode_attention`
    reads, or None when no block of whole 16-row tiles divides ``S``: the
    power of two of rows of ``row_bytes`` that fills ``LATENT_BLOCK_BYTES``,
    halved until it divides ``S``."""
    if block_s is None:
        rows = max(LATENT_BLOCK_BYTES // int(row_bytes), 16)
        block_s = min(1 << (rows.bit_length() - 1), S)
        while block_s > 16 and S % block_s:
            block_s //= 2
    if S % block_s or block_s % 16:
        return None
    return block_s


def latent_decode_attention(q, cache, pos, rank, scale, block_s=None):
    """One query row a head and sample against a latent cache where it lies.

    ``q [B, heads, rank + rope]``: head ``i``'s query with the key's
    expansion absorbed, ``[W_uk,i^T q_nope,i ; q_rope,i]``; ``cache [B, S,
    rank + rope]`` holds ``[c_s ; k_rope(s)]`` in row ``s`` (row ``pos[b]``
    already written); ``pos [B]`` int32. Slot ``b`` attends rows ``s <=
    pos[b]``: ``score_i(s) = scale * q_i . cache[b, s]``, float32 softmax,
    and returns ``o_lat [B, heads, rank]``, ``sum_s att_i(s) c_s``, in
    ``q``'s dtype; the caller applies ``W_uv,i``.

    A grid step is one block of one slot's rows. A block is fetched ONCE and
    serves as the key of every head (all its columns) and as their value (the
    first ``rank``), so a position costs ``rank + rope`` values of traffic
    whatever the number of heads. The grid is ONE dimension over the live
    blocks of all slots (:func:`live_blocks`, as :func:`decode_attention`'s,
    with no finer tail): a block past a slot's live length is no grid step.
    Scores, the
    running maximum and sum and the output accumulate in float32. Needs
    ``rank`` in whole 128-lane slabs and a block of whole 16-row tiles that
    divides ``S``; forward only."""
    B, S, W = cache.shape
    blk = latent_block(S, W * jnp.dtype(cache.dtype).itemsize, block_s)
    if int(rank) % 128 or W <= int(rank) or blk is None or q.shape[2] != W:
        raise MXNetError(
            "latent_decode_attention: the rank %d must be whole 128-lane "
            "slabs and under the row's %d columns (the query's: %d), and a "
            "block of whole 16-row tiles must divide the cache length %d"
            % (rank, W, q.shape[2], S))
    # a slot the scheduler left idle may count past the cache: it holds
    # nothing anyone reads, only keep its block index inside the array
    p = jnp.clip(pos.astype(jnp.int32).reshape(-1), 0, S - 1)
    return _latent_call()(q.astype(cache.dtype), cache, p, int(rank), blk,
                          float(scale))


# ---------------------------------------------------------------------------
# a chunk's causal attention over expanded latent rows (a prefill, PR 36):
# the flash tiling, forward only, on operands as they are stored; queries,
# keys and values ride as [B, T, heads x d], the layout the projections give
# them, so nothing is transposed and no [heads, T, S] scores exist.
# ``ops.nn.latent_attention``'s chunk path takes it where query, key and
# value heads are one width of whole slabs. (Described here and not in the
# module's docstring, and absent from ``__all__``: the kernels above carry
# their source lines into their compiled bodies, and lines that stay put
# keep the accepted programs' compile-cache keys.)
# ---------------------------------------------------------------------------

PREFILL_BLOCK_Q, PREFILL_BLOCK_K = 1024, 1024


def prefill_block(n, most):
    """The largest power of two up to ``most`` that divides ``n`` rows, or
    None under a tile of 8."""
    block = most
    while block >= 8 and n % block:
        block //= 2
    return block if block >= 8 else None


@functools.cache
def _prefill_call():
    """Build the prefill kernel's pallas_call wrapper on first use."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from .pallas_util import per_platform

    def last_live(i, lead, kv_len, block_q, block_k):
        """The last key block a block of queries sees: the one that holds
        its last row's own position, and no block past the live keys."""
        return jnp.maximum(jnp.minimum(
            (i * block_q + block_q - 1 + lead) // block_k,
            (kv_len - 1) // block_k), 0)

    def kernel(lead_ref, len_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
               acc_ref, m_ref, l_ref, *, scale, block_q, block_k, nk):
        b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
        lead, kv_len = lead_ref[b], len_ref[b]

        @pl.when(j == 0)
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)
            m_ref[:] = jnp.full_like(m_ref, _NEG)
            l_ref[:] = jnp.zeros_like(l_ref)

        def attend(masked):
            k, v = k_ref[0], v_ref[0]
            s = jax.lax.dot_general(
                q_ref[0], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if masked:
                qi = i * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 0)
                ki = j * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 1)
                seen = (ki <= qi + lead) & (ki < kv_len)
                s = jnp.where(seen, s, _NEG)
            m_prev = m_ref[:]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            pr = jnp.exp(s - m_new)
            if masked:      # a row that has seen no key yet: exp(0) = 1
                pr = jnp.where(seen, pr, 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_ref[:] = l_ref[:] * corr + jnp.sum(pr, axis=-1, keepdims=True)
            m_ref[:] = m_new
            acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
                pr.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        # a block above the diagonal or past the live keys is no work (and
        # no fetch: its index is the last live block's); one wholly under
        # both needs no mask
        live = (j <= last_live(i, lead, kv_len, block_q, block_k)) \
            & (kv_len > 0)
        whole = ((j + 1) * block_k - 1 <= i * block_q + lead) \
            & ((j + 1) * block_k <= kv_len)

        @pl.when(live & whole)
        def _whole():
            attend(False)

        @pl.when(live & ~whole)
        def _edge():
            attend(True)

        @pl.when(j == nk - 1)
        def _fin():
            l = l_ref[:]
            l_safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
            # a row that saw no key keeps lse = _NEG, so a merge ignores it
            lse_ref[0, 0] = jnp.where(l == 0.0, _NEG,
                                      m_ref[:] + jnp.log(l_safe))

    @functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
    def call(q, k, v, lead, kv_len, heads, scale, block_q, block_k):
        B, T, D = q.shape
        d = D // heads
        nq, nk = T // block_q, k.shape[1] // block_k

        def q_map(b, h, i, j, *_):
            return (b, i, h)

        def k_map(b, h, i, j, lead_ref, len_ref):
            return (b, jnp.minimum(j, last_live(
                i, lead_ref[b], len_ref[b], block_q, block_k)), h)

        kern = functools.partial(kernel, scale=scale, block_q=block_q,
                                 block_k=block_k, nk=nk)
        return per_platform(functools.partial(
            pl.pallas_call,
            kern,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(B, heads, nq, nk),
                in_specs=[
                    pl.BlockSpec((1, block_q, d), q_map),
                    pl.BlockSpec((1, block_k, d), k_map),
                    pl.BlockSpec((1, block_k, d), k_map),
                ],
                out_specs=[
                    pl.BlockSpec((1, block_q, d), q_map),
                    pl.BlockSpec((1, 1, block_q, 1),
                                 lambda b, h, i, j, *_: (b, h, i, 0)),
                ],
                scratch_shapes=[
                    pltpu.VMEM((block_q, d), jnp.float32),
                    pltpu.VMEM((block_q, 1), jnp.float32),
                    pltpu.VMEM((block_q, 1), jnp.float32),
                ]),
            out_shape=[
                jax.ShapeDtypeStruct((B, T, D), q.dtype),
                jax.ShapeDtypeStruct((B, heads, T, 1), jnp.float32),
            ],
            name="latent_prefill_attention",
        ), lead, kv_len, q, k, v)

    return call


def latent_prefill_attention(q, k, v, lead, kv_len, num_heads, scale,
                             block_q=None, block_k=None):
    """A chunk's queries against one stretch of expanded keys and values,
    tile by tile with a running softmax: no ``[heads, T, S]`` scores.

    ``q [B, T, heads x d]``, ``k, v [B, N, heads x d]`` (head ``h`` in
    columns ``h d .. (h + 1) d - 1``, the layout the projections write, so
    nothing is transposed); ``lead [B]``, ``kv_len [B]`` int32: sample
    ``b``'s query row ``t`` sees key row ``n`` where ``n <= t + lead[b]``
    and ``n < kv_len[b]`` (a chunk against its own rows: ``lead`` 0,
    ``kv_len`` ``T``; against rows that all lie before it: ``lead >= N``
    and ``kv_len`` the live ones). Returns ``(out [B, T, heads x d], lse
    [B, heads, T, 1])``: the softmax-weighted values in ``q``'s dtype and
    the log of each row's sum of ``exp(score)`` in float32 (``-1e30`` for a
    row that saw no key), by which two stretches' results merge: ``lse' =
    logaddexp(lse1, lse2)``, ``o' = o1 exp(lse1 - lse') + o2 exp(lse2 -
    lse')``.

    Products take their operands as they are stored (bfloat16 on the MXU)
    and accumulate in float32, as do the running maximum and sum. A key
    block above a query block's diagonal or past ``kv_len`` is neither
    computed nor fetched (its index is the last live block's). Needs ``d``
    in whole 128-lane slabs and blocks that divide ``T`` and ``N``
    (:func:`prefill_block`); forward only. In a device trace the kernel is
    ``latent_prefill_attention``."""
    B, T, D = q.shape
    H = int(num_heads)
    bq = block_q or prefill_block(T, PREFILL_BLOCK_Q)
    bk = block_k or prefill_block(k.shape[1], PREFILL_BLOCK_K)
    if (D % H or (D // H) % 128 or not bq or not bk or T % bq
            or k.shape[1] % bk or k.shape != v.shape or k.shape[2] != D):
        raise MXNetError(
            "latent_prefill_attention: heads of %s columns must be whole "
            "128-lane slabs, alike for queries, keys and values, and blocks "
            "of 8 rows or more must divide %d query and %d key rows"
            % (D / H, T, k.shape[1]))
    def as_i32(x):      # a scalar or ``[B]``, a sample each
        return jnp.broadcast_to(jnp.asarray(x, jnp.int32).reshape(-1), (B,))

    return _prefill_call()(q, k, v, as_i32(lead), as_i32(kv_len), H,
                           float(scale), int(bq), int(bk))
