"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

Long-context scaling is first-class in mxtpu. The reference's only
sequence-length tooling is bucketing (SURVEY §5.7 — BucketingModule,
``python/mxnet/module/bucketing_module.py:36``); on TPU we scale the
sequence dimension itself across the mesh ``seq`` axis:

* **Ring attention** — K/V blocks rotate around the ring via
  ``lax.ppermute`` over ICI while each device holds its Q shard and
  accumulates the softmax online (flash-attention style running max /
  denominator), so attention over sequence length S costs O(S/n) memory
  per device and the permute overlaps with the block matmuls.
* **Ulysses all-to-all** — ``lax.all_to_all`` re-shards [seq-sharded,
  heads-replicated] activations into [seq-replicated, heads-sharded]
  around a standard attention core, for models whose head count divides
  the seq axis.

Both are pure jax functions usable inside any jitted step; `shard_map`
wrappers bind them to a MeshContext.
"""
from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .mesh import MeshContext, AXIS_SEQ, AXIS_DATA, shard_map

__all__ = ["ring_attention", "ring_attention_sharded", "ulysses_attention",
           "local_attention"]


def _alibi_slopes(h, dtype=jnp.float32):
    """Per-head ALiBi slopes ``2^(-8(i+1)/H)`` (Press et al.) — the
    same formula ``ops.nn.cached_attention`` uses, so the ring route
    and the dense cache route agree on the bias."""
    return jnp.asarray([2.0 ** (-8.0 * (i + 1) / h) for i in range(h)],
                       dtype)


def _resolve_impl(impl, alibi, t):
    """Settle what can be settled from static facts: "auto" stays "auto"
    only where flash is possible (the platform then decides at
    lowering); ALiBi has no Pallas kernel, so it takes the dense einsum
    — said (once per call site) when the caller asked for flash."""
    if alibi and impl == "flash":
        warnings.warn(
            "ring_attention: impl='flash' with alibi=True runs the dense "
            "einsum attention (the Pallas kernels carry no ALiBi bias)",
            RuntimeWarning, stacklevel=3)
    if alibi or (impl == "auto" and t < 128):
        return "xla"
    return impl


def local_attention(q, k, v, causal=False, scale=None, q_offset=0,
                    k_offset=0, impl="auto", alibi=False):
    """Softmax attention on local shards. q,k,v: [B, H, T, D].

    ``q_offset``/``k_offset`` give the global positions of the local rows
    for causal masking under sequence sharding. ``impl``: "flash" lowers
    to the Pallas flash-attention kernels (ops/pallas_attention.py),
    "xla" is the plain einsum+softmax path, "auto" picks flash when the
    computation is lowered for TPU and the sequences are long enough to
    tile. ``alibi=True`` subtracts the per-head linear distance bias
    from the scores (the Pallas kernels do not carry the bias, so alibi
    takes the xla path — with a warning when "flash" was asked for)."""
    impl = _resolve_impl(impl, alibi, min(q.shape[2], k.shape[2]))
    if impl == "auto":
        def run(q, k, v, q_offset, k_offset, impl):
            return local_attention(q, k, v, causal, scale, q_offset,
                                   k_offset, impl)
        return lax.platform_dependent(
            q, k, v, jnp.asarray(q_offset), jnp.asarray(k_offset),
            tpu=functools.partial(run, impl="flash"),
            default=functools.partial(run, impl="xla"))
    if impl == "flash":
        from ..ops.pallas_attention import flash_attention
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               q_offset=q_offset, k_offset=k_offset)
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / jnp.sqrt(d).astype(q.dtype)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    qi = q_offset + jnp.arange(q.shape[2])
    ki = k_offset + jnp.arange(k.shape[2])
    if alibi:
        dist = (qi[:, None] - ki[None, :]).astype(s.dtype)
        s = s - _alibi_slopes(q.shape[1], s.dtype)[None, :, None, None] \
            * dist[None, None]
    if causal:
        mask = qi[:, None] >= ki[None, :]
        s = jnp.where(mask[None, None], s, jnp.finfo(s.dtype).min)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def ring_attention(q, k, v, axis_name=AXIS_SEQ, causal=False, scale=None,
                   impl="auto", alibi=False):
    """Ring attention over a shard_map axis. q,k,v: local [B, H, T/n, D].

    Must run inside shard_map (or pmap) with ``axis_name`` bound. Each of
    the n ring steps attends Q_local against one rotating K/V block with a
    numerically-stable online softmax, then ppermutes K/V to the next
    neighbour — the all-gather-free formulation (Liu et al., Ring
    Attention; blockwise parallel transformers).

    ``impl="flash"`` computes each ring step with the Pallas flash
    kernels (ops/pallas_attention.py): per-step (out, lse) pairs merge
    online via logaddexp, so the whole ring is one flash pass per K/V
    block — "auto" picks flash when lowered for TPU and the local shard
    has >= 128 rows.

    ``alibi=True`` subtracts the per-head linear distance bias from
    every block's scores; the absolute ring positions (``my*t + i`` vs
    ``src*t + j``) make the bias identical to the dense single-device
    computation, so the ring route stays numerically compatible with
    ``cached_attention``'s full-window path."""
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    b, h, t, d = q.shape
    impl = _resolve_impl(impl, alibi, t)
    if impl == "auto":
        return lax.platform_dependent(
            q, k, v,
            tpu=functools.partial(ring_attention, axis_name=axis_name,
                                  causal=causal, scale=scale, impl="flash"),
            default=functools.partial(ring_attention, axis_name=axis_name,
                                      causal=causal, scale=scale,
                                      impl="xla"))
    if impl == "flash":
        return _ring_attention_flash(q, k, v, axis_name, causal, scale,
                                     n, my)
    if scale is None:
        scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    q32 = q.astype(jnp.float32)
    neg = jnp.finfo(jnp.float32).min
    slopes = _alibi_slopes(h) if alibi else None

    def absorb(i, o, m, l, kk, vv):
        src = (my - i) % n          # whose K/V block we now hold
        s = jnp.einsum("bhqd,bhkd->bhqk", q32,
                       kk.astype(jnp.float32)) * scale
        qi = my * t + jnp.arange(t)
        ki = src * t + jnp.arange(t)
        if alibi:
            dist = (qi[:, None] - ki[None, :]).astype(jnp.float32)
            s = s - slopes[None, :, None, None] * dist[None, None]
        if causal:
            mask = qi[:, None] >= ki[None, :]
            s = jnp.where(mask[None, None], s, neg)
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        # guard fully-masked rows (exp of min stays finite at 0 via where)
        p = jnp.exp(s - m_new[..., None])
        if causal:
            p = jnp.where(mask[None, None], p, 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        o_new = o * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vv.astype(jnp.float32))
        return o_new, m_new, l_new

    perm = [(j, (j + 1) % n) for j in range(n)]

    def step(i, carry):
        o, m, l, kk, vv = carry
        o, m, l = absorb(i, o, m, l, kk, vv)
        kk = lax.ppermute(kk, axis_name, perm)
        vv = lax.ppermute(vv, axis_name, perm)
        return o, m, l, kk, vv

    o = jnp.zeros((b, h, t, d), jnp.float32)
    m = jnp.full((b, h, t), neg, jnp.float32)
    l = jnp.zeros((b, h, t), jnp.float32)
    # permute only BETWEEN steps: the last block is absorbed outside the
    # loop so no dead final K/V rotation rides the ICI
    o, m, l, kk, vv = lax.fori_loop(0, n - 1, step, (o, m, l, k, v))
    o, m, l = absorb(n - 1, o, m, l, kk, vv)
    l = jnp.where(l == 0.0, 1.0, l)
    return (o / l[..., None]).astype(q.dtype)


def _ring_attention_flash(q, k, v, axis_name, causal, scale, n, my):
    """Ring steps as Pallas flash-attention calls merged via lse.

    Each step yields a normalized partial (o_b, lse_b) for the K/V block
    currently held; disjoint-key partials combine exactly with
    lse' = logaddexp(lse, lse_b), o' = o*e^(lse-lse') + o_b*e^(lse_b-lse').
    Fully-masked partials carry lse_b = -1e30 and drop out of the merge."""
    from ..ops.pallas_attention import flash_attention_with_lse, _NEG

    b, h, t, d = q.shape

    def absorb(i, o, lse, kk, vv):
        src = (my - i) % n          # whose K/V block we now hold
        o_b, lse_b = flash_attention_with_lse(
            q, kk, vv, causal=causal, scale=scale,
            q_offset=my * t, k_offset=src * t)
        lse_new = jnp.logaddexp(lse, lse_b)
        o = (o * jnp.exp(lse - lse_new)[..., None]
             + o_b.astype(jnp.float32) * jnp.exp(lse_b - lse_new)[..., None])
        return o, lse_new

    perm = [(j, (j + 1) % n) for j in range(n)]

    def step(i, carry):
        o, lse, kk, vv = carry
        o, lse = absorb(i, o, lse, kk, vv)
        kk = lax.ppermute(kk, axis_name, perm)
        vv = lax.ppermute(vv, axis_name, perm)
        return o, lse, kk, vv

    o = jnp.zeros((b, h, t, d), jnp.float32)
    lse = jnp.full((b, h, t), _NEG, jnp.float32)
    # last block absorbed outside the loop: no dead final K/V rotation
    o, lse, kk, vv = lax.fori_loop(0, n - 1, step, (o, lse, k, v))
    o, _ = absorb(n - 1, o, lse, kk, vv)
    return o.astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh, causal=False,
                           data_axis=AXIS_DATA, seq_axis=AXIS_SEQ,
                           impl="auto", alibi=False):
    """shard_map-bound ring attention over a MeshContext.

    q,k,v: global [B, H, T, D]; B sharded over ``data``, T over ``seq``.
    Returns the attention output with the same layout."""
    if isinstance(mesh, MeshContext):
        mesh = mesh.mesh
    spec = P(data_axis if data_axis in mesh.axis_names else None, None,
             seq_axis if seq_axis in mesh.axis_names else None, None)
    if seq_axis not in mesh.axis_names:
        return local_attention(q, k, v, causal=causal, impl=impl,
                               alibi=alibi)
    fn = shard_map(
        functools.partial(ring_attention, axis_name=seq_axis, causal=causal,
                          impl=impl, alibi=alibi),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)


def ulysses_attention(q, k, v, axis_name=AXIS_SEQ, causal=False,
                      attn_fn=None):
    """DeepSpeed-Ulysses style sequence parallelism inside shard_map.

    Local inputs [B, H, T/n, D] are all-to-all'd to [B, H/n, T, D] (full
    sequence, sharded heads), attention runs locally, then the layout is
    restored. Requires H % n == 0."""
    n = lax.psum(1, axis_name)
    b, h, t, d = q.shape

    def scatter_heads(x):   # [B,H,T/n,D] -> [B,H/n,T,D]
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def gather_heads(x):    # [B,H/n,T,D] -> [B,H,T/n,D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qh, kh, vh = scatter_heads(q), scatter_heads(k), scatter_heads(v)
    if attn_fn is None:
        attn_fn = lambda a, b_, c: local_attention(a, b_, c, causal=causal)
    oh = attn_fn(qh, kh, vh)
    return gather_heads(oh)
