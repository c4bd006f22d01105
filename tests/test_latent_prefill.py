"""``latent_attention``'s block-wise chunk path (``ops/nn.py``) and its kernel
(``ops/pallas_attention.py::latent_prefill_attention``, interpreted here)
against the dense formulas and the per-head definition: a chunk expands its
OWN rows once and attends them tile by tile, then the cache's live rows in
front of it block after block; no ``[B, heads, T, S]`` scores exist."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxtpu.base import MXNetError
from mxtpu.ops import nn
from mxtpu.ops.pallas_attention import (latent_prefill_attention,
                                        prefill_block)

H, NOPE, ROPE, VD, RANK = 2, 64, 64, 128, 128
ATTRS = dict(num_heads=H, nope_dim=NOPE, rope_dim=ROPE, v_dim=VD,
             scale=0.21, rope_theta=10000.0, rope_factor=8.0,
             rope_beta_fast=4.0, rope_beta_slow=1.0, rope_orig_len=16,
             norm_eps=1e-6)


def leaves(seed, B, T, dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    query = jax.random.normal(k[0], (B, T, H * (NOPE + ROPE)), dtype)
    kv_row = jax.random.normal(k[1], (B, T, RANK + ROPE), dtype)
    gain = 1.0 + 0.1 * jax.random.normal(k[2], (RANK,), dtype)
    w_up = jax.random.normal(k[3], (H * (NOPE + VD), RANK), dtype) / 8.0
    return query, kv_row, gain, w_up


def definition(query, kv_row, gain, w_up, beta=0.0):
    """The per-head formula over whole sequences, float64 numpy: no cache,
    nothing absorbed, the position's query scale as the issue writes it."""
    q, row, g, w = (np.asarray(a, np.float64)
                    for a in (query, kv_row, gain, w_up))
    B, T, _ = q.shape
    freqs = np.asarray(nn.yarn_frequencies(
        ROPE, ATTRS["rope_theta"], ATTRS["rope_factor"],
        ATTRS["rope_beta_fast"], ATTRS["rope_beta_slow"],
        ATTRS["rope_orig_len"]))

    def rotate(x):                      # [B, T, ..., ROPE]
        ang = np.arange(T).reshape((1, T) + (1,) * (x.ndim - 2)) * freqs
        x1, x2 = x[..., :ROPE // 2], x[..., ROPE // 2:]
        return np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                               x2 * np.cos(ang) + x1 * np.sin(ang)], -1)

    q = q.reshape(B, T, H, NOPE + ROPE)
    q_nope, q_rope = q[..., :NOPE], rotate(q[..., NOPE:])
    c = row[..., :RANK]
    c = c / np.sqrt(np.mean(c * c, -1, keepdims=True) + 1e-6) * g
    k_rope = rotate(row[..., RANK:])
    kv = np.einsum("btr,hor->btho", c, w.reshape(H, NOPE + VD, RANK))
    k_nope, v = kv[..., :NOPE], kv[..., NOPE:]
    s = ATTRS["scale"] * (np.einsum("bthd,bshd->bhts", q_nope, k_nope)
                          + np.einsum("bthd,bsd->bhts", q_rope, k_rope))
    s = s * (1.0 + beta * np.log1p(np.floor(
        np.arange(T) / ATTRS["rope_orig_len"])))[None, None, :, None]
    s = np.where(np.arange(T)[:, None] >= np.arange(T)[None, :], s, -np.inf)
    att = np.exp(s - s.max(-1, keepdims=True))
    att /= att.sum(-1, keepdims=True)
    return np.einsum("bhts,bshd->bthd", att, v).reshape(B, T, H * VD)


def run_in_pieces(args, pieces, S, width, padded_to=None, beta=0.0):
    """The op over a sequence cut into ``pieces``, each at the position where
    the one before ended; ``padded_to``: every chunk is padded with garbage
    rows up to this many, as a bucketed prefill is."""
    query, kv_row, gain, w_up = args
    B = query.shape[0]
    cache = jnp.zeros((B, S, width), query.dtype)
    outs, at = [], 0
    for n in pieces:
        q, r = query[:, at:at + n], kv_row[:, at:at + n]
        if padded_to and n > 1:
            junk = 7.0 * jnp.ones((B, padded_to - n, 1), query.dtype)
            q = jnp.concatenate([q, junk * jnp.ones_like(q[:, :1])], 1)
            r = jnp.concatenate([r, junk * jnp.ones_like(r[:, :1])], 1)
        out, cache = jax.jit(lambda q, r, c, p: nn.latent_attention(
            q, r, gain, w_up, c, p, pos_scale_beta=beta, **ATTRS))(
                q, r, cache, jnp.full((B,), at, jnp.int32))
        outs.append(out[:, :n])
        at += n
    return jnp.concatenate(outs, 1), cache


@pytest.mark.parametrize("pieces,padded_to,beta", [
    ((40,), None, 0.0),               # one chunk at pos 0
    ((16, 24), None, 0.0),            # a chunk behind 16 live rows
    ((8, 8, 16, 8), None, 0.1),       # chunks behind 8, 16, 32: two blocks
    ((13,) + (1,) * 11, 16, 0.1),     # a padded prefill, then decode steps
    ((21, 11), 24, 0.0),              # padded chunks at pos 0 and 21
])
def test_block_wise_chunks_equal_the_per_head_definition(pieces, padded_to,
                                                         beta):
    """Whatever the cut, the op gives the definition's rows, on the
    block-wise path for every chunk of whole tiles; padded rows lie past the
    true ones and no true row sees them; the cache's columns past the
    published row stay zero. With a query scale the decode steps across
    position 16 carry it too."""
    args = leaves(3, 2, sum(pieces))
    before = nn.latent_blockwise_nodes()
    out, cache = run_in_pieces(args, pieces, 64, 256, padded_to, beta)
    want = definition(*args, beta=beta)
    assert np.max(np.abs(np.asarray(out, np.float64) - want)) < 3e-5
    chunks = {(padded_to or n) for n in pieces if n > 1}
    assert nn.latent_blockwise_nodes() - before >= len(chunks)
    assert not np.any(np.asarray(cache)[:, :, RANK + ROPE:])


def test_block_wise_equals_dense_and_the_same_cache(monkeypatch):
    """One chunk both ways over the same cache at ragged positions (a sample
    at 0, one behind 5 rows, one behind 40: two blocks of a 64-row cache's
    32): the block-wise path and the dense formulas over the whole cache give
    the same rows and, bit for bit, the same cache."""
    B, S, T = 3, 64, 16
    query, kv_row, gain, w_up = leaves(11, B, T)
    k = jax.random.split(jax.random.PRNGKey(5), 2)
    cache = jax.random.normal(k[0], (B, S, 256), jnp.float32) * (
        jnp.arange(256) < RANK + ROPE)
    pos = jnp.asarray([0, 5, 40], jnp.int32)
    monkeypatch.setattr("mxtpu.ops.pallas_attention.PREFILL_BLOCK_K", 32)
    op = lambda c: nn.latent_attention(                      # noqa: E731
        query, kv_row, gain, w_up, c, pos, pos_scale_beta=0.1, **ATTRS)
    before = nn.latent_blockwise_nodes()
    blockwise, cache_b = jax.jit(op)(cache)
    assert nn.latent_blockwise_nodes() == before + 1
    monkeypatch.setattr(nn, "_latent_blockwise_path", lambda *a: False)
    dense, cache_d = jax.jit(op)(cache)
    assert nn.latent_blockwise_nodes() == before + 1
    assert np.max(np.abs(np.asarray(blockwise) - np.asarray(dense))) < 2e-5
    assert np.array_equal(np.asarray(cache_b), np.asarray(cache_d))


@pytest.mark.parametrize("shape,why", [
    (dict(nope=16, rope=8, vd=16), "query heads 24 wide, values 16"),
    (dict(nope=128, rope=64, vd=128), "query heads 192 wide, values 128"),
    (dict(T=13), "13 rows are no whole tiles"),
    (dict(T=1), "one row a sample is the decode step's"),
])
def test_other_shapes_keep_the_dense_formulas(shape, why):
    """The path is decided from shapes: heads of one width in whole slabs and
    whole tiles of rows. ``xing4.0-29b-a4b``'s (192 and 128) keep the dense
    formulas, so its programs are what they were."""
    nope, rope, vd = (shape.get(k, d) for k, d in (
        ("nope", 64), ("rope", 64), ("vd", 128)))
    T = shape.get("T", 16)
    k = jax.random.split(jax.random.PRNGKey(1), 4)
    args = (jax.random.normal(k[0], (1, T, 2 * (nope + rope))),
            jax.random.normal(k[1], (1, T, 128 + rope)),
            jnp.ones((128,)),
            jax.random.normal(k[2], (2 * (nope + vd), 128)) / 8.0,
            jnp.zeros((1, 32, 256)), jnp.zeros((1,), jnp.int32))
    before = nn.latent_blockwise_nodes()
    out, _cache = jax.jit(lambda *a: nn.latent_attention(
        *a, num_heads=2, nope_dim=nope, rope_dim=rope, v_dim=vd))(*args)
    assert out.shape == (1, T, 2 * vd), why
    assert nn.latent_blockwise_nodes() == before, why


def heads_first(x, h):
    b, t, d = x.shape
    return np.asarray(x, np.float64).reshape(b, t, h, d // h).transpose(
        0, 2, 1, 3)


def softmax_rows(q, k, v, seen, scale):
    s = scale * np.einsum("bhtd,bhnd->bhtn", q, k)
    s = np.where(seen, s, -np.inf)
    with np.errstate(invalid="ignore"):
        m = np.where(np.isfinite(s.max(-1, keepdims=True)),
                     s.max(-1, keepdims=True), 0.0)
        e = np.where(seen, np.exp(s - m), 0.0)
    total = e.sum(-1, keepdims=True)
    out = np.einsum("bhtn,bhnd->bhtd", e / np.where(total == 0, 1, total), v)
    lse = np.where(total == 0, -1e30, m + np.log(np.where(total == 0, 1,
                                                          total)))
    return out, lse


@pytest.mark.parametrize("T,N,lead,kv_len,blocks", [
    (32, 32, [0, 0], [32, 32], (8, 16)),        # a chunk against itself
    (32, 32, [0, 0], [32, 32], (32, 8)),        # key blocks under a tile
    (16, 32, [32, 32], [32, 7], (8, 8)),        # rows in front, ragged
    (16, 32, [32, 32], [0, 19], (16, 32)),      # a sample with none
    (24, 24, [3, 0], [24, 10], (8, 8)),         # a lead, a short length
])
def test_kernel_masks_by_lead_and_length_and_gives_the_log_sum(T, N, lead,
                                                               kv_len, blocks):
    """``latent_prefill_attention``, interpreted: sample ``b``'s row ``t``
    sees key ``n`` where ``n <= t + lead[b]`` and ``n < kv_len[b]``; the
    log-sum-exp is the rows' own, ``-1e30`` where a row saw nothing; dead key
    blocks are skipped without a trace in the result."""
    k0 = jax.random.split(jax.random.PRNGKey(T + N), 3)
    q = jax.random.normal(k0[0], (2, T, 2 * 128))
    k = jax.random.normal(k0[1], (2, N, 2 * 128))
    v = jax.random.normal(k0[2], (2, N, 2 * 128))
    lead, kv_len = np.asarray(lead), np.asarray(kv_len)
    out, lse = jax.jit(lambda q, k, v: latent_prefill_attention(
        q, k, v, jnp.asarray(lead), jnp.asarray(kv_len), 2, 0.3,
        block_q=blocks[0], block_k=blocks[1]))(q, k, v)
    t, n = np.arange(T)[:, None], np.arange(N)[None, :]
    seen = ((n <= t + lead[:, None, None]) & (n < kv_len[:, None, None])
            )[:, None]
    want, want_lse = softmax_rows(heads_first(q, 2), heads_first(k, 2),
                                  heads_first(v, 2), seen, 0.3)
    got = heads_first(out, 2)
    assert np.max(np.abs(got - want)) < 2e-5
    assert lse.shape == (2, 2, T, 1)
    # (-1e30 in float32 is not float64's)
    assert np.allclose(np.asarray(lse)[..., 0], want_lse[..., 0], rtol=1e-6,
                       atol=2e-5)


def test_two_stretches_merge_by_their_log_sums():
    """Keys cut in two stretches, attended apart and merged by the rule the
    docstring gives, are the keys attended at once: what the chunk path does
    with the cache's blocks in front of a chunk."""
    k0 = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(k0[0], (1, 16, 256))
    k = jax.random.normal(k0[1], (1, 48, 256))
    v = jax.random.normal(k0[2], (1, 48, 256))
    f = jax.jit(lambda q, k, v, n: latent_prefill_attention(
        q, k, v, 48, n, 2, 0.3))
    whole, _ = f(q, k, v, 40)
    o1, l1 = f(q, k[:, :32], v[:, :32], 32)
    o2, l2 = f(q, k[:, 32:], v[:, 32:], 8)
    both = np.logaddexp(l1, l2)

    def weigh(o, l):
        share = np.exp(np.asarray(l) - both)[..., 0].swapaxes(1, 2)
        return np.asarray(o).reshape(1, 16, 2, 128) * share[..., None]
    merged = (weigh(o1, l1) + weigh(o2, l2)).reshape(1, 16, 256)
    assert np.max(np.abs(merged - np.asarray(whole))) < 2e-5


def test_blocks_and_refusals():
    assert prefill_block(8192, 512) == 512 and prefill_block(10240, 1024) == 1024
    assert prefill_block(3072, 1024) == 1024 and prefill_block(24, 512) == 8
    assert prefill_block(13, 512) is None and prefill_block(4, 512) is None
    q = jnp.zeros((1, 16, 256))
    with pytest.raises(MXNetError):     # heads of half a slab
        latent_prefill_attention(jnp.zeros((1, 16, 128)),
                                 jnp.zeros((1, 16, 128)),
                                 jnp.zeros((1, 16, 128)), 0, 16, 2, 1.0)
    with pytest.raises(MXNetError):     # 13 key rows are no whole tiles
        latent_prefill_attention(q, jnp.zeros((1, 13, 256)),
                                 jnp.zeros((1, 13, 256)), 0, 13, 2, 1.0)
    with pytest.raises(MXNetError):     # values of another width
        latent_prefill_attention(q, q, jnp.zeros((1, 16, 128)), 0, 16, 2, 1.0)
