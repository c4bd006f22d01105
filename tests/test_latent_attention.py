"""``latent_attention`` (``ops/nn.py``) and its decode kernel
(``ops/pallas_attention.py::latent_decode_attention``) against the per-head
definition: a position is ONE cached row ``[c ; k_rope]`` shared by all
heads; a chunk expands keys and values from the rows, one row a sample
absorbs the expansion into the query and the output and runs the kernel
(interpreted here)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxtpu.base import MXNetError
from mxtpu.ops import nn
from mxtpu.ops.pallas_attention import (cache_write_row,
                                        latent_decode_attention)

H, NOPE, ROPE, VD, RANK = 4, 16, 8, 16, 128
ATTRS = dict(num_heads=H, nope_dim=NOPE, rope_dim=ROPE, v_dim=VD,
             scale=0.31, rope_theta=10000.0, rope_factor=8.0,
             rope_beta_fast=4.0, rope_beta_slow=1.0, rope_orig_len=64,
             norm_eps=1e-6)


def leaves(seed, B, T, dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    query = jax.random.normal(k[0], (B, T, H * (NOPE + ROPE)), dtype)
    kv_row = jax.random.normal(k[1], (B, T, RANK + ROPE), dtype)
    gain = 1.0 + 0.1 * jax.random.normal(k[2], (RANK,), dtype)
    w_up = jax.random.normal(k[3], (H * (NOPE + VD), RANK), dtype) / 8.0
    return query, kv_row, gain, w_up


def definition(query, kv_row, gain, w_up):
    """The per-head formula over whole sequences, float64 numpy: no cache,
    nothing absorbed. ``query [B, T, ...]`` at positions ``0 .. T-1``."""
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
    s = np.where(np.arange(T)[:, None] >= np.arange(T)[None, :], s, -np.inf)
    att = np.exp(s - s.max(-1, keepdims=True))
    att /= att.sum(-1, keepdims=True)
    return np.einsum("bhts,bshd->bthd", att, v).reshape(B, T, H * VD)


def run_in_pieces(args, pieces, S, width, padded_to=None):
    """The op over a sequence cut into ``pieces`` (lengths), each at the
    position where the one before ended; a piece of one row takes the
    kernel. ``padded_to``: every chunk is padded with garbage rows up to
    this many, as a bucketed prefill is."""
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
            q, r, gain, w_up, c, p, **ATTRS))(
                q, r, cache, jnp.full((B,), at, jnp.int32))
        outs.append(out[:, :n])
        at += n
    return jnp.concatenate(outs, 1), cache


@pytest.mark.parametrize("pieces,padded_to,width", [
    ((12,), None, RANK + ROPE),                # one chunk at pos 0
    ((5, 4, 3), None, RANK + ROPE),            # chunks at pos > 0
    ((5,) + (1,) * 7, None, RANK + ROPE),      # prefill, then decode steps
    ((5,) + (1,) * 7, 8, RANK + ROPE),         # a padded chunk, then decode
    ((6,) + (1,) * 6, 8, 256),                 # rows padded to whole slabs
])
def test_prefill_then_decode_equals_the_per_head_definition(pieces, padded_to,
                                                            width):
    """Whatever the cut, the op gives the definition's rows: chunks expand
    the cache's rows, single rows run the absorbed form on the kernel; the
    garbage rows a padded chunk leaves past its true length are each
    overwritten by the decode step of their position before anything
    attends them."""
    args = leaves(3, 2, sum(pieces))
    before = nn.latent_decode_nodes()
    out, cache = run_in_pieces(args, pieces, 32, width, padded_to)
    want = definition(*args)
    assert np.max(np.abs(np.asarray(out, np.float64) - want)) < 2e-5
    assert (nn.latent_decode_nodes() > before) == (1 in pieces)
    assert not np.any(np.asarray(cache)[:, :, RANK + ROPE:])   # the padding
    assert np.any(np.asarray(cache)[:, sum(pieces) - 1, :RANK + ROPE])


def test_absorbed_equals_expanded(monkeypatch):
    """One row a sample both ways over the same cache: through the kernel
    with ``W_uk`` absorbed into the query and ``W_uv`` applied to the
    output, and through the chunk's path, which expands every cached row
    into per-head keys and values. The same mathematics; and the same cache
    out, bit for bit."""
    B, S = 3, 32
    query, kv_row, gain, w_up = leaves(11, B, 1)
    k = jax.random.split(jax.random.PRNGKey(5), 2)
    cache = jax.random.normal(k[0], (B, S, RANK + ROPE), jnp.float32)
    pos = jnp.asarray([0, 9, 31], jnp.int32)
    op = jax.jit(lambda c: nn.latent_attention(
        query, kv_row, gain, w_up, c, pos, **ATTRS))
    absorbed, cache_a = op(cache)
    monkeypatch.setattr(nn, "_latent_decode_path", lambda *a: False)
    expanded, cache_e = jax.jit(lambda c: nn.latent_attention(
        query, kv_row, gain, w_up, c, pos, **ATTRS))(cache)
    assert np.max(np.abs(np.asarray(absorbed) - np.asarray(expanded))) < 2e-5
    assert np.array_equal(np.asarray(cache_a), np.asarray(cache_e))


@pytest.mark.parametrize("dtype,S,block_s,tol", [
    (jnp.float32, 64, 16, 1e-5), (jnp.float32, 64, None, 1e-5),
    (jnp.bfloat16, 128, 32, 2e-2)])
def test_kernel_equals_the_formula(dtype, S, block_s, tol):
    """``latent_decode_attention``, interpreted: every head's scores against
    ALL columns of the rows at or below ``pos[b]``, softmax, times the rows'
    first ``rank`` columns. A slot at position 0, slots inside a block and
    at a block's edge, and an idle slot counted past the cache."""
    B, W = 5, RANK + ROPE + 8
    k = jax.random.split(jax.random.PRNGKey(2), 2)
    q = jax.random.normal(k[0], (B, H, W), dtype)
    cache = jax.random.normal(k[1], (B, S, W), dtype)
    pos = jnp.asarray([0, 15, 16, S - 1, S + 40], jnp.int32)
    got = jax.jit(lambda q, c, p: latent_decode_attention(
        q, c, p, RANK, 0.2, block_s=block_s))(q, cache, pos)
    assert got.shape == (B, H, RANK) and got.dtype == dtype
    q64, c64 = np.asarray(q, np.float64), np.asarray(cache, np.float64)
    for b in range(B):
        live = min(int(pos[b]), S - 1) + 1
        s = 0.2 * q64[b] @ c64[b, :live].T
        att = np.exp(s - s.max(-1, keepdims=True))
        att /= att.sum(-1, keepdims=True)
        want = att @ c64[b, :live, :RANK]
        assert np.max(np.abs(np.asarray(got[b], np.float64) - want)) < tol, b


@pytest.mark.parametrize("pos", [
    (0, 0, 0, 0),                    # the grid is ``slots`` long
    (15, 16, 63, 31),                # a block's two edges, the last row
    (5, 64 + 40, 32, 0)])            # an idle slot among live ones
def test_kernel_visits_only_live_blocks(pos):
    """The grid runs over the live blocks alone (PR 35): NaN in every block
    past a slot's last live one reaches nothing. An idle slot counts past
    the cache: held inside it, all its blocks live."""
    B, S, W, blk = 4, 64, RANK + ROPE + 8, 16
    k = jax.random.split(jax.random.PRNGKey(7), 2)
    q = jax.random.normal(k[0], (B, H, W), jnp.float32)
    cache = jax.random.normal(k[1], (B, S, W), jnp.float32)
    newest = np.minimum(np.asarray(pos), S - 1)
    past = np.arange(S)[None, :, None] // blk > (newest // blk)[:, None, None]
    got = jax.jit(lambda q, c, p: latent_decode_attention(
        q, c, p, RANK, 0.2, block_s=blk))(
            q, jnp.where(past, jnp.nan, cache), jnp.asarray(pos, jnp.int32))
    q64, c64 = np.asarray(q, np.float64), np.asarray(cache, np.float64)
    for b in range(B):
        s = 0.2 * q64[b] @ c64[b, :newest[b] + 1].T
        att = np.exp(s - s.max(-1, keepdims=True))
        att /= att.sum(-1, keepdims=True)
        want = att @ c64[b, :newest[b] + 1, :RANK]
        assert np.max(np.abs(np.asarray(got[b], np.float64) - want)) < 1e-5, b


def test_kernel_refuses_shapes_it_cannot_take():
    q = jnp.zeros((2, H, 72), jnp.float32)
    cache = jnp.zeros((2, 32, 72), jnp.float32)
    pos = jnp.zeros((2,), jnp.int32)
    with pytest.raises(MXNetError, match="128-lane"):
        latent_decode_attention(q, cache, pos, 64, 1.0)     # rank of half a slab
    with pytest.raises(MXNetError, match="128-lane"):
        latent_decode_attention(jnp.zeros((2, H, 136)),
                                jnp.zeros((2, 24, 136)), pos, 128, 1.0)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_row_write_leaves_the_cache_bit_identical_to_the_scatter(dtype):
    """The decode step's cache out is the scatter's: the same row of the
    same values at ``pos[b]`` and every other row as it was, bit for bit; an
    idle slot's position past the cache is held inside it, as
    ``dynamic_update_slice`` holds its start."""
    B, S, W = 4, 48, 256
    query, kv_row, gain, w_up = leaves(4, B, 1, dtype)
    cache = jax.random.normal(jax.random.PRNGKey(8), (B, S, W), dtype)
    pos = jnp.asarray([0, 17, 47, 900], jnp.int32)
    before = nn.latent_decode_nodes()
    _out, by_kernel = jax.jit(lambda c: nn.latent_attention(
        query, kv_row, gain, w_up, c, pos, **ATTRS))(cache)
    assert nn.latent_decode_nodes() == before + 1
    rows = np.asarray(by_kernel)[np.arange(B), np.minimum(pos, S - 1)]
    by_scatter = nn._scatter_rows(cache, jnp.asarray(rows)[:, None],
                                  jnp.clip(pos, 0, S - 1))
    assert np.array_equal(np.asarray(by_kernel).view(np.uint8),
                          np.asarray(by_scatter).view(np.uint8))
    assert np.array_equal(
        np.asarray(cache_write_row(cache, jnp.asarray(rows)[:, None],
                                   jnp.clip(pos, 0, S - 1))).view(np.uint8),
        np.asarray(by_scatter).view(np.uint8))
    assert not np.any(rows[:, RANK + ROPE:].astype(np.float32))


def test_a_cache_of_no_whole_blocks_keeps_the_chunks_path():
    """40 rows are not whole 16-row tiles: no kernel, the same numbers."""
    args = leaves(6, 2, 6)
    before = nn.latent_decode_nodes()
    out, _cache = run_in_pieces(args, (3, 1, 1, 1), 40, RANK + ROPE)
    assert nn.latent_decode_nodes() == before
    assert np.max(np.abs(np.asarray(out, np.float64)
                         - definition(*args))) < 2e-5


def test_yarn_frequencies_ramp_between_the_two_pairs():
    """Pairs that turn more than ``beta_fast`` times over the original
    context keep their frequency, pairs that turn less than ``beta_slow``
    times are stretched by ``factor``, a linear ramp between; ``factor`` 1
    is plain rotary positions."""
    w = np.asarray(nn.yarn_frequencies(64, 10000.0, 64.0, 32.0, 1.0, 4096))
    f = 10000.0 ** (-2.0 * np.arange(32) / 64)
    assert np.allclose(w[:11], f[:11]) and np.allclose(w[23:], f[23:] / 64)
    g = (np.arange(11, 23) - 10) / 13
    assert np.allclose(w[11:23], f[11:23] * ((1 - g) + g / 64))
    assert np.allclose(nn.yarn_frequencies(64, 10000.0), f)
