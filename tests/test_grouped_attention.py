"""``cached_attention`` with grouped queries, ring caches, rotary positions
and per-head norms, against a plain float64 definition over the whole
sequence: prefill (padded, longer than the ring) then decode past the ring's
wrap, on the dense formula (heads of 32) and on the kernel
``decode_attention`` (heads of 128, through the Pallas interpreter)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxtpu.ops.nn import cached_attention, decode_path_nodes

EPS = 1e-5


def definition(q, k, v, heads, kv_heads, window, theta, q_gain, k_gain):
    """Causal attention over a whole sequence ``[T, heads x hd]`` in
    float64: per-head RMS norm, rotary positions (half-split pairs),
    query head ``i`` on key/value head ``i // group``, a band of
    ``window`` positions."""
    T = q.shape[0]
    hd = q.shape[1] // heads
    q = q.astype(np.float64).reshape(T, heads, hd)
    k = k.astype(np.float64).reshape(T, kv_heads, hd)
    v = v.astype(np.float64).reshape(T, kv_heads, hd)
    if q_gain is not None:
        q = q / np.sqrt((q * q).mean(-1, keepdims=True) + EPS) * q_gain
        k = k / np.sqrt((k * k).mean(-1, keepdims=True) + EPS) * k_gain
    if theta:
        half = hd // 2
        ang = np.arange(T)[:, None, None] * theta ** (-np.arange(half) / half)

        def rot(x):
            x1, x2 = x[..., :half], x[..., half:]
            return np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                                   x2 * np.cos(ang) + x1 * np.sin(ang)], -1)
        q, k = rot(q), rot(k)
    out = np.zeros((T, heads, hd))
    dist = np.arange(T)[:, None] - np.arange(T)[None, :]
    ok = dist >= 0
    if window:
        ok &= dist < window
    for h in range(heads):
        s = q[:, h] @ k[:, h // (heads // kv_heads)].T / np.sqrt(hd)
        s = np.where(ok, s, -np.inf)
        a = np.exp(s - s.max(-1, keepdims=True))
        out[:, h] = (a / a.sum(-1, keepdims=True)) @ v[:, h // (heads // kv_heads)]
    return out.reshape(T, heads * hd)


def serve(q, k, v, plen, bucket, S, heads, kv_heads, window, theta, gains):
    """Prefill ``plen`` rows padded to ``bucket``, then decode the rest one
    row at a time; returns the outputs at every true position."""
    T = q.shape[0]
    dkv = k.shape[1]
    kw = dict(num_heads=heads, num_kv_heads=kv_heads, window=window,
              rope_theta=theta)
    if gains is not None:
        kw.update(q_gain=jnp.asarray(gains[0], jnp.float32),
                  k_gain=jnp.asarray(gains[1], jnp.float32))
    pad = lambda a: jnp.asarray(np.concatenate(  # noqa: E731
        [a[:plen], 7.0 * np.ones((bucket - plen, a.shape[1]), a.dtype)])[None])
    kc = jnp.zeros((1, S, dkv), jnp.float32)
    vc = jnp.zeros((1, S, dkv), jnp.float32)
    out, kc, vc = cached_attention(
        pad(q), pad(k), pad(v), kc, vc, jnp.zeros((1,), jnp.int32),
        valid_len=jnp.asarray([plen], jnp.int32), **kw)
    outs = [np.asarray(out[0, :plen])]
    step = jax.jit(lambda a, b, c, kc, vc, p: cached_attention(
        a, b, c, kc, vc, p, valid_len=jnp.ones((1,), jnp.int32), **kw))
    for t in range(plen, T):
        row = lambda a: jnp.asarray(a[t][None, None])  # noqa: E731
        out, kc, vc = step(row(q), row(k), row(v), kc, vc,
                           jnp.asarray([t], jnp.int32))
        outs.append(np.asarray(out[0]))
    return np.concatenate(outs)


CASES = {
    # name: heads, kv_heads, hd, window, ring rows, theta, gains, T, plen, bucket
    "grouped-full": (8, 2, 32, 0, 48, 0.0, False, 40, 21, 32),
    "grouped-full-rope-norm": (8, 2, 32, 0, 48, 1e4, True, 40, 21, 32),
    "window-ring-padded-prefill-longer-than-ring": (8, 2, 32, 8, 8, 1e4, True, 40, 21, 32),
    "window-ring-exact-prefill": (8, 2, 32, 8, 8, 1e4, True, 40, 21, 21),
    "window-ring-wider-than-window": (4, 4, 32, 6, 16, 0.0, False, 40, 5, 8),
    "window-short-prompt": (8, 2, 32, 8, 8, 1e4, True, 30, 3, 16),
    "kernel-grouped-full": (8, 1, 128, 0, 64, 1e4, True, 40, 21, 32),
    "kernel-window-ring": (8, 1, 128, 16, 16, 1e4, True, 40, 21, 32),
    "kernel-window-ring-wider": (8, 8, 128, 5, 16, 0.0, False, 40, 21, 32),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_prefill_then_decode_equals_the_definition(name):
    heads, kv_heads, hd, window, S, theta, gains, T, plen, bucket = CASES[name]
    rng = np.random.default_rng(sum(map(ord, name)))
    q = rng.standard_normal((T, heads * hd)).astype(np.float32)
    k = rng.standard_normal((T, kv_heads * hd)).astype(np.float32)
    v = rng.standard_normal((T, kv_heads * hd)).astype(np.float32)
    g = (1.0 + 0.2 * rng.standard_normal((2, hd))) if gains else None
    before = decode_path_nodes()
    got = serve(q, k, v, plen, bucket, S, heads, kv_heads, window, theta, g)
    want = definition(q, k, v, heads, kv_heads, window, theta,
                      None if g is None else g[0], None if g is None else g[1])
    # float32 throughout; the kernel's matrix products run at the
    # interpreter's default precision
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=2e-3)
    on_kernel = decode_path_nodes() - before
    assert (on_kernel > 0) == name.startswith("kernel")


def _ring_rows_definition(q, kc, vc, pos, heads, kv_heads, window):
    """float64, a row at a time: row ``r`` of a ring of ``S`` rows holds the
    newest position at or below ``pos`` that is ``r`` modulo ``S`` (with
    ``window`` 0 the cache is full: row ``r`` is position ``r``); a slot
    attends what lies within ``window`` positions of ``pos``."""
    q, kc, vc = (np.asarray(a, np.float64) for a in (q, kc, vc))
    B, S, D = kc.shape
    hd = D // kv_heads
    out = np.zeros((B, heads * hd))
    for b in range(B):
        p = int(pos[b])
        at = np.array([p - (p - r) % S if window else r for r in range(S)])
        live = (at >= 0) & (at <= p) & ((p - at < window) | (window == 0))
        for h in range(heads):
            c = slice(h // (heads // kv_heads) * hd,
                      (h // (heads // kv_heads) + 1) * hd)
            s = kc[b, live, c] @ q[b, 0, h * hd:(h + 1) * hd] / np.sqrt(hd)
            w = np.exp(s - s.max())
            out[b, h * hd:(h + 1) * hd] = (w / w.sum()) @ vc[b, live, c]
    return out[:, None]


@pytest.mark.parametrize("name,S,window,pos", [
    ("full-all-at-0", 128, 0, (0, 0, 0, 0)),
    ("full-block-edges", 128, 0, (15, 16, 63, 64)),
    ("full-idle-slot-among-live", 128, 0, (5, 128 + 9, 127, 70)),
    ("ring-first-turn", 128, 40, (0, 47, 64, 126)),
    ("ring-past-its-first-turn", 128, 40, (128, 200, 1000, 5)),
    ("ring-of-one-block", 64, 64, (0, 17, 64, 300))])
def test_grouped_kernel_visits_only_live_blocks(name, S, window, pos):
    """``decode_attention`` itself, 8 query heads over 2 key/value heads of
    128, blocks of 64 rows and tail blocks of 16, on the live grid (PR 35).
    NaN fills every row past a slot's last live block, whole or tail (a ring
    that has not turned yet has such rows; one that has, or an idle slot held
    at a full cache's end, has none): it may reach nothing."""
    from mxtpu.ops.pallas_attention import decode_attention
    from test_decode_attention import _past_the_live_blocks
    heads, kv_heads, hd, blk = 8, 2, 128, 64
    rng = np.random.default_rng(sum(map(ord, name)))
    q = jnp.asarray(rng.standard_normal((4, 1, heads * hd)), jnp.float32)
    kc, vc = (jnp.asarray(rng.standard_normal((4, S, kv_heads * hd)),
                          jnp.float32) for _ in range(2))
    newest = np.minimum(np.asarray(pos), S - 1)
    past = _past_the_live_blocks(newest, S, blk)
    assert past.any(axis=(1, 2)).tolist() == [n < S - 16 for n in newest]
    out = decode_attention(q, jnp.where(past, jnp.nan, kc),
                           jnp.where(past, jnp.nan, vc),
                           jnp.asarray(pos, jnp.int32), heads, block_s=blk,
                           num_kv_heads=kv_heads, window=window)
    want = _ring_rows_definition(q, kc, vc, pos if window else newest, heads,
                                 kv_heads, window)
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-3, rtol=2e-3)


def test_padding_never_reaches_the_ring():
    """A prompt padded to a bucket four times the ring leaves exactly the
    rows an exact-length prefill leaves."""
    heads, kv_heads, hd, window, S = 4, 2, 32, 8, 8
    rng = np.random.default_rng(3)
    plen = 13
    rows = {}
    for bucket in (13, 32):
        a = [np.zeros((1, bucket, w * hd), np.float32)
             for w in (heads, kv_heads, kv_heads)]
        src = np.random.default_rng(4)
        for x in a:
            x[0, :plen] = src.standard_normal((plen, x.shape[2]))
            x[0, plen:] = 1e3 * rng.standard_normal((bucket - plen, x.shape[2]))
        _out, kc, vc = cached_attention(
            *map(jnp.asarray, a), jnp.zeros((1, S, kv_heads * hd)),
            jnp.zeros((1, S, kv_heads * hd)), jnp.zeros((1,), jnp.int32),
            valid_len=jnp.asarray([plen], jnp.int32), num_heads=heads,
            num_kv_heads=kv_heads, window=window)
        rows[bucket] = (np.asarray(kc), np.asarray(vc))
    np.testing.assert_array_equal(rows[13][0], rows[32][0])
    np.testing.assert_array_equal(rows[13][1], rows[32][1])
    assert np.abs(rows[32][0]).max() < 10


def test_window_nodes_are_counted():
    from mxtpu import obs
    series = obs.REGISTRY.counter("ops.cached_attention.window_nodes").default()
    before = series.value
    z = jnp.zeros((1, 1, 64))
    cached_attention(z, z, z, jnp.zeros((1, 8, 64)), jnp.zeros((1, 8, 64)),
                     jnp.zeros((1,), jnp.int32), num_heads=2, window=4)
    assert series.value == before + 1


@pytest.mark.parametrize("heads,hd,T", [(4, 32, 1), (4, 32, 6), (2, 128, 1)])
def test_default_arguments_take_the_old_op_bit_for_bit(heads, hd, T):
    """Every new argument at its default: the multi-head op as it was
    (``tests/test_decode_attention.py`` keeps it word for word), dense
    formula and kernel alike; stating the defaults changes no bit."""
    rng = np.random.default_rng(heads * hd + T)
    d = heads * hd
    q, k, v = (jnp.asarray(rng.standard_normal((2, T, d)), jnp.bfloat16)
               for _ in range(3))
    kc, vc = (jnp.asarray(rng.standard_normal((2, 16, d)), jnp.bfloat16)
              for _ in range(2))
    pos = jnp.asarray([3, 7], jnp.int32)
    f = jax.jit(lambda **kw: cached_attention(q, k, v, kc, vc, pos,
                                              num_heads=heads, alibi=True, **kw),
                static_argnames=("num_kv_heads", "window", "rope_theta"))
    plain = f()
    stated = f(num_kv_heads=heads, window=0, rope_theta=0.0)
    for a, b in zip(plain, stated):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    # and the dense shapes against the old op kept word for word
    if not (T == 1 and hd == 128):         # the kernel has its own tests
        from test_decode_attention import _frozen_dense_op
        frozen = jax.jit(lambda: _frozen_dense_op(q, k, v, kc, vc, pos,
                                                  heads, True))()
        for a, b in zip(plain, frozen):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
