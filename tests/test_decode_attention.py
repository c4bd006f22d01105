"""The one-token decode path of ``cached_attention`` (PR 28): the Pallas
kernel ``pallas_attention.decode_attention`` against the definition and the
dense formula, which shapes the op puts on it, and (PR 33) the step's rows
going into the caches through ``pallas_attention.cache_write_row``. Runs
through the Pallas interpreter on the CPU;
``tests/test_kernels_compile_v5e.py`` compiles the same kernels through
Mosaic at the benchmark's shapes."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest


DB, DS, DH, DHD = 3, 256, 2, 128        # slots, cache rows, heads, head dim
DD = DH * DHD
DBLK = 64                                # so a slot's rows span 1 to 4 blocks


def _np_decode_reference(q, kc, vc, pos, heads, alibi):
    """float64, by the definition: slot b's one query row against rows
    s <= pos[b] of its own cache, softmax, times V; head h of width hd."""
    q, kc, vc = (np.asarray(a, np.float64) for a in (q, kc, vc))
    B, S, D = kc.shape
    hd = D // heads
    out = np.zeros((B, 1, D))
    for b in range(B):
        n = int(pos[b]) + 1
        for h in range(heads):
            sl = slice(h * hd, (h + 1) * hd)
            s = kc[b, :n, sl] @ q[b, 0, sl] / np.sqrt(hd)
            if alibi:
                s = s - 2.0 ** (-8.0 * (h + 1) / heads) * (
                    pos[b] - np.arange(n))
            w = np.exp(s - s.max())
            out[b, 0, sl] = (w / w.sum()) @ vc[b, :n, sl]
    return out


def _decode_case(cache_dtype, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.standard_normal((DB, 1, DD)), jnp.float32)
    kc, vc = (jnp.asarray(rng.standard_normal((DB, DS, DD)), cache_dtype)
              for _ in range(2))
    return q, kc, vc


@pytest.mark.parametrize("cache_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("alibi", [False, True])
@pytest.mark.parametrize("pos", [
    (0, DBLK, DS - 1),                   # first row, a block edge, last row
    (DBLK - 1, 2 * DBLK, 2 * DBLK + 1)])  # an edge minus 1, mid-cache
def test_decode_kernel_matches_the_definition(pos, alibi, cache_dtype):
    from mxtpu.ops.nn import _attend_dense
    from mxtpu.ops.pallas_attention import decode_attention
    q, kc, vc = _decode_case(cache_dtype)
    p = jnp.asarray(pos, jnp.int32)
    out = decode_attention(q, kc, vc, p, DH, alibi=alibi, block_s=DBLK)
    assert out.shape == (DB, 1, DD) and out.dtype == q.dtype
    ref = _np_decode_reference(q, kc, vc, pos, DH, alibi)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)
    # and the dense formula, called directly, reads the same
    dense = _attend_dense(q, kc, vc, p, DH, alibi)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("alibi", [False, True])
def test_decode_kernel_never_reads_rows_past_pos(alibi):
    """Rows past pos[b] hold what a padded prefill or an earlier tenant of
    the slot left: large values here, and none may reach the output (the
    last live block holds live and dead rows side by side)."""
    from mxtpu.ops.pallas_attention import decode_attention
    q, kc, vc = _decode_case(jnp.bfloat16, seed=3)
    pos = (0, DBLK + 5, 3 * DBLK - 1)
    dead = np.arange(DS)[None, :, None] > np.asarray(pos)[:, None, None]
    dirty_k = jnp.where(dead, jnp.asarray(3e4, kc.dtype), kc)
    dirty_v = jnp.where(dead, jnp.asarray(-3e4, vc.dtype), vc)
    p = jnp.asarray(pos, jnp.int32)
    clean = decode_attention(q, kc, vc, p, DH, alibi=alibi, block_s=DBLK)
    dirty = decode_attention(q, dirty_k, dirty_v, p, DH, alibi=alibi,
                             block_s=DBLK)
    np.testing.assert_array_equal(np.asarray(dirty), np.asarray(clean))
    # a slot the scheduler left idle counts past the cache: no fault
    idle = decode_attention(q, kc, vc, jnp.asarray([DS, DS + 7, 0]), DH,
                            alibi=alibi, block_s=DBLK)
    assert np.isfinite(np.asarray(idle)).all()


@pytest.mark.parametrize("alibi", [False, True])
@pytest.mark.parametrize("pos", [
    (0, 0, 0),                           # the grid is ``slots`` long
    (DBLK - 1, DBLK, DS - 1),            # a block's two edges, every block
    (5, DS + 9, 2 * DBLK)])              # an idle slot among live ones
def test_decode_kernel_visits_only_live_blocks(pos, alibi):
    """The grid runs over the live blocks alone (PR 35): NaN in every block
    past a slot's last live one, whole or tail, reaches nothing, where a
    block that was fetched, or a step that was taken, would put it into the
    output. An idle slot counts past the cache: held inside it, all its
    blocks live."""
    from mxtpu.ops.pallas_attention import decode_attention
    q, kc, vc = _decode_case(jnp.float32, seed=9)
    newest = np.minimum(np.asarray(pos), DS - 1)
    past = _past_the_live_blocks(newest, DS, DBLK)
    p = jnp.asarray(pos, jnp.int32)
    out = decode_attention(q, jnp.where(past, jnp.nan, kc),
                           jnp.where(past, jnp.nan, vc), p, DH, alibi=alibi,
                           block_s=DBLK)
    ref = _np_decode_reference(q, kc, vc, newest, DH, alibi)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)


def _past_the_live_blocks(newest, S, block_s):
    """[B, S, 1]: True for the rows no live block holds: past the slot's
    whole blocks and the tail blocks over what is left up to ``newest``."""
    from mxtpu.ops.pallas_attention import tail_rows
    tail_s = tail_rows(block_s)
    assert tail_s < block_s
    rows = np.asarray(newest) + 1
    whole = rows // block_s * block_s
    held = whole + -(-(rows - whole) // tail_s) * tail_s
    return np.arange(S)[None, :, None] >= held[:, None, None]


def _live_blocks_by_a_loop(pos, S, block_s, tail_s):
    """``(slot, block, tail, flags)`` of every grid step: a slot's whole
    blocks, then tail blocks over the rest; each window names what it last
    showed where the step does not use it."""
    steps, block, tail = [], 0, 0
    for b, p in enumerate(pos):
        rows = min(max(int(p), 0), S - 1) + 1
        whole = rows // block_s
        n = whole + -(-(rows - whole * block_s) // tail_s)
        for k in range(n):
            if k < whole:
                block = b * (S // block_s) + k
            else:
                tail = b * (S // tail_s) + whole * (block_s // tail_s) \
                    + k - whole
            steps.append((b, block, tail,
                          (k == 0) + 2 * (k == n - 1) + 4 * (k >= whole)))
    return steps


@pytest.mark.parametrize("S,block_s,tail_s,pos", [
    (256, 64, 16, (0, 0, 0)),            # one tail block a slot
    (256, 64, 16, (63, 64, 255)),        # a block's two edges, the last row
    (256, 64, 16, (255, 255, 255)),      # every whole block of every slot
    (256, 64, 16, (254, 254, 254)),      # the most steps a slot can have
    (256, 64, 16, (5, 300, 128)),        # an idle slot counting past the cache
    (32, 16, 16, (5, 40, 31, 16)),       # a ring before and past its first
                                         # turn; the tail is the block
    (128, 128, 32, (0, 500, 127, 3)),    # one block a slot (K-EXAONE's rings)
    (2048, 128, 32, tuple(range(7, 2048, 131))),
    (3072, 512, 128, tuple(range(0, 3072, 41)))])
def test_live_blocks_against_a_plain_loop(S, block_s, tail_s, pos):
    """Slot, whole block, tail block and flags of every grid step up to the
    total, and the entries past it: one more entry than the most steps the
    slots can have (a pipeline reads one step ahead of the last, also when
    every slot has the most), each repeating the last step, so inside the
    arrays."""
    from mxtpu.ops.pallas_attention import live_blocks
    *tables, total = jax.jit(lambda p: live_blocks(p, S, block_s, tail_s))(
        jnp.asarray(pos, jnp.int32))
    want = _live_blocks_by_a_loop(pos, S, block_s, tail_s)
    n = len(pos) * (S // block_s - 1 + block_s // tail_s) + 1
    assert int(total) == len(want) < n
    for table in tables:
        assert table.shape == (n,) and table.dtype == jnp.int32
    got = list(zip(*(np.asarray(t).tolist() for t in tables)))
    assert got[:len(want)] == want
    assert got[len(want):] == [want[-1]] * (n - len(want))
    if pos == (254,) * 3:
        assert len(want) == n - 1
    # no loop in the compiled step: comparisons against the running sum
    text = jax.jit(lambda p: live_blocks(p, S, block_s, tail_s)).lower(
        jnp.asarray(pos, jnp.int32)).compile().as_text()
    assert "while(" not in text


def _op_inputs(T, heads, hd, S=DS, seed=5, dtype=jnp.bfloat16):
    rng = np.random.RandomState(seed)
    D = heads * hd
    q, k, v = (jnp.asarray(rng.standard_normal((DB, T, D)), jnp.float32)
               for _ in range(3))
    kc, vc = (jnp.asarray(rng.standard_normal((DB, S, D)), dtype)
              for _ in range(2))
    return q, k, v, kc, vc


def _frozen_dense_op(q, k, v, kc, vc, pos, heads, alibi):
    """cached_attention as it stood before the decode path, kept here word
    for word so that "the dense path is unchanged" compares with the past
    and not with the code under test."""
    p = pos.astype(jnp.int32).reshape(-1)
    B, T, D = q.shape
    S, H = kc.shape[1], heads
    hd = D // H
    write = jax.vmap(lambda cache, rows, at: jax.lax.dynamic_update_slice(
        cache, rows, (at, 0)))
    new_k = write(kc, k.astype(kc.dtype), p)
    new_v = write(vc, v.astype(vc.dtype), p)
    qh = q.reshape(B, T, H, hd)
    kh = new_k.astype(q.dtype).reshape(B, S, H, hd)
    vh = new_v.astype(q.dtype).reshape(B, S, H, hd)
    scores = jnp.einsum("bthd,bshd->bhts", qh, kh) / jnp.sqrt(
        jnp.asarray(hd, q.dtype))
    t_idx = jnp.arange(T, dtype=jnp.int32)[None, :, None]
    s_idx = jnp.arange(S, dtype=jnp.int32)[None, None, :]
    q_abs = p[:, None, None] + t_idx
    if alibi:
        slopes = jnp.asarray([2.0 ** (-8.0 * (i + 1) / H)
                              for i in range(H)], scores.dtype)
        dist = (q_abs - s_idx).astype(scores.dtype)
        scores = scores - slopes[None, :, None, None] * dist[:, None]
    scores = jnp.where((s_idx <= q_abs)[:, None, :, :], scores,
                       jnp.asarray(-1e30, scores.dtype))
    att = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", att, vh).reshape(B, T, D)
    return out.astype(q.dtype), new_k, new_v


@pytest.mark.parametrize("alibi", [False, True])
def test_cached_attention_takes_the_decode_path_at_one_token(alibi):
    """T == 1 with heads of 128: the op counts itself onto the kernel,
    writes the row as before and attends it; jax.grad gives what the dense
    formula gives."""
    from mxtpu.ops.nn import cached_attention, decode_path_nodes
    q, k, v, kc, vc = _op_inputs(1, DH, DHD)
    pos = jnp.asarray([0, DS // 2, DS - 1], jnp.int32)
    before = decode_path_nodes()
    out, nk, nv = jax.jit(lambda *a: cached_attention(
        *a, num_heads=DH, alibi=alibi))(q, k, v, kc, vc, pos)
    assert decode_path_nodes() == before + 1
    want, wk, wv = _frozen_dense_op(q, k, v, kc, vc, pos, DH, alibi)
    np.testing.assert_array_equal(np.asarray(nk), np.asarray(wk))
    np.testing.assert_array_equal(np.asarray(nv), np.asarray(wv))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)

    def loss(op):
        return lambda q, k, v: jnp.sum(
            op(q, k, v, kc, vc, pos)[0] * jnp.cos(
                jnp.arange(DD, dtype=jnp.float32)))
    got = jax.grad(loss(lambda *a: cached_attention(
        *a, num_heads=DH, alibi=alibi)), argnums=(0, 1, 2))(q, k, v)
    ref = jax.grad(loss(lambda *a: _frozen_dense_op(*a, DH, alibi)),
                   argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(got, ref):
        assert float(jnp.abs(r).max()) > 0
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("cache_dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("pos", [
    (0, 15, 16),                 # first row, a 16-row block's two edges
    (DS - 1, DS, DS + 7)])       # last row; past the cache: an idle slot
def test_decode_step_writes_its_rows_through_the_kernel(pos, cache_dtype):
    """On the kernel path a cache of whole 16-row blocks takes the step's
    rows through ``cache_write_row``: the node counts itself, and both
    caches are the old op's bit for bit, an idle slot's included
    (``dynamic_update_slice`` holds its start inside the cache too)."""
    from mxtpu.ops.nn import cached_attention, row_write_nodes
    q, k, v, kc, vc = _op_inputs(1, DH, DHD, dtype=cache_dtype)
    p = jnp.asarray(pos, jnp.int32)
    before = row_write_nodes()
    out, nk, nv = jax.jit(lambda *a: cached_attention(
        *a, num_heads=DH, alibi=True))(q, k, v, kc, vc, p)
    assert row_write_nodes() == before + 1
    want, wk, wv = _frozen_dense_op(q, k, v, kc, vc, p, DH, True)
    np.testing.assert_array_equal(np.asarray(nk), np.asarray(wk))
    np.testing.assert_array_equal(np.asarray(nv), np.asarray(wv))
    assert not np.array_equal(np.asarray(nk), np.asarray(kc))
    live = np.asarray(pos) < DS          # an idle slot's output is nobody's
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(want)[live],
                               atol=2e-5, rtol=2e-5)


def test_decode_step_keeps_the_scatter_where_the_cache_is_no_whole_blocks():
    """24 cache rows are a block for the attention kernel and no whole
    16-row blocks: the step attends through the kernel behind XLA's
    scatter, counts no row write and returns the old op's caches."""
    from mxtpu.ops.nn import (cached_attention, decode_path_nodes,
                              row_write_nodes)
    q, k, v, kc, vc = _op_inputs(1, DH, DHD, S=24, dtype=jnp.float32)
    pos = jnp.asarray([0, 17, 23], jnp.int32)
    attended, written = decode_path_nodes(), row_write_nodes()
    out, nk, nv = cached_attention(q, k, v, kc, vc, pos, num_heads=DH,
                                   alibi=True)
    assert decode_path_nodes() == attended + 1
    assert row_write_nodes() == written
    want, wk, wv = _frozen_dense_op(q, k, v, kc, vc, pos, DH, True)
    np.testing.assert_array_equal(np.asarray(nk), np.asarray(wk))
    np.testing.assert_array_equal(np.asarray(nv), np.asarray(wv))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("T,heads,hd,why", [
    (4, 2, 8, "a prefill chunk of small heads"),
    (1, 2, 8, "one token, but a head is no whole 128-lane slab"),
    (4, 2, 128, "heads of 128, but four query rows"),
    (1, 2, 128, "one token, heads of 128, under an ambient mesh")])
def test_cached_attention_keeps_the_dense_path(T, heads, hd, why):
    """Everything but the decode shape returns what it returned before,
    bit for bit, and is not counted, on the attention kernel or on the
    row-write kernel."""
    import contextlib
    from mxtpu.ops.nn import (cached_attention, decode_path_nodes,
                              row_write_nodes)
    from mxtpu.parallel import MeshContext
    q, k, v, kc, vc = _op_inputs(T, heads, hd, S=32, dtype=jnp.float32)
    pos = jnp.asarray([0, 5, 32 - T], jnp.int32)
    mesh = MeshContext(jax.devices()[:1], data=1) if "mesh" in why \
        else contextlib.nullcontext()
    before = decode_path_nodes(), row_write_nodes()
    with mesh:
        got = cached_attention(q, k, v, kc, vc, pos, num_heads=heads,
                               alibi=True)
    assert (decode_path_nodes(), row_write_nodes()) == before, why
    for g, w in zip(got, _frozen_dense_op(q, k, v, kc, vc, pos, heads, True)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
