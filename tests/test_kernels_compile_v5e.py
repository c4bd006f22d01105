"""AOT-compile the Pallas kernels for a TPU v5e without a device.

libtpu is installed in the sandbox, so
``jax.experimental.topologies.get_topology_desc("v5e:2x2", "tpu")`` gives a
compile target with no chip. This is the check that found, before any chip
time was spent, that Mosaic refuses flash-attention blocks (1024, 2048) and
the Pallas LSTM at H >= 1024 (its 16 MiB scoped-VMEM limit) — kept so the
next kernel change meets the compiler here first. It pins three things: the
defaults compile, the limits the framework enforces are real, and a shape
over the limit raises the framework's own error instead of a Mosaic dump.
"""
import functools
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from mxtpu.base import MXNetError
from mxtpu.ops import pallas_attention, pallas_rnn, rnn as rnn_ops
from mxtpu.ops.pallas_attention import flash_attention

@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    except (NotImplementedError, RuntimeError) as e:
        pytest.skip("no libtpu AOT target here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *avals):
    """Lower for TPU and compile; returns the lowered text. A Mosaic
    refusal surfaces as the compile error it is."""
    lowered = jax.jit(fn).trace(*avals).lower(lowering_platforms=("tpu",))
    lowered.compile()
    return lowered.as_text()


def _refused(fn, *avals):
    with pytest.raises(Exception) as err:
        _compile(fn, *avals)
    m = re.search(r"Scoped allocation with size ([\d.]+)M and limit "
                  r"([\d.]+)M", str(err.value))
    assert m, str(err.value)[:500]
    assert float(m.group(1)) > float(m.group(2)) == 16.0
    return float(m.group(1))


def _attn_train(**kw):
    def f(q, k, v):
        return jax.grad(lambda *a: flash_attention(*a, causal=True, **kw)
                        .astype(jnp.float32).sum(), argnums=(0, 1, 2))(
            q, k, v)
    return f


# -- flash attention -------------------------------------------------------------

@pytest.mark.parametrize("d", [64, 128])
def test_flash_default_blocks_compile_at_8k(v5e, d):
    a = jax.ShapeDtypeStruct((1, 8, 8192, d), jnp.bfloat16, sharding=v5e)
    text = _compile(_attn_train(), a, a, a)
    assert "tpu_custom_call" in text       # Mosaic, not the interpreter


@pytest.mark.parametrize("dtype,d", [
    (jnp.bfloat16, 64), (jnp.bfloat16, 256), (jnp.float32, 128),
    (jnp.float32, 256), (jnp.bfloat16, 512), (jnp.float32, 512)])
def test_flash_largest_allowed_blocks_compile(v5e, dtype, d):
    """``_max_block`` is a table read off this compiler: keep it true."""
    edge = pallas_attention._max_block(d, dtype)
    a = jax.ShapeDtypeStruct((1, 2, 8192, d), dtype, sharding=v5e)
    _compile(_attn_train(block_q=edge, block_k=edge), a, a, a)


def test_flash_over_limit_blocks_raise_the_frameworks_error(v5e):
    q = jnp.zeros((1, 8, 8192, 64), jnp.bfloat16)
    with pytest.raises(MXNetError, match="scoped-VMEM limit"):
        flash_attention(q, q, q, causal=True, block_q=1024, block_k=2048)
    with pytest.raises(MXNetError, match="head dim 1024"):
        flash_attention(jnp.zeros((1, 1, 256, 1024), jnp.float32),
                        jnp.zeros((1, 1, 256, 1024), jnp.float32),
                        jnp.zeros((1, 1, 256, 1024), jnp.float32))
    # and the limit is the compiler's, not ours: past the check, Mosaic
    # refuses the same pair
    a = jax.ShapeDtypeStruct((1, 8, 8192, 64), jnp.bfloat16, sharding=v5e)
    offs = jax.ShapeDtypeStruct((4,), jnp.float32, sharding=v5e)
    size = _refused(
        lambda q, k, v, o: pallas_attention._flash_with_lse(
            q, k, v, o, True, 1024, 2048)[0], a, a, a, offs)
    assert 19.0 <= size <= 26.0


# -- recurrent kernels -------------------------------------------------------------

@pytest.mark.parametrize("mode,gates", [("lstm", 4), ("gru", 3)])
def test_default_rnn_path_compiles_at_ptb_sizes(v5e, mode, gates):
    """The default op path (lax.scan) at T=35, N=32 for the PTB small,
    medium and large hidden sizes."""
    assert rnn_ops.USE_PALLAS_RNN is False
    for h in (200, 650, 1500):
        psize = rnn_ops.rnn_param_size(mode, h, h, 1, False)
        S = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32,
                              sharding=v5e)
        args = [S((35, 32, h)), S((psize,)), S((1, 32, h))]
        if mode == "lstm":
            args.append(S((1, 32, h)))
        text = _compile(functools.partial(rnn_ops.rnn, state_size=h,
                                          mode=mode), *args)
        assert "tpu_custom_call" not in text


def test_pallas_rnn_fits_or_raises(v5e):
    def S(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=v5e)

    def lstm(h, n=32):
        return S(35, n, 4 * h), S(n, h), S(n, h), S(h, 4 * h)

    def gru(h, n=32):
        return S(35, n, 3 * h), S(n, h), S(h, 2 * h), S(h, h), S(h)

    # what the estimate admits, Mosaic compiles
    assert "tpu_custom_call" in _compile(pallas_rnn.lstm_scan, *lstm(650))
    assert "tpu_custom_call" in _compile(pallas_rnn.lstm_scan, *lstm(896))
    assert "tpu_custom_call" in _compile(pallas_rnn.gru_scan, *gru(1024))
    # what it cannot hold raises the framework's error, naming the limit
    for fn, avals in ((pallas_rnn.lstm_scan, lstm(1500)),
                      (pallas_rnn.lstm_scan, lstm(1024)),
                      (pallas_rnn.lstm_scan, lstm(896, n=128)),
                      (pallas_rnn.gru_scan, gru(2048))):
        with pytest.raises(MXNetError, match="limit is 16 MiB"):
            jax.jit(fn).trace(*avals)


def test_pallas_rnn_limit_is_the_compilers(v5e, monkeypatch):
    monkeypatch.setattr(pallas_rnn, "_require_fit", lambda *a: None)
    pallas_rnn._fwd_call.cache_clear()       # drop jit caches built above
    try:
        S = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32,
                              sharding=v5e)
        _refused(pallas_rnn.lstm_scan, S((35, 32, 4096)), S((32, 1024)),
                 S((32, 1024)), S((1024, 4096)))
    finally:
        pallas_rnn._fwd_call.cache_clear()


# -- decode attention (cached_attention's one-token path) ------------------------

CELL = dict(B=16, S=2048, D=2048, H=16)     # bloom1b7-saturated's decode step


def _decode_step_avals(v5e, B, S, D, cache_dtype=jnp.bfloat16):
    S_ = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    row, cache = S_((B, 1, D), jnp.bfloat16), S_((B, S, D), cache_dtype)
    return row, row, row, cache, cache, S_((B,), jnp.int32)


def test_decode_path_at_the_cells_shapes_copies_no_cache(v5e):
    """What ISSUEs 28 and 33 are about, pinned without a chip: at the
    saturated cell's decode shapes the op is the Mosaic kernel, both caches
    are still written in place, each by one row-write kernel and no scatter
    loop (a ``while`` of 16 trips a cache), and nothing cache-sized is
    copied or kept as a temporary (the dense formula re-tiled both whole
    caches to heads-minor every step: ``temp_size_in_bytes``
    134,411,264)."""
    from mxtpu.ops.nn import (cached_attention, decode_path_nodes,
                              row_write_nodes)
    B, S, D, H = (CELL[k] for k in "BSDH")
    before, written = decode_path_nodes(), row_write_nodes()
    compiled = jax.jit(
        lambda *a: cached_attention(*a, num_heads=H, alibi=True),
        donate_argnums=(3, 4)).trace(
            *_decode_step_avals(v5e, B, S, D)).lower(
                lowering_platforms=("tpu",)).compile()
    assert decode_path_nodes() == before + 1
    assert row_write_nodes() == written + 1
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not re.search(r"\bwhile\(", text)
    assert len(re.findall(r"cache_write_row\S* = \S+ custom-call\(", text)) == 2
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 2 * B * S * D * 2 == 268435456
    assert mem.temp_size_in_bytes < 8 * 2 ** 20
    for m in re.finditer(r"= \w+\[([\d,]+)\]\S* copy\(", text):
        dims = [int(d) for d in m.group(1).split(",")]
        assert functools.reduce(int.__mul__, dims) < B * S * D, m.group(0)


def test_two_layers_of_a_decode_step_share_one_table_of_live_blocks(v5e):
    """The grid of ``decode_attention`` is the list of live blocks (PR 35),
    four tables and their length reckoned from ``pos``: two layers of a
    BLOOM-shaped step hand their kernels the SAME arrays (XLA merged the
    identical expressions), and the tables brought no loop."""
    from mxtpu.ops.nn import cached_attention
    B, S, D, H = (CELL[k] for k in "BSDH")
    row, _k, _v, cache, _vc, pos = _decode_step_avals(v5e, B, S, D)

    def two_layers(x, kc1, vc1, kc2, vc2, pos):
        for kc, vc in ((kc1, vc1), (kc2, vc2)):
            x = cached_attention(x, x, x, kc, vc, pos, num_heads=H,
                                 alibi=True)[0]
        return x

    text = jax.jit(two_layers).trace(row, cache, cache, cache, cache,
                                     pos).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    assert not re.search(r"\bwhile\(", text)
    calls = re.findall(r"decode_attention\S* = \S+ custom-call\(([^)]*)\)",
                       text)
    assert len(calls) == 2
    # the grid's length, slot, block, tail, flags, pos: the same operands
    tables = [[a.strip() for a in c.split(",")][:6] for c in calls]
    assert tables[0] == tables[1] and len(set(tables[0])) == 6, tables


@pytest.mark.parametrize("heads,d,cache_dtype", [
    (12, 1536, jnp.bfloat16), (16, 2048, jnp.float32),
    (2, 256, jnp.bfloat16)])
def test_decode_kernel_compiles_at_other_widths(v5e, heads, d, cache_dtype):
    from mxtpu.ops.pallas_attention import decode_attention
    q, _k, _v, kc, vc, pos = _decode_step_avals(v5e, 8, 1024, d, cache_dtype)
    assert "tpu_custom_call" in _compile(
        lambda q, kc, vc, pos: decode_attention(q, kc, vc, pos, heads,
                                                alibi=True), q, kc, vc, pos)


def test_decode_over_limit_block_raises_the_frameworks_error(v5e):
    from mxtpu.ops.pallas_attention import _decode_call, decode_attention
    B, S, D, H = (CELL[k] for k in "BSDH")
    q, _k, _v, kc, vc, pos = _decode_step_avals(v5e, B, S, D)
    with pytest.raises(MXNetError, match="scoped-VMEM limit"):
        jax.jit(lambda *a: decode_attention(*a, H, block_s=1024)).trace(
            q, kc, vc, pos)
    # the limit is the compiler's: past the check, Mosaic refuses the block
    slopes = jax.ShapeDtypeStruct((H, 1), jnp.float32, sharding=v5e)
    size = _refused(lambda q, kc, vc, pos, sl: _decode_call()(
        q, kc, vc, pos, sl, D // H, 1024), q, kc, vc, pos, slopes)
    assert size > 16.0


# -- grouped queries and ring caches (kexaone-reasoning-saturated's decode step) --

KEXAONE = dict(B=64, H=64, K=8, hd=128, window=128)


@pytest.mark.parametrize("rows,window", [(2048, 0), (128, 128)])
def test_grouped_decode_step_at_the_cells_shapes(v5e, rows, window):
    """64 query heads over 8 key/value heads, 64 slots: a full layer's cache
    of 2048 rows and a window layer's ring of 128. The op is the Mosaic
    kernel, both caches are written in place, nothing cache-sized is kept."""
    from mxtpu.ops.nn import cached_attention, decode_path_nodes
    B, H, K, hd = (KEXAONE[k] for k in ("B", "H", "K", "hd"))
    S_ = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    q, kv = S_((B, 1, H * hd), jnp.bfloat16), S_((B, 1, K * hd), jnp.bfloat16)
    cache = S_((B, rows, K * hd), jnp.bfloat16)
    gain = S_((hd,), jnp.bfloat16)
    before = decode_path_nodes()
    compiled = jax.jit(
        lambda q, k, v, kc, vc, pos, n, qg, kg: cached_attention(
            q, k, v, kc, vc, pos, n, qg, kg, num_heads=H, num_kv_heads=K,
            window=window, rope_theta=1e6 if window else 0.0),
        donate_argnums=(3, 4)).trace(
            q, kv, kv, cache, cache, S_((B,), jnp.int32), S_((B,), jnp.int32),
            gain, gain).lower(lowering_platforms=("tpu",)).compile()
    assert decode_path_nodes() == before + 1
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 2 * B * rows * K * hd * 2
    assert mem.temp_size_in_bytes < 8 * 2 ** 20


# -- latent attention (xing4.0-29b-a4b's decode step at its serving sizes) ---------

XING4 = dict(B=128, S=3072, H=32, nope=128, rope=64, vd=128, rank=512)


def _latent_step(v5e, width):
    """``latent_attention``'s one-row step at the cell's shapes over a cache
    whose rows are ``width`` columns, compiled for the v5e."""
    from mxtpu.ops.nn import latent_attention
    c = XING4
    S_ = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    bf = jnp.bfloat16
    return jax.jit(
        lambda q, row, g, w, cache, pos: latent_attention(
            q, row, g, w, cache, pos, num_heads=c["H"], nope_dim=c["nope"],
            rope_dim=c["rope"], v_dim=c["vd"], scale=0.14468, rope_factor=64.0),
        donate_argnums=(4,)).trace(
            S_((c["B"], 1, c["H"] * (c["nope"] + c["rope"])), bf),
            S_((c["B"], 1, c["rank"] + c["rope"]), bf), S_((c["rank"],), bf),
            S_((c["H"] * (c["nope"] + c["vd"]), c["rank"]), bf),
            S_((c["B"], c["S"], width), bf), S_((c["B"],), jnp.int32)).lower(
                lowering_platforms=("tpu",)).compile()


def test_latent_decode_step_at_the_cells_shapes(v5e):
    """128 slots, 32 heads, a cache of 3072 rows of 576 values padded to
    640 columns: the op is the Mosaic kernel behind the row-write kernel, the
    cache is written in place, no loop, nothing cache-sized copied or kept."""
    from mxtpu.ops.nn import latent_decode_nodes
    c = XING4
    before = latent_decode_nodes()
    compiled = _latent_step(v5e, 640)
    assert latent_decode_nodes() == before + 1
    text = compiled.as_text()
    assert not re.search(r"\bwhile\(", text)
    assert len(re.findall(
        r"latent_decode_attention\S* = \S+ custom-call\(", text)) == 1
    assert len(re.findall(r"cache_write_row\S* = \S+ custom-call\(", text)) == 1
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == c["B"] * c["S"] * 640 * 2 == 503316480
    assert mem.temp_size_in_bytes < 16 * 2 ** 20
    for m in re.finditer(r"= \w+\[([\d,]+)\]\S* copy\(", text):
        dims = [int(d) for d in m.group(1).split(",")]
        assert functools.reduce(int.__mul__, dims) < 2 ** 24, m.group(0)


def test_a_576_column_cache_is_copied_whole_every_step(v5e):
    """Why the rows are padded: XLA lays an array of 576 columns out
    positions-minor on the TPU (no padding to 640 lanes that way), the
    kernels want rows-minor, and a copy of the whole cache, padded, stands
    in front of them every step."""
    c = XING4
    mem = _latent_step(v5e, 576).memory_analysis()
    assert mem.temp_size_in_bytes >= c["B"] * c["S"] * 640 * 2


@pytest.mark.parametrize("block_s", [256, 1024])
def test_latent_kernel_compiles_at_other_blocks(v5e, block_s):
    from mxtpu.ops.pallas_attention import latent_decode_attention
    c = XING4
    S_ = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    assert "tpu_custom_call" in _compile(
        lambda q, cache, pos: latent_decode_attention(
            q, cache, pos, c["rank"], 0.14468, block_s=block_s),
        S_((c["B"], c["H"], 640), jnp.bfloat16),
        S_((c["B"], c["S"], 640), jnp.bfloat16), S_((c["B"],), jnp.int32))


# -- latent attention at mistral-small-4-119b-2603's serving sizes: a row of
# 320 values in 384 columns, 96 slots of 10,240 rows; a prefill of 8,192 ------

MISTRAL4 = dict(B=96, S=10240, W=384, H=32, nope=64, rope=64, vd=128,
                rank=256)


def _mistral4_attention(v5e, B, T, donate=True):
    """``latent_attention`` as the ``mistral4`` symbol calls it, ``T`` rows a
    sample over the cell's cache, compiled for the v5e."""
    from mxtpu.ops.nn import latent_attention
    c = MISTRAL4
    S_ = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    bf = jnp.bfloat16
    return jax.jit(
        lambda q, row, g, w, cache, pos: latent_attention(
            q, row, g, w, cache, pos, num_heads=c["H"], nope_dim=c["nope"],
            rope_dim=c["rope"], v_dim=c["vd"], scale=0.19497,
            rope_factor=128.0, rope_orig_len=8192, pos_scale_beta=0.1),
        donate_argnums=(4,) if donate else ()).trace(
            S_((B, T, c["H"] * (c["nope"] + c["rope"])), bf),
            S_((B, T, c["rank"] + c["rope"]), bf), S_((c["rank"],), bf),
            S_((c["H"] * (c["nope"] + c["vd"]), c["rank"]), bf),
            S_((B, c["S"], c["W"]), bf), S_((B,), jnp.int32)).lower(
                lowering_platforms=("tpu",)).compile()


def test_latent_decode_step_over_a_384_column_cache(v5e):
    """96 slots, 32 heads, a cache of 10,240 rows of 320 values padded to
    384 columns (three 128-lane slabs): the op is the Mosaic kernel behind
    the row-write kernel, the 755 MB cache is written in place, no loop,
    nothing cache-sized copied or kept."""
    from mxtpu.ops.nn import latent_decode_nodes
    c = MISTRAL4
    before = latent_decode_nodes()
    compiled = _mistral4_attention(v5e, c["B"], 1)
    assert latent_decode_nodes() == before + 1
    text = compiled.as_text()
    assert not re.search(r"\bwhile\(", text)
    assert len(re.findall(
        r"latent_decode_attention\S* = \S+ custom-call\(", text)) == 1
    assert len(re.findall(r"cache_write_row\S* = \S+ custom-call\(", text)) == 1
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == c["B"] * c["S"] * c["W"] * 2 == 754974720
    assert mem.temp_size_in_bytes < 16 * 2 ** 20
    for m in re.finditer(r"= \w+\[([\d,]+)\]\S* copy\(", text):
        dims = [int(d) for d in m.group(1).split(",")]
        assert functools.reduce(int.__mul__, dims) < 2 ** 24, m.group(0)


def test_latent_prefill_of_8192_rows_builds_no_scores_over_the_cache(v5e):
    """One sample, a chunk of 8,192 rows into a cache of 10,240: the chunk
    attends on the kernel ``latent_prefill_attention`` (once for its own
    rows, once inside the loop over the cache's blocks in front of it), and
    the program's temporaries are the chunk's own expanded keys and values,
    a few of 67 MB, where scores ``[32, 8192, 10240]`` in float32 alone
    would be 10.7 GB and keys and values of all 10,240 rows 252 MB."""
    from mxtpu.ops.nn import latent_blockwise_nodes
    c = MISTRAL4
    before = latent_blockwise_nodes()
    compiled = _mistral4_attention(v5e, 1, 8192, donate=False)
    assert latent_blockwise_nodes() == before + 1
    text = compiled.as_text()
    assert len(re.findall(
        r"latent_prefill_attention\S* = \([^=]*\) custom-call\(", text)) == 2
    assert "latent_decode_attention" not in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1 * 2 ** 30
    # no array of heads x T x S, or of all S rows expanded a head, anywhere
    for m in re.finditer(r"= \w+\[([\d,]+)\]", text):
        dims = [int(d) for d in m.group(1).split(",")]
        assert functools.reduce(int.__mul__, dims) <= 8192 * 32 * 192, m.group(0)


@pytest.mark.parametrize("T", [256, 2048])
def test_latent_prefill_kernel_compiles_at_other_buckets(v5e, T):
    from mxtpu.ops.pallas_attention import latent_prefill_attention
    S_ = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    text = _compile(
        lambda q, k, v, n: latent_prefill_attention(q, k, v, 0, n, 32, 0.19497),
        *[S_((1, T, 32 * 128), jnp.bfloat16)] * 3, S_((1,), jnp.int32))
    assert "tpu_custom_call" in text


# -- the expert layer of a prefill chunk (mistral4-longdoc-saturated's largest bucket) --

def _expert_layer(v5e, n, k, d, f, held, wide):
    from mxtpu.ops.nn import moe_ffn_held
    S_ = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    bf = jnp.bfloat16
    return jax.jit(
        lambda x, r, b, g, u, w: moe_ffn_held(x, r, b, g, u, w, top_k=k,
                                              scoring="softmax")).trace(
        S_((1, n, d), bf), S_((wide, d), bf), S_((wide,), jnp.float32),
        S_((held, d, f), bf), S_((held, d, f), bf), S_((held, f, d), bf)
    ).lower(lowering_platforms=("tpu",)).compile()


def test_a_chunks_expert_layer_walks_its_held_rows_in_windows(v5e,
                                                              monkeypatch):
    """The expert layer at Mistral-Small-4's 8,192 bucket (32,768
    assignments, 4,096 wide, experts of 2,048, 16 of 128 held): the three
    grouped products sit inside a ``while`` of traced length over windows
    of 5,120 rows, and the program's temporaries are under the one pass's,
    which holds ``[32768, 4096]`` in float32 more than once."""
    from mxtpu.ops.nn import held_window_nodes
    from mxtpu.parallel import moe
    shapes = (8192, 4, 4096, 2048, 16, 128)
    assert moe.held_window(32768, 16, 128) == (5120, 256)
    # a decode step of the same layer (96 slots) keeps the one pass
    assert moe.held_window(96 * 4, 16, 128) is None
    before = held_window_nodes()
    walked = _expert_layer(v5e, *shapes)
    assert held_window_nodes() == before + 1
    text = walked.as_text()
    kernels = re.findall(r"%gmm\S* = [^\n]*custom-call\([^\n]*"
                         r"op_name=\"([^\"]*)/pallas_call\"", text)
    assert len(kernels) == 3 and all("/while/body/" in k for k in kernels)
    for m in re.finditer(r"= f32\[([\d,]+)\]", text):
        dims = [int(n) for n in m.group(1).split(",")]
        assert functools.reduce(int.__mul__, dims) <= 8192 * 4096, m.group(0)
    monkeypatch.setattr(moe, "held_window", lambda *shapes: None)
    one_pass = _expert_layer(v5e, *shapes)
    assert held_window_nodes() == before + 1
    assert (walked.memory_analysis().temp_size_in_bytes
            < one_pass.memory_analysis().temp_size_in_bytes)
    assert "f32[32768,4096]" in one_pass.as_text()
