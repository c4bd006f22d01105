"""The ``exaone_moe`` symbol (``benchmarks/models``) through
``InferenceEngine`` against its plain reference (``benchmarks/reference``),
at tiny widths on the CPU with seeded weights: grouped-query attention over
ring and full caches, rotary positions, per-head norms, RMSNorm, the
sigmoid top-k expert layer on the experts held, the generate contract with
a length per state."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny_cfg(**over):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "k-exaone-236b-a23b.json")) as f:
        cfg = json.load(f)
    cfg.update({"hidden_size": 64, "head_dim": 16, "num_attention_heads": 4,
                "num_key_value_heads": 2, "intermediate_size": 96,
                "moe_intermediate_size": 32, "num_experts": 4,
                "router_width": 16, "num_experts_per_tok": 4,
                "vocab_size": 256, "sliding_window": 8, "ring_rows": 8,
                "cache_len": 64, "slots": 4, "cache_dtype": "float32",
                "init_std": {"matrix": 0.2, "emb": 1.0, "out_attn": 0.002,
                             "out_dense": 0.002, "out_expert": 0.002,
                             "out_shared": 0.002, "router": 0.2,
                             "bias": 0.05, "qk_gamma": 1.0}})
    cfg.update(over)
    return cfg


def float32_weights(cfg, seed):
    """The reference's own leaves (bfloat16 values) held in float32, so that
    program and reference compute on the same numbers in one precision."""
    from benchmarks.reference import exaone_moe as ref
    w = ref.init_weights(cfg, seed)
    return {n: np.asarray(w[n].astype(jnp.float32)) for n in w}


class Held(dict):
    def layer(self, i):
        p = "l%d_" % i
        return {n[len(p):]: v for n, v in self.items() if n.startswith(p)}


@pytest.fixture(scope="module")
def served():
    """One engine, one prompt of 21 tokens prefilled in a bucket of 32 (four
    times the rings' 8 rows) and 24 tokens decoded in slot 2 of 4."""
    from benchmarks.models import exaone_moe as model
    from benchmarks.reference import exaone_moe as ref
    from mxtpu.serving import InferenceEngine
    os.environ["MXTPU_SERVE_GENERATE_PREFILL_BUCKETS"] = "8,32"
    cfg = tiny_cfg()
    weights = float32_weights(cfg, 7)
    engine = InferenceEngine(model.symbol(cfg), weights, {}, {"data": (1,)},
                             buckets=(1,), dtype="float32", warm=False)
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg["vocab_size"], size=21)
    first, rows = engine.gen_prefill(prompt, engine._param_vals,
                                     engine._aux_vals)
    state = engine.gen_state_init(4)
    state = engine.gen_adopt(state, first, len(prompt), rows, 2)
    tokens = [int(np.asarray(first)[0])]
    for _ in range(23):
        nxt, state = engine.gen_step(state, engine._param_vals,
                                     engine._aux_vals)
        tokens.append(int(np.asarray(nxt)[2]))
    seq = np.concatenate([prompt, tokens[:-1]])
    lg = np.asarray(ref.logits(cfg, Held(weights), seq,
                               np.arange(len(prompt) - 1, len(seq))))
    return cfg, engine, prompt, np.asarray(tokens), lg


def test_prefill_then_decode_follows_the_reference(served):
    """Every served token is the reference's best but for a gap of 1e-3: in
    float32 on both sides what is left is the order of summation (the
    reference runs ``highest`` precision over the whole sequence, the program
    a row at a time through the caches), some 1e-5 on logits of a few
    units. A wrong band, ring row, position or expert reads whole units."""
    _cfg, _engine, _prompt, tokens, lg = served
    took = lg[np.arange(len(tokens)), tokens]
    assert float(np.max(lg.max(-1) - took)) < 1e-3
    assert len(set(tokens.tolist())) > 4         # not one token repeated


def test_each_state_keeps_its_own_length(served):
    cfg, engine, _prompt, _tokens, _lg = served
    spec = engine.generate_spec()
    assert spec["cache_len"] == cfg["cache_len"] == 64   # the full layers'
    assert engine.gen_prefill_menu() == (8, 32)          # 32 > a ring's 8
    assert spec["state_rows"]["kc0"] == 8 and spec["state_rows"]["kc3"] == 64
    assert spec["state_kinds"]["vc1"] == "ring"
    assert spec["state_kinds"]["moe_load1"] == "sum:moe_load"
    stats = engine.stats()
    by_kind = stats["gen_state_bytes"]
    row = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * 4   # K and V
    assert by_kind["ring"] == 6 * 4 * 8 * row
    assert by_kind["full"] == 2 * 4 * 64 * row
    assert by_kind["sum"] == 7 * 9 * 4


def test_expert_sums_count_every_assignment(served):
    """The device sums, read in ``stats()``: every true row a program routed
    (a prompt of 21, the padding of its bucket of 32 left out, and 23 steps
    of 4 slots, idle ones included), 4 choices a row, in each of the 7
    expert layers; held assignments no more than all; the registry holds
    the same."""
    from mxtpu import obs
    cfg, engine, prompt, tokens, _lg = served
    sums = engine.stats()["gen_sums"]
    assert sorted(sums) == ["moe_load%d" % i for i in range(1, 8)]
    routed = (len(prompt) + 4 * (len(tokens) - 1)) * cfg["num_experts_per_tok"]
    for total in sums.values():
        assert total[-5] == routed and 0 < sum(total[:-5]) < routed
        # 23 decode runs, each hitting at most all four held experts, and
        # the one prefill chunk
        assert total[-3] == 23 and 0 < total[-4] <= 4 * 23
        assert total[-1] == 1 and 0 < total[-2] <= 4
    snap = obs.REGISTRY.snapshot()["metrics"]
    assert snap["ops.moe_ffn.assignments"]["series"]["moe_load1"] >= routed
    assert snap["ops.moe_ffn.max_load"]["series"]["moe_load1"] >= 1.0
    assert 0.0 < snap["ops.moe_ffn.experts_hit"]["series"]["moe_load1"] <= 4.0
    assert 0.0 < snap["ops.moe_ffn.experts_hit_run"]["series"]["moe_load1"] <= 4.0


def test_counters_say_where_the_held_rows_are_walked_in_windows(
        served, tiny_windows):
    """At these widths no program walks windows (a chunk of 32 rows is 128
    assignments, a step 16). With the rule at these widths' scale the
    bucket of 32 rows walks them in each of the 7 expert layers (windows of
    40 rows, 4 of 16 experts held), the decode program in none, and the
    prefill's first token and expert counts are the one pass's."""
    from benchmarks.models import exaone_moe as model
    from mxtpu.serving import InferenceEngine
    cfg, one_pass, prompt, tokens, _lg = served
    st = one_pass.stats()
    assert st["gen_prefill_moe_window"] == st["gen_decode_moe_window"] == 0
    engine = InferenceEngine(model.symbol(cfg), float32_weights(cfg, 7), {},
                             {"data": (1,)}, buckets=(1,), dtype="float32",
                             warm=False)
    first, rows = engine.gen_prefill(prompt, engine._param_vals,
                                     engine._aux_vals)
    engine.gen_decode_program(4)
    st = engine.stats()
    assert st["gen_prefill_moe_window"] == 7
    assert st["gen_decode_moe_window"] == 0
    assert int(np.asarray(first)[0]) == int(tokens[0])
    _first, want = one_pass.gen_prefill(prompt, one_pass._param_vals,
                                        one_pass._aux_vals)
    sums = one_pass._gen["sum_states"]
    assert len(sums) == 7
    for i in sums:
        assert np.asarray(rows[i]).tolist() == np.asarray(want[i]).tolist()
        assert np.asarray(rows[i])[0, -5] == 21 * 4


def test_a_device_sum_that_wraps_is_read_as_what_it_gained(served):
    """The device's sums are int32 and never reset: one left just under
    2^31 wraps in the next steps, and the reading after takes the difference
    modulo 2^32: the registry grows by what was routed, the totals go on in
    int64, and the gauges say how the steps since the last reading went."""
    from mxtpu import obs
    cfg, engine, _prompt, _tokens, _lg = served
    series = lambda: obs.REGISTRY.snapshot()["metrics"][  # noqa: E731
        "ops.moe_ffn.assignments"]["series"]["moe_load1"]
    before_total = engine.stats()["gen_sums"]["moe_load1"]
    before = series()
    with engine._sums_lock:
        near = [np.asarray(v).copy() for v in jax.device_get(engine._sums())]
        shift = 2 ** 31 - 5 - int(near[0][0, -5])
        near[0][0, -5] += shift                # 5 short of wrapping
        engine._gen_sums = tuple(jax.device_put(v) for v in near)
        seen, total = engine._sums_published["moe_load1"]
        seen = seen.copy()
        seen[-5] += shift
        engine._sums_published["moe_load1"] = (seen, total)
    state = engine.gen_state_init(4)
    for _ in range(3):
        _nxt, state = engine.gen_step(state, engine._param_vals,
                                      engine._aux_vals)
    gained = 3 * 4 * cfg["num_experts_per_tok"]
    assert int(np.asarray(engine._gen_sums[0])[0, -5]) < 0      # it wrapped
    after_total = engine.stats()["gen_sums"]["moe_load1"]
    assert series() - before == gained
    assert after_total[-5] - before_total[-5] == gained
    assert after_total[-3] - before_total[-3] == 3
    hit = obs.REGISTRY.snapshot()["metrics"]["ops.moe_ffn.experts_hit"][
        "series"]["moe_load1"]
    assert hit == (after_total[-4] - before_total[-4]) / 3


def moe_inputs(seed, n=40, d=32, f=16, wide=16):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d)).astype(np.float32)
    return x, {
        "router_weight": rng.standard_normal((wide, d)).astype(np.float32) / 4,
        "router_bias": 0.1 * rng.standard_normal(wide).astype(np.float32),
        "eg_weight": 0.2 * rng.standard_normal((wide, d, f)).astype(np.float32),
        "eu_weight": 0.2 * rng.standard_normal((wide, d, f)).astype(np.float32),
        "ed_weight": 0.2 * rng.standard_normal((wide, f, d)).astype(np.float32),
        "sg_weight": 0.2 * rng.standard_normal((f, d)).astype(np.float32),
        "su_weight": 0.2 * rng.standard_normal((f, d)).astype(np.float32),
        "sd_weight": 0.2 * rng.standard_normal((d, f)).astype(np.float32)}


def test_eight_shares_add_up_to_the_uncut_layer():
    """Each of 8 devices holds 2 of 16 experts and computes its own experts'
    part; with the shared expert, which every device computes alike, counted
    once, the parts add up to the reference's uncut layer."""
    from benchmarks.reference import exaone_moe as ref
    from mxtpu.ops.nn import moe_ffn_held
    x, w = moe_inputs(11)
    with jax.default_matmul_precision("highest"):
        whole = ref.moe(jnp.asarray(x), {k: jnp.asarray(v) for k, v in w.items()},
                        4, 2.5, 0, False)
        shared = ref.gated(jnp.asarray(x), w["sg_weight"], w["su_weight"],
                           w["sd_weight"], False)
        parts, held_total = 0.0, 0
        for share in range(8):
            lo = 2 * share
            y, load = moe_ffn_held(
                jnp.asarray(x), w["router_weight"], w["router_bias"],
                *(w[k][lo:lo + 2] for k in ("eg_weight", "eu_weight",
                                            "ed_weight")),
                top_k=4, expert_first=lo, scale=2.5)
            parts = parts + y
            held_total += int(np.asarray(load)[0, :-5].sum())
            assert int(np.asarray(load)[0, -5]) == 40 * 4
    assert held_total == 40 * 4          # every assignment is someone's
    np.testing.assert_allclose(np.asarray(parts + shared), np.asarray(whole),
                               atol=2e-5, rtol=2e-5)


def windowed_layer(case):
    """Inputs of one expert layer whose chunk is large enough for the walk
    in windows (2,048 assignments over 16 experts, 2 held from expert 6: a
    window of 384 rows in tiles of 128), shaped by ``case``: ``(x [1, N, D],
    router_weight, select_bias, the three held weights, top_k, valid_len or
    None, the held rows the case must give or None)``."""
    rng = np.random.default_rng(21)
    d, f, wide, lo = 32, 16, 16, 6
    n, k, valid, n_held = 512, 4, None, None
    router = rng.standard_normal((wide, d)).astype(np.float32) / 4
    bias = np.zeros(wide, np.float32)
    if case == "none held":
        bias[lo:lo + 2], n_held = -100.0, 0
    elif case != "an eighth held":
        # one expert a token, chosen by the bias: every true row is held
        n, k = 2048, 1
        bias[lo + 1] = 100.0
        valid = {"all on one held expert": None, "twice the window": 768,
                 "one over twice the window": 769,
                 "an expert across two windows": 600,
                 "padding that would have been held": 100}[case]
        n_held = valid or n
    x = rng.standard_normal((n, d)).astype(np.float32)
    if case == "an expert across two windows":
        # rows 0 .. 299 of the sorted order are expert 6's, 300 .. 599 expert
        # 7's: the window's edge at 384 cuts the second group
        bias[lo] = 100.0
        x[:, 0] = np.where(np.arange(n) % 2 == 0, 40.0, -40.0)
        router[lo], router[lo + 1] = 0.0, 0.0
        router[lo, 0], router[lo + 1, 0] = 1.0, -1.0
    held = [0.2 * rng.standard_normal(shape).astype(np.float32)
            for shape in ((2, d, f), (2, d, f), (2, f, d))]
    return x[None], router, bias, held, k, lo, valid, n_held


def plain_held_layer(x, router, bias, held, k, lo, valid, scale):
    """The held experts' part by the formula, a token at a time in float64:
    sigmoid scores, the ``k`` largest of score + bias, their scores
    renormalised; rows past ``valid`` are nobody's."""
    x = x[0].astype(np.float64)
    y = np.zeros_like(x)
    load = np.zeros(len(held[0]) + 1, np.int64)
    score = 1 / (1 + np.exp(-(x @ router.T.astype(np.float64))))
    for t in range(x.shape[0] if valid is None else valid):
        chosen = np.argsort(-(score[t] + bias), kind="stable")[:k]
        w = scale * score[t, chosen] / score[t, chosen].sum()
        load[-1] += k
        for e, we in zip(chosen - lo, w):
            if 0 <= e < len(held[0]):
                h = x[t] @ held[0][e]
                y[t] += we * ((h / (1 + np.exp(-h))) * (x[t] @ held[1][e])
                              ) @ held[2][e]
                load[e] += 1
    return y, load


@pytest.mark.parametrize("case", [
    "an eighth held", "none held", "all on one held expert",
    "twice the window", "one over twice the window",
    "an expert across two windows", "padding that would have been held"])
def test_the_walk_in_windows_is_the_one_pass_and_the_formula(case,
                                                             monkeypatch):
    """A chunk whose shapes take the walk in windows (``held_window``: 384
    rows a trip here) gives what the one pass over all 2,048 rows gives,
    with the same ``load`` bit for bit, and what the plain formula gives: no
    held row is lost where the held rows fill several windows or end on a
    window's edge, none is invented where none is held or where padding
    would have been."""
    from mxtpu.ops import nn
    from mxtpu.parallel import moe
    x, router, bias, held, k, lo, valid, n_held = windowed_layer(case)
    assert moe.held_window(x.shape[1] * k, 2, 16) == (384, 128)
    args = (jnp.asarray(x), router, bias) + tuple(held)
    kw = dict(top_k=k, expert_first=lo, scale=2.5,
              valid_len=None if valid is None else jnp.asarray([valid]))
    before = nn.held_window_nodes()
    with jax.default_matmul_precision("highest"):
        y, load = nn.moe_ffn_held(*args, **kw)
        assert nn.held_window_nodes() == before + 1
        monkeypatch.setattr(moe, "held_window", lambda *shapes: None)
        y_one, load_one = nn.moe_ffn_held(*args, **kw)
        assert nn.held_window_nodes() == before + 1
    want, want_load = plain_held_layer(x, router, bias, held, k, lo, valid,
                                       2.5)
    load = np.asarray(load)[0]
    assert load.tolist() == np.asarray(load_one)[0].tolist()
    assert load[:3].tolist() == want_load.tolist()
    if n_held is not None:
        assert load[:2].sum() == n_held
    trips = -(-int(load[:2].sum()) // 384)
    assert trips == {"none held": 0, "all on one held expert": 6,
                     "twice the window": 2, "one over twice the window": 3,
                     "an expert across two windows": 2,
                     "padding that would have been held": 1}.get(case, trips)
    if case == "an expert across two windows":
        assert load[:2].tolist() == [300, 300]
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_one), atol=1e-5,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(y)[0], want, atol=2e-5, rtol=2e-5)
    if valid is not None:
        assert not np.asarray(y)[0, valid:].any()
        assert np.abs(np.asarray(y)[0, :valid]).min(axis=1).max() > 0
    if case == "none held":
        assert not np.asarray(y).any()


def test_every_token_on_one_expert_and_none_is_lost():
    """No capacity: with a selection bias that sends all 40 tokens to expert
    5, the device that holds it computes all 40 and the others none."""
    from mxtpu.ops.nn import moe_ffn_held
    x, w = moe_inputs(12)
    bias = w["router_bias"].copy()
    bias[5] = 100.0
    args = lambda lo: (jnp.asarray(x), w["router_weight"], bias) + tuple(  # noqa: E731
        w[k][lo:lo + 4] for k in ("eg_weight", "eu_weight", "ed_weight"))
    y, load = moe_ffn_held(*args(4), top_k=1, expert_first=4, scale=2.5)
    assert np.asarray(load)[0].tolist() == [0, 40, 0, 0, 40, 0, 0, 1, 1]
    h = x @ w["eg_weight"][5]
    want = 2.5 * ((h / (1 + np.exp(-h))) * (x @ w["eu_weight"][5])) @ w["ed_weight"][5]
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-4, rtol=1e-4)
    assert np.abs(np.asarray(y)).min(axis=1).max() > 0      # none is zero
    y, load = moe_ffn_held(*args(8), top_k=1, expert_first=8, scale=2.5)
    assert np.asarray(load)[0].tolist() == [0, 0, 0, 0, 40, 0, 0, 0, 1]
    assert not np.asarray(y).any()


def test_rms_norm_symbol():
    import mxtpu as mx
    x = np.random.default_rng(0).standard_normal((3, 5, 8)).astype(np.float32)
    g = np.linspace(0.5, 1.5, 8).astype(np.float32)
    sym = mx.sym.RMSNorm(mx.sym.Variable("data"), eps=1e-5, name="n")
    assert sym.list_arguments() == ["data", "n_gamma"]
    _args, outs, _aux = sym.infer_shape(data=x.shape)
    assert tuple(outs[0]) == x.shape
    got = mx.nd.RMSNorm(mx.nd.array(x), mx.nd.array(g), eps=1e-5).asnumpy()
    want = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * g
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=1e-5)
