"""The ``mistral4`` symbol (``benchmarks/models``) through ``InferenceEngine``
and ``GenerateScheduler`` against its plain reference
(``benchmarks/reference``), at tiny widths on the CPU with seeded weights:
latent attention over one cached row a position (prefills on the block-wise
chunk path and its interpreted kernel, decode steps absorbed on the
interpreted decode kernel), the position's query scale and YaRN's ramp inside
a 64-row cache (the original context is cut to 16), the softmax top-k expert
layer that is told which experts it holds, the generate contract."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "benchmarks", "configs",
                      "mistral-small-4-119b-2603.json")


def published():
    with open(CONFIG) as f:
        return json.load(f)


def tiny_cfg(**over):
    """Widths cut, structure kept: heads of 64 + 64 query columns and 128
    value columns (one width of a whole slab, as published, so that a chunk
    takes the block-wise path) over a latent row of 128 + 64 in 256 columns
    (a rank of one slab, so that a decode step takes the kernel); 4 of 16
    experts held; an original context of 16, so that positions 16 .. 63 carry
    a query scale over 1 and YaRN's ramp (pairs 0 to 4) acts."""
    cfg = published()
    cfg.update({"hidden_size": 64, "num_attention_heads": 2,
                "q_lora_rank": 32, "kv_lora_rank": 128,
                "moe_intermediate_size": 32, "n_routed_experts": 4,
                "router_width": 16, "num_experts_per_tok": 2,
                "vocab_size": 256, "num_hidden_layers": 3, "cache_len": 64,
                "cache_row": 256, "slots": 4, "cache_dtype": "float32",
                "rope_parameters": dict(
                    cfg["rope_parameters"], factor=8,
                    original_max_position_embeddings=16)})
    cfg.update(over)
    return cfg


class Held(dict):
    def layer(self, i):
        p = "l%d_" % i
        return {n[len(p):]: v for n, v in self.items() if n.startswith(p)}


def float32_weights(cfg, seed):
    """The reference's own leaves (bfloat16 values) held in float32, so that
    program and reference compute on the same numbers in one precision."""
    from benchmarks.reference import mistral4 as ref
    w = ref.init_weights(cfg, seed)
    return Held({n: np.asarray(w[n].astype(jnp.float32)) for n in w})


def program_logits(cfg, weights, tokens, pieces, padded_to=None):
    """The symbol evaluated as the engine's programs evaluate it, over
    ``tokens`` cut into ``pieces`` (a piece of one token is a decode step; a
    longer one is padded to ``padded_to`` rows as a bucketed prefill is, with
    ``len`` its true length): logits ``[len(tokens), vocab]``."""
    from benchmarks.models import mistral4 as model
    from mxtpu.symbol import eval_graph
    sym = model.symbol(cfg)
    names = sym.list_outputs()
    states = {"lc%d" % i: jnp.zeros((1, cfg["cache_len"], cfg["cache_row"]),
                                    jnp.float32)
              for i in range(cfg["num_hidden_layers"])}
    sums = {n[:-len("_next_output")]: jnp.zeros(
        (1, cfg["n_routed_experts"] + 5), jnp.int32)
        for n in names if n.startswith("moe_load")}

    @jax.jit
    def piece(chunk, pos, true_len, states):
        feed = {n: jnp.asarray(v) for n, v in weights.items()}
        feed.update(states, **sums)
        feed.update(data=chunk, pos=pos, len=true_len)
        outs, _aux = eval_graph(sym._outputs, feed, False)
        by_name = dict(zip(names, outs))
        return by_name["head_output"], {
            n: by_name[n + "_next_output"] for n in states}

    out, at = [], 0
    for n in pieces:
        chunk = np.asarray(tokens[at:at + n], np.float32)
        if padded_to and n > 1:
            chunk = np.concatenate([chunk, np.full(padded_to - n, 7.0,
                                                   np.float32)])
        lg, states = piece(jnp.asarray(chunk[None]),
                           jnp.full((1,), at, jnp.int32),
                           jnp.full((1,), n, jnp.int32), states)
        out.append(np.asarray(lg[0, :n]))
        at += n
    return np.concatenate(out)


@pytest.fixture(scope="module")
def text():
    """A model, 40 random tokens and the reference's logits at each."""
    from benchmarks.reference import mistral4 as ref
    cfg = tiny_cfg()
    weights = float32_weights(cfg, 7)
    tokens = np.random.default_rng(5).integers(0, cfg["vocab_size"], size=40)
    want = np.asarray(ref.logits(cfg, weights, tokens, np.arange(40)))
    return cfg, weights, tokens, want


# float32 on both sides: what is left is the order of summation (the
# reference runs the whole sequence at once, unabsorbed, a softmax over whole
# rows; the program a piece at a time through the caches, a chunk tile by
# tile with a running softmax). Logits are a few units wide.
TOLERANCE = 3e-4


@pytest.mark.parametrize("pieces,padded_to", [
    ((40,), None),                      # one block-wise chunk at pos 0
    ((24, 16), None),                   # a second block-wise chunk at pos 24
    ((30,), 32),                        # a padded prefill: 30 true rows of 32
    ((21,) + (1,) * 19, 32),            # prefill in a bucket, then decode
    ((13, 18), None),                   # chunks that keep the dense formulas
])
def test_logits_follow_the_reference(text, pieces, padded_to):
    """Chunks at position 0 and beyond, padded and not, then decode steps on
    the kernel across position 16, where the query scale leaves 1: every
    logit of every true position is the reference's."""
    from mxtpu.ops import nn
    cfg, weights, tokens, want = text
    n = sum(pieces)
    before = nn.latent_blockwise_nodes(), nn.latent_decode_nodes()
    got = program_logits(cfg, weights, tokens[:n], pieces, padded_to)
    assert np.max(np.abs(got - want[:n])) < TOLERANCE
    assert want.std() > 1.0
    chunks = [p for p in pieces if p > 1]
    on_kernel = [p for p in chunks if (padded_to or p) % 8 == 0]
    assert (nn.latent_blockwise_nodes() - before[0]
            == 3 * len(set(on_kernel)))      # a trace a shape, 3 layers
    assert (nn.latent_decode_nodes() > before[1]) == (1 in pieces)


def test_a_dropped_query_scale_or_a_sigmoid_router_fails_that_tolerance(
        text, monkeypatch):
    """The tolerance is tight enough to see both faults the chip's run
    plants (``benchmarks/tests/on_chip_fault_mistral4.py``): the position's
    query scale left at 1, and the router's softmax replaced by the
    sigmoid."""
    import sys
    sys.path.insert(0, os.path.join(ROOT, "benchmarks", "tests"))
    import on_chip_fault_mistral4 as faults
    cfg, weights, tokens, want = text
    pieces = (24,) + (1,) * 16
    for fault in ("query_scale_dropped", "router_sigmoid"):
        with faults.planted(fault):
            off = np.max(np.abs(program_logits(cfg, weights, tokens, pieces)
                                - want))
        assert off > 30 * TOLERANCE, fault


def test_the_scale_is_the_symbols_the_references_and_the_published():
    """Program and reference each compute YaRN's ``mscale``-squared scale
    from the configuration's keys; both are the 0.19497 that the
    configuration's ``assumed`` states, and the symbol's nodes carry the
    program's own, with the published beta; ``g`` is 1 inside the first
    original context and 1 + 0.1 ln 2 in the second; the ramp runs from pair
    12 to pair 25, and the program's table is the reference's."""
    from benchmarks.models import mistral4 as model
    from benchmarks.reference import mistral4 as ref
    from mxtpu.ops.nn import yarn_frequencies
    pub = published()
    assert abs(model.attention_scale(pub) - 0.19497) < 5e-6
    assert abs(128 ** -0.5 * (0.1 * np.log(128) + 1) ** 2
               - model.attention_scale(pub)) < 1e-12
    assert any("0.19497" in line for line in pub["assumed"])
    for cfg in (pub, tiny_cfg()):
        mine, theirs = model.attention_scale(cfg), ref.softmax_scale(cfg)
        assert abs(mine - theirs) <= 1e-12 * theirs
    cfg = tiny_cfg()
    nodes = json.loads(model.symbol(cfg).tojson())["nodes"]
    attn = [n["attrs"] for n in nodes if n["op"] == "latent_attention"]
    assert [float(a["scale"]) for a in attn] == [model.attention_scale(cfg)] * 3
    assert [float(a["pos_scale_beta"]) for a in attn] == [0.1] * 3
    routers = [n["attrs"]["scoring"].strip("'") for n in nodes
               if n["op"] == "moe_ffn_held"]
    assert routers == ["softmax"] * 3
    g = ref.position_scale(pub, [0, 8191, 8192, 10239, 16384])
    assert np.allclose(g, [1, 1, 1 + 0.1 * np.log(2), 1 + 0.1 * np.log(2),
                           1 + 0.1 * np.log(3)])
    w = ref.yarn_freqs(pub)
    f = 10000.0 ** (-2.0 * np.arange(32) / 64)
    assert np.allclose(w[:13], f[:13], rtol=1e-6)           # untouched
    assert np.allclose(w[25:], f[25:] / 128, rtol=1e-6)     # stretched 128 x
    assert f[18] / 128 < w[18] < f[18]
    rs = pub["rope_parameters"]
    mine = yarn_frequencies(64, rs["rope_theta"], rs["factor"],
                            rs["beta_fast"], rs["beta_slow"],
                            rs["original_max_position_embeddings"])
    assert np.allclose(mine, w, rtol=1e-6)


@pytest.fixture(scope="module")
def served():
    """One engine behind a ``GenerateScheduler`` of 4 slots; six prompts of
    unequal length, 12 tokens each."""
    from benchmarks.models import mistral4 as model
    from mxtpu.serving import InferenceEngine
    from mxtpu.serving.batcher import GenerateScheduler
    os.environ["MXTPU_SERVE_GENERATE_PREFILL_BUCKETS"] = "8,32"
    cfg = tiny_cfg()
    weights = float32_weights(cfg, 7)
    engine = InferenceEngine(model.symbol(cfg), dict(weights), {},
                             {"data": (1,)}, buckets=(1,), dtype="float32",
                             warm=False)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg["vocab_size"], size=n)
               for n in (3, 12, 1, 7, 21, 29)]
    sched = GenerateScheduler(engine, 16, slots=4)
    try:
        reqs = [sched.submit("r%d" % j, p, 12, None)
                for j, p in enumerate(prompts)]
        replies = [r.wait(300) for r in reqs]
        stats = sched.stats()
    finally:
        sched.stop()
    assert all(r[0] == "ok" for r in replies), replies
    return cfg, weights, engine, prompts, [
        np.asarray(r[1]["tokens"]) for r in replies], stats


def test_served_tokens_are_the_references_best_by_its_logits(served):
    """Through ``GenerateScheduler`` (prefill in a bucket, adoption into a
    slot, batched decode steps beside other sequences, positions up to 40:
    past the original context of 16): at every position the served token's
    logit, in the reference's full forward pass over prompt and served
    tokens, is the best one but for the tolerance."""
    from benchmarks.reference import mistral4 as ref
    cfg, weights, _engine, prompts, tokens, _stats = served
    for prompt, out in zip(prompts, tokens):
        assert len(out) == 12
        seq = np.concatenate([prompt, out[:-1]])
        lg = np.asarray(ref.logits(cfg, weights, seq,
                                   np.arange(len(prompt) - 1, len(seq))))
        took = lg[np.arange(len(out)), out]
        assert float(np.max(lg.max(-1) - took)) < TOLERANCE
    assert len({tuple(t.tolist()) for t in tokens}) == len(tokens)


def test_a_layer_keeps_one_full_state_and_its_expert_sums(served):
    cfg, _weights, engine, _prompts, _tokens, _stats = served
    spec = engine.generate_spec()
    assert spec["cache_len"] == cfg["cache_len"] == 64
    caches = [n for n in spec["states"] if n.startswith("lc")]
    assert caches == ["lc0", "lc1", "lc2"]         # ONE a layer, no k and v
    assert all(spec["state_kinds"][n] == "full" for n in caches)
    assert spec["state_kinds"]["moe_load0"] == "sum:moe_load"
    by_kind = engine.stats()["gen_state_bytes"]
    assert by_kind["full"] == 3 * 4 * 64 * cfg["cache_row"] * 4
    assert by_kind["sum"] == 3 * (4 + 5) * 4
    sums = engine.stats()["gen_sums"]
    assert sorted(sums) == ["moe_load0", "moe_load1", "moe_load2"]
    for total in sums.values():
        # 4 of 16 held: some of a text's assignments are someone else's
        assert 0 < sum(total[:-5]) < total[-5]


def test_counters_say_which_path_each_program_took(served):
    """The decode program: every layer's attention on the latent decode
    kernel. The two prefill programs (buckets 8 and 32): every layer on the
    block-wise chunk path, none on the decode kernel. The scheduler counts
    the prompt rows it prefilled, true and as the buckets padded them."""
    from mxtpu import obs
    cfg, _weights, engine, prompts, _tokens, stats = served
    st = engine.stats()
    layers = cfg["num_hidden_layers"]
    assert st["gen_decode_latent_path"] == layers
    assert st["gen_decode_latent_blockwise"] == 0
    assert st["gen_prefill_latent_path"] == 0
    assert st["gen_prefill_latent_blockwise"] == 2 * layers
    # chunks of 16 and 64 assignments, steps of 8: one pass (held_window)
    assert st["gen_prefill_moe_window"] == st["gen_decode_moe_window"] == 0
    snap = obs.REGISTRY.snapshot()["metrics"]
    assert snap["ops.latent_attention.chunk_blockwise"]["series"]
    # the process's own counters outlive a stopped scheduler's series
    rows = sum(snap["serve.gen.prefill_rows"]["series"].values())
    padded = sum(snap["serve.gen.prefill_rows_padded"]["series"].values())
    assert rows >= 73 and padded >= 3 * 8 + 3 * 32 and padded > rows
    assert stats["prefills"] == 6
    assert stats["prefill_rows"] == sum(len(p) for p in prompts) == 73
    assert stats["prefill_rows_padded"] == 3 * 8 + 3 * 32


def test_a_prefill_that_walks_its_held_rows_in_windows(served, tiny_windows):
    """With the rule at these widths' scale the bucket of 32 rows (64
    assignments, 4 of 16 experts held: windows of 24 rows) walks its held
    rows in every layer and the decode program (8 assignments) in none; the
    prefill's first token and its expert counts are the one pass's."""
    from benchmarks.models import mistral4 as model
    from mxtpu.serving import InferenceEngine
    cfg, weights, one_pass, prompts, tokens, _stats = served
    engine = InferenceEngine(model.symbol(cfg), dict(weights), {},
                             {"data": (1,)}, buckets=(1,), dtype="float32",
                             warm=False)
    first, rows = engine.gen_prefill(prompts[5], engine._param_vals,
                                     engine._aux_vals)
    engine.gen_decode_program(4)
    st = engine.stats()
    assert st["gen_prefill_moe_window"] == cfg["num_hidden_layers"]
    assert st["gen_decode_moe_window"] == 0
    assert st["gen_prefill_latent_blockwise"] == cfg["num_hidden_layers"]
    assert int(np.asarray(first)[0]) == int(tokens[5][0])
    _first, want = one_pass.gen_prefill(prompts[5], one_pass._param_vals,
                                        one_pass._aux_vals)
    sums = one_pass._gen["sum_states"]
    assert len(sums) == cfg["num_hidden_layers"]
    for i in sums:
        assert np.asarray(rows[i]).tolist() == np.asarray(want[i]).tolist()
        assert 0 < np.asarray(rows[i])[0, :-5].sum() < 29 * 2


def test_eight_shares_add_up_to_the_uncut_layer():
    """Each of 8 devices holds 2 of 16 experts and computes its own experts'
    part under the softmax router; with the shared expert, which every
    device computes alike, counted once, the parts add up to the reference's
    uncut layer."""
    from benchmarks.reference import mistral4 as ref
    from mxtpu.ops.nn import moe_ffn_held
    rng = np.random.default_rng(11)
    n, d, f, wide = 40, 32, 16, 16
    x = rng.standard_normal((n, d)).astype(np.float32)
    w = {"router_weight": rng.standard_normal((wide, d)).astype(np.float32) / 4}
    for name, shape in (("eg", (wide, d, f)), ("eu", (wide, d, f)),
                        ("ed", (wide, f, d)), ("sg", (f, d)), ("su", (f, d)),
                        ("sd", (d, f))):
        w[name + "_weight"] = 0.2 * rng.standard_normal(shape).astype(
            np.float32)
    with jax.default_matmul_precision("highest"):
        whole = ref.moe(jnp.asarray(x), {k: jnp.asarray(v) for k, v in
                                         w.items()}, 4, 1.0, 0, False)
        shared = ref.gated(jnp.asarray(x), w["sg_weight"], w["su_weight"],
                           w["sd_weight"], False)
        parts, held_total = 0.0, 0
        for share in range(8):
            lo = 2 * share
            y, load = moe_ffn_held(
                jnp.asarray(x), w["router_weight"], jnp.zeros(wide),
                *(w[k][lo:lo + 2] for k in ("eg_weight", "eu_weight",
                                            "ed_weight")),
                top_k=4, expert_first=lo, scale=1.0, scoring="softmax")
            parts = parts + y
            held_total += int(np.asarray(load)[0, :-5].sum())
            assert int(np.asarray(load)[0, -5]) == n * 4
            one = ref.moe(jnp.asarray(x), dict(
                {k: jnp.asarray(v) for k, v in w.items()},
                **{k: jnp.asarray(w[k][lo:lo + 2]) for k in (
                    "eg_weight", "eu_weight", "ed_weight")}), 4, 1.0, lo,
                False)
            # the reference, given the same share, is the program's share
            np.testing.assert_allclose(np.asarray(one - shared),
                                       np.asarray(y), atol=2e-5, rtol=2e-5)
    assert held_total == n * 4          # every assignment is someone's
    np.testing.assert_allclose(np.asarray(parts + shared), np.asarray(whole),
                               atol=2e-5, rtol=2e-5)


def test_softmax_scores_against_the_definition_and_sigmoid_as_it_was():
    """``scoring="softmax"``: the 4 largest softmax scores of 16, renormalised
    (which is the softmax over the chosen logits alone). The default is the
    sigmoid, bit for bit the arithmetic ``route_topk`` had: scores of each
    expert alone, chosen with the bias, renormalised without it."""
    from mxtpu.parallel.moe import route_topk
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((24, 32)).astype(np.float32))
    rw = jnp.asarray(rng.standard_normal((16, 32)).astype(np.float32) / 4)
    bias = jnp.asarray(0.1 * rng.standard_normal(16).astype(np.float32))
    logits = np.asarray(x) @ np.asarray(rw).T
    experts, w = route_topk(x, rw, jnp.zeros(16), 4, 1.0, "softmax")
    order = np.argsort(-logits, axis=1)[:, :4]
    assert np.array_equal(np.sort(np.asarray(experts), 1), np.sort(order, 1))
    top = np.take_along_axis(logits, np.asarray(experts), 1)
    want = np.exp(top - top.max(1, keepdims=True))
    want /= want.sum(1, keepdims=True)
    np.testing.assert_allclose(np.asarray(w), want, rtol=1e-5)
    assert np.allclose(np.asarray(w).sum(1), 1.0, atol=1e-6)
    # the default: what the function computed before it had the attribute
    s = jax.nn.sigmoid(jnp.einsum("nd,ed->ne", x, rw,
                                  preferred_element_type=jnp.float32))
    _top, chosen = jax.lax.top_k(s + bias, 4)
    ws = jnp.take_along_axis(s, chosen, axis=1)
    ws = 2.5 * ws / jnp.sum(ws, axis=1, keepdims=True)
    for args in ((), ("sigmoid",)):
        e2, w2 = route_topk(x, rw, bias, 4, 2.5, *args)
        assert np.array_equal(np.asarray(e2), np.asarray(chosen))
        assert np.array_equal(np.asarray(w2), np.asarray(ws))
    with pytest.raises(ValueError):
        route_topk(x, rw, bias, 4, 1.0, "tanh")
