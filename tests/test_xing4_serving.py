"""The ``xing4_0`` symbol (``benchmarks/models``) through ``InferenceEngine``
and ``GenerateScheduler`` against its plain reference
(``benchmarks/reference``), at tiny widths on the CPU with seeded weights:
latent attention over one cached row a position (chunks expanded, decode
steps absorbed on the interpreted kernel), four residual streams under
hyper-connections, the sigmoid top-k expert layer, the generate contract."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny_cfg(**over):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "xing4.0-29b-a4b.json")) as f:
        cfg = json.load(f)
    # a rank of one 128-lane slab, so that a decode step takes the kernel;
    # the row is 128 + 8 values, the cache's rows are padded to 144
    cfg.update({"hidden_size": 64, "num_attention_heads": 4,
                "q_lora_rank": 32, "kv_lora_rank": 128,
                "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
                "v_head_dim": 16, "intermediate_size": 96,
                "moe_intermediate_size": 32, "n_routed_experts": 8,
                "num_experts_per_tok": 2, "vocab_size": 256,
                "num_hidden_layers": 3, "cache_len": 64, "cache_row": 144,
                "slots": 4, "cache_dtype": "float32", "balance": None})
    cfg.update(over)
    return cfg


class Held(dict):
    def layer(self, i):
        p = "l%d_" % i
        return {n[len(p):]: v for n, v in self.items() if n.startswith(p)}


def float32_weights(cfg, seed):
    """The reference's own leaves (bfloat16 values) held in float32, so that
    program and reference compute on the same numbers in one precision."""
    from benchmarks.reference import xing4_0 as ref
    w = ref.init_weights(cfg, seed)
    return Held({n: np.asarray(w[n].astype(jnp.float32)) for n in w})


def program_logits(cfg, weights, tokens, pieces):
    """The symbol evaluated as the engine's programs evaluate it, over
    ``tokens`` cut into ``pieces`` (a piece of one token is a decode step):
    logits ``[len(tokens), vocab]``."""
    from benchmarks.models import xing4_0 as model
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
    def piece(chunk, pos, states):
        feed = {n: jnp.asarray(v) for n, v in weights.items()}
        feed.update(states, **sums)
        feed.update(data=chunk, pos=pos,
                    len=jnp.full((1,), chunk.shape[1], jnp.int32))
        outs, _aux = eval_graph(sym._outputs, feed, False)
        by_name = dict(zip(names, outs))
        return by_name["head_output"], {
            n: by_name[n + "_next_output"] for n in states}

    out, at = [], 0
    for n in pieces:
        lg, states = piece(jnp.asarray(tokens[None, at:at + n], jnp.float32),
                           jnp.full((1,), at, jnp.int32), states)
        out.append(np.asarray(lg[0]))
        at += n
    return np.concatenate(out)


@pytest.fixture(scope="module")
def text():
    """A model, 30 random tokens and the reference's logits at each."""
    from benchmarks.reference import xing4_0 as ref
    cfg = tiny_cfg()
    weights = float32_weights(cfg, 7)
    tokens = np.random.default_rng(5).integers(0, cfg["vocab_size"], size=30)
    want = np.asarray(ref.logits(cfg, weights, tokens, np.arange(30)))
    return cfg, weights, tokens, want


# float32 on both sides: what is left is the order of summation (the
# reference runs the whole sequence at once, unabsorbed; the program a piece
# at a time through the caches). Logits are a few units wide.
TOLERANCE = 2e-4


@pytest.mark.parametrize("pieces", [(30,), (12, 18), (21,) + (1,) * 9])
def test_logits_follow_the_reference(text, pieces):
    """Chunks at position 0 and beyond, then decode steps on the kernel:
    every logit of every position is the reference's."""
    cfg, weights, tokens, want = text
    got = program_logits(cfg, weights, tokens, pieces)
    assert np.max(np.abs(got - want)) < TOLERANCE
    assert want.std() > 1.0


def test_a_bfloat16_mixing_or_a_dropped_rotary_term_fails_that_tolerance(
        text, monkeypatch):
    """The tolerance is tight enough to see both: the stream mixing's
    product with ``phi`` rounded to bfloat16 (what the TPU's default
    precision would do to a float32 product), and a score without its rotary
    term."""
    from mxtpu.ops import nn
    from mxtpu.ops.registry import get_op
    cfg, weights, tokens, want = text
    pieces = (21,) + (1,) * 9
    op = get_op("hyper_mix")
    sound = op.fn

    def rounded(streams, phi, *rest, **attrs):
        low = lambda a: a.astype(jnp.bfloat16).astype(a.dtype)  # noqa: E731
        return sound(low(streams), low(phi), *rest, **attrs)
    monkeypatch.setattr(op, "fn", rounded)
    off = np.max(np.abs(program_logits(cfg, weights, tokens, pieces) - want))
    assert off > 10 * TOLERANCE
    monkeypatch.setattr(op, "fn", sound)
    monkeypatch.setattr(nn, "_rotate", lambda x, positions, freqs: x)
    off = np.max(np.abs(program_logits(cfg, weights, tokens, pieces) - want))
    assert off > 100 * TOLERANCE


def test_the_symbols_softmax_scale_is_the_references_and_the_published():
    """Program and reference each compute YaRN's ``mscale``-squared scale
    from the configuration's keys; both are the 0.14468 that the
    configuration's ``assumed`` states, at the published and at the tiny
    widths alike, and the symbol's nodes carry the program's own."""
    from benchmarks.models import xing4_0 as model
    from benchmarks.reference import xing4_0 as ref
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "xing4.0-29b-a4b.json")) as f:
        published = json.load(f)
    assert abs(model.attention_scale(published) - 0.14468) < 5e-6
    assert any("0.14468" in line for line in published["assumed"])
    for cfg in (published, tiny_cfg()):
        mine, theirs = model.attention_scale(cfg), ref.softmax_scale(cfg)
        assert abs(mine - theirs) <= 1e-12 * theirs
    cfg = tiny_cfg()
    nodes = json.loads(model.symbol(cfg).tojson())["nodes"]
    scales = [float(n["attrs"]["scale"]) for n in nodes
              if n["op"] == "latent_attention"]
    assert scales == [model.attention_scale(cfg)] * 3


@pytest.fixture(scope="module")
def served():
    """One engine behind a ``GenerateScheduler`` of 4 slots; six prompts of
    unequal length, 12 tokens each."""
    from benchmarks.models import xing4_0 as model
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
    finally:
        sched.stop()
    assert all(r[0] == "ok" for r in replies), replies
    return cfg, weights, engine, prompts, [
        np.asarray(r[1]["tokens"]) for r in replies]


def test_served_tokens_are_the_references_best_by_its_logits(served):
    """Through ``GenerateScheduler`` (prefill in a bucket, adoption into a
    slot, batched decode steps beside other sequences): at every position
    the served token's logit, in the reference's full forward pass over
    prompt and served tokens, is the best one but for the tolerance."""
    from benchmarks.reference import xing4_0 as ref
    cfg, weights, _engine, prompts, tokens = served
    for prompt, out in zip(prompts, tokens):
        assert len(out) == 12
        seq = np.concatenate([prompt, out[:-1]])
        lg = np.asarray(ref.logits(cfg, weights, seq,
                                   np.arange(len(prompt) - 1, len(seq))))
        took = lg[np.arange(len(out)), out]
        assert float(np.max(lg.max(-1) - took)) < TOLERANCE
    assert len({tuple(t.tolist()) for t in tokens}) == len(tokens)
    assert min(len(set(t.tolist())) for t in tokens) > 4   # no one token repeated


def test_a_layer_keeps_one_full_state_of_the_rows_width(served):
    cfg, _weights, engine, _prompts, _tokens = served
    spec = engine.generate_spec()
    assert spec["cache_len"] == cfg["cache_len"] == 64
    caches = [n for n in spec["states"] if n.startswith("lc")]
    assert caches == ["lc0", "lc1", "lc2"]         # ONE a layer, no k and v
    assert all(spec["state_kinds"][n] == "full" for n in caches)
    assert all(spec["state_rows"][n] == 64 for n in caches)
    assert spec["state_kinds"]["moe_load1"] == "sum:moe_load"
    by_kind = engine.stats()["gen_state_bytes"]
    row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    assert row == 136 <= cfg["cache_row"] == 144
    assert by_kind["full"] == 3 * 4 * 64 * cfg["cache_row"] * 4
    # per-head keys and values of the same heads would hold 4 x (24 + 16)
    # values a position; the latent row is shared by all heads
    assert by_kind["sum"] == 2 * (8 + 5) * 4


def test_a_layer_that_holds_every_expert_makes_one_pass(served, tiny_windows):
    """Every expert is held, so ``n_held = N k`` and a walk in windows would
    be the same work in more calls: with the rule brought down to these
    widths' scale (where a model that holds a share walks its chunks of 32
    rows in windows) no program of this model takes it."""
    from benchmarks.models import xing4_0 as model
    from mxtpu.parallel.moe import held_window
    from mxtpu.serving import InferenceEngine
    cfg, weights, _engine, prompts, tokens = served
    assert held_window(64, 2, 8) is not None and held_window(64, 8, 8) is None
    engine = InferenceEngine(model.symbol(cfg), dict(weights), {},
                             {"data": (1,)}, buckets=(1,), dtype="float32",
                             warm=False)
    first, _rows = engine.gen_prefill(prompts[5], engine._param_vals,
                                      engine._aux_vals)
    engine.gen_decode_program(4)
    st = engine.stats()
    assert st["gen_prefill_moe_window"] == st["gen_decode_moe_window"] == 0
    assert st["gen_prefill_hyper_mix"] == 2 * cfg["num_hidden_layers"]
    assert int(np.asarray(first)[0]) == int(tokens[5][0])


def test_counters_say_which_nodes_took_the_kernels(served):
    """The decode program: every layer's attention on the latent kernel
    (which takes its row through the row-write kernel), two stream mixings
    a layer; the prefill programs (two buckets) mix as often and touch
    neither kernel."""
    from mxtpu import obs
    cfg, _weights, engine, _prompts, _tokens = served
    st = engine.stats()
    layers = cfg["num_hidden_layers"]
    assert st["gen_decode_latent_path"] == layers
    assert st["gen_decode_hyper_mix"] == 2 * layers
    assert st["gen_prefill_latent_path"] == 0
    assert st["gen_prefill_hyper_mix"] == 2 * 2 * layers
    assert st["gen_decode_attn_path"] == st["gen_decode_row_write"] == 0
    assert st["gen_prefill_moe_window"] == st["gen_decode_moe_window"] == 0
    snap = obs.REGISTRY.snapshot()["metrics"]
    assert snap["ops.latent_attention.decode_path"]["series"]
    assert snap["ops.hyper_mix.nodes"]["series"]
    sums = st["gen_sums"]
    assert sorted(sums) == ["moe_load1", "moe_load2"]
    for total in sums.values():
        assert total[-5] > 0 and sum(total[:-5]) == total[-5]   # all held
