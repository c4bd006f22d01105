"""The ``xing4_0`` family at a size a test run can hold: the reference's
leaves against the symbol's, the counts the metrics stand on as sums over
``reference.layout``, and a tiny preset of its own through the whole driver,
where the float8 control and both planted faults have to come out not
correct under a limit set from the program's own reading.

The cell (``xing4-reasoning-saturated``) runs through the driver
``serve_generate_drain``: ``serve_generate`` as it stands, with the wait
after the window from the traffic file's ``drain_s``."""
import json
import os

import numpy as np
import pytest

import presets
from conftest import ROOT

CELL = "xing4-reasoning-saturated"

# widths cut, structure kept: a dense layer and two expert layers, four
# streams, 4 heads of 16 + 8 (rotary) query columns and 16 value columns over
# a latent row of 128 + 8 (the rank in whole 128-lane slabs, so that the
# decode step runs the kernel, interpreted), padded to 144 columns
TINY = {"config": {"hidden_size": 64, "num_attention_heads": 4,
                   "q_lora_rank": 32, "kv_lora_rank": 128,
                   "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
                   "v_head_dim": 16, "intermediate_size": 96,
                   "moe_intermediate_size": 32, "n_routed_experts": 8,
                   "num_experts_per_tok": 2, "vocab_size": 512,
                   "num_hidden_layers": 3, "cache_len": 64, "cache_row": 144,
                   "slots": 4, "queue_depth": 16, "max_new": 8,
                   "check_pad_to": 40, "check_requests": 8,
                   "balance": {"sequences": 2, "length": 16},
                   "limits": {"logit_gap": 1e9},
                   "env": {"MXTPU_SERVE_GENERATE_SLOTS": "4",
                           "MXTPU_SERVE_GENERATE_PREFILL_BUCKETS": "8,16,32",
                           "MXTPU_SERVE_GENERATE_MAX_NEW": "8"}},
        "traffic": {"clients": 8, "ramp_s": 0.3,
                    "prompt_len": {"dist": "lognormal", "median": 12,
                                   "sigma": 0.8, "min": 4, "max": 30},
                    "output_len": {"dist": "lognormal", "median": 6,
                                   "sigma": 0.6, "min": 2, "max": 8}}}
presets.PRESETS[CELL] = TINY

def cfg():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "xing4.0-29b-a4b.json")) as f:
        return json.load(f)


def test_counts_are_sums_over_the_layout(monkeypatch):
    from benchmarks.layer_metrics import moe_held_share_sat
    from benchmarks.reference import xing4_0 as ref
    monkeypatch.setattr(moe_held_share_sat, "registry", lambda name: [])
    c = cfg()
    leaves = {n: (int(np.prod(s)), k) for n, s, k in ref.layout(c)}
    count = lambda pick: sum(  # noqa: E731
        size for n, (size, _k) in leaves.items() if pick(n))
    attention = (768 * 3584 + 768 + 6144 * 768 + 576 * 3584 + 512
                 + 8192 * 512 + 3584 * 4096)
    mixing = 14336 * 24 + 3 + 24
    expert = 3 * 3584 * 1024
    assert attention == 28_411_136 and mixing == 344_091
    assert count(lambda n: n.startswith("l3_") and n[3:].split("_")[0] in (
        "dq", "qn", "uq", "dkv", "cn", "ukv", "o")) == attention
    assert count(lambda n: n.startswith("l3_ah_")) == mixing
    dense_layer = attention + 2 * mixing + 2 * 3584 + 3 * 3584 * 9216
    moe_layer = (attention + 2 * mixing + 2 * 3584 + 64 * 3584 + 64
                 + 65 * expert)
    table = 131072 * 3584
    total = dense_layer + 5 * moe_layer + 2 * table + 3584
    assert ref.parameter_count(c) == count(lambda n: True) == total
    assert total == 4_792_669_828
    routed = count(lambda n: n.endswith(("eg_weight", "eu_weight",
                                          "ed_weight")))
    assert routed == 5 * 64 * expert == 3_523_215_360
    # a token meets 4 of an expert layer's 64 routed experts
    assert ref.ops_per_token(c) == 2 * (total - table - routed
                                        + 5 * 4 * expert)
    assert ref.routed_expert_bytes(c) == routed * 2
    assert ref.routed_expert_bytes(c, experts=300) == 300 * expert * 2
    # 128 slots of 1,100 live positions: one row of 576 values a position
    # and layer, once, in bfloat16
    live = 128 * 1100
    assert ref.decode_attention_bytes(c, live) == live * 6 * 576 * 2
    # no counter in this process: the experts a step hits come from shapes
    hit = 5 * 64 * (1 - (1 - 4 / 64) ** 128)
    assert abs(ref.experts_hit_a_step(c) - hit) < 1e-9 and 319.9 < hit < 320
    float32 = count(lambda n: leaves[n][1] in ref.FLOAT32_KINDS)
    assert float32 == 12 * mixing + 5 * 64
    fixed = 2 * (total - table - routed - float32) + 4 * float32
    assert ref.decode_step_bytes(c, live) == int(
        fixed + 128 * 3584 * 2 + hit * expert * 2 + live * 6 * 576 * 2)
    # the program's own count where it keeps one: 60 of 64 hit a layer
    monkeypatch.setattr(moe_held_share_sat, "registry", lambda name: [60.0] * 5)
    assert ref.decode_step_bytes(c, live) == (
        fixed + 128 * 3584 * 2 + 300 * expert * 2 + live * 6 * 576 * 2)


def test_leaves_match_symbol_and_a_layer_keeps_one_latent_state():
    from benchmarks.models import xing4_0 as model
    from benchmarks.reference import xing4_0 as ref
    c = cfg()
    sym = model.symbol(c)
    args = set(sym.list_arguments())
    leaves = {n for n, _s, _k in ref.layout(c)}
    assert leaves <= args
    states = args - leaves - {"data", "pos", "len"}
    assert states == ({"lc%d" % i for i in range(6)}
                      | {"moe_load%d" % i for i in range(1, 6)})
    attrs = sym.attr_dict()
    for i in range(6):
        # 576 published values and 64 columns of zeros: whole 128-lane slabs
        assert tuple(attrs["lc%d" % i]["__shape__"]) == (0, 3072, 640)
        assert attrs["lc%d" % i].get("__state_kind__", "full") == "full"
    assert attrs["moe_load1"]["__state_kind__"] == "sum:moe_load"
    assert c["kv_lora_rank"] + c["qk_rope_head_dim"] == 576 <= c["cache_row"]


def test_configuration_keeps_every_published_width():
    c = cfg()
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    assert (c["hidden_size"], c["num_attention_heads"], c["q_lora_rank"],
            c["kv_lora_rank"]) == (3584, 32, 768, 512)
    assert (c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"],
            c["hc_mult"]) == (128, 64, 128, 4)
    assert (c["moe_intermediate_size"], c["intermediate_size"],
            c["n_routed_experts"], c["num_experts_per_tok"],
            c["vocab_size"]) == (1024, 9216, 64, 4, 131072)
    assert c["published"] == {"num_hidden_layers": 40,
                              "first_k_dense_replace": 2}
    if os.path.exists(catalog):
        rows = [json.loads(line) for line in open(catalog)]
        pub = [r for r in rows if r["name"] == "Xing4.0-29B-A4B"][0]["config"]
        differ = {k for k, v in pub.items() if c.get(k) != v}
        assert differ == {"num_hidden_layers", "first_k_dense_replace"}


def test_scale_and_frequencies_are_the_issues():
    """``scale`` 0.14468; the ramp runs from pair 10 to pair 23; the program's
    own table (``ops.nn.yarn_frequencies``) is the reference's."""
    from benchmarks.reference import xing4_0 as ref
    from mxtpu.ops.nn import yarn_frequencies
    c = cfg()
    assert abs(ref.softmax_scale(c) - 0.14468) < 1e-5
    w = ref.yarn_freqs(c)
    f = 10000.0 ** (-2.0 * np.arange(32) / 64)
    assert np.allclose(w[:11], f[:11], rtol=1e-6)           # untouched
    assert np.allclose(w[23:], f[23:] / 64, rtol=1e-6)      # stretched 64 x
    assert f[16] / 64 < w[16] < f[16]
    rs = c["rope_scaling"]
    mine = yarn_frequencies(64, 10000.0, rs["factor"], rs["beta_fast"],
                            rs["beta_slow"],
                            rs["original_max_position_embeddings"])
    assert np.allclose(mine, w, rtol=1e-6)


def test_tiny_preset_through_the_driver_and_the_control():
    """Through ``GenerateScheduler`` on the symbol of ``models/xing4_0``:
    prefill, the absorbed decode step on the interpreted kernel, the stream
    mixing, the expert layer's sums; and over the same requests the
    reference in scaled float8 in the program's place. Under a limit between
    the two readings the harness's verdict takes the program and refuses the
    control."""
    from benchmarks import run
    out = presets.drive(CELL, seconds=2.0, stand_ins=("fp8",))
    assert out["correct"] is True and out["failed"] == 0
    gap = out["compared"]["logit_gap"]["value"]
    low = out["stand_ins"]["fp8"]["compared"]["logit_gap"]["value"]
    assert low > 1.8 * gap and low > 0.01
    limits = {"logit_gap": (gap + low) / 2}
    assert run.judge([("logit_gap", gap)], limits)[0] is True
    assert run.judge([("logit_gap", low)], limits)[0] is False
    from benchmarks.layer_metrics import moe_max_load_sat
    assert moe_max_load_sat.read(None, None) >= 1.0


@pytest.mark.parametrize("fault", ["rope_key_dropped", "streams_collapsed"])
def test_a_fault_planted_in_the_program_is_read(fault):
    """The registry op with the score's rotary term left out, or with the
    streams collapsed into a plain residual, served over the same requests:
    the served tokens fall below the reference's best by several times what
    the sound program reads."""
    import on_chip_fault_xing4
    sound = presets.drive(CELL, seconds=2.0)["compared"]["logit_gap"]["value"]
    with on_chip_fault_xing4.planted(fault):
        out = presets.drive(CELL, seconds=2.0)
    assert out["failed"] == 0
    assert out["compared"]["logit_gap"]["value"] > 3 * sound + 0.01


def test_the_cell_is_the_issues():
    """ISSUE 34's traffic, number for number, and the entries it names."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    cell = [w for w in m["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "xing4.0-29b-a4b", "closed-256-reasoning-long", 1)
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    assert (mix["loop"], mix["clients"], mix["pool"], mix["ramp_s"],
            mix["greedy"]) == ("closed", 256, 32, 32, True)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 256,
                                 "sigma": 0.8, "min": 32, "max": 1024}
    assert mix["output_len"] == {"dist": "lognormal", "median": 1280,
                                 "sigma": 0.4, "min": 640, "max": 2048}
    c = cfg()
    assert (c["slots"], c["cache_len"], c["max_new"], c["queue_depth"],
            c["check_requests"], c["check_pad_to"]) == (128, 3072, 2048, 512,
                                                        6, 3072)
    assert c["entry"] == "serve_generate_drain" and mix["drain_s"] > 60
    listed = {x["name"] for g in ("end_to_end", "per_layer") for x in m[g]
              if CELL in x.get("workloads", ())}
    assert listed == {
        "output_tokens_per_s", "step.mfu.serve", "decode.hbm_roofline.sat",
        "sched.occupancy.sat", "device.idle.sat", "sched.idle_step_host.sat",
        "sched.idle_read.sat", "sched.idle_admit.sat",
        "sched.idle_unattributed.sat", "sched.step_period_ms.sat",
        "moe.max_load.sat", "moe.grouped_hbm_roofline.sat",
        "engine.prefill_share.sat", "decode.latent_attn_hbm_roofline.sat",
        "decode.latent_attn_share.sat"}


def test_the_drain_is_the_traffic_files_and_the_rest_is_serve_generates():
    """``serve_generate_drain`` hands ``serve_generate.run`` a ``Load`` whose
    waits last ``drain_s`` at least, for the length of the call; a wait whose
    requests have all resolved returns at once."""
    import time
    import types
    from benchmarks.drivers import serve_generate, serve_generate_drain
    seen = []

    def fake_run(run):
        seen.append(serve_generate.Load)
        return "result"
    real, serve_generate.run = serve_generate.run, fake_run
    try:
        assert serve_generate_drain.run(None) == "result"
    finally:
        serve_generate.run = real
    assert seen == [serve_generate_drain.Load]
    assert serve_generate.Load is not serve_generate_drain.Load
    run = types.SimpleNamespace(traffic={"drain_s": 0.3})
    load = serve_generate_drain.Load(run, None, None)
    done = types.SimpleNamespace(reply=("ok", {}))
    late = types.SimpleNamespace(reply=None)
    t = time.perf_counter()
    load.wait_all([done], 0.01)
    assert time.perf_counter() - t < 0.2
    t = time.perf_counter()
    load.wait_all([done, late], 0.01)          # 0.01 asked, 0.3 waited
    assert 0.3 <= time.perf_counter() - t < 1.0
    t = time.perf_counter()
    load.wait_all([late], 0.5)                 # a longer wait stays as asked
    assert 0.5 <= time.perf_counter() - t < 1.2


def test_latent_readers_count_the_published_row_and_the_windows_own_steps():
    """100 traced decode steps of 16.5 ms with 3 ms of the kernel each; the
    window stamped 262,344 tokens of 200 requests on 128 slots (2,048 steps)
    that attended 136,000 live rows a step: 6 layers x 576 x 2 bytes a row
    over the HBM's rate over 3 ms. A ``sched_steps`` read double after the
    drain moves neither number."""
    import types
    from benchmarks.layer_metrics import (decode_latent_attn_hbm_roofline_sat,
                                          decode_latent_attn_share_sat)
    from benchmarks.reference import xing4_0 as ref
    c = cfg()
    steps, live_a_step = 2048, 136_000
    counters = {"tokens_in_window": steps * 128 + 200, "requests": 200,
                "slots": 128, "live_positions": steps * live_a_step,
                "sched_steps": 2 * steps}
    run = types.SimpleNamespace(cfg=c, reference=ref, counters=counters,
                                peaks={"hbm_bytes_per_s": 819e9})
    programs = {"jit_decode_fn": [0.0165] * 100, "jit_prefill_fn": [0.05] * 9}
    trace = types.SimpleNamespace(
        op_s={"latent_decode_attention": 0.3, "gmm": 0.92}, programs=programs,
        program_mean_s=lambda name: sum(programs[name]) / len(programs[name]))
    want = 100.0 * live_a_step * 6 * 576 * 2 / 819e9 / 0.003
    got = decode_latent_attn_hbm_roofline_sat.read(run, trace)
    assert abs(got - want) < 1e-9 and 35 < got < 40
    share = decode_latent_attn_share_sat.read(run, trace)
    assert abs(share - 100.0 * 0.003 / 0.0165) < 1e-9
    # a program without the kernel (the parent), or a trace without a decode
    # run: nothing to read, and the line leaves both out
    bare = types.SimpleNamespace(op_s={"gmm": 0.92}, programs=programs,
                                 program_mean_s=trace.program_mean_s)
    assert decode_latent_attn_hbm_roofline_sat.read(run, bare) is None
    assert decode_latent_attn_share_sat.read(run, bare) is None
    none = types.SimpleNamespace(op_s=trace.op_s, programs={},
                                 program_mean_s=lambda name: None)
    assert decode_latent_attn_hbm_roofline_sat.read(run, none) is None
    assert decode_latent_attn_share_sat.read(run, none) is None
