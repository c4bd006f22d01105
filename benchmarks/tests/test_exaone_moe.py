"""The ``exaone_moe`` family at a size a test run can hold: the reference's
leaves against the symbol's, the counts the metrics stand on, and a tiny
preset through the driver, where the float8 control has to come out not
correct under limits set from the program's own reading."""
import json
import os

import pytest

import presets
from conftest import ROOT

CELL = "kexaone-reasoning-saturated"

# widths cut, structure kept: 8 layers (dense, then LLLG LLLG less one),
# 4 query heads over 2 key/value heads, a window of 8 on rings of 8 rows,
# 4 of 16 experts held, top-4, a prefill bucket (32) four times the ring
TINY = {"config": {"hidden_size": 64, "head_dim": 16,
                   "num_attention_heads": 4, "num_key_value_heads": 2,
                   "intermediate_size": 96, "moe_intermediate_size": 32,
                   "num_experts": 4, "router_width": 16,
                   "num_experts_per_tok": 4, "vocab_size": 512,
                   "sliding_window": 8, "ring_rows": 8, "cache_len": 64,
                   "slots": 4, "queue_depth": 16, "max_new": 8,
                   "check_pad_to": 40, "check_requests": 8,
                   # the cell's own initialisation at these widths: each
                   # std is the configuration's times sqrt(its fan-in
                   # there / its fan-in here)
                   "init_std": {"matrix": 0.196, "emb": 1.0, "out_attn": 0.075,
                                "out_dense": 0.037, "out_expert": 0.035,
                                "out_shared": 0.042, "router": 0.196,
                                "bias": 0.01, "qk_gamma": 2.0},
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
                           "k-exaone-236b-a23b.json")) as f:
        return json.load(f)


def test_counts_at_the_published_widths(monkeypatch):
    from benchmarks.layer_metrics import moe_held_share_sat
    from benchmarks.reference import exaone_moe as ref
    monkeypatch.setattr(moe_held_share_sat, "registry", lambda name: [])
    c = cfg()
    attention = 2 * 6144 * 8192 + 2 * 6144 * 1024
    expert = 3 * 6144 * 2048
    gains = 2 * 6144 + 2 * 128
    moe_layer = attention + gains + 17 * expert + 128 * 6144 + 128
    dense_layer = attention + gains + 3 * 6144 * 18432
    table = 19200 * 6144
    assert ref.parameter_count(c) == (
        dense_layer + 7 * moe_layer + 2 * table + 6144) == 5_979_349_888
    # a token meets 8 x 16 / 128 = 1 routed expert a MoE layer here
    assert ref.ops_per_token(c) == 2 * (
        ref.parameter_count(c) - table - 7 * 15 * expert)
    assert ref.routed_expert_bytes(c) == 7 * 16 * expert * 2
    assert ref.routed_expert_bytes(c, experts=90) == 90 * expert * 2
    # 64 slots of 500 live positions: 2 full layers read them all, 6
    # window layers 128 a slot; a row is K and V, 1024 wide, bfloat16
    rows = 2 * 64 * 500 + 6 * 64 * 128
    assert ref.decode_attention_bytes(c, 64 * 500) == rows * 4096
    # no counter in this process: the experts a step hits come from shapes
    hit = 7 * 16 * (1 - 0.9375 ** 64)
    assert abs(ref.experts_hit_a_step(c) - hit) < 1e-9 and 110 < hit < 111
    assert ref.decode_step_bytes(c, 64 * 500) == int(2 * (
        ref.parameter_count(c) - table + 64 * 6144 - 7 * 16 * expert)
        + hit * expert * 2 + rows * 4096)
    # the program's own count where it keeps one: 15 of 16 hit a layer
    monkeypatch.setattr(moe_held_share_sat, "registry", lambda name: [15.0] * 7)
    assert ref.decode_step_bytes(c, 64 * 500) == 2 * (
        ref.parameter_count(c) - table + 64 * 6144 - 7 * expert) + rows * 4096


def test_leaves_match_symbol_and_states_are_of_three_kinds():
    from benchmarks.models import exaone_moe as model
    from benchmarks.reference import exaone_moe as ref
    c = cfg()
    sym = model.symbol(c)
    args = set(sym.list_arguments())
    leaves = {n for n, _s, _k in ref.layout(c)}
    assert leaves <= args
    states = args - leaves - {"data", "pos", "len"}
    assert states == ({"%sc%d" % (k, i) for k in "kv" for i in range(8)}
                      | {"moe_load%d" % i for i in range(1, 8)})
    attrs = sym.attr_dict()
    rows = {n: tuple(attrs[n]["__shape__"])[1] for n in states}
    kinds = {n: attrs[n].get("__state_kind__", "full") for n in states}
    assert {rows["kc%d" % i] for i in (3, 7)} == {2048}
    assert {rows["kc%d" % i] for i in (0, 1, 2, 4, 5, 6)} == {128}
    assert {kinds["vc%d" % i] for i in (0, 1, 2, 4, 5, 6)} == {"ring"}
    assert kinds["kc3"] == "full" and kinds["moe_load1"] == "sum:moe_load"


def test_configuration_keeps_every_published_width():
    c = cfg()
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"]) == (6144, 64, 8, 128)
    assert (c["moe_intermediate_size"], c["intermediate_size"],
            c["router_width"], c["num_experts_per_tok"]) == (2048, 18432, 128, 8)
    assert (c["sliding_window"], c["rope_parameters"]["rope_theta"],
            c["routed_scaling_factor"]) == (128, 1000000, 2.5)
    assert c["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                              "vocab_size": 153600}
    if os.path.exists(catalog):
        rows = [json.loads(line) for line in open(catalog)]
        pub = [r for r in rows if r["name"] == "K-EXAONE-236B-A23B"][0]["config"]
        differ = {k for k, v in pub.items() if c.get(k) != v}
        assert differ == {"num_hidden_layers", "num_experts", "vocab_size"}


def test_tiny_preset_through_the_driver_and_the_control():
    """Through ``GenerateScheduler`` on the symbol of ``models/exaone_moe``:
    prefill (a bucket of 32 over rings of 8), decode past the rings' wrap,
    the expert layer's sums; and over the same requests the reference in
    scaled float8 in the program's place. Under a limit between the two
    readings (the cell's own is set so from the chip's, PERF.md section 2)
    the harness's verdict takes the program and refuses the control."""
    from benchmarks import run
    out = presets.drive(CELL, seconds=2.0, stand_ins=("fp8",))
    assert out["correct"] is True and out["failed"] == 0
    gap = out["compared"]["logit_gap"]["value"]
    low = out["stand_ins"]["fp8"]["compared"]["logit_gap"]["value"]
    assert low > 1.8 * gap and low > 0.01   # CPU readings: 2.6 times and up
    limits = {"logit_gap": (gap + low) / 2}
    assert run.judge([("logit_gap", gap)], limits)[0] is True
    assert run.judge([("logit_gap", low)], limits)[0] is False
    # the scheduler's stats() at the window's ends folded the engine's
    # device sums into the registry
    from benchmarks.layer_metrics import moe_held_share_sat, moe_max_load_sat
    share = moe_held_share_sat.read(None, None)
    assert 5.0 < share < 60.0           # 4 of 16 held: 25% under even routing
    assert moe_max_load_sat.read(None, None) >= 1.0


@pytest.mark.parametrize("fault", ["routed_dropped", "band_off_by_one"])
def test_a_fault_planted_in_the_program_is_read(fault):
    """The symbol built with the routed experts' terms left out, or with a
    window one position short, served over the same requests: the served
    tokens fall below the reference's best by several times what the sound
    program reads (CPU readings, tiny preset, float32 program... the sound
    one reads under 0.02; routed_dropped 0.3 and up, band_off_by_one 0.1
    and up)."""
    import on_chip_fault
    sound = presets.drive(CELL, seconds=2.0)["compared"]["logit_gap"]["value"]
    with on_chip_fault.planted("exaone_moe", fault):
        out = presets.drive(CELL, seconds=2.0)
    assert out["failed"] == 0
    assert out["compared"]["logit_gap"]["value"] > 3 * sound + 0.01


def test_grouped_roofline_counts_all_runs_against_all_of_the_kernels_time(
        monkeypatch):
    """1,000 decode steps and 100 prefill chunks, each hitting 15.5 of 16
    held experts in each of 7 layers, against 12 s of ``gmm``: bytes over
    the HBM's rate over that time, no share-out between the programs."""
    import types
    from benchmarks.layer_metrics import (moe_grouped_hbm_roofline_sat,
                                          moe_held_share_sat)
    from benchmarks.reference import exaone_moe as ref
    c = cfg()
    run = types.SimpleNamespace(cfg=c, reference=ref,
                                peaks={"hbm_bytes_per_s": 819e9})
    trace = types.SimpleNamespace(
        op_s={"gmm": 12.0},
        programs={"jit_decode_fn": [0.018] * 1000,
                  "jit_prefill_fn": [0.022] * 100, "jit_adopt_fn": [1e-4] * 100})
    monkeypatch.setattr(moe_held_share_sat, "registry", lambda name: {
        "ops.moe_ffn.experts_hit_run": [15.5] * 7}.get(name, []))
    monkeypatch.setattr(moe_grouped_hbm_roofline_sat, "registry",
                        moe_held_share_sat.registry)
    want = 100.0 * 1100 * 7 * 15.5 * 3 * 6144 * 2048 * 2 / 819e9 / 12.0
    got = moe_grouped_hbm_roofline_sat.read(run, trace)
    assert abs(got - want) < 1e-9 and 80 < got < 100
    # a program without the counter (the parent): nothing to read
    monkeypatch.setattr(moe_grouped_hbm_roofline_sat, "registry",
                        lambda name: [])
    assert moe_grouped_hbm_roofline_sat.read(run, trace) is None
