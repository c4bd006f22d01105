"""The ``mistral4`` family at a size a test run can hold: the reference's
leaves against the symbol's, the counts the metrics stand on as sums over
``reference.layout``, the configuration against the catalog, and a tiny
preset of its own through the whole driver, where the float8 control and both
planted faults have to come out not correct under a limit set from the
program's own reading.

The cell (``mistral4-longdoc-saturated``) runs through the driver
``serve_generate_drain``: ``serve_generate`` as it stands, with the wait
after the window from the traffic file's ``drain_s``."""
import json
import os
import types

import numpy as np
import pytest

import presets
from conftest import ROOT

CELL = "mistral4-longdoc-saturated"

# widths cut, structure kept: three expert layers, 2 heads of 64 + 64 query
# columns and 128 value columns (one width of a whole slab, as published, so
# that a prefill takes the block-wise path and its interpreted kernel) over a
# latent row of 128 + 64 in 256 columns (a rank of one slab: the decode
# kernel), 4 of 16 experts held, an original context of 16 so that the
# position's query scale and YaRN's ramp act inside a 64-row cache
TINY = {"config": {"hidden_size": 64, "num_attention_heads": 2,
                   "q_lora_rank": 32, "kv_lora_rank": 128,
                   "moe_intermediate_size": 32, "n_routed_experts": 4,
                   "router_width": 16, "num_experts_per_tok": 2,
                   "vocab_size": 512, "num_hidden_layers": 3,
                   "cache_len": 64, "cache_row": 256, "slots": 4,
                   "queue_depth": 16, "max_new": 8, "check_pad_to": 40,
                   "check_requests": 8,
                   "rope_parameters": {
                       "beta_fast": 32, "beta_slow": 1, "factor": 8,
                       "llama_4_scaling_beta": 0.1, "mscale": 1,
                       "mscale_all_dim": 1,
                       "original_max_position_embeddings": 16,
                       "rope_theta": 10000, "rope_type": "yarn",
                       "type": "yarn"},
                   "limits": {"logit_gap": 1e9},
                   # the cell's gains but the routed experts' (0.3 there):
                   # at 64 columns a routed term that small hides the
                   # sigmoid fault under a run's own wavering
                   "init_gain": {"emb": 1.0, "matrix": 1.0, "q_up": 1.5,
                                 "expert_in": 1.0, "router": 1.57,
                                 "head": 1.57, "out_attn": 0.8,
                                 "out_expert": 1.0, "out_shared": 0.3},
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
                           "mistral-small-4-119b-2603.json")) as f:
        return json.load(f)


def test_counts_are_sums_over_the_layout(monkeypatch):
    from benchmarks.layer_metrics import moe_held_share_sat
    from benchmarks.reference import mistral4 as ref
    monkeypatch.setattr(moe_held_share_sat, "registry", lambda name: [])
    c = cfg()
    leaves = {n: int(np.prod(s)) for n, s, _k in ref.layout(c)}
    count = lambda pick: sum(  # noqa: E731
        size for n, size in leaves.items() if pick(n))
    attention = (1024 * 4096 + 1024 + 4096 * 1024 + 320 * 4096 + 256
                 + 32 * 192 * 256 + 4096 * 4096)
    expert = 3 * 4096 * 2048
    assert attention == 28_050_688 and expert == 25_165_824
    assert count(lambda n: n.startswith("l3_") and n[3:].split("_")[0] in (
        "dq", "qn", "uq", "dkv", "cn", "ukv", "o")) == attention
    layer = attention + 2 * 4096 + 128 * 4096 + 17 * expert
    assert layer == 456_402_176
    table = 16384 * 4096
    total = 6 * layer + 2 * table + 4096
    assert ref.parameter_count(c) == count(lambda n: True) == total
    assert total == 2_872_634_880
    routed = count(lambda n: n.endswith(("eg_weight", "eu_weight",
                                          "ed_weight")))
    assert routed == 6 * 16 * expert
    # a token meets 4 x 16 / 128 = half a routed expert a layer here
    assert ref.ops_per_token(c) == 2 * (total - table - routed) + 6 * expert
    assert ref.routed_expert_bytes(c) == routed * 2
    assert ref.routed_expert_bytes(c, experts=90) == 90 * expert * 2
    # 96 slots of 3,400 live positions: one row of 320 values a position and
    # layer, once, in bfloat16: 640 bytes, whatever the state's 384 columns
    live = 96 * 3400
    assert ref.decode_attention_bytes(c, live) == live * 6 * 640
    # no counter in this process: the experts a step hits come from shapes
    hit = 6 * 16 * (1 - (1 - 4 / 128) ** 96)
    assert abs(ref.experts_hit_a_step(c) - hit) < 1e-9 and 91 < hit < 92
    fixed = 2 * (total - table - routed)
    assert ref.decode_step_bytes(c, live) == int(
        fixed + 96 * 4096 * 2 + hit * expert * 2 + live * 6 * 640)
    monkeypatch.setattr(moe_held_share_sat, "registry",
                        lambda name: [15.0] * 6)
    assert ref.decode_step_bytes(c, live) == (
        fixed + 96 * 4096 * 2 + 90 * expert * 2 + live * 6 * 640)
    # a prefill's attention: 2 x 32 heads x (128 + 128) a pair, n (n + 1) / 2
    # pairs a layer, from true lengths
    assert ref.prefill_attention_ops(c, [2048]) == (
        2048 * 2049 // 2 * 16384 * 6)
    assert ref.prefill_attention_ops(c, [300, 8192]) == (
        (300 * 301 // 2 + 8192 * 8193 // 2) * 16384 * 6)


def test_leaves_match_symbol_and_a_layer_keeps_one_latent_state():
    from benchmarks.models import mistral4 as model
    from benchmarks.reference import mistral4 as ref
    c = cfg()
    sym = model.symbol(c)
    args = set(sym.list_arguments())
    leaves = {n for n, _s, _k in ref.layout(c)}
    assert leaves <= args
    states = args - leaves - {"data", "pos", "len"}
    assert states == ({"lc%d" % i for i in range(6)}
                      | {"moe_load%d" % i for i in range(6)})
    attrs = sym.attr_dict()
    for i in range(6):
        # 320 published values in three 128-lane slabs
        assert tuple(attrs["lc%d" % i]["__shape__"]) == (0, 10240, 384)
        assert attrs["lc%d" % i].get("__state_kind__", "full") == "full"
        assert attrs["moe_load%d" % i]["__state_kind__"] == "sum:moe_load"
    assert c["kv_lora_rank"] + c["qk_rope_head_dim"] == 320 <= c["cache_row"]


def test_configuration_keeps_every_published_width():
    c = cfg()
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    assert (c["hidden_size"], c["num_attention_heads"], c["q_lora_rank"],
            c["kv_lora_rank"]) == (4096, 32, 1024, 256)
    assert (c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"],
            c["moe_intermediate_size"], c["num_experts_per_tok"],
            c["router_width"]) == (64, 64, 128, 2048, 4, 128)
    assert c["published"] == {"num_hidden_layers": 36,
                              "n_routed_experts": 128, "vocab_size": 131072}
    assert (c["num_hidden_layers"], c["n_routed_experts"],
            c["vocab_size"]) == (6, 16, 16384)
    said = " ".join(c["assumed"])
    for word in ("softmax", "llama_4_scaling_beta", "half-split",
                 "vision tower", "slots 96", "init_gain"):
        assert word in said, word
    if os.path.exists(catalog):
        rows = [json.loads(line) for line in open(catalog)]
        pub = [r for r in rows
               if r["name"] == "Mistral-Small-4-119B-2603"][0]
        assert c["source"].startswith(pub["source_url"])
        differ = {k for k, v in pub["config"].items() if c.get(k) != v}
        assert differ == {"num_hidden_layers", "n_routed_experts",
                          "vocab_size"}
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            entry = [x for x in json.load(f)["configs"]
                     if x["name"] == "mistral-small-4-119b-2603"][0]
        assert set(entry["reduced"]) == differ
        assert entry["source"] == pub["source_url"]


def test_tiny_preset_through_the_driver_and_the_control():
    """Through ``GenerateScheduler`` on the symbol of ``models/mistral4``:
    prefill on the interpreted block-wise kernel, the absorbed decode step on
    the interpreted decode kernel, the softmax expert layer's sums; and over
    the same requests the reference in scaled float8 in the program's place.
    Under a limit between the two readings the harness's verdict takes the
    program and refuses the control."""
    from benchmarks import run
    out = presets.drive(CELL, seconds=2.0, stand_ins=("fp8",))
    assert out["correct"] is True and out["failed"] == 0
    gap = out["compared"]["logit_gap"]["value"]
    low = out["stand_ins"]["fp8"]["compared"]["logit_gap"]["value"]
    assert low > 1.8 * gap and low > 0.01
    limits = {"logit_gap": (gap + low) / 2}
    assert run.judge([("logit_gap", gap)], limits)[0] is True
    assert run.judge([("logit_gap", low)], limits)[0] is False
    from benchmarks.layer_metrics import (engine_prefill_pad_share_sat,
                                          moe_held_share_sat,
                                          moe_max_load_sat)
    assert moe_max_load_sat.read(None, None) >= 1.0
    assert 5 < moe_held_share_sat.read(None, None) < 60      # 4 of 16 held
    from mxtpu.ops import nn
    assert nn.latent_blockwise_nodes() >= 3 and nn.latent_decode_nodes() >= 3
    # read after the scheduler has stopped, as the harness reads it
    assert 0 < engine_prefill_pad_share_sat.read(None, None) < 75


@pytest.mark.parametrize("fault", ["query_scale_dropped", "router_sigmoid"])
def test_a_fault_planted_in_the_program_is_read(fault):
    """The registry op without the position's query scale (positions past the
    original context of 16 lose a factor ``1 + 0.1 ln 2`` and more), or with
    the sigmoid in the softmax's place, served over the same requests: the
    served tokens fall below the reference's best by several times what the
    sound program reads."""
    import on_chip_fault_mistral4
    sound = presets.drive(CELL, seconds=2.0)["compared"]["logit_gap"]["value"]
    with on_chip_fault_mistral4.planted(fault):
        out = presets.drive(CELL, seconds=2.0)
    assert out["failed"] == 0
    assert out["compared"]["logit_gap"]["value"] > 3 * sound + 0.01


def test_the_cell_is_the_issues():
    """ISSUE 36's traffic, number for number (the prompts' median its one
    other allowed value, 1,536: PERF.md section 6 says why), and the entries
    it names."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    cell = [w for w in m["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mistral-small-4-119b-2603", "closed-192-longdoc-reasoning", 1)
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    assert (mix["loop"], mix["clients"], mix["pool"], mix["ramp_s"],
            mix["greedy"], mix["drain_s"]) == ("closed", 192, 32, 32, True,
                                               120)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 1536,
                                 "sigma": 0.8, "min": 256, "max": 8192}
    assert mix["output_len"] == {"dist": "lognormal", "median": 1280,
                                 "sigma": 0.4, "min": 640, "max": 2048}
    c = cfg()
    assert (c["slots"], c["cache_len"], c["cache_row"], c["max_new"],
            c["check_requests"], c["check_pad_to"]) == (96, 10240, 384, 2048,
                                                        6, 10240)
    assert c["queue_depth"] >= 384 and c["entry"] == "serve_generate_drain"
    assert c["prefill_buckets"] == [256, 512, 1024, 2048, 4096, 8192]
    # the longest pair fills a slot's cache to its last row
    from benchmarks import traffic
    pool = traffic.Stream(mix, c["vocab_size"], 1).pool
    assert max(p + o for _g, p, o in pool) == 10240 == c["cache_len"]
    assert max(p for _g, p, _o in pool) == 8192
    listed = {x["name"] for g in ("end_to_end", "per_layer") for x in m[g]
              if CELL in x.get("workloads", ())}
    assert listed == {
        "output_tokens_per_s", "step.mfu.serve", "decode.hbm_roofline.sat",
        "sched.occupancy.sat", "device.idle.sat", "sched.idle_step_host.sat",
        "sched.idle_read.sat", "sched.idle_admit.sat",
        "sched.idle_unattributed.sat", "sched.step_period_ms.sat",
        "moe.held_share.sat", "moe.max_load.sat",
        "moe.grouped_hbm_roofline.sat", "engine.prefill_share.sat",
        "decode.latent_attn_hbm_roofline.sat", "decode.latent_attn_share.sat",
        "prefill.latent_attn_mfu.sat", "engine.prefill_pad_share.sat"}
    # the new metrics are this cell's alone, and end the list
    assert [x["name"] for x in m["per_layer"][-2:]] == [
        "prefill.latent_attn_mfu.sat", "engine.prefill_pad_share.sat"]
    assert all(x["workloads"] == [CELL] for x in m["per_layer"][-2:])


def test_prefill_readers_count_true_lengths_and_read_nothing_of_a_parent(
        monkeypatch):
    """Three traced prefill runs whose spans say 300, 2,048 and 8,192 true
    rows, 0.2 s of the kernel: the pairs of those lengths, 16,384 operations
    each, six layers, over the peak over the kernel's time; spans past the
    traced runs are left out. A trace without the kernel (the parent) or
    without a prefill run reads nothing; the pad share is one less the
    counters' ratio."""
    from benchmarks.layer_metrics import (engine_prefill_pad_share_sat,
                                          prefill_latent_attn_mfu_sat,
                                          moe_held_share_sat)
    from benchmarks.reference import mistral4 as ref
    c = cfg()
    run = types.SimpleNamespace(cfg=c, reference=ref, window=(10.0, 50.0),
                                peaks={"flops_bf16": 197e12})
    monkeypatch.setattr(prefill_latent_attn_mfu_sat, "prompt_lengths",
                        lambda run: [300, 2048, 8192, 4000, 512])
    programs = {"jit_prefill_fn": [0.01, 0.03, 0.15],
                "jit_decode_fn": [0.013] * 50}
    trace = types.SimpleNamespace(
        op_s={"latent_prefill_attention": 0.2, "gmm": 1.0}, programs=programs)
    pairs = sum(n * (n + 1) // 2 for n in (300, 2048, 8192))
    want = 100.0 * pairs * 16384 * 6 / 197e12 / 0.2
    got = prefill_latent_attn_mfu_sat.read(run, trace)
    assert abs(got - want) < 1e-9 and 5 < got < 100
    bare = types.SimpleNamespace(op_s={"gmm": 1.0}, programs=programs)
    assert prefill_latent_attn_mfu_sat.read(run, bare) is None
    none = types.SimpleNamespace(op_s=trace.op_s,
                                 programs={"jit_decode_fn": [0.013]})
    assert prefill_latent_attn_mfu_sat.read(run, none) is None
    # fewer spans than runs: the lengths are not all known, nothing is read
    monkeypatch.setattr(prefill_latent_attn_mfu_sat, "prompt_lengths",
                        lambda run: [300])
    assert prefill_latent_attn_mfu_sat.read(run, trace) is None
    table = {"serve.gen.prefill_rows": [2667.0 * 32],
             "serve.gen.prefill_rows_padded": [3632.0 * 32]}
    monkeypatch.setattr(engine_prefill_pad_share_sat, "registry",
                        lambda name: table.get(name, []))
    got = engine_prefill_pad_share_sat.read(None, None)
    assert abs(got - 100.0 * (1 - 2667 / 3632)) < 1e-9
    monkeypatch.setattr(engine_prefill_pad_share_sat, "registry",
                        lambda name: [])
    assert engine_prefill_pad_share_sat.read(None, None) is None
    assert moe_held_share_sat.registry("no.such.metric") == []


def test_prompt_lengths_are_the_windows_prefill_spans_in_order(monkeypatch):
    """The reader takes ``plen`` from the program's own ``serve.gen.prefill``
    spans that start inside the window, by start."""
    from benchmarks.layer_metrics import prefill_latent_attn_mfu_sat as reader
    from mxtpu import profiler
    off = profiler.EPOCH_OFFSET_US

    def event(name, start_s, plen):
        return {"name": name, "ph": "X", "cat": "trace",
                "ts": start_s * 1e6 + off, "dur": 5e4,
                "args": {"plen": str(plen), "rid": "r"}}
    events = [event("serve.gen.prefill", 9.0, 111),       # the ramp's
              event("serve.gen.prefill", 30.0, 2048),
              event("serve.gen.prefill", 12.0, 300),
              event("serve.gen.adopt", 12.5, 999),
              event("serve.gen.prefill", 51.0, 512)]      # past the window
    monkeypatch.setattr(profiler, "snapshot_events", lambda: events)
    run = types.SimpleNamespace(window=(10.0, 50.0))
    assert reader.prompt_lengths(run) == [300, 2048]
