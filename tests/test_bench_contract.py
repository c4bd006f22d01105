"""Output contracts of the benchmark scripts.

bench.py measures on the chip or not at all: with no TPU it exits
non-zero and prints no metric (a CPU number is never written under a TPU
metric's name), and a chip it has no peak for is an error. The tools/
benches print exactly one JSON line with fixed keys; MXTPU_BENCH_TINY
shrinks them so the contract tests stay fast."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.mark.parametrize("script", ["bench.py", "chip_smoke.py"])
def test_no_tpu_means_no_metric(script):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_ROOT)
    res = subprocess.run([sys.executable, os.path.join(_ROOT, script)],
                         capture_output=True, text=True, timeout=300,
                         env=env)
    assert res.returncode != 0
    said = [l for l in res.stderr.splitlines() if "no TPU" in l]
    assert len(said) == 1, res.stderr[-500:]
    # nothing on stdout parses as a result: no JSON line, no rate
    for line in res.stdout.splitlines():
        assert not line.lstrip().startswith("{"), line
        assert "img" not in line and "images/sec" not in line, line


def test_unknown_device_kind_is_an_error():
    spec = importlib.util.spec_from_file_location(
        "bench_mod", os.path.join(_ROOT, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    assert bench.peak_tflops("TPU v5 lite") == 197.0
    with pytest.raises(KeyError, match="no peak"):
        bench.peak_tflops("TPU v9")
    # the script decides in ONE process: nothing left that re-executes
    # it, probes in a child, falls back to the CPU or reads a report file
    src = open(os.path.join(_ROOT, "bench.py")).read()
    for gone in ("subprocess", "cpu_fallback", "tpu_unavailable",
                 "best_measured_config", "tpu_checks_report"):
        assert gone not in src, gone


def test_module_bench_contract():
    """tools/bench_module.py: exactly one JSON line, rc 0, with the
    fused-vs-eager fields the perf trajectory (docs/perf_analysis.md
    "Module fast path") is tracked by — tiny models, CPU-only."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", MXTPU_BENCH_TINY="1",
               PYTHONPATH=_ROOT)
    env.pop("MXTPU_MODULE_FUSED", None)
    res = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "bench_module.py"),
         "--batches", "3", "--warmup", "2", "--no-write"],
        capture_output=True, text=True, timeout=600, env=env)
    assert res.returncode == 0, res.stderr[-800:]
    lines = [l for l in res.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, "must print exactly ONE JSON line"
    payload = json.loads(lines[0])
    assert payload["bench"] == "module_fit"
    assert payload["tiny"] is True
    assert set(payload["models"]) == {"mlp", "lenet"}
    for model, row in payload["models"].items():
        for field in ("fused_img_s", "eager_img_s", "speedup",
                      "batch_size"):
            assert isinstance(row[field], (int, float)), (model, field)
        assert row["fused_img_s"] > 0 and row["eager_img_s"] > 0


def test_module_bench_dist_contract():
    """tools/bench_module.py --dist: exactly one JSON line, rc 0, with
    the eager vs fused-sync vs fused-async loopback-PS fields the
    distributed perf trajectory (docs/perf_analysis.md "Distributed
    Module fast path") is tracked by — tiny model, CPU-only."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", MXTPU_BENCH_TINY="1",
               MXTPU_PS_HEARTBEAT="0", PYTHONPATH=_ROOT)
    for k in ("MXTPU_MODULE_FUSED", "MXTPU_MODULE_FUSED_DIST",
              "MXTPU_MODULE_DIST_MODE"):
        env.pop(k, None)
    res = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "bench_module.py"),
         "--dist", "--batches", "3", "--warmup", "2", "--no-write"],
        capture_output=True, text=True, timeout=600, env=env)
    assert res.returncode == 0, res.stderr[-800:]
    lines = [l for l in res.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, "must print exactly ONE JSON line"
    payload = json.loads(lines[0])
    assert payload["bench"] == "module_fit_dist"
    assert payload["tiny"] is True
    row = payload["models"]["mlp"]
    for field in ("batch_size", "eager_img_s", "fused_sync_img_s",
                  "fused_async_img_s", "speedup_sync", "speedup_async"):
        assert isinstance(row[field], (int, float)), field
    assert row["eager_img_s"] > 0 and row["fused_sync_img_s"] > 0 \
        and row["fused_async_img_s"] > 0


def test_module_bench_amp_contract():
    """tools/bench_module.py --amp: exactly one JSON line, rc 0, with
    the fp32-vs-bf16 fused fields AND the half-width-wire bytes the
    mixed-precision trajectory (docs/perf_analysis.md "Mixed
    precision") is tracked by — tiny model, CPU-only."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", MXTPU_BENCH_TINY="1",
               MXTPU_PS_HEARTBEAT="0", PYTHONPATH=_ROOT)
    for k in ("MXTPU_AMP", "MXTPU_MODULE_FUSED", "MXTPU_MODULE_FUSED_DIST",
              "MXTPU_MODULE_DIST_MODE"):
        env.pop(k, None)
    res = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "bench_module.py"),
         "--amp", "--batches", "3", "--warmup", "2", "--no-write"],
        capture_output=True, text=True, timeout=600, env=env)
    assert res.returncode == 0, res.stderr[-800:]
    lines = [l for l in res.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, "must print exactly ONE JSON line"
    payload = json.loads(lines[0])
    assert payload["bench"] == "module_fit_amp"
    assert payload["tiny"] is True
    row = payload["models"]["mlp"]
    for field in ("batch_size", "fp32_img_s", "bf16_img_s", "speedup"):
        assert isinstance(row[field], (int, float)), field
    dist = payload["dist"]
    for field in ("batch_size", "fp32_img_s", "bf16_img_s", "speedup",
                  "fp32_bytes_per_step", "bf16_bytes_per_step",
                  "wire_bytes_ratio"):
        assert isinstance(dist[field], (int, float)), field
    # the half-width wire holds at ANY size (it is structural, not a
    # wall-clock number): bf16 frames carry half the payload bytes
    assert dist["wire_bytes_ratio"] <= 0.55


def test_module_bench_mesh_contract():
    """tools/bench_module.py --mesh: exactly one JSON line, rc 0, with
    the single-vs-sharded train/serve fields the mesh trajectory
    (docs/perf_analysis.md "Sharded Module") is tracked by — tiny
    model, 8 emulated CPU devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", MXTPU_BENCH_TINY="1",
               MXTPU_PS_HEARTBEAT="0", PYTHONPATH=_ROOT,
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    for k in ("MXTPU_MODULE_FUSED", "MXTPU_MESH"):
        env.pop(k, None)
    res = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "bench_module.py"),
         "--mesh", "--batches", "3", "--warmup", "2", "--no-write"],
        capture_output=True, text=True, timeout=600, env=env)
    assert res.returncode == 0, res.stderr[-800:]
    lines = [l for l in res.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, "must print exactly ONE JSON line"
    payload = json.loads(lines[0])
    assert payload["bench"] == "module_fit_mesh"
    assert payload["tiny"] is True
    assert payload["devices"] == 8
    train = payload["train"]
    for field in ("batch_size", "fused_img_s", "mesh_img_s",
                  "mesh_vs_single", "store_bytes",
                  "store_bytes_worst_device", "store_devices"):
        assert isinstance(train[field], (int, float)), field
    assert train["fused_img_s"] > 0 and train["mesh_img_s"] > 0
    # the structural half of the row holds at ANY size: the donated
    # store (params + opt state) really splits ~1/N across the mesh
    assert train["store_devices"] == 8
    assert train["store_bytes_worst_device"] <= \
        train["store_bytes"] // 8 + 8 * 1024
    serve = payload["serve"]
    for field in ("batch_size", "single_req_s", "mesh_req_s",
                  "mesh_vs_single"):
        assert isinstance(serve[field], (int, float)), field
    assert serve["single_req_s"] > 0 and serve["mesh_req_s"] > 0
    # steady-state sharded serving never recompiles (AOT menu)
    assert serve["recompiles"] == 0


def test_kvstore_bench_contract(tmp_path):
    """tools/bench_kvstore.py: exactly one JSON line, rc 0, with the
    fields the perf trajectory (docs/perf_analysis.md "Comms fast
    path") is tracked by — on a fault-free tiny loopback run."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_ROOT,
               MXTPU_PS_HEARTBEAT="0")
    res = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "bench_kvstore.py"),
         "--mb", "2", "--small-keys", "16", "--iters", "2", "--no-write"],
        capture_output=True, text=True, timeout=600, env=env)
    assert res.returncode == 0, res.stderr[-800:]
    lines = [l for l in res.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, "must print exactly ONE JSON line"
    payload = json.loads(lines[0])
    assert payload["bench"] == "kvstore_loopback"
    assert payload["transport"] in ("local", "tcp")
    for field in ("payload_mb", "push_mb_s", "pull_mb_s",
                  "small_push_ops_s", "small_pull_ops_s", "n_parts",
                  "window", "iters"):
        assert isinstance(payload[field], (int, float)), field
    for lat in (payload["push"], payload["pull"]):
        assert lat["p50_ms"] > 0 and lat["p99_ms"] >= lat["p50_ms"]
    # both transports always reported: local headline + tcp sub-object
    assert isinstance(payload["tcp"]["push_mb_s"], (int, float))
    # comms counters rode along (the fault-free run retransmits nothing)
    assert payload["wire"]["retransmits"] == 0
    assert payload["wire"]["bytes_sent"] > 0
    assert payload["wire"]["coalesced_subs"] >= 16


def test_serving_bench_contract():
    """tools/bench_serving.py: exactly one JSON line, rc 0, with the
    offered-load sweep fields the perf trajectory (docs/perf_analysis.md
    "Serving") is tracked by — tiny levels, CPU-only loopback."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_ROOT,
               MXTPU_BENCH_TINY="1", MXTPU_PS_HEARTBEAT="0")
    res = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "bench_serving.py"),
         "--no-write"],
        capture_output=True, text=True, timeout=600, env=env)
    assert res.returncode == 0, res.stderr[-800:]
    lines = [l for l in res.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, "must print exactly ONE JSON line"
    payload = json.loads(lines[0])
    assert payload["bench"] == "serving_loopback"
    assert payload["tiny"] is True
    assert payload["transport"] in ("local", "tcp")
    assert payload["buckets"] and payload["queue_depth"] >= 1
    assert payload["levels"], "offered-load sweep missing"
    for row in payload["levels"]:
        for field in ("clients", "attempts", "answered", "req_s",
                      "shed", "shed_rate", "expired"):
            assert isinstance(row[field], (int, float)), field
        assert row["p50_ms"] > 0 and row["p99_ms"] >= row["p50_ms"]
        # every attempt has exactly one terminal outcome
        assert row["answered"] + row["shed"] + row["expired"] \
            + row["errors"] == row["attempts"]
        # server-side latency histograms (ISSUE 14): per-level bucket
        # deltas of serve.request_ms / serve.batch.flush_ms ride every
        # offered-load point — the same registry numbers mxtop and the
        # telemetry plane read
        for kind in ("request", "batch"):
            h = row["server_lat"][kind]
            assert h["count"] >= row["answered"] or kind == "batch", h
            if h["count"]:
                assert h["p50_ms"] > 0, h
                assert h["p99_ms"] >= h["p50_ms"], h
    # both transports always reported: local headline + tcp sub-object
    assert isinstance(payload["tcp"]["req_s"], (int, float))
    # the dynamic batcher actually batched, and steady state never
    # retraced (the AOT bucket menu absorbed every request)
    assert payload["batches"] <= payload["batched_requests"]
    assert payload["retraces_after_warmup"] == 0
    # continuous deployment (ISSUE 11): swap latency + poll-mode
    # weight-staleness lag ride every bench line, and a weight swap is
    # never a retrace (same shapes -> program-cache hit)
    ro = payload["rollout"]
    assert ro["swaps"] >= 1
    assert ro["swap_ms_p50"] > 0 and ro["swap_ms_max"] >= ro["swap_ms_p50"]
    assert ro["staleness_ms_p50"] > 0
    assert ro["staleness_ms_max"] >= ro["staleness_ms_p50"]
    assert ro["retraces"] == 0
    # continuous-batching generation (ISSUE 17): tokens/s per sweep
    # level with TTFT/per-step percentiles from the serve.gen.*
    # registry histograms, and a retrace-free steady state (the >= 2x
    # batching win at 64-vs-8 is pinned by ci/check_generate_perf.py,
    # not here — tiny levels are too small to assert a ratio)
    gen = payload["generate"]
    assert gen["slots"] >= 1 and gen["max_new"] >= 1
    assert gen["levels"], "generate sweep missing"
    for row in gen["levels"]:
        assert row["errors"] == 0, row
        assert row["tokens"] == row["sequences"] * gen["max_new"], row
        assert row["tok_s"] > 0
        assert row["ttft"]["count"] >= row["sequences"], row
        assert row["ttft"]["p99_ms"] >= row["ttft"]["p50_ms"] > 0
        assert row["step"]["count"] >= 1, row
        assert row["step"]["p99_ms"] >= row["step"]["p50_ms"] > 0
    assert gen["decode_steps"] >= 1
    assert gen["retraces_after_warmup"] == 0


def test_embedding_bench_contract(tmp_path):
    """tools/bench_embedding.py: exactly one JSON line, rc 0, with the
    sparse-wire scaling evidence (docs/perf_analysis.md "Sparse fast
    path"): bytes/step tracking rows touched, never table size."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_ROOT,
               MXTPU_PS_HEARTBEAT="0", MXTPU_BENCH_TINY="1")
    res = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools",
                                      "bench_embedding.py"),
         "--no-write"],
        capture_output=True, text=True, timeout=600, env=env)
    assert res.returncode == 0, res.stderr[-800:]
    lines = [l for l in res.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, "must print exactly ONE JSON line"
    payload = json.loads(lines[0])
    assert payload["bench"] == "embedding_sparse_wire"
    assert payload["transport"] == "tcp"
    for pt in payload["points"]:
        for kind in ("dense", "sparse"):
            assert pt[kind]["bytes_per_step"] > 0
            assert pt[kind]["steps_per_s"] > 0
        # the contract: sparse bytes track rows touched (within 2x of
        # the touch fraction — headers/ids are the slack), dense don't
        assert pt["bytes_ratio"] <= 2 * pt["touch_fraction"] + 0.01, pt


def test_streaming_bench_contract():
    """tools/bench_streaming.py (ISSUE 18): exactly one JSON line, rc 0,
    with the durable-log + exactly-once loop fields docs/perf_analysis.md
    "Streaming" is tracked by — tiny counts, CPU-only loopback."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_ROOT,
               MXTPU_BENCH_TINY="1", MXTPU_PS_HEARTBEAT="0")
    res = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "bench_streaming.py"),
         "--no-write"],
        capture_output=True, text=True, timeout=600, env=env)
    assert res.returncode == 0, res.stderr[-800:]
    lines = [l for l in res.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, "must print exactly ONE JSON line"
    payload = json.loads(lines[0])
    assert payload["bench"] == "streaming_loopback"
    assert payload["tiny"] is True
    assert payload["records"] >= 1 and payload["payload_bytes"] >= 1
    # durable log: append (buffered + fsync-per-record) and sealed tail
    for section in ("append", "append_fsync"):
        assert payload[section]["records_s"] > 0
        assert payload[section]["mb_s"] > 0
    # per-record durability must cost more than seal-time durability
    assert payload["append_fsync"]["records_s"] \
        <= payload["append"]["records_s"]
    assert payload["tail"]["records_s"] > 0
    # exactly-once loop: tail→train steps with the offset commit riding
    # each stream_push frame, plus the respawn-storm dup-refusal rate
    loop = payload["loop"]
    assert loop["steps_s"] > 0 and loop["records_s"] > 0
    assert loop["dup_refused_s"] > 0
