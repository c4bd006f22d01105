#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, one JSON object as the last line of standard output.
The cell's configuration, traffic mix, model builder, plain reference and
per-layer readers are files found by the names in ``BENCHMARK.json``; see
``benchmarks/README.md``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse          # noqa: E402
import importlib         # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(name):
    manifest = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit("no workload %r in BENCHMARK.json (have: %s)"
                         % (name, ", ".join(sorted(cells))))
    return manifest, cells[name]


def metrics_of(manifest, group, cell_name):
    """The metrics of ``group`` that list this cell (or list no cells)."""
    return [m for m in manifest[group]
            if cell_name in m.get("workloads", (cell_name,))]


def require_chips(jax, chips):
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        sys.stderr.write("benchmark needs %d TPU chip(s); JAX found %d x %s\n"
                         % (chips, len(devs), devs[0].platform))
        raise SystemExit(3)


def peak_bytes(jax):
    """``(in use, reserved)`` at their peaks on the fullest chip. The
    allocator's ``peak_bytes_in_use`` holds the arrays (weights, optimizer
    state, batch, cache); the compiled programs' temporaries live in what the
    TPU runtime reserves for them, ``peak_bytes_reserved``, and never show in
    the first (PERF.md section 4 sets the reservation beside the compiler's
    own ``temp_size_in_bytes``). ``memory_peak_bytes`` is their sum."""
    fullest = (0, 0)
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        fullest = max(fullest, (int(stats.get("peak_bytes_in_use", 0)),
                                int(stats.get("peak_bytes_reserved", 0))),
                      key=sum)
    return fullest


def judge(numbers, limits, failed=0):
    """``(correct, compared)``: every number beside its limit. A number
    without a limit is printed for the record and not compared."""
    compared = {name: {"value": value, "limit": limits.get(name)}
                for name, value in numbers}
    correct = failed == 0 and all(
        c["value"] <= c["limit"] for c in compared.values()
        if c["limit"] is not None)
    return bool(correct), compared


def main(argv=None, require_chip=True, overrides=None, stand_ins=()):
    """Run one cell. The command line reaches none of the keyword arguments:
    ``require_chip=False`` and ``overrides`` (a dict merged over the
    configuration and traffic files) are for the tests, which drive a tiny
    preset on the CPU; ``stand_ins`` names readings with the plain reference
    put in the program's place (the control ``fp8``, the fault ``half_batch``,
    the witness ``bf16``), each judged by the run's own comparison and
    limits (``tests/on_chip_control.py`` reads them on the chip)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    manifest, cell = find_cell(args.workload)
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    cfg = load_json(ROOT, entry["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    for name, patch in (overrides or {}).items():
        {"config": cfg, "traffic": traffic}[name].update(patch)

    # the program reads its sizes from the environment as it is imported
    for k, v in cfg.get("env", {}).items():
        os.environ[k] = str(v)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    import jax
    if require_chip:
        require_chips(jax, int(cell["chips"]))
    dev = jax.devices()[0]
    peaks_table = load_json(HERE, "peaks.json")["devices"]
    if dev.device_kind not in peaks_table:
        if require_chip:
            raise SystemExit("no peaks for device kind %r in peaks.json"
                             % dev.device_kind)
        peaks = {}
    else:
        peaks = peaks_table[dev.device_kind]

    from benchmarks import common, trace_reduce
    family = cfg["family"]
    run = common.Run(
        cell=cell, cfg=cfg, traffic=traffic, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), t_start=T_START,
        model=importlib.import_module("benchmarks.models." + family),
        reference=importlib.import_module("benchmarks.reference." + family),
        peaks=peaks, stand_ins=tuple(stand_ins))
    run.trace_dir = os.path.join(ROOT, ".bench_trace", cell["name"])
    driver = importlib.import_module("benchmarks.drivers." + cfg["entry"])
    run.mark("imports")
    res = driver.run(run)

    in_use, reserved = peak_bytes(jax)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": int(cell["chips"]),
              "memory_peak_bytes": in_use + reserved}
    res["metrics"]["setup_s"] = run.setup_s
    t0, t1 = run.window
    print(json.dumps({"info": {
        "device": "%s x%d" % (dev.device_kind, device["count"]),
        "peak_bytes_in_use": in_use, "peak_bytes_reserved": reserved,
        "window_s": t1 - t0, "setup_phases": run.marks,
        "memory_stats": dev.memory_stats(),
        **{k: v for k, v in run.counters.items()
           if isinstance(v, (int, float, str))}}}), flush=True)

    # the comparison: after the window, after the peak has been read
    t_check = time.perf_counter()
    numbers, stood_in = res["check"]()
    correct, compared = judge(numbers, cfg["limits"], res["failed"])
    compared["check_s"] = {"value": time.perf_counter() - t_check,
                           "limit": None}

    out = {"correct": correct, "attempted": int(res["attempted"]),
           "failed": int(res["failed"])}
    e2e = metrics_of(manifest, "end_to_end", cell["name"])
    if run.trace:
        reduced = trace_reduce.reduce_dir(run.trace_dir)
        shutil.rmtree(run.trace_dir, ignore_errors=True)
        device["busy_s"], device["window_s"] = reduced.busy_s, reduced.window_s
        out["metrics"] = {}
        for m in metrics_of(manifest, "per_layer", cell["name"]):
            reader = importlib.import_module(common.reader_module(m["name"]))
            v = reader.read(run, reduced)
            if v is not None:       # nothing to read: left out of the line
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        out["breakdown"] = reduced.breakdown()
    else:
        out["metrics"] = {m["name"]: {"value": res["metrics"][m["name"]],
                                      "unit": m["unit"]} for m in e2e}
    out["device"] = device
    # the same comparison, with the reference in the program's place
    for kind, other in stood_in.items():
        verdict, beside = judge(other, cfg["limits"])
        out.setdefault("stand_ins", {})[kind] = {"correct": verdict,
                                                 "compared": beside}
    if run.stand_ins:
        out["looks"] = run.looks
    out["compared"] = compared
    for name, c in compared.items():
        sys.stderr.write("compared %s = %r (limit %r)\n"
                         % (name, c["value"], c["limit"]))
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    main()
