#!/usr/bin/env python3
"""By hand, on the chip: one run of a cell at its own size with a fault
planted in the PROGRAM (the symbol the engine is built from), judged by the
run's own comparison and limits. It has to come out not correct.

    python3 benchmarks/tests/on_chip_fault.py <cell> <seed> <seconds> <fault>

Faults, by the configuration's ``family``: ``exaone_moe``: ``routed_dropped``
(the held routed experts' terms are left out: the symbol is built with
``routed_scaling_factor`` 0), ``band_off_by_one`` (window layers attend one
position fewer: ``sliding_window`` less 1 over the same rings). The
reference keeps the configuration as it is. Exits 1 if the verdict is
``correct``. Not collected by pytest; the benchmark's own runs never do
this.
"""
import contextlib
import importlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

FAULTS = {"exaone_moe": {
    "routed_dropped": lambda cfg: dict(cfg, routed_scaling_factor=0.0),
    "band_off_by_one": lambda cfg: dict(
        cfg, sliding_window=int(cfg["sliding_window"]) - 1)}}


@contextlib.contextmanager
def planted(family, fault):
    """The family's ``symbol`` built from a configuration with the fault in
    it, for as long as the block runs."""
    model = importlib.import_module("benchmarks.models." + family)
    sound, spoil = model.symbol, FAULTS[family][fault]
    model.symbol = lambda cfg: sound(spoil(cfg))
    try:
        yield
    finally:
        model.symbol = sound


def main(cell, seed, seconds, fault):
    from benchmarks import run
    manifest, entry = run.find_cell(cell)
    config = {c["name"]: c for c in manifest["configs"]}[entry["config"]]
    family = run.load_json(run.ROOT, config["file"])["family"]
    with planted(family, fault):
        out = run.main(["--workload", cell, "--seed", seed, "--seconds",
                        seconds, "--trace", "0"])
    sys.stderr.write("fault %s: correct %r, compared %r\n"
                     % (fault, out["correct"], out["compared"]))
    return 1 if out["correct"] else 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:5]))
