#!/usr/bin/env python3
"""By hand, on the chip: one run of a cell at its own size with the plain
reference put in the program's place, each stand-in judged by the run's own
comparison and limits.

    python3 benchmarks/tests/on_chip_control.py <cell> <seed> <seconds> fp8[,half_batch,bf16]

The control (``fp8``) and the fault (``half_batch``) have to come out not
correct, the program and the witness at the stated precision (``bf16``)
correct. Exits 1 otherwise. Not collected by pytest; the benchmark's own
runs never do this.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

MUST_BE = {"fp8": False, "half_batch": False, "bf16": True}


def main(cell, seed, seconds, kinds):
    from benchmarks import run
    kinds = tuple(kinds.split(","))
    out = run.main(["--workload", cell, "--seed", seed, "--seconds", seconds,
                    "--trace", "0"], stand_ins=kinds)
    got = {"program": out["correct"],
           **{k: out["stand_ins"][k]["correct"] for k in kinds}}
    want = {"program": True, **{k: MUST_BE[k] for k in kinds}}
    sys.stderr.write("verdicts %r, wanted %r\n" % (got, want))
    return 0 if got == want else 1


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:5]))
