#!/usr/bin/env python3
"""By hand, on the chip: one run of a ``mistral4`` cell at its own size with
a fault planted in the PROGRAM, judged by the run's own comparison and
limits. It has to come out not correct.

    python3 benchmarks/tests/on_chip_fault_mistral4.py <cell> <seed> <seconds> <fault>

The faults are planted in the registry ops the symbol is built from, for as
long as the run lasts; the reference keeps the equations as they are:

- ``query_scale_dropped``: ``g`` = 1 at every position (``latent_attention``
  is called with ``pos_scale_beta`` 0, in prefill and decode alike): scores
  at positions past the original context lose their factor ``1 + 0.1 ln 2``;
- ``router_sigmoid``: the router's softmax replaced by the sigmoid
  (``moe_ffn_held`` is called with ``scoring="sigmoid"``): the same experts
  are chosen, since both grow with the logit, and weighed otherwise.

Exits 1 if the verdict is ``correct``. Not collected by pytest; the
benchmark's own runs never do this.
"""
import contextlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def _with_attribute(name, value):
    def spoil(sound):
        def op(*args, **attrs):
            return sound(*args, **dict(attrs, **{name: value}))
        return op
    return spoil


FAULTS = {"query_scale_dropped": ("latent_attention",
                                  _with_attribute("pos_scale_beta", 0.0)),
          "router_sigmoid": ("moe_ffn_held",
                             _with_attribute("scoring", "sigmoid"))}


@contextlib.contextmanager
def planted(fault):
    """The registry op with the fault in it, for as long as the block runs
    (a symbol's nodes call their op's ``fn`` when a program is traced)."""
    from mxtpu.ops.registry import get_op
    name, spoil = FAULTS[fault]
    op = get_op(name)
    sound = op.fn
    op.fn = spoil(sound)
    try:
        yield
    finally:
        op.fn = sound


def main(cell, seed, seconds, fault):
    from benchmarks import run
    with planted(fault):
        out = run.main(["--workload", cell, "--seed", seed, "--seconds",
                        seconds, "--trace", "0"])
    sys.stderr.write("fault %s: correct %r, compared %r\n"
                     % (fault, out["correct"], out["compared"]))
    return 1 if out["correct"] else 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:5]))
