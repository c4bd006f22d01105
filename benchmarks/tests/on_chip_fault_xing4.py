#!/usr/bin/env python3
"""By hand, on the chip: one run of a ``xing4_0`` cell at its own size with
a fault planted in the PROGRAM, judged by the run's own comparison and
limits. It has to come out not correct.

    python3 benchmarks/tests/on_chip_fault_xing4.py <cell> <seed> <seconds> <fault>

The faults are planted in the registry ops the symbol is built from, for as
long as the run lasts; the reference keeps the equations as they are:

- ``rope_key_dropped``: the score's rotary term is left out (``latent_attention``
  is handed queries whose rotary columns are zero, in prefill and decode
  alike; the cache still holds ``k_rope``);
- ``streams_collapsed``: ``hyper_mix`` with ``R`` the identity, every read
  weight ``1 / n`` and every write weight 1: a plain residual stream.

Exits 1 if the verdict is ``correct``. Not collected by pytest; the
benchmark's own runs never do this. (``on_chip_fault.py`` plants its faults
through the configuration; these two have no configuration key.)
"""
import contextlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def _rope_key_dropped(sound):
    import jax.numpy as jnp

    def latent_attention(query, *rest, **attrs):
        heads = int(attrs["num_heads"])
        nope, rope = int(attrs["nope_dim"]), int(attrs["rope_dim"])
        keep = jnp.tile(jnp.arange(nope + rope) < nope, heads)
        return sound(jnp.where(keep, query, 0).astype(query.dtype), *rest,
                     **attrs)
    return latent_attention


def _streams_collapsed(sound):
    import jax.numpy as jnp

    def hyper_mix(streams, *_leaves, **_attrs):
        x = streams.astype(jnp.float32)
        read = jnp.mean(x, axis=2).astype(streams.dtype)
        return read, x, jnp.ones(streams.shape[:3], jnp.float32)
    return hyper_mix


FAULTS = {"rope_key_dropped": ("latent_attention", _rope_key_dropped),
          "streams_collapsed": ("hyper_mix", _streams_collapsed)}


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
