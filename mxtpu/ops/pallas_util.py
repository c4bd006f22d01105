"""What every Pallas kernel in the tree shares: the rule for which
lowering a call gets, and the VMEM budget a kernel has to fit.

The lowering follows the platform the computation is lowered FOR (where
its operands live), never the process-wide default backend: on a machine
whose default backend is the TPU an array committed to ``mx.cpu()`` still
lowers for the CPU, where only the Pallas interpreter exists.
"""
from __future__ import annotations

import jax

__all__ = ["per_platform", "SCOPED_VMEM_LIMIT"]

# Mosaic's default scoped-VMEM limit on v5e (libtpu 0.0.34) — the figure
# its "Scoped allocation with size ... and limit 16.00M" refusal names.
SCOPED_VMEM_LIMIT = 16 * 2 ** 20


def per_platform(make_call, *args):
    """Apply ``make_call(interpret=False)`` to ``args`` when lowered for
    TPU (Mosaic) and ``make_call(interpret=True)`` on any other platform;
    ``make_call`` is typically ``functools.partial(pl.pallas_call, ...)``.
    Call it under ``jax.jit`` only: eagerly there is no lowering to read
    the platform from, and jax would answer with the process default."""
    return jax.lax.platform_dependent(
        *args, tpu=make_call(interpret=False),
        default=make_call(interpret=True))
