"""XLA-chosen (AUTO) layouts for persistent training state.

The round-5 TPU trace attributes ~22% of ResNet-50 step time to layout
copies: conv weights live in the layout the previous program produced
and get relaid out at every dispatch into the layout the convolutions
want. The fix is to let XLA choose the layouts ONCE at compile time and
then carry them across steps through donation — the step's outputs
adopt the chosen input layouts, so the steady state is relayout-free.

:class:`AutoLayoutStep` is the one implementation of that contract,
shared by :class:`~mxtpu.parallel.trainer.ShardedTrainer` (where it was
born) and the fused Module train step (:mod:`mxtpu.module.fused`,
``MXTPU_AUTO_LAYOUT=1`` on the single-host and both dist modes): wrap a
``jax.jit``-ted step whose persistent-state arguments were declared with
AUTO in/out layouts (:func:`auto_format`), and the wrapper AOT-compiles
on first call, relayouts the persistent state into the executable's
chosen input formats exactly once (``jax.device_put`` is a no-copy no-op
when the layouts already match — every later call), and invokes the
Compiled object directly.

:class:`MeshStep` (ISSUE 20) is the same carry-through-donation idea
one level up: instead of XLA-chosen layouts on one device, explicit
``NamedSharding`` placements over a device mesh — the wrapper scatters
the donated store across the mesh once and the program's matching
out_shardings keep it there.
"""
from __future__ import annotations

import contextlib
import os

import jax

__all__ = ["AutoLayoutStep", "MeshStep", "auto_format",
           "auto_layout_enabled"]


def auto_layout_enabled(default=None):
    """MXTPU_AUTO_LAYOUT: ``1`` compiles train steps with XLA-chosen
    (AUTO) layouts for the persistent state (params/optimizer
    state/aux), carried across steps via donation. Off by default."""
    if default is not None:
        return bool(default)
    return os.environ.get("MXTPU_AUTO_LAYOUT", "0") == "1"


def auto_format():
    """The AUTO-layout in/out sharding marker."""
    from jax.experimental.layout import Format, Layout
    return Format(Layout.AUTO)


class AutoLayoutStep:
    """A train-step callable compiled with XLA-chosen (AUTO) layouts for
    the persistent state.

    First call: AOT-lower/compile, relayout the ``state_argnums``
    arguments once into the executable's chosen input formats, then
    invoke the Compiled object directly. Steady state: the step's
    outputs already carry the chosen layouts (out layouts are
    AUTO-matched to the donated inputs), so every later call is
    relayout-free — the whole point: conv weights stay in the layout
    the convolutions want instead of paying a copy per step.

    ``mesh``: optional MeshContext whose ``.mesh`` scopes lowering
    (the ShardedTrainer SPMD path); None for single-device callers
    (the fused Module step)."""

    def __init__(self, jitted, mesh=None, state_argnums=(0, 1, 2)):
        self._jit = jitted
        self._mesh = mesh
        self._state_argnums = tuple(state_argnums)
        self._compiled = None

    def _scope(self):
        return self._mesh.mesh if self._mesh is not None \
            else contextlib.nullcontext()

    @staticmethod
    def _abstract(args):
        # AUTO-layout lowering demands abstract args (a concrete
        # jax.Array carries a concrete layout, which contradicts
        # "compiler's choice"); shardings ride along so the SPMD
        # partition matches the eventual real calls
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding), args)

    def lower(self, *args):  # compiled_step() parity with plain jit
        with self._scope():
            return self._jit.lower(*self._abstract(args))

    def __call__(self, *args):
        if self._compiled is None:
            abst = self._abstract(args)
            with self._scope():
                self._compiled = self._jit.lower(*abst).compile()
        # relayout the persistent state into the executable's chosen
        # input formats on EVERY call — device_put is a no-copy no-op
        # once the layouts already match (the donated steady state), but
        # it must run unconditionally: a second batch shape compiles a
        # NEW executable whose chosen layouts may differ from what the
        # first one's outputs carry, and with donate=False the step's
        # outputs never adopt the input formats at all — both used to
        # raise layout-mismatch on the second call.
        fmts = self._compiled.input_formats[0]
        args = list(args)
        for i in self._state_argnums:
            args[i] = jax.device_put(args[i], fmts[i])
        return self._compiled(*args)


class MeshStep:
    """A fused step compiled as an SPMD program over a device mesh
    (ISSUE 20): the ``jax.jit`` was built with explicit NamedSharding
    ``in_shardings``/``out_shardings`` so the donated param/opt-state/
    aux store lives SHARDED across the mesh — per-device memory ~1/N —
    and GSPMD inserts the collectives.

    ``shardings`` maps argnum -> the placement of that argument: a
    single sharding, a tuple of shardings, or a nested tuple tree
    mirroring an optimizer-state tree. Every call device_puts the
    mapped arguments into their target shardings first: the FIRST call
    scatters the single-device seed store across the mesh (one real
    transfer), and every later call is a no-copy no-op because the
    step's out_shardings equal its in_shardings — donation carries the
    sharded buffers across steps, so the steady state is
    reshard-free. Batch arguments mapped here pay one host->mesh
    placement per step, which is the input pipeline, not a sync.
    """

    def __init__(self, jitted, mesh, shardings):
        self._jit = jitted
        self.mesh = mesh
        self._shardings = dict(shardings)

    @staticmethod
    def _put(val, sh):
        # pairwise recursion over matching tuple structure; a single
        # sharding against a subtree broadcasts over its leaves
        # (jax.device_put pytree semantics)
        if isinstance(val, (tuple, list)) and \
                isinstance(sh, (tuple, list)) and len(val) == len(sh):
            return tuple(MeshStep._put(v, s) for v, s in zip(val, sh))
        if val is None or sh is None:
            return val
        return jax.device_put(val, sh)

    def lower(self, *args):  # compiled_step() parity with plain jit
        # abstract avals: the jit's in_shardings place every argument,
        # wherever the caller's concrete arrays happen to sit
        return self._jit.lower(*jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args))

    def __call__(self, *args):
        args = list(args)
        for i, sh in self._shardings.items():
            args[i] = self._put(args[i], sh)
        return self._jit(*args)
