"""Device-mesh construction and sharding rules.

TPU-first replacement for MXNet's device-placement machinery: where the
reference assigns whole ops to devices (``group2ctx`` →
``nnvm::pass::PlaceDevice`` inserting ``_CrossDeviceCopy`` nodes,
``src/executor/graph_executor.cc:313-406``) and replicates whole models per
GPU for data parallelism (``python/mxnet/module/executor_group.py:289``),
here a single jitted program is laid out over a named
``jax.sharding.Mesh`` and XLA/GSPMD inserts the collectives (psum /
all-gather / reduce-scatter over ICI) that the reference's KVStore comm
trees (``src/kvstore/comm.h``) and NCCL backend performed by hand.

Canonical axis names:

* ``data``   — batch sharding (DP; the DataParallelExecutorGroup axis)
* ``model``  — tensor parallelism (the superset of group2ctx placement)
* ``pipe``   — pipeline stages
* ``seq``    — sequence/context parallelism (ring attention)
* ``expert`` — expert parallelism for MoE
"""
from __future__ import annotations

import math
import re

import numpy as _np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from jax import shard_map


def use_mesh(mesh):
    """Context manager installing ``mesh`` (a ``Mesh`` or ``MeshContext``)
    as the ambient mesh for pjit/sharding resolution: ``with mesh:``."""
    return mesh.mesh if isinstance(mesh, MeshContext) else mesh


__all__ = ["AXIS_DATA", "AXIS_MODEL", "AXIS_PIPE", "AXIS_SEQ", "AXIS_EXPERT",
           "make_mesh", "MeshContext", "ShardingRules", "PartitionSpec",
           "NamedSharding", "Mesh", "current_mesh", "shard_map", "use_mesh"]

AXIS_DATA = "data"
AXIS_MODEL = "model"
AXIS_PIPE = "pipe"
AXIS_SEQ = "seq"
AXIS_EXPERT = "expert"

_CURRENT_MESH = []


def make_mesh(devices=None, **axis_sizes):
    """Build a ``jax.sharding.Mesh`` from named axis sizes.

    ``make_mesh(data=4, model=2)`` arranges 8 devices into a 4x2 mesh.
    An axis size of -1 absorbs the remaining devices (like a reshape -1).
    With no axes given, all devices go on the ``data`` axis — the
    equivalent of the reference's default ``ctx=[mx.gpu(i) for i in ...]``
    data-parallel setup.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if not axis_sizes:
        axis_sizes = {AXIS_DATA: n}
    names = list(axis_sizes.keys())
    sizes = list(axis_sizes.values())
    n_fill = sizes.count(-1)
    if n_fill > 1:
        raise ValueError("at most one axis may be -1")
    if n_fill == 1:
        known = int(_np.prod([s for s in sizes if s != -1]))
        if n % known:
            raise ValueError("cannot infer -1 axis: %d devices / %d" % (n, known))
        sizes[sizes.index(-1)] = n // known
    total = int(_np.prod(sizes))
    if total > n:
        raise ValueError("mesh wants %d devices, only %d available" % (total, n))
    dev_array = _np.array(devices[:total]).reshape(sizes)
    return Mesh(dev_array, tuple(names))


class MeshContext:
    """A mesh plus the sharding helpers built on it.

    The mxtpu analogue of a ``Context`` list: where reference code wrote
    ``ctx=[mx.gpu(0), mx.gpu(1)]``, mxtpu code builds a ``MeshContext``
    and hands it to ``ShardedTrainer`` / ``Module(..., mesh=...)``.
    """

    def __init__(self, mesh_or_sizes=None, **axis_sizes):
        if isinstance(mesh_or_sizes, Mesh):
            self.mesh = mesh_or_sizes
        elif isinstance(mesh_or_sizes, dict):
            self.mesh = make_mesh(**mesh_or_sizes)
        else:
            self.mesh = make_mesh(devices=mesh_or_sizes, **axis_sizes)
        self._mesh_cm = None

    # -- properties --------------------------------------------------------
    @property
    def axis_names(self):
        return tuple(self.mesh.axis_names)

    @property
    def shape(self):
        return dict(zip(self.mesh.axis_names, self.mesh.devices.shape))

    def axis_size(self, name):
        return self.shape.get(name, 1)

    @property
    def num_devices(self):
        return int(self.mesh.devices.size)

    # -- sharding constructors --------------------------------------------
    def sharding(self, *spec):
        """NamedSharding from a PartitionSpec-style tuple."""
        if len(spec) == 1 and isinstance(spec[0], PartitionSpec):
            return NamedSharding(self.mesh, spec[0])
        return NamedSharding(self.mesh, PartitionSpec(*spec))

    def replicated(self):
        return NamedSharding(self.mesh, PartitionSpec())

    def batch_sharding(self, ndim=None, axis=AXIS_DATA):
        """Shard dim 0 over the data axis (and optionally dim 1 over seq):
        the _split_input_slice equivalent, done by XLA instead of host-side
        np splits (reference executor_group.py:330)."""
        if axis not in self.axis_names:
            return self.replicated()
        return self.sharding(axis)

    def __enter__(self):
        _CURRENT_MESH.append(self)
        self._mesh_cm = use_mesh(self.mesh)
        self._mesh_cm.__enter__()
        return self

    def __exit__(self, *a):
        cm, self._mesh_cm = self._mesh_cm, None
        cm.__exit__(*a)
        _CURRENT_MESH.pop()

    def __repr__(self):
        return "MeshContext(%s)" % (self.shape,)


def current_mesh():
    return _CURRENT_MESH[-1] if _CURRENT_MESH else None


class ShardingRules:
    """Regex → PartitionSpec rules mapping parameter names to shardings.

    The TPU-native rendering of the reference's per-layer placement
    (``group2ctx``): instead of naming a device group per layer, name a
    partition spec per parameter pattern and let GSPMD place the
    computation. First match wins; unmatched params are replicated
    (pure DP).

    Example (tensor parallelism for a dense tower)::

        rules = ShardingRules([
            (r".*dense\\d*_weight", P(None, "model")),   # col-parallel
            (r".*conv\\d*_weight",  P("model", None, None, None)),
        ])
    """

    def __init__(self, rules=None):
        self.rules = [(re.compile(pat), spec) for pat, spec in (rules or [])]

    @classmethod
    def from_ctx_groups(cls, symbol, group2spec):
        """Build rules from ``ctx_group`` attributes stamped by AttrScope
        (the reference's group2ctx flow, ``with mx.AttrScope(ctx_group=
        'dev1'):`` + ``group2ctx`` in bind): every variable whose node
        carries ``ctx_group: g`` gets ``group2spec[g]``.

        >>> with mx.AttrScope(ctx_group="experts"):
        ...     w = mx.sym.var("expert_weight")
        >>> rules = ShardingRules.from_ctx_groups(
        ...     net, {"experts": P("model", None)})
        """
        attrs = symbol.attr_dict() if hasattr(symbol, "attr_dict") else {}
        names = set(symbol.list_arguments()) | \
            set(symbol.list_auxiliary_states()) \
            if hasattr(symbol, "list_arguments") else set(attrs)
        rules = []
        for name, a in attrs.items():
            if name not in names:     # variables only, not op nodes
                continue
            g = a.get("ctx_group")
            if g is not None and g in group2spec:
                rules.append((re.escape(name) + "$", group2spec[g]))
        return cls(rules)

    def spec_for(self, name, shape):
        for pat, spec in self.rules:
            if pat.match(name):
                return self._fit(spec, shape)
        return PartitionSpec()

    @staticmethod
    def _fit(spec, shape):
        """Trim a spec to the array rank and drop axes that don't divide
        the dim (falls back to replication on that dim, like GSPMD's
        padding-free behaviour for ragged shapes)."""
        spec = tuple(spec)[: len(shape)]
        spec = spec + (None,) * (len(shape) - len(spec))
        return PartitionSpec(*spec)

    def sharding_for(self, mesh_ctx, name, shape):
        spec = self.spec_for(name, shape)
        # drop mesh axes that don't divide the dimension
        cleaned = []
        for dim, ax in zip(shape, tuple(spec)):
            if ax is None:
                cleaned.append(None)
                continue
            axes = ax if isinstance(ax, (list, tuple)) else (ax,)
            size = int(math.prod(mesh_ctx.axis_size(a) for a in axes))
            cleaned.append(ax if size and dim % size == 0 else None)
        return mesh_ctx.sharding(PartitionSpec(*cleaned))
