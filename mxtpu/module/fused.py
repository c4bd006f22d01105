"""Fused Module train step: one donated XLA program per bucket.

The eager ``Module.fit`` hot loop pays three distinct overheads per batch:
``forward_backward`` dispatches a (speculatively fused) forward+vjp
program, ``update`` walks every parameter through a Python updater loop —
one eager optimizer-op dispatch per parameter — and ``update_metric``
forces a full ``asnumpy()`` device sync. This module collapses all three
into ONE jitted XLA program per (bucket, batch shape, dtype): forward +
backward + the ENTIRE optimizer update as a multi-tensor apply (reusing
the ``ops/optim_ops.py`` kernels through
:func:`mxtpu.optimizer.functional_optimizer_step`), plus the metric's
device-side (sum, count) accumulation (``EvalMetric.update_async``), with
params / optimizer state / rng key / step count / metric accumulator all
DONATED so XLA updates the buffers in place.

Donation semantics: after every fused step the previous parameter and
optimizer-state buffers are invalidated and each ``NDArray``'s ``_data``
is rebound to the program's output — holders of the NDArray *wrappers*
(executor ``arg_dict``, ``param_arrays``, updater states) always see the
fresh values; raw ``jax.Array`` handles taken before a step are dead
after it.

``BucketingModule`` buckets share one optimizer (``borrow_optimizer``)
and, here, one :class:`FusedGroupState`: every bucket's executor aliases
the SAME parameter/aux NDArray objects (``Executor.adopt_arrays``), each
bucket keeps its own compiled program per batch signature, and a bucket
switch is a program-cache hit — no host-side parameter propagation, no
re-dispatch.

Distributed mode (ISSUE 10): a kvstore-managed Module no longer falls
back to eager — it is a FAST path. The donated program switches to the
grad-EMITTING form (``Executor.make_fused_grad_step``: forward +
backward + device-metric accumulation, returning gradients), and the
update rides the kvstore per its mode: ``update_on_kvstore`` pushes the
gradients and pulls the server-updated weights straight back into the
shared device parameter store (so bucket switches keep working), while
the locally-applied mode pushes, pulls the merged gradients, and runs
them through a donated multi-tensor apply program
(``make_fused_apply_step``). ``MXTPU_MODULE_DIST_MODE=async`` pipelines
the push+pull on the store's worker pool under the PR-2 bounded-inflight
window (``mxtpu/dist_hooks.py``, ``MXTPU_MODULE_PUSH_INFLIGHT``) so the
next step's compute overlaps the wire; the default ``sync`` mode ships
inline and matches the eager dist path bit-for-bit.
``MXTPU_MODULE_FUSED_DIST=0`` confines fusion to the local path.

Mixed precision (ISSUE 12): ``MXTPU_AMP=bf16`` makes bf16-with-fp32-
master-weights a MODE of the same one-program contract, not a separate
path. The donated store keeps fp32 master weights, fp32 optimizer state
and fp32 aux (BN running statistics); the program casts params and
floating inputs (never labels, never aux) to bf16 INSIDE the trace, so
activations and the backward run on the MXU's native reduced precision
while gradients return fp32 through the cast VJP and
``functional_optimizer_step`` applies in fp32 — cast-in/cast-out in the
SAME program: zero extra host syncs, zero retraces. On the dist modes
the grad-emitting program additionally casts the EMITTED gradients to
bf16 for the wire (``kv.push_pull`` frames carry the dtype in the
payload; the server's fp32 master table upcasts on apply and replies
bf16 in kind — wire bytes per step drop ~2x on top of coalescing),
unless GradientCompression is installed (2-bit beats bf16: compressed
parts skip the cast, no double-compress). ``MXTPU_AMP_LOSS_SCALE=S``
optionally scales the loss by S and reuses the TrainGuard isfinite
verdict in-program: an overflow step is skipped (local modes: every
donated buffer held at its pre-step value; dist mode: zero gradients
ship, a server no-op) with the skip count readable via
``FusedGroupState.amp_overflow_skips()``. AMP-ineligible setups (non-
fp32 parameters) log their reason once at warning level and keep the
fp32 fused path — never a silent wrong-dtype step.

``MXTPU_AUTO_LAYOUT=1`` (shared with ShardedTrainer via
``mxtpu/layout.py``) compiles the fused programs with XLA-chosen AUTO
layouts for the donated persistent state and relayouts the store ONCE
at compile, not per call — the layout-copy share of the step trace
goes to the compiler's choice.

Sparse embeddings (ISSUE 13): a kvstore-managed module whose
row-sparse parameters are Embedding tables stays ONE XLA program — the
grad-emitting step dedupes the batch's indices on device (static-shape
sort/segment unique) and gathers the touched rows out of the dense VJP
gradient (``Executor.make_fused_grad_step(sparse_emits=...)``), so the
emitted entry is a ``(row_ids, rows)`` pair. ``finish_update`` ships
it over the ``sparse_push_pull`` wire op: only touched rows travel,
the server applies with the row-wise optimizer mirror
(``Optimizer.update_host_rows``), and the gathered reply scatters back
into the shared device store — wire bytes and server optimizer cost
scale with rows touched, never with table size. bf16 rows compose with
``MXTPU_AMP`` exactly like dense gradients. Requires
``update_on_kvstore`` (the server owns the full table and its state —
the reference's sparse-table contract); ``MXTPU_MODULE_FUSED_SPARSE=0``
restores the eager densifying fallback.

Escape hatch: anything the one-program contract can't honor — a
``Monitor`` install (wants per-node outputs), a custom Python updater,
sparse parameters off the server-managed dist path, multi-context
groups, ``inputs_need_grad`` — falls
back to the eager path (warning once for monitor / custom updaters;
every silent fallback logs its reason once at warning level, see
``_fused_eligible``). ``MXTPU_MODULE_FUSED=0`` disables the whole
mechanism (``docs/env_vars.md``).
"""
from __future__ import annotations

import copy
import logging
import os
import threading
import time
import warnings

import numpy as _np
import jax
import jax.numpy as jnp

from .. import fault as _fault
from .. import ndarray as nd
from .. import obs as _obs
from .. import optimizer as opt_mod
from ..dist_hooks import AsyncPushWindow, push_inflight
from ..layout import auto_layout_enabled
from ..model import _module_fused_enabled
from ..ndarray import NDArray, _wrap
from ..optimizer import state_to_tree

# the training-side fleet instruments (ISSUE 14): attempted fused
# steps, and the steady-state step wall time measured as the gap
# between consecutive step() entries — the donated-buffer handoff
# already serializes consecutive dispatches, so the gap IS the step
# time in steady state with NO extra device sync (the same
# no-extra-sync discipline as the guard's packed read).
_M_STEPS = _obs.counter("module.steps", "fused train steps dispatched")
_M_STEP_MS = _obs.histogram(
    "module.step_ms",
    "inter-step wall time of the fused train loop (steady state)")

__all__ = ["ProgramCache", "FusedGroupState", "FusedModuleTrainer",
           "maybe_create", "attach_borrowed", "metric_readback_interval",
           "_fused_eligible", "amp_mode", "amp_loss_scale"]


class ProgramCache:
    """Per-signature compiled-program cache shared by the fused Module
    train step and the serving engine (``mxtpu/serving/engine.py``).

    One entry per signature key — for training a (data shapes, label
    shapes, metric) tuple, for serving a (bucket, input signature)
    tuple — built exactly once by the caller's ``build`` closure.
    ``compiles``/``hits`` are the retrace observability both
    ``ci/check_module_perf.py`` and ``ci/check_serving.py`` pin their
    zero-retraces-after-warmup contracts on. Thread-safe: the serving
    batcher compiles from its flush thread while handler threads may
    probe stats concurrently."""

    def __init__(self):
        self._programs = {}
        self._lock = threading.Lock()
        self.compiles = 0
        self.hits = 0
        self.imports = 0

    def get(self, key, build):
        """The program for ``key``, building (and counting a compile)
        on first use. Returns ``(program, hit)`` so callers can keep
        their own per-group counters."""
        with self._lock:
            entry = self._programs.get(key)
            if entry is not None:
                self.hits += 1
                return entry, True
        # compile OUTSIDE the lock: a slow trace must not block stats
        # probes (a racing duplicate build is benign — last write wins,
        # both programs are identical)
        entry = build()
        with self._lock:
            self._programs[key] = entry
            self.compiles += 1
        return entry, False

    def __len__(self):
        with self._lock:
            return len(self._programs)

    def keys(self):
        with self._lock:
            return list(self._programs)

    def stats(self):
        with self._lock:
            return {"programs": len(self._programs),
                    "compiles": self.compiles, "hits": self.hits,
                    "imports": self.imports}

    # -- AOT program export/import (ISSUE 16 prewarm) -------------------
    # A joiner that can LOAD a peer's compiled executables skips the
    # cold compile entirely: `jax.experimental.serialize_executable`
    # round-trips an AOT-compiled program (XLA serialized executable +
    # pickled in/out trees), and the cache file is just a pickle of
    # {key: serialized-program}. Entries that are not serializable
    # executables (training closures) are skipped on export, so the
    # same cache class serves both the fused trainer and the serving
    # engine unchanged.

    def export_to(self, path, meta=None):
        """Serialize every exportable compiled entry to ``path``
        (atomic tmp + rename); returns how many entries landed, 0 when
        nothing in the cache can be serialized (no file written)."""
        import pickle
        from jax.experimental import serialize_executable as _se
        with self._lock:
            items = list(self._programs.items())
        programs = {}
        for key, entry in items:
            try:
                programs[key] = pickle.dumps(_se.serialize(entry))
            except Exception:
                continue         # not an AOT executable: skip, no harm
        if not programs:
            return 0
        doc = {"meta": meta, "programs": programs}
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = "%s.tmp.%d" % (path, os.getpid())
        with open(tmp, "wb") as f:
            pickle.dump(doc, f, protocol=pickle.HIGHEST_PROTOCOL)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        return len(programs)

    def import_from(self, path, expect_meta=None):
        """Load a peer's exported programs into this cache; returns
        the number imported (cached keys are never overwritten, so a
        warm cache imports 0). Raises ``ValueError`` when the file's
        meta fingerprint does not match ``expect_meta`` — a prewarm
        file from a different model/signature must never install."""
        import pickle
        from jax.experimental import serialize_executable as _se
        with open(path, "rb") as f:
            doc = pickle.load(f)
        if expect_meta is not None and doc.get("meta") != expect_meta:
            raise ValueError(
                "program cache %s was exported for a different "
                "signature (meta mismatch)" % path)
        imported = 0
        for key, blob in (doc.get("programs") or {}).items():
            with self._lock:
                if key in self._programs:
                    continue
            payload, in_tree, out_tree = pickle.loads(blob)
            program = _se.deserialize_and_load(payload, in_tree,
                                               out_tree)
            with self._lock:
                if key in self._programs:
                    continue     # racing warm(): first entry wins
                self._programs[key] = program
                self.imports += 1
                imported += 1
        return imported


def metric_readback_interval():
    """MXTPU_METRIC_READBACK: drain the device metric accumulator every N
    batches (0 = only when the metric is read: epoch end / callbacks)."""
    try:
        return int(os.environ.get("MXTPU_METRIC_READBACK", "0"))
    except ValueError:
        return 0


def _fused_dist_enabled():
    """MXTPU_MODULE_FUSED_DIST: default on; ``0`` keeps kvstore-managed
    modules on the eager push/pull loop (the pre-ISSUE-10 behavior)."""
    return os.environ.get("MXTPU_MODULE_FUSED_DIST", "1").strip().lower() \
        not in ("0", "false", "off")


def _fused_sparse_enabled():
    """MXTPU_MODULE_FUSED_SPARSE: default on; ``0`` sends modules with
    row-sparse parameters back to the eager dist path (which densifies
    every embedding gradient onto the wire — the pre-ISSUE-13
    behavior, kept as the escape hatch)."""
    return os.environ.get("MXTPU_MODULE_FUSED_SPARSE",
                          "1").strip().lower() not in ("0", "false",
                                                       "off")


def _sparse_param_names(exec_):
    """Names bound with sparse storage (arg or grad) — the set the
    eligibility predicate and the sparse-emit plan both key on."""
    out = []
    for name, arr in exec_.arg_dict.items():
        if hasattr(arr, "_aux") or \
                hasattr(exec_.grad_dict.get(name), "_aux"):
            out.append(name)
    return out


def _sparse_grad_feeds(module, sparse_names):
    """Resolve each sparse parameter's index feeds: the DIRECT-input
    data variables of the Embedding nodes consuming it. Returns
    ``(feeds dict, reason)`` — feeds is None with a human-readable
    reason when the one-program sparse contract can't hold (a consumer
    other than Embedding would put gradient mass outside the touched
    rows; a computed index feed has no value the emit can read)."""
    feeds = {n: [] for n in sparse_names}
    sparse_set = set(sparse_names)
    for node in module._symbol._topo():
        if node.op is None:
            continue
        for pos, (src, _oi) in enumerate(node.inputs):
            if not src.is_variable or src.name not in sparse_set:
                continue
            if getattr(node.op, "name", None) != "Embedding" or pos != 1:
                return None, (
                    "sparse parameter %r consumed by %r (only Embedding"
                    " lookups emit row-sparse gradients)"
                    % (src.name, getattr(node.op, "name", node.name)))
            data_node = node.inputs[0][0]
            if not data_node.is_variable:
                return None, (
                    "sparse parameter %r indexed by a computed value "
                    "(the sparse emit needs a direct input feed)"
                    % (src.name,))
            feeds[src.name].append(data_node.name)
    for name, fs in feeds.items():
        if not fs:
            return None, ("sparse parameter %r has no Embedding "
                          "consumer" % (name,))
    return {n: tuple(fs) for n, fs in feeds.items()}, None


def amp_mode():
    """MXTPU_AMP: mixed-precision mode of the fused Module path.
    Default off; ``bf16`` = bf16 compute params + activations with fp32
    master weights, optimizer state and aux living in the donated store
    (module docstring, "Mixed precision"). Anything else raises — a
    typo'd dtype silently training fp32 would defeat the point."""
    v = os.environ.get("MXTPU_AMP", "").strip().lower()
    if v in ("", "0", "off", "none", "false"):
        return None
    if v in ("bf16", "bfloat16"):
        return "bf16"
    raise ValueError("MXTPU_AMP must be unset/'bf16', got %r" % v)


def amp_loss_scale():
    """MXTPU_AMP_LOSS_SCALE: static loss scale S for the AMP step
    (0/unset = off — bf16 shares fp32's exponent range, so scaling is
    optional belt-and-braces). When set, the fused program scales the
    head cotangent by S, unscales gradients by 1/S in fp32, and skips
    the step in-program when the TrainGuard isfinite verdict fails."""
    try:
        return float(os.environ.get("MXTPU_AMP_LOSS_SCALE", "0") or 0.0)
    except ValueError:
        return 0.0


def dist_mode():
    """MXTPU_MODULE_DIST_MODE: ``sync`` (default — push+pull inline,
    bit-for-bit with the eager dist path) or ``async`` (pipelined on the
    store's worker pool under the bounded-inflight window)."""
    mode = os.environ.get("MXTPU_MODULE_DIST_MODE", "sync").strip().lower()
    return "async" if mode == "async" else "sync"


def mesh_spec():
    """MXTPU_MESH: engage the mesh-sharded fused step (ISSUE 20) with
    no code changes — comma-separated ``axis=size`` pairs building a
    MeshContext over all local devices, e.g. ``model=-1`` (every device
    on the tensor axis) or ``data=2,model=4``; ``-1`` absorbs the
    remainder like :func:`~mxtpu.parallel.mesh.make_mesh`. Unset/empty
    keeps the single-device program. Modules configured explicitly via
    ``Module.set_sharding`` win over the env."""
    v = os.environ.get("MXTPU_MESH", "").strip()
    if not v:
        return None
    out = {}
    for part in v.split(","):
        axis, sep, size = part.partition("=")
        axis = axis.strip()
        if not sep or not axis:
            raise ValueError(
                "MXTPU_MESH wants 'axis=size[,axis=size...]', got %r"
                % (v,))
        try:
            out[axis] = int(size)
        except ValueError:
            raise ValueError("MXTPU_MESH axis %r has non-integer size "
                             "%r" % (axis, size.strip()))
    return out


def _mesh_config(module):
    """Resolve the module's mesh engagement: ``(mesh, rules, reason)``.
    An explicit ``Module.set_sharding(mesh, rules)`` wins; otherwise
    ``MXTPU_MESH`` builds the mesh and, with no rules given, every
    parameter's dim 0 shards over the FIRST mesh axis where it divides
    (FSDP-style — the 1/N memory default; non-dividing dims replicate
    per ``ShardingRules._fit``)."""
    mesh = getattr(module, "_mesh_ctx", None)
    rules = getattr(module, "_sharding_rules", None)
    if mesh is None:
        spec = mesh_spec()
        if spec is None:
            return None, None, None
        from ..parallel.mesh import MeshContext
        mesh = MeshContext(spec)
    if mesh.num_devices <= 1:
        return None, None, "mesh has a single device"
    if rules is None:
        from ..parallel.mesh import PartitionSpec
        from ..partition import PartitionRules
        rules = PartitionRules([(r".*", PartitionSpec(mesh.axis_names[0]))])
    return mesh, rules, None


class FusedGroupState:
    """State shared by every module driving one optimizer (the
    ``borrow_optimizer`` group — a BucketingModule's buckets): the
    canonical device-side parameter/aux store, the donated rng/step/lr
    scalars, the device metric accumulator, and the step counters."""

    def __init__(self, optimizer, updater, ctx):
        self.optimizer = optimizer
        self.updater = updater
        self.ctx = ctx
        self.num_update = int(optimizer.num_update)
        self.key_dev = None
        self.t_dev = None
        self.lr_dev = None
        self.lr_host = None
        self.param_store = {}
        self.aux_store = {}
        # device-side metric accumulation
        self.metric = None
        self.metric_fn = None
        self.metric_key = None
        self.metric_acc = None
        self.batches_since_drain = 0
        self.readback_every = metric_readback_interval()
        self.warned_fallback = False
        self.stats = {"steps": 0, "compiles": 0, "cache_hits": 0,
                      "metric_drains": 0}
        # observability (ISSUE 14): sampled step tracing + the group's
        # registry view; inter-step timing state for module.step_ms
        self.tracer = _obs.Sampler()
        self.last_step_t = None
        self._view_key = _obs.view("module.fused",
                                   lambda: dict(self.stats))
        # mixed precision (MXTPU_AMP, module docstring): fixed for the
        # group's lifetime at maybe_create so every bucket and every
        # cached program agrees on the one policy
        self.amp = None                  # None | "bf16"
        self.compute_dtype = None        # jnp dtype params/inputs cast to
        self.loss_scale = None           # static S, or None
        self.wire_dtype = None           # dist: emitted-gradient dtype
        self.auto_layout = auto_layout_enabled()
        # mesh sharding (ISSUE 20, set_mesh): compile the group's
        # programs as SPMD mesh programs with the store sharded by rule
        self.mesh = None
        self.rules = None
        # dist modes (attach_kvstore): the store, the sync/async policy
        # and the ONE shared push window across the group's buckets
        self.kv = None
        self.dist_mode = None
        self.window = None

    def note_step(self):
        """Per-step instrumentation on the training thread: count the
        attempt and observe the gap since the previous step (the
        steady-state step wall time — no device sync involved)."""
        now = time.perf_counter()
        if self.last_step_t is not None:
            _M_STEP_MS.observe((now - self.last_step_t) * 1e3)
        self.last_step_t = now
        _M_STEPS.inc()

    def set_amp(self, amp):
        """Engage the group's mixed-precision policy (maybe_create)."""
        self.amp = amp
        if amp == "bf16":
            self.compute_dtype = jnp.bfloat16
            scale = amp_loss_scale()
            self.loss_scale = scale if scale else None
            self.wire_dtype = jnp.bfloat16

    def amp_overflow_skips(self):
        """Loss-scale overflow steps skipped so far, on the modes whose
        program carries the donated step count (local / dist_local):
        attempted steps (the host counter) minus applied steps (ONE
        on-demand device read of the donated ``t`` — never on the hot
        path). 0 when loss scaling is off."""
        if not self.loss_scale or self.t_dev is None:
            return 0
        return int(self.num_update) - int(jax.device_get(self.t_dev))

    def set_mesh(self, mesh, rules):
        """Engage mesh-sharded compilation for the group (ISSUE 20):
        every program the group builds from here on places its donated
        param/opt-state/aux store with the rules' NamedShardings over
        ``mesh`` — per-device memory ~1/N. Fixed at maybe_create like
        the AMP policy, so every bucket and cached program agrees.
        AUTO layout markers don't compose with explicit NamedShardings,
        so the mesh wins over ``MXTPU_AUTO_LAYOUT``."""
        self.mesh = mesh
        self.rules = rules
        self.auto_layout = False

    def scalar_target(self):
        """Placement of the donated device scalars (rng key, step
        count, lr, metric accumulator): replicated over the mesh in
        mesh mode — a single-device scalar next to sharded stores
        would make the program's device sets disagree — else the
        group's context device."""
        return self.mesh.replicated() if self.mesh is not None \
            else self.ctx.jax_device()

    def attach_kvstore(self, kv):
        """Wire the group to its kvstore (dist modes): the shared async
        push/pull window (one per optimizer group — buckets share it)
        plus the ``kv.stats()['module_fused_dist']`` counter source the
        ``ci/check_module_perf.py --dist`` bounded-inflight contract
        reads. With AMP on, gradient compression wins the wire-format
        contest: 2-bit beats bf16, so compressed stores keep fp32
        emitted gradients (no double-compress) while compute stays
        bf16."""
        self.kv = kv
        self.dist_mode = dist_mode()
        self.window = AsyncPushWindow(push_inflight())
        if getattr(kv, "_compression", None) is not None:
            self.wire_dtype = None
        if hasattr(kv, "add_stats_source"):
            kv.add_stats_source("module_fused_dist", self.window.stats)

    # -- donated device scalars -------------------------------------------
    def device_state(self):
        if self.key_dev is None:
            dev = self.scalar_target()
            key = jax.random.PRNGKey(_np.random.randint(0, 2 ** 31 - 1))
            self.key_dev = jax.device_put(_np.asarray(key), dev)
            self.t_dev = jax.device_put(
                _np.asarray(self.num_update, _np.int32), dev)
            self.lr_host = self.host_lr()
            self.lr_dev = jax.device_put(
                _np.asarray(self.lr_host, _np.float32), dev)
        return self.key_dev, self.t_dev, self.lr_dev

    def host_lr(self):
        o = self.optimizer
        return float(o.lr_scheduler(self.num_update)) \
            if o.lr_scheduler is not None else float(o.lr)

    def refresh_lr(self):
        """Push a new lr scalar only when the schedule actually moved —
        the steady state makes zero host->device transfers."""
        new_lr = self.host_lr()
        if new_lr != self.lr_host:
            self.lr_host = new_lr
            self.lr_dev = jax.device_put(
                _np.asarray(new_lr, _np.float32), self.scalar_target())
        return self.lr_dev

    # -- device metric accumulator ----------------------------------------
    def _zero_acc(self):
        return jax.device_put(_np.zeros(2, _np.float32),
                              self.scalar_target())

    def drain_metric(self):
        """Fetch-and-zero the device (sum, count) pair — the ONE host
        sync of the whole metric path, paid at read time, not per batch."""
        acc = self.metric_acc
        if acc is None:
            return 0.0, 0.0
        self.metric_acc = self._zero_acc()
        self.batches_since_drain = 0
        self.stats["metric_drains"] += 1
        host = _np.asarray(jax.device_get(acc))
        return float(host[0]), float(host[1])

    def zero_metric(self):
        if self.metric_acc is not None:
            self.metric_acc = self._zero_acc()
        self.batches_since_drain = 0

    def detach_metric(self):
        m = self.metric
        if m is not None:
            if self.metric_fn is not None:
                m._drain_async()
            m.detach_async()
        self.metric = None
        self.metric_fn = None
        self.metric_key = None


class FusedModuleTrainer:
    """Per-Module driver of the fused train step over its executor.

    ``mode`` selects which one-program contract drives the step:

    * ``"local"`` — PR-5: forward+backward+optimizer (+metric) in one
      donated program, ``update()`` is an acknowledgement;
    * ``"dist"`` — kvstore-managed (``update_on_kvstore``): the program
      emits gradients, ``update()`` pushes them and pulls the
      server-updated weights back into the shared device store;
    * ``"dist_local"`` — kvstore-merged gradients with a local
      optimizer: push, pull the merged gradients, then one donated
      multi-tensor apply program.
    """

    def __init__(self, module, group, mode="local"):
        self._module = module
        self._group = group
        self._mode = mode
        exec_group = module._exec_group
        exec_ = exec_group.execs[0]
        # updater slot i = position in the executor group's param list
        # (the exact indices the eager per-param loop would use, so lr/wd
        # multipliers and saved optimizer states line up bit-for-bit)
        names_in_graph = [n for n in exec_group.param_names
                          if n in exec_group.arg_names]
        self._param_names = names_in_graph
        self._train_names, self._opt_slots = [], []
        for i, name in enumerate(names_in_graph):
            if exec_.grad_dict.get(name) is not None:
                self._train_names.append(name)
                self._opt_slots.append(i)
        self._cache = ProgramCache()
        self._last_fused = False
        self._last_metric_applied = False
        # sampled step tracing: the span opens at dispatch and — in
        # the dist modes — stays open through finish_update so the
        # wire spans nest under it (one timeline per sampled step)
        self._trace_sampled = False
        self._step_span = None
        self._trace_tok = None
        # dist modes: this step's emitted gradients, awaiting update()
        self._pending_grads = None
        # dist_local: reusable zero buffer backing the pull targets
        self._grad_zeros = None
        # sparse fast path (ISSUE 13): param name -> its Embedding
        # index feeds; empty when no sparse params ride this module
        self._sparse_feeds = {}
        if mode == "dist":
            sparse_names = _sparse_param_names(exec_)
            if sparse_names:
                feeds, _ = _sparse_grad_feeds(module, sparse_names)
                self._sparse_feeds = feeds or {}

    @property
    def mode(self):
        return self._mode

    # -- group plumbing ----------------------------------------------------
    def seed_store(self):
        """First module of the group: its executor's arrays become the
        canonical device parameter store."""
        exec_ = self._module._exec_group.execs[0]
        fs = self._group
        fs.param_store = {n: exec_.arg_dict[n] for n in self._param_names}
        fs.aux_store = {n: exec_.aux_dict[n] for n in exec_._aux_names}

    def adopt_store(self):
        """Alias this module's executors to the group's shared arrays
        (values are already equal — bind copied them host-side once)."""
        fs = self._group
        if fs.param_store:
            self._module._exec_group.adopt_store(fs.param_store,
                                                 fs.aux_store)

    def store_compatible(self):
        """Every shared param name must agree on shape+dtype, or bucket
        updates would fork — mismatches fall back to the eager path."""
        exec_ = self._module._exec_group.execs[0]
        for n, src in self._group.param_store.items():
            dst = exec_.arg_dict.get(n)
            if dst is not None and (dst.shape != src.shape or
                                    dst.dtype != src.dtype):
                return False
        return True

    def shares_store_with(self, other_module):
        other = getattr(other_module, "_fused", None)
        return other is not None and other._group is self._group

    # -- fallback ----------------------------------------------------------
    def _disable(self, reason):
        fs = self._group
        self.flush()
        self._pending_grads = None
        if not fs.warned_fallback:
            warnings.warn(
                "Module fused train step disabled: %s — falling back to "
                "the eager forward/backward/update path." % reason,
                stacklevel=4)
            fs.warned_fallback = True
        fs.detach_metric()
        self._module._fused = None

    def flush(self):
        """Drain the async push/pull window (dist modes; no-op on the
        local path) — every emitted gradient has landed and every
        pulled value is rebound when this returns."""
        fs = self._group
        if fs.window is not None:
            fs.window.flush()
        self._end_step_trace()

    # -- metric routing ----------------------------------------------------
    def note_eager_forward(self):
        self._last_fused = False

    def note_metric(self, metric):
        """True when this batch's contribution is already accumulated on
        device; False routes the caller to the host update path (and
        registers the metric so SUBSEQUENT steps fuse it)."""
        fs = self._group
        if not self._last_fused:
            return False
        if fs.metric is metric and self._last_metric_applied:
            fs.batches_since_drain += 1
            if fs.readback_every > 0 and \
                    fs.batches_since_drain >= fs.readback_every:
                metric._drain_async()
            return True
        if fs.metric is not metric:
            self._register_metric(metric)
        return False

    def _register_metric(self, metric):
        fs = self._group
        fs.detach_metric()
        fs.metric = metric
        if not metric.supports_device_update():
            return
        label_names = tuple(self._module._label_names)

        def metric_fn(feed, outs):
            labels = tuple(feed[n] for n in label_names if n in feed)
            return metric.device_batch(labels, outs)

        try:
            kw = tuple(sorted((k, repr(v))
                              for k, v in metric._kwargs.items()))
        except Exception:
            kw = (id(metric),)
        fs.metric_fn = metric_fn
        fs.metric_key = (type(metric).__name__, kw)
        if fs.metric_acc is None:
            fs.metric_acc = fs._zero_acc()
        metric.update_async(fs.drain_metric, fs.zero_metric)

    # -- the step ----------------------------------------------------------
    @staticmethod
    def _shape_sig(arrs):
        return tuple((tuple(a.shape), str(a.dtype)) for a in (arrs or []))

    def _batch_names(self):
        """The per-batch inputs (data + labels) — the names the mesh
        plan may shard dim 0 over the ``data`` axis; fixed params keep
        rule placement."""
        mod = self._module
        return tuple(mod._data_names) + tuple(mod._label_names)

    @staticmethod
    def _write_state(dst, tree):
        if dst is None:
            return
        if isinstance(dst, (tuple, list)):
            for d, t in zip(dst, tree):
                FusedModuleTrainer._write_state(d, t)
        else:
            dst._data = tree

    @staticmethod
    def _dedupe_donated(train_vals, state_trees):
        """A state leaf aliasing a donated weight buffer (e.g. the Test
        optimizer's state) would be donated twice — break the alias."""
        seen = {id(v) for v in train_vals}

        def fix(leaf):
            if leaf is None:
                return None
            if isinstance(leaf, (tuple, list)):
                return tuple(fix(x) for x in leaf)
            if id(leaf) in seen:
                return jnp.copy(leaf)
            seen.add(id(leaf))
            return leaf

        return tuple(fix(t) for t in state_trees)

    def _local_program(self, data_batch):
        """The local-mode program for ``data_batch``'s signature and the
        store arguments it runs on: ``(fn, cache_hit, states_nd,
        (train_vals, state_trees, aux_vals, other_vals))``. Loads the
        batch into the executor; touches no step counter."""
        mod = self._module
        fs = self._group
        exec_group = mod._exec_group
        exec_ = exec_group.execs[0]
        key = (self._shape_sig(data_batch.data),
               self._shape_sig(data_batch.label), fs.metric_key)
        metric_fn = fs.metric_fn if fs.metric_key is not None else None
        # state trees are gathered BEFORE the program build: the mesh
        # plan places optimizer-state leaves by their actual shapes
        train_vals = tuple(exec_.arg_dict[n]._data
                           for n in self._train_names)
        states_nd = [fs.updater.ensure_state(slot, exec_.arg_dict[name])
                     for slot, name in zip(self._opt_slots,
                                           self._train_names)]
        state_trees = self._dedupe_donated(
            train_vals, tuple(state_to_tree(s) for s in states_nd))
        entry, hit = self._cache.get(
            key, lambda: exec_.make_fused_train_step(
                self._train_names, fs.optimizer, self._opt_slots,
                metric_fn=metric_fn,
                compute_dtype=fs.compute_dtype,
                loss_scale=fs.loss_scale,
                cast_exclude=tuple(mod._label_names),
                auto_layout=fs.auto_layout,
                mesh=fs.mesh, rules=fs.rules,
                state_trees=state_trees,
                batch_names=self._batch_names()))
        fn, other_names = entry
        exec_group.load_batch(data_batch)
        aux_vals = tuple(exec_.aux_dict[n]._data for n in exec_._aux_names)
        other_vals = tuple(exec_.arg_dict[n]._data for n in other_names)
        if fs.metric_acc is None:
            fs.metric_acc = fs._zero_acc()
        return fn, hit, states_nd, (train_vals, state_trees, aux_vals,
                                    other_vals)

    def compiled_step(self, data_batch):
        """The compiled local-mode train step for ``data_batch``'s
        signature — ``as_text()``, ``cost_analysis()``,
        ``input_shardings`` (``ShardedTrainer.compiled_step`` parity).
        Runs nothing and advances nothing; costs one compile unless the
        persistent compile cache already holds the program."""
        fs = self._group
        fn, _hit, _states, store_args = self._local_program(data_batch)
        key_dev, t_dev, lr_dev = fs.device_state()
        return fn.lower(*store_args, key_dev, t_dev, lr_dev,
                        fs.metric_acc).compile()

    def step(self, data_batch):
        """Run one fused forward+backward[+update][+metric] step.
        Returns False (after disabling, where appropriate) when the
        batch must take the eager path instead. In the dist modes the
        step emits gradients and stashes them for :meth:`finish_update`
        (driven by ``Module.update()``)."""
        mod = self._module
        fs = self._group
        if isinstance(data_batch, list):
            return False  # multi-module list batches: eager path
        # deterministic injection point of the fused training loop
        # (fault-matrix: the loss-scale overflow-skip drill seeds
        # nan_grad here, once per fused step)
        act = _fault.fire("module.step", op="step")
        if act == "nan_grad":
            data_batch = copy.copy(data_batch)
            data_batch.data = [NDArray(d._data * _np.nan)
                               for d in data_batch.data]
        exec_group = mod._exec_group
        exec_ = exec_group.execs[0]
        if exec_._monitor_callback is not None:
            self._disable("a Monitor is installed (per-node outputs need "
                          "the eager executor)")
            return False
        if self._mode == "dist":
            if mod._updater is not None:
                self._disable("a custom updater replaced the "
                              "kvstore-managed update")
                return False
        elif not isinstance(mod._updater, opt_mod.Updater) or \
                mod._updater is not fs.updater:
            self._disable("a custom updater replaced the shared "
                          "optimizer Updater")
            return False
        # late reshape (bucketing-style): same contract as forward()
        curr_shapes = tuple(i.shape for i in mod._data_shapes)
        new_shapes = tuple(i.shape for i in data_batch.data)
        if curr_shapes != new_shapes:
            mod.reshape(*mod._shapes_for_batch(data_batch, new_shapes))
            exec_group = mod._exec_group
            exec_ = exec_group.execs[0]

        if self._mode != "local":
            return self._dist_step(data_batch, exec_group, exec_)

        fs.note_step()
        self._begin_step_trace()
        fn, hit, states_nd, store_args = self._local_program(data_batch)
        fs.stats["cache_hits" if hit else "compiles"] += 1
        train_vals, state_trees, aux_vals, other_vals = store_args
        key_dev, t_dev, _ = fs.device_state()
        if fs.optimizer.num_update > fs.num_update:
            # eager update() calls interleaved with fused steps (mixed
            # driving) advanced the host counters; re-sync the device
            # step count so Adam-style bias correction stays aligned
            fs.num_update = int(fs.optimizer.num_update)
            t_dev = fs.t_dev = jax.device_put(
                _np.asarray(fs.num_update, _np.int32), fs.scalar_target())
        fs.num_update += 1
        lr_dev = fs.refresh_lr()

        (new_vals, new_states, new_aux, outs, new_key, new_t,
         new_acc) = fn(train_vals, state_trees, aux_vals, other_vals,
                       key_dev, t_dev, lr_dev, fs.metric_acc)

        # rebind every donated buffer's wrapper to the fresh value
        for n, v in zip(self._train_names, new_vals):
            exec_.arg_dict[n]._data = v
        for dst, tree in zip(states_nd, new_states):
            self._write_state(dst, tree)
        for n, v in zip(exec_._aux_names, new_aux):
            exec_.aux_dict[n]._data = v
        fs.key_dev, fs.t_dev, fs.metric_acc = new_key, new_t, new_acc
        exec_._outputs = [_wrap(o, exec_._ctx) for o in outs]
        exec_._cached_grads = None
        exec_._state_snapshot = None
        # host mirrors of the in-program counters, so schedulers,
        # `optimizer.learning_rate` and saved optimizer states agree with
        # what the eager per-param loop would have recorded
        opt = fs.optimizer
        opt.num_update = fs.num_update
        for slot in self._opt_slots:
            opt._index_update_count[slot] = fs.num_update
        fs.stats["steps"] += 1
        self._last_fused = True
        self._last_metric_applied = fs.metric_fn is not None
        self._end_step_trace()
        return True

    # -- sampled step tracing ----------------------------------------------
    def _begin_step_trace(self):
        """Open this step's ``module.step`` span. It records when the
        sampler says so (MXTPU_TRACE_SAMPLE opens a trace context that
        rides the kvstore wire) or a jax.profiler session is live; else
        it is one counter tick and a span that finds nothing to do."""
        self._end_step_trace()   # a step whose update never came
        self._trace_sampled = self._group.tracer.sample()
        if self._trace_sampled:
            self._trace_tok = _obs.start_trace()
        self._step_span = _obs.span("module.step", mode=self._mode,
                                    step=self._group.stats["steps"])
        self._step_span.__enter__()

    def _end_step_trace(self):
        if self._step_span is None:
            return
        self._step_span.__exit__(None, None, None)
        self._step_span = None
        if self._trace_sampled:
            _obs.end_trace(self._trace_tok)

    # -- the dist step -----------------------------------------------------
    def _dist_step(self, data_batch, exec_group, exec_):
        """Grad-emitting step of the kvstore modes: ONE donated program
        runs forward+backward(+metric) and returns the gradients; they
        are stashed for :meth:`finish_update` (``Module.update()``)."""
        fs = self._group
        fs.note_step()
        self._begin_step_trace()
        key = ("grad", self._shape_sig(data_batch.data),
               self._shape_sig(data_batch.label), fs.metric_key)
        metric_fn = fs.metric_fn if fs.metric_key is not None else None
        entry, hit = self._cache.get(
            key, lambda: exec_.make_fused_grad_step(
                self._train_names, metric_fn=metric_fn,
                compute_dtype=fs.compute_dtype,
                loss_scale=fs.loss_scale,
                cast_exclude=tuple(self._module._label_names),
                wire_dtype=fs.wire_dtype,
                auto_layout=fs.auto_layout,
                sparse_emits=self._sparse_feeds or None,
                mesh=fs.mesh, rules=fs.rules,
                batch_names=self._batch_names()))
        fs.stats["cache_hits" if hit else "compiles"] += 1
        fn, other_names = entry

        exec_group.load_batch(data_batch)
        train_vals = tuple(exec_.arg_dict[n]._data
                           for n in self._train_names)
        aux_vals = tuple(exec_.aux_dict[n]._data for n in exec_._aux_names)
        other_vals = tuple(exec_.arg_dict[n]._data for n in other_names)
        key_dev, _, _ = fs.device_state()
        if fs.metric_acc is None:
            fs.metric_acc = fs._zero_acc()

        grads, new_aux, outs, new_key, new_acc = fn(
            train_vals, aux_vals, other_vals, key_dev, fs.metric_acc)

        # rebind every donated buffer's wrapper (params are NOT donated
        # here — the kvstore pull rebinds them after the update lands)
        for n, v in zip(exec_._aux_names, new_aux):
            exec_.aux_dict[n]._data = v
        fs.key_dev, fs.metric_acc = new_key, new_acc
        exec_._outputs = [_wrap(o, exec_._ctx) for o in outs]
        exec_._cached_grads = None
        exec_._state_snapshot = None
        self._pending_grads = grads
        fs.stats["steps"] += 1
        self._last_fused = True
        self._last_metric_applied = fs.metric_fn is not None
        return True

    def finish_update(self):
        """Complete a dist step after ``forward_backward``: ship the
        emitted gradients through the kvstore and land the update.

        * ``dist`` (update_on_kvstore): push gradients, pull the
          server-updated weights straight into the SHARED device
          parameter store — every bucket's executor aliases the same
          NDArray objects, so a bucket switch stays a cache hit.
        * ``dist_local``: push, pull the merged gradients, run one
          donated multi-tensor apply program over them.

        Sync mode ships inline (per-key order identical to the eager
        ``_update_params_on_kvstore`` loop — bit-for-bit parity);
        async mode dispatches one worker-pool job per step under the
        bounded-inflight window, so the next step's compute overlaps
        the wire and the device->host gradient read happens OFF the
        training thread (the zero-host-sync contract)."""
        try:
            return self._finish_update_impl()
        finally:
            # the sampled step's span closes HERE, after the wire work
            # it owns (sync mode: push+pull nested inside it)
            self._end_step_trace()

    def _finish_update_impl(self):
        grads = self._pending_grads
        self._pending_grads = None
        if self._mode == "local" or grads is None:
            return
        fs = self._group
        kv = fs.kv
        names = list(self._train_names)
        if self._mode == "dist" and self._sparse_feeds:
            return self._finish_update_sparse(grads, names)
        if fs.dist_mode == "sync":
            # one batched d2h for the step's gradients (the async path
            # does the same inside push_pull_async, off-thread)
            vals = list(jax.device_get(list(grads)))
        else:
            vals = [NDArray(g) for g in grads]
        if self._mode == "dist":
            outs = [fs.param_store[n] for n in names]
            if fs.dist_mode == "sync":
                kv.push_pull(names, vals, out=outs)
            else:
                fs.window.dispatch(
                    lambda: kv.push_pull_async(names, vals, out=outs))
            return
        # dist_local: fresh pull-target WRAPPERS per dispatch (sharing
        # one zero buffer) so overlapping async windows never write the
        # same wrapper; the apply runs on the training thread at reap
        # time (AsyncPushWindow contract), where donation is safe
        gouts = self._grad_targets()
        if fs.dist_mode == "sync":
            kv.push_pull(names, vals, out=gouts)
            self._apply_pulled(gouts)
        else:
            fs.window.dispatch(
                lambda: kv.push_pull_async(names, vals, out=gouts),
                on_complete=lambda _res, g=gouts: self._apply_pulled(g))

    def _finish_update_sparse(self, grads, names):
        """The dist update when sparse embeddings ride the step
        (ISSUE 13): dense gradients take the ``pushpull`` wire exactly
        as before; each sparse parameter's emitted ``(row_ids, rows)``
        pair takes ``sparse_push_pull`` — only touched rows travel,
        the server applies row-wise, and the gathered reply scatters
        straight back into the SHARED device parameter store (bucket
        switches stay cache hits; untouched rows keep their values,
        which is exactly what the server did too). Sync mode reads the
        whole step — dense grads, ids, rows — in ONE batched
        device_get; async ships both wire jobs on the ordered pool
        under the same bounded window."""
        fs = self._group
        kv = fs.kv
        sparse = self._sparse_feeds
        d_idx = [i for i, n in enumerate(names) if n not in sparse]
        s_idx = [i for i, n in enumerate(names) if n in sparse]
        d_names = [names[i] for i in d_idx]
        s_names = [names[i] for i in s_idx]
        d_outs = [fs.param_store[n] for n in d_names]
        s_outs = [fs.param_store[n] for n in s_names]
        if fs.dist_mode == "sync":
            leaves = [grads[i] for i in d_idx]
            for i in s_idx:
                leaves += [grads[i][0], grads[i][1]]
            host = jax.device_get(leaves)     # ONE batched d2h
            d_vals = host[:len(d_idx)]
            sp = host[len(d_idx):]
            if d_names:
                kv.push_pull(d_names, d_vals, out=d_outs)
            kv.sparse_push_pull(
                s_names, [sp[2 * j] for j in range(len(s_idx))],
                [sp[2 * j + 1] for j in range(len(s_idx))],
                out=s_outs, drop_padding=True)
            return
        if d_names:
            d_vals = [NDArray(grads[i]) for i in d_idx]
            fs.window.dispatch(
                lambda: kv.push_pull_async(d_names, d_vals,
                                           out=d_outs))
        ids_list = [grads[i][0] for i in s_idx]
        rows_list = [grads[i][1] for i in s_idx]
        fs.window.dispatch(
            lambda: kv.sparse_push_pull_async(
                s_names, ids_list, rows_list, out=s_outs,
                drop_padding=True))

    def _grad_targets(self):
        exec_ = self._module._exec_group.execs[0]
        if self._grad_zeros is None:
            self._grad_zeros = {
                n: nd.zeros(exec_.arg_dict[n].shape,
                            dtype=exec_.arg_dict[n].dtype)
                for n in self._train_names}
        return [NDArray(self._grad_zeros[n]._data)
                for n in self._train_names]

    def _apply_pulled(self, gouts):
        """dist_local: one donated multi-tensor apply of the pulled
        (merged) gradients — the optimizer half of the PR-5 program on
        its own, sharing the Updater state dict slot-for-slot."""
        fs = self._group
        exec_ = self._module._exec_group.execs[0]
        grad_vals = tuple(g._data for g in gouts)
        key = ("apply", tuple((tuple(g.shape), str(g.dtype))
                              for g in grad_vals))
        train_vals = tuple(exec_.arg_dict[n]._data
                           for n in self._train_names)
        states_nd = [fs.updater.ensure_state(slot, exec_.arg_dict[name])
                     for slot, name in zip(self._opt_slots,
                                           self._train_names)]
        state_trees = self._dedupe_donated(
            train_vals, tuple(state_to_tree(s) for s in states_nd))
        fn, hit = self._cache.get(
            key, lambda: exec_.make_fused_apply_step(
                self._train_names, fs.optimizer, self._opt_slots,
                auto_layout=fs.auto_layout,
                mesh=fs.mesh, rules=fs.rules,
                state_trees=state_trees))
        fs.stats["cache_hits" if hit else "compiles"] += 1
        _, t_dev, _ = fs.device_state()
        if fs.optimizer.num_update > fs.num_update:
            fs.num_update = int(fs.optimizer.num_update)
            t_dev = fs.t_dev = jax.device_put(
                _np.asarray(fs.num_update, _np.int32), fs.scalar_target())
        fs.num_update += 1
        lr_dev = fs.refresh_lr()

        new_vals, new_states, new_t = fn(train_vals, state_trees,
                                         grad_vals, t_dev, lr_dev)

        for n, v in zip(self._train_names, new_vals):
            exec_.arg_dict[n]._data = v
        for dst, tree in zip(states_nd, new_states):
            self._write_state(dst, tree)
        fs.t_dev = new_t
        opt = fs.optimizer
        opt.num_update = fs.num_update
        for slot in self._opt_slots:
            opt._index_update_count[slot] = fs.num_update


def _fused_eligible(module):
    """The fused-path eligibility predicate, narrowed by ISSUE 10 and
    again by ISSUE 13: kvstore-managed updates are a FAST path
    (``dist`` / ``dist_local`` modes), and row-sparse embedding
    parameters now ride the ``dist`` mode too (device-side
    unique/gather in the grad program, sparse pushpull on the wire) —
    silent fallback remains only for the still-unsupported set —
    multi-context groups, ``inputs_need_grad``, sparse params off the
    server-managed path — plus the explicit configuration outs (env
    kill switches, non-write grad_req, state inputs, custom updaters).

    Returns ``(mode, reason)``: ``mode`` is ``'local'`` (in-program
    optimizer), ``'dist'`` (server-side update via the kvstore),
    ``'dist_local'`` (kvstore-merged gradients + fused local apply) or
    ``None`` with the human-readable fallback reason — logged once at
    warning level so fallbacks are diagnosable instead of silent."""
    from ..ndarray.sparse import RowSparseNDArray, CompactRowSparseNDArray
    if not _module_fused_enabled():
        return None, "MXTPU_MODULE_FUSED=0"
    if len(module._context) != 1 or len(module._exec_group.execs) != 1:
        return None, "multi-context executor group"
    if not module.for_training:
        return None, "bound for inference (for_training=False)"
    if module.inputs_need_grad:
        return None, "inputs_need_grad (callers read input gradients)"
    if module._state_names:
        return None, "explicit state inputs (state_names)"
    if module._grad_req != "write":
        return None, "grad_req=%r (fused step assumes 'write')" \
            % (module._grad_req,)
    exec_ = module._exec_group.execs[0]
    sparse_names = _sparse_param_names(exec_)
    if module._kvstore is not None:
        if not _fused_dist_enabled():
            return None, "MXTPU_MODULE_FUSED_DIST=0"
        if not hasattr(module._kvstore, "push_async"):
            return None, "kvstore %r has no async push path" \
                % (getattr(module._kvstore, "type",
                           type(module._kvstore).__name__),)
        if sparse_names:
            # the sparse fast path (ISSUE 13): server-managed row-wise
            # updates over the spushpull wire — the program must be
            # able to emit (row_ids, rows) for every sparse param
            if not _fused_sparse_enabled():
                return None, "MXTPU_MODULE_FUSED_SPARSE=0"
            if not module._update_on_kvstore:
                return None, ("sparse parameters with "
                              "update_on_kvstore=False (the local "
                              "apply would densify every gradient)")
            if not hasattr(module._kvstore, "sparse_push_pull"):
                return None, "kvstore %r has no sparse_push_pull" \
                    % (getattr(module._kvstore, "type",
                               type(module._kvstore).__name__),)
            for n in sparse_names:
                for arr in (exec_.arg_dict.get(n),
                            exec_.grad_dict.get(n)):
                    if arr is None:
                        continue
                    if isinstance(arr, CompactRowSparseNDArray):
                        return None, ("compact row_sparse parameter %r"
                                      " (no dense device value for the"
                                      " one-program step)" % (n,))
                    if hasattr(arr, "_aux") and \
                            not isinstance(arr, RowSparseNDArray):
                        return None, ("non-row_sparse sparse "
                                      "parameter %r" % (n,))
            feeds, reason = _sparse_grad_feeds(module, sparse_names)
            if feeds is None:
                return None, reason
        if module._update_on_kvstore:
            return "dist", None
        if not isinstance(module._updater, opt_mod.Updater):
            return None, "custom updater"
        return "dist_local", None
    if sparse_names:
        return None, "sparse parameters (lazy-update path)"
    if not isinstance(module._updater, opt_mod.Updater):
        return None, "custom updater"
    return "local", None


def _log_fallback(module, reason):
    """One-shot warning naming why the fused path disengaged: the
    eager per-parameter loop costs speed, so the drop is never silent."""
    if getattr(module, "_fused_fallback_logged", None) == reason:
        return
    module._fused_fallback_logged = reason
    logger = getattr(module, "logger", None) or logging
    logger.warning(
        "Module fused train step not engaged: %s — eager path "
        "(eligibility matrix: docs/perf_analysis.md "
        "'Distributed Module fast path')", reason)


def _amp_eligible(module):
    """The AMP-mode eligibility predicate (``MXTPU_AMP=bf16``): returns
    ``(amp, reason)``. An ineligible combination NAMES its reason —
    logged once at warning level, like the PR-10 fallback matrix — and
    keeps the fp32 fused path: never a silent wrong-dtype step. The
    custom-updater/monitor outs are handled upstream (they leave the
    fused path entirely)."""
    amp = amp_mode()
    if amp is None:
        return None, None
    exec_ = module._exec_group.execs[0]
    for name, arr in exec_.arg_dict.items():
        if exec_.grad_dict.get(name) is None:
            continue
        if _np.dtype(arr.dtype) != _np.float32:
            return None, (
                "MXTPU_AMP=bf16 requested but parameter %r is %s — AMP "
                "needs fp32 master weights (fp64/fp16 params keep the "
                "fp32 fused step)" % (name, _np.dtype(arr.dtype).name))
    return amp, None


def _log_amp_fallback(module, reason):
    """One-shot warning naming why AMP stayed off while the fused
    path engaged (the wrong-dtype half of the fallback contract)."""
    if getattr(module, "_amp_fallback_logged", None) == reason:
        return
    module._amp_fallback_logged = reason
    logger = getattr(module, "logger", None) or logging
    logger.warning("Module AMP mode not engaged: %s — fp32 fused step "
                 "(docs/perf_analysis.md 'Mixed precision')", reason)


def maybe_create(module):
    """Called at the end of ``Module.init_optimizer``: build the fused
    trainer (and become the group's store owner) when eligible."""
    mode, reason = _fused_eligible(module)
    if mode is None:
        _log_fallback(module, reason)
        return None
    group = FusedGroupState(module._optimizer, module._updater,
                            module._context[0])
    amp, amp_reason = _amp_eligible(module)
    if amp is not None:
        group.set_amp(amp)
    elif amp_reason is not None:
        _log_amp_fallback(module, amp_reason)
    mesh, rules, mesh_reason = _mesh_config(module)
    if mesh is not None:
        group.set_mesh(mesh, rules)
    elif mesh_reason is not None:
        logger = getattr(module, "logger", None) or logging
        logger.warning("Module mesh sharding not engaged: %s — "
                     "single-device fused step (docs/sharding.md)",
                     mesh_reason)
    if mode != "local":
        group.attach_kvstore(module._kvstore)
    trainer = FusedModuleTrainer(module, group, mode)
    trainer.seed_store()
    return trainer


def attach_borrowed(module, shared_module):
    """Called from ``Module.borrow_optimizer``: join the lender's fused
    group, aliasing this module's executors to the shared device store
    (the BucketingModule bucket-switch fast path)."""
    lender = getattr(shared_module, "_fused", None)
    if lender is None:
        _log_fallback(module, "shared optimizer owner runs eager")
        return None
    mode, reason = _fused_eligible(module)
    if mode is None:
        _log_fallback(module, reason)
        return None
    if mode != lender.mode:
        _log_fallback(module, "kvstore mode differs from the lender")
        return None
    trainer = FusedModuleTrainer(module, lender._group, mode)
    if not trainer.store_compatible():
        _log_fallback(module, "parameter shape/dtype mismatch across "
                              "buckets")
        return None
    trainer.adopt_store()
    return trainer
