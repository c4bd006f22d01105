"""Inference engine: AOT-compiled, donated, per-bucket predict programs.

The deploy surface of the reference is ``c_predict_api.h`` — bind once,
forward one batch at a time, every call shape-specialized by a full
executor rebind. Serving wants the opposite cost model: a FIXED menu of
batch shapes (the buckets), every program compiled BEFORE the first
request lands (AOT, not first-call JIT), and zero per-request retraces
in steady state. :class:`InferenceEngine` renders that:

* **Checkpoint load.** ``InferenceEngine.from_checkpoint(prefix, epoch)``
  loads the ``Module.save_checkpoint`` artifact (``prefix-symbol.json``
  + ``prefix-%04d.params``) — the same files every training path in
  this tree writes. Parameters and aux states are device-put ONCE and
  shared by every bucket program (the serving analogue of the fused
  Module path's shared device param store).
* **Per-bucket donated programs.** For each bucket batch size the whole
  symbol forward is lowered and compiled ahead of time as one XLA
  program with the (padded) input batch DONATED — the request payload
  buffer is dead the moment the program runs, so XLA may reuse it for
  activations. Programs live in the same
  :class:`~mxtpu.module.fused.ProgramCache` the fused train step uses;
  its ``compiles``/``hits`` counters are what ``ci/check_serving.py``
  pins the zero-per-request-retraces contract on.
* **Determinism.** ``training=False`` (BatchNorm runs on its aux
  running stats, Dropout is identity) and a trace-constant RNG key make
  the program a pure function of (params, input): two replicas loaded
  from the same checkpoint answer the same request bit-for-bit — the
  property the failover drill's exactly-once/bit-identical acceptance
  check rests on.

* **Sharded serving (ISSUE 20).** Pass ``mesh=``/``rules=`` (or set
  ``MXTPU_MESH``) and the whole menu — predict buckets AND the
  prefill/decode/adopt generation programs — lowers as SPMD programs
  over the device mesh: the weight stores and the packed KV caches
  live sharded per the rules (per-device bytes ~1/N), GSPMD inserts
  the collectives, :meth:`swap_weights` device_puts each incoming
  version straight into its per-name ``NamedSharding``, and
  :meth:`program_fingerprint` grows the mesh topology + rules so a
  prewarm file only installs on a matching fleet. Generation programs
  carry explicit ``out_shardings`` because their outputs feed other
  AOT programs (prefill rows -> adopt, decode state -> decode state):
  an AOT call rejects an input whose placement differs from the
  lowered aval, so the handoffs are pinned, not GSPMD's choice.
* **Versioned weights (live streaming).** The params/aux device copies
  live in immutable per-version *stores*; :meth:`swap_weights` installs
  a fresh version (same names/shapes/dtypes — so every AOT program is a
  cache HIT, zero recompiles) and bumps the serving epoch atomically
  between batches. A request's version is resolved ONCE at admission
  and its whole batch dispatches against that store, so every request
  is answered by exactly one coherent version — never a half-swapped
  table. Stores are retained keep-last-K plus whatever is stable /
  canary / pinned, which is what makes bit-exact rollback to a pinned
  version an O(1) route change (docs/serving.md "Rollout & weight
  streaming").

The engine itself is stateless across calls and thread-safe for
concurrent :meth:`predict` calls; the serving batcher drives it from
one flush thread.
"""
from __future__ import annotations

import contextlib
import os
import threading
import warnings
import zlib

import numpy as _np
import jax
import jax.numpy as jnp
from jax import lax

from .. import obs as _obs
from ..base import canonical_dtype
from ..checkpoint import weight_digest
from ..context import current_context
from ..module.fused import ProgramCache, mesh_spec
from ..symbol import eval_graph
from ..ops.registry import rng_scope

__all__ = ["InferenceEngine", "parse_buckets", "parse_shape_spec"]


def version_keep():
    """MXTPU_SERVE_VERSION_KEEP: in-memory weight versions retained
    beyond the live set (stable/canary/pinned) — enough history that a
    request admitted against version v is still answerable after the
    next swap lands mid-batch."""
    return max(1, int(os.environ.get("MXTPU_SERVE_VERSION_KEEP", "2")))


def gen_slots():
    """MXTPU_SERVE_GENERATE_SLOTS: decode-batch capacity — the fixed
    slot count every decode program is compiled for. One XLA dispatch
    per step serves up to this many in-flight sequences."""
    return max(1, int(os.environ.get("MXTPU_SERVE_GENERATE_SLOTS", "32")))


def gen_max_new():
    """MXTPU_SERVE_GENERATE_MAX_NEW: hard cap on tokens generated per
    sequence (a request's ``max_new`` is clamped to it)."""
    return max(1, int(os.environ.get("MXTPU_SERVE_GENERATE_MAX_NEW",
                                     "256")))


def gen_prefill_buckets():
    """MXTPU_SERVE_GENERATE_PREFILL_BUCKETS: prompt-length buckets the
    prefill programs are compiled for (same grammar as
    MXTPU_SERVE_BUCKETS; a prompt pads into the smallest fit)."""
    return parse_buckets(os.environ.get(
        "MXTPU_SERVE_GENERATE_PREFILL_BUCKETS", "8,16,32"))


def parse_buckets(spec):
    """``MXTPU_SERVE_BUCKETS`` grammar: comma-separated batch sizes,
    e.g. ``1,2,4,8,16,32`` — sorted, deduped, all positive."""
    sizes = sorted({int(b) for b in str(spec).split(",") if b.strip()})
    if not sizes or sizes[0] < 1:
        raise ValueError("bucket spec %r needs positive batch sizes"
                         % (spec,))
    return tuple(sizes)


def parse_shape_spec(spec):
    """``MXTPU_SERVE_DATA_SHAPES`` grammar: ``name=dims;name=dims``
    with dims a comma list of PER-SAMPLE dimensions (no batch dim),
    e.g. ``data=3,32,32`` or ``data=64;mask=64``."""
    shapes = {}
    for item in str(spec).split(";"):
        item = item.strip()
        if not item:
            continue
        name, _, dims = item.partition("=")
        if not dims:
            raise ValueError("shape spec %r needs name=dims" % (item,))
        shapes[name.strip()] = tuple(
            int(d) for d in dims.split(",") if d.strip())
    if not shapes:
        raise ValueError("empty data shape spec %r" % (spec,))
    return shapes


class InferenceEngine:
    """Per-bucket AOT predict programs over one loaded model."""

    def __init__(self, symbol, arg_params, aux_params, data_shapes,
                 buckets=(1, 2, 4, 8, 16, 32), ctx=None, dtype="float32",
                 warm=True, version=0, mesh=None, rules=None):
        self._symbol = symbol
        self._ctx = ctx if ctx is not None else current_context()
        self._dev = self._ctx.jax_device()
        self._mesh, self._rules = self._resolve_mesh(mesh, rules)
        self._buckets = parse_buckets(
            buckets if isinstance(buckets, str)
            else ",".join(str(b) for b in buckets))
        self._dtype = canonical_dtype(dtype)
        # data inputs in a canonical order; everything else in the
        # symbol's argument list must come from the checkpoint
        self._data_names = tuple(sorted(data_shapes))
        self._sample_shapes = {n: tuple(data_shapes[n])
                               for n in self._data_names}
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        missing = [n for n in self._data_names if n not in arg_names]
        if missing:
            raise ValueError("data inputs %r are not arguments of the "
                             "symbol (args: %r)" % (missing, arg_names))
        # three kinds of symbol arguments: serving inputs (data_shapes),
        # checkpoint parameters, and loss-head leftovers (label vars a
        # training symbol carries — SoftmaxOutput's forward ignores its
        # label, so they are fed as trace-constant zeros per bucket)
        self._param_names = tuple(n for n in arg_names
                                  if n not in self._data_names
                                  and n in arg_params)
        self._extra_names = tuple(n for n in arg_names
                                  if n not in self._data_names
                                  and n not in arg_params)
        self._aux_names = tuple(aux_names)
        self._gen = self._detect_generate()
        self._gen_sums = None          # device sums, made on first use
        self._sums_published = {}      # state -> totals already folded in
        self._sums_lock = threading.Lock()
        # one shared device-resident copy of params/aux for all buckets,
        # per weight VERSION: an immutable store tuple swap_weights
        # replaces wholesale (programs take params as runtime arguments,
        # so a same-shape swap is always a program-cache hit)
        param_vals = tuple(self._put_param(n, arg_params[n])
                           for n in self._param_names)
        aux_vals = tuple(self._put_param(n, aux_params[n])
                         for n in self._aux_names)
        self._param_shapes = tuple((v.shape, _np.dtype(v.dtype))
                                   for v in param_vals)
        self._aux_shapes = tuple((v.shape, _np.dtype(v.dtype))
                                 for v in aux_vals)
        self._store_lock = threading.Lock()
        v0 = int(version)
        self._stores = {v0: (param_vals, aux_vals, None)}
        self._latest = v0          # swap watermark (stream dedupe)
        self._stable = v0          # the version requests default to
        self._canary = None        # (version, fraction) under rollout
        self._pinned = None        # rollback anchor: stable is frozen
        self._serve_epoch = 0      # bumps on every swap/policy change
        self._keep = version_keep()
        # back-compat aliases: always the STABLE store's tuples
        self._param_vals = param_vals
        self._aux_vals = aux_vals
        self.cache = ProgramCache()
        self._build_lock = threading.Lock()
        self._stats_lock = threading.Lock()
        self._stats = {"predicts": 0, "rows": 0, "pad_rows": 0,
                       "swaps": 0, "swaps_refused": 0,
                       "version_rebinds": 0,
                       "gen_prefills": 0, "gen_steps": 0,
                       "gen_decode_attn_path": 0,
                       "gen_prefill_attn_path": 0,
                       "gen_decode_row_write": 0,
                       "gen_prefill_row_write": 0,
                       "gen_decode_latent_path": 0,
                       "gen_prefill_latent_path": 0,
                       "gen_decode_latent_blockwise": 0,
                       "gen_prefill_latent_blockwise": 0,
                       "gen_decode_hyper_mix": 0,
                       "gen_prefill_hyper_mix": 0,
                       "gen_decode_moe_window": 0,
                       "gen_prefill_moe_window": 0}
        if warm:
            self.warm()

    @staticmethod
    def _host_array(v):
        return v.asnumpy() if hasattr(v, "asnumpy") else _np.asarray(v)

    # -- sharded placement (ISSUE 20) --------------------------------------
    @staticmethod
    def _resolve_mesh(mesh, rules):
        """``(mesh, rules)`` for sharded serving, or ``(None, None)``
        for the single-device engine. An explicit ``mesh=`` wins;
        otherwise ``MXTPU_MESH`` builds one (same grammar as the fused
        trainer). Default rules shard every parameter's dim 0 over the
        first mesh axis where it divides — the FSDP-style 1/N-memory
        default the trainer uses, so a server started with the same
        env shards the same way the trainer trained."""
        if mesh is None:
            spec = mesh_spec()
            if spec is None:
                return None, None
            from ..parallel.mesh import MeshContext
            mesh = MeshContext(spec)
        if mesh.num_devices <= 1:
            return None, None
        if rules is None:
            from ..parallel.mesh import PartitionSpec
            from ..partition import PartitionRules
            rules = PartitionRules(
                [(r".*", PartitionSpec(mesh.axis_names[0]))])
        return mesh, rules

    def _placement(self, name, shape):
        """Where a named store array lives: the rules' NamedSharding
        over the mesh (unmatched -> replicated; non-dividing mesh axes
        dropped per-dim) in sharded mode, else the context device."""
        if self._mesh is None:
            return self._dev
        return self._rules.sharding_for(self._mesh, name, tuple(shape))

    def _put_param(self, name, v):
        """A store array from what the caller handed in. A ``jax.Array``
        goes from device to device (12 GB of weights would otherwise cross
        the host twice); everything else through ``numpy``."""
        if isinstance(v, jax.Array):
            return jax.device_put(v, self._placement(name, v.shape))
        return self._put_named(name, self._host_array(v))

    def _put_named(self, name, host):
        host = _np.asarray(host)
        return jax.device_put(host, self._placement(name, host.shape))

    def _data_placement(self, shape):
        """Where a (padded) input batch lives: dim 0 over the ``data``
        mesh axis when the bucket divides it, else replicated — never
        a lone device, which would not compose with sharded params."""
        if self._mesh is None:
            return self._dev
        from ..parallel.mesh import AXIS_DATA
        d = self._mesh.axis_size(AXIS_DATA)
        if shape and d > 1 and int(shape[0]) % d == 0:
            return self._mesh.batch_sharding()
        return self._mesh.replicated()

    def _replicated(self):
        return self._dev if self._mesh is None \
            else self._mesh.replicated()

    def _abs(self, shape, dtype, sharding=None):
        """Abstract aval for AOT lowering, placement included — the
        compiled program IS the one the real calls dispatch: on the
        engine's own device in single-device mode (so N engines in one
        process can each own a chip), the SPMD partition in sharded
        mode (``AutoLayoutStep._abstract``'s trick one level up).
        Default placement on a mesh is replicated."""
        if self._mesh is None:
            sharding = jax.sharding.SingleDeviceSharding(self._dev)
        elif sharding is None or not hasattr(sharding, "mesh"):
            sharding = self._mesh.replicated()
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    # -- construction ------------------------------------------------------
    @classmethod
    def from_checkpoint(cls, prefix, epoch, data_shapes, **kw):
        """Load a ``save_checkpoint`` artifact (symbol json + params)
        into a ready engine — the serving half of ``Module.load``."""
        from ..model import load_checkpoint
        symbol, arg_params, aux_params = load_checkpoint(prefix, epoch)
        return cls(symbol, arg_params, aux_params, data_shapes, **kw)

    # -- introspection -----------------------------------------------------
    @property
    def buckets(self):
        return self._buckets

    @property
    def max_bucket(self):
        return self._buckets[-1]

    @property
    def data_names(self):
        return self._data_names

    def signature(self):
        """The wire-visible input contract (hello reply)."""
        sig = {"data_names": list(self._data_names),
               "sample_shapes": {n: list(s) for n, s
                                 in self._sample_shapes.items()},
               "dtype": str(_np.dtype(self._dtype)),
               "buckets": list(self._buckets)}
        if self._gen is not None:
            sig["generate"] = self.generate_spec()
        return sig

    def store_devices(self):
        """The devices that hold the stable weight store, read from the
        arrays themselves (not from the context the engine was given)."""
        devs = set()
        for v in self._param_vals + self._aux_vals:
            devs |= v.devices()
        return sorted(devs, key=lambda d: d.id)

    def stats(self):
        sums = self.gen_publish_sums()
        with self._stats_lock:
            out = dict(self._stats)
        if sums:
            out["gen_sums"] = sums
        out.update(self.cache.stats())
        out.update(self.version_state())
        return out

    # -- versioned weights -------------------------------------------------
    def version_state(self):
        """The rollout-visible version picture (rides hello/stats)."""
        with self._store_lock:
            return {"version": self._stable,
                    "latest": self._latest,
                    "versions": sorted(self._stores),
                    "serve_epoch": self._serve_epoch,
                    "canary": list(self._canary) if self._canary
                    else None,
                    "pinned": self._pinned}

    def current_params(self, version=None):
        """Host copies of a resident version's params (stable by
        default), name -> numpy — what a publisher-side drill mutates
        into the next version."""
        with self._store_lock:
            v = self._stable if version is None else int(version)
            store = self._stores[v]
        return {n: _np.asarray(val) for n, val in
                zip(self._param_names, store[0])}

    def store_digest(self, version=None):
        """The digest recorded (or computed on demand) for a resident
        version's params — rollback's bit-identity evidence."""
        with self._store_lock:
            v = self._stable if version is None else int(version)
            store = self._stores.get(v)
        if store is None:
            return None
        if store[2] is not None:
            return store[2]
        return weight_digest({n: _np.asarray(val) for n, val in
                              zip(self._param_names, store[0])})

    def swap_weights(self, arg_params, aux_params=None, version=None,
                     digest=None, activate=True):
        """Install ``arg_params`` (dict name -> numpy/NDArray; must
        cover every checkpoint parameter with identical shapes/dtypes —
        a mismatch would force a retrace and is refused) as a fresh
        weight version, device_put into a NEW store; the serving epoch
        bumps atomically so in-flight batches keep their resolved
        store and the NEXT batch reads the new one. Returns the
        installed version, or None when refused (stale version — the
        stream-replay dedupe — or a half table). ``digest`` (the
        publisher's :func:`~mxtpu.checkpoint.weight_digest`) is
        verified against the incoming bytes before anything swaps."""
        with self._store_lock:
            v = self._latest + 1 if version is None else int(version)
            if v <= self._latest:
                self._note("swaps_refused")
                return None
        host = {}
        for name in self._param_names:
            if name not in arg_params:
                # never a half-swapped table: all params or nothing
                self._note("swaps_refused")
                return None
            host[name] = _np.ascontiguousarray(
                self._host_array(arg_params[name]))
        for name, (shape, dtype) in zip(self._param_names,
                                        self._param_shapes):
            a = host[name]
            if tuple(a.shape) != tuple(shape):
                raise ValueError(
                    "weight version %d: param %r has shape %r, the "
                    "compiled programs take %r — a swap must never "
                    "retrace" % (v, name, tuple(a.shape), tuple(shape)))
            if a.dtype != dtype:
                host[name] = a.astype(dtype)
        if digest is not None:
            got = weight_digest(host)
            if got != digest:
                raise ValueError(
                    "weight version %d failed digest verification "
                    "(%s != %s) — refusing to serve corrupt params"
                    % (v, got[:12], digest[:12]))
        param_vals = tuple(self._put_named(n, host[n])
                           for n in self._param_names)
        if aux_params is not None:
            aux_vals = tuple(
                self._put_named(n, _np.ascontiguousarray(
                    self._host_array(aux_params[n])).astype(dt))
                for n, (_s, dt) in zip(self._aux_names,
                                       self._aux_shapes))
        else:
            aux_vals = None
        with self._store_lock:
            if v <= self._latest:          # raced with a newer swap
                self._note("swaps_refused")
                return None
            if aux_vals is None:
                # aux (BN running stats) not republished: carry the
                # latest store's forward
                aux_vals = self._stores[self._latest][1]
            self._stores[v] = (param_vals, aux_vals,
                               digest or weight_digest(host))
            self._latest = v
            if activate and self._pinned is None:
                self._stable = v
                self._param_vals = param_vals
                self._aux_vals = aux_vals
            self._serve_epoch += 1
            self._gc_stores_locked()
            self._note("swaps")
        return v

    @contextlib.contextmanager
    def _noting_attn_path(self, program):
        """Around the trace of a generate program (``"decode"`` or
        ``"prefill"``): add to ``gen_<program>_attn_path`` how many of its
        ``cached_attention`` nodes the trace put on the one-token decode
        kernel (``ops/nn.py``), and to ``gen_<program>_row_write`` how many
        of those write their cache rows through the row-write kernel:
        ``n_layer`` each for a decode program whose shapes engage them, 0
        for a prefill. The ``latent_attention`` nodes traced onto their
        kernel, each of which writes its row through the row-write kernel
        too (``gen_<program>_latent_path``), those traced onto the
        block-wise chunk path and its kernel ``latent_prefill_attention``
        (``gen_<program>_latent_blockwise``: a layer each for every prefill
        bucket compiled, 0 for a decode program), the ``hyper_mix`` nodes
        traced at all (``gen_<program>_hyper_mix``: two a layer in every
        program of a model with hyper-connections), and the ``moe_ffn_held``
        nodes traced with their held rows walked in windows
        (``gen_<program>_moe_window``: an expert layer each for a prefill
        bucket large enough of a model that holds a share of its experts, 0
        for a decode program and where every expert is held)."""
        from ..ops import nn
        counts = {"gen_%s_attn_path" % program: nn.decode_path_nodes,
                  "gen_%s_row_write" % program: nn.row_write_nodes,
                  "gen_%s_latent_path" % program: nn.latent_decode_nodes,
                  "gen_%s_latent_blockwise" % program:
                      nn.latent_blockwise_nodes,
                  "gen_%s_hyper_mix" % program: nn.hyper_mix_nodes,
                  "gen_%s_moe_window" % program: nn.held_window_nodes}
        before = {field: read() for field, read in counts.items()}
        yield
        with self._stats_lock:
            for field, read in counts.items():
                self._stats[field] += read() - before[field]

    def _note(self, field):
        with self._stats_lock:
            self._stats[field] += 1

    def _gc_stores_locked(self):
        live = {self._stable, self._latest, self._pinned}
        if self._canary is not None:
            live.add(self._canary[0])
        keep = sorted(self._stores)[-self._keep:]
        for v in [v for v in self._stores
                  if v not in live and v not in keep]:
            del self._stores[v]

    def set_canary(self, version, fraction):
        """Route ``fraction`` of requests (deterministic per request
        id) to ``version``; the rest stay on stable."""
        fraction = float(fraction)
        with self._store_lock:
            if version is not None and int(version) not in self._stores:
                raise ValueError("canary version %r is not resident "
                                 "(have %r)" % (version,
                                                sorted(self._stores)))
            self._canary = (int(version), fraction) \
                if version is not None else None
            self._serve_epoch += 1

    def promote(self, version=None):
        """Make ``version`` (default: the canary) the stable route and
        end the rollout — the canary's traffic share becomes 100%."""
        with self._store_lock:
            if version is None and self._canary is not None:
                version = self._canary[0]
            if version is None:
                version = self._latest
            version = int(version)
            if version not in self._stores:
                raise ValueError("cannot promote non-resident version "
                                 "%d" % version)
            self._stable = version
            store = self._stores[version]
            self._param_vals, self._aux_vals = store[0], store[1]
            self._canary = None
            self._pinned = None
            self._serve_epoch += 1
            return version

    def abort_canary(self):
        with self._store_lock:
            self._canary = None
            self._serve_epoch += 1

    def pin(self, version):
        """Freeze stable on ``version`` (must be resident): streamed
        swaps keep landing as resident stores but stop auto-activating
        — the engine half of bit-exact rollback."""
        with self._store_lock:
            version = int(version)
            if version not in self._stores:
                raise ValueError("cannot pin non-resident version %d "
                                 "(have %r)" % (version,
                                                sorted(self._stores)))
            self._pinned = version
            self._stable = version
            store = self._stores[version]
            self._param_vals, self._aux_vals = store[0], store[1]
            self._canary = None
            self._serve_epoch += 1

    def unpin(self):
        with self._store_lock:
            self._pinned = None
            self._serve_epoch += 1

    def load_store(self, arg_params, version, digest=None,
                   aux_params=None):
        """Install a HISTORICAL version as a resident store WITHOUT
        touching routing: unlike :meth:`swap_weights` this bypasses the
        monotone version watermark (canary/rollback deliberately serve
        older versions) and activates nothing — pair with
        :meth:`set_canary`/:meth:`pin`/:meth:`promote`. Verifies
        ``digest`` against the restored bytes; raises on any mismatch,
        never half-installs."""
        version = int(version)
        host = {}
        for name in self._param_names:
            if name not in arg_params:
                raise ValueError(
                    "weight version %d is missing param %r — "
                    "refusing a half table" % (version, name))
            host[name] = _np.ascontiguousarray(
                self._host_array(arg_params[name]))
        for name, (shape, dtype) in zip(self._param_names,
                                        self._param_shapes):
            if tuple(host[name].shape) != tuple(shape):
                raise ValueError(
                    "weight version %d: param %r has shape %r, want "
                    "%r" % (version, name, tuple(host[name].shape),
                            tuple(shape)))
            if host[name].dtype != dtype:
                host[name] = host[name].astype(dtype)
        if digest is not None and weight_digest(host) != digest:
            raise ValueError(
                "weight version %d failed digest verification — "
                "the restored snapshot is not the recorded bits"
                % version)
        param_vals = tuple(self._put_named(n, host[n])
                           for n in self._param_names)
        aux_vals = None
        if aux_params is not None:
            aux_vals = tuple(
                self._put_named(n, _np.ascontiguousarray(
                    self._host_array(aux_params[n])).astype(dt))
                for n, (_s, dt) in zip(self._aux_names,
                                       self._aux_shapes))
        with self._store_lock:
            if aux_vals is None:
                aux_vals = self._stores[self._stable][1]
            self._stores[version] = (param_vals, aux_vals,
                                     digest or weight_digest(host))
            self._serve_epoch += 1
        return version

    def restore_version(self, arg_params, aux_params=None, version=0,
                        digest=None):
        """The rollback composite: :meth:`load_store` + :meth:`pin` —
        install the historical version (digest-verified) and freeze
        routing on it."""
        version = self.load_store(arg_params, version, digest=digest,
                                  aux_params=aux_params)
        self.pin(version)
        return version

    def route_version(self, rid):
        """Resolve which weight version answers request ``rid`` —
        called ONCE at admission, so the whole batch a request joins
        dispatches against one coherent store. Deterministic: the
        canary split hashes the request id, never a clock or RNG."""
        with self._store_lock:
            if self._canary is None:
                return self._stable
            version, fraction = self._canary
            if zlib.crc32(str(rid).encode()) % 10000 < fraction * 10000:
                return version
            return self._stable

    def _resolve_store(self, version):
        """The (params, aux, answered_version) for ``version``; a
        version GC'd between admission and dispatch rebinds to stable
        (counted — the batch is still answered by ONE coherent
        version)."""
        with self._store_lock:
            v = self._stable if version is None else int(version)
            store = self._stores.get(v)
            if store is None:
                v = self._stable
                store = self._stores[v]
                rebind = True
            else:
                rebind = False
        if rebind:
            self._note("version_rebinds")
        return store[0], store[1], v

    def store_exact(self, version):
        """``(params, aux)`` for EXACTLY ``version``, or None. The
        pinned-replay resolver for generation: a replayed sequence that
        already streamed tokens must never silently rebind to stable —
        that would tear the token stream across weight versions."""
        with self._store_lock:
            store = self._stores.get(int(version))
        return None if store is None else (store[0], store[1])

    def check_rows(self, arrays):
        """Validate one request payload (a list/tuple of numpy arrays,
        one per data input in ``data_names`` order). Returns the row
        count; raises ValueError naming the mismatch."""
        if len(arrays) != len(self._data_names):
            raise ValueError(
                "payload has %d arrays, model takes %d inputs %r"
                % (len(arrays), len(self._data_names), self._data_names))
        rows = None
        for name, arr in zip(self._data_names, arrays):
            arr = _np.asarray(arr)
            want = self._sample_shapes[name]
            if arr.ndim != len(want) + 1 or tuple(arr.shape[1:]) != want:
                raise ValueError(
                    "input %r has shape %r, want (rows,)+%r"
                    % (name, tuple(arr.shape), want))
            if rows is None:
                rows = int(arr.shape[0])
            elif int(arr.shape[0]) != rows:
                raise ValueError(
                    "inputs disagree on rows: %r has %d, expected %d"
                    % (name, arr.shape[0], rows))
        if rows == 0:
            raise ValueError("empty request (0 rows)")
        if rows > self.max_bucket:
            raise ValueError(
                "request rows %d exceed the largest bucket %d"
                % (rows, self.max_bucket))
        return rows

    def bucket_for(self, rows):
        """Smallest configured bucket holding ``rows``."""
        for b in self._buckets:
            if rows <= b:
                return b
        raise ValueError("rows %d exceed the largest bucket %d"
                         % (rows, self.max_bucket))

    # -- program construction ---------------------------------------------
    def _declared_var_specs(self):
        """``name -> (shape, dtype)`` for every symbol VARIABLE that
        declared a ``__shape__`` with a leading 0 (batch) dimension —
        the per-sample contract generative state vars ride (shape
        inference cannot derive them: nothing upstream constrains a
        cache input's shape)."""
        out = {}
        for node_name, attrs in self._symbol.attr_dict().items():
            s = attrs.get("__shape__")
            if s is None:
                continue
            s = tuple(int(d) for d in s)
            if s and s[0] == 0 and all(d > 0 for d in s[1:]):
                out[node_name] = (s, canonical_dtype(
                    attrs.get("__dtype__", self._dtype)))
        return out

    def _extra_shapes(self, bucket):
        """(name, shape, dtype) of the non-data non-param leftovers for
        ``bucket``: label vars a training head carries (inferred — the
        SoftmaxOutput shape hint scales them with the batch) and
        generative state vars (declared ``__shape__``, batch dim 0)."""
        if not self._extra_names:
            return ()
        declared = self._declared_var_specs()
        resolved = {n: ((bucket,) + declared[n][0][1:], declared[n][1])
                    for n in self._extra_names if n in declared}
        missing = [n for n in self._extra_names if n not in resolved]
        if missing:
            kwargs = {n: (bucket,) + self._sample_shapes[n]
                      for n in self._data_names}
            arg_shapes, _outs, _aux = self._symbol.infer_shape(**kwargs)
            by_name = dict(zip(self._symbol.list_arguments(), arg_shapes))
            bad = [n for n in missing if by_name.get(n) is None]
            if bad:
                raise ValueError(
                    "symbol arguments %r are neither checkpoint "
                    "parameters nor declared data inputs, and their "
                    "shapes cannot be inferred — pass them in "
                    "data_shapes or declare var shapes" % (bad,))
            for n in missing:
                resolved[n] = (tuple(by_name[n]), self._dtype)
        return tuple((n,) + resolved[n] for n in self._extra_names)

    def _build_program(self, bucket):
        """Lower + compile the bucket's forward AOT. Donation: the
        padded input batch (argument 0) is donated — request payload
        buffers are dead once the program runs."""
        data_names = self._data_names
        param_names = self._param_names
        aux_names = self._aux_names
        outputs_ref = self._symbol._outputs
        extra_shapes = self._extra_shapes(bucket)

        def predict_fn(data_vals, param_vals, aux_vals):
            feed = dict(zip(param_names, param_vals))
            feed.update(zip(aux_names, aux_vals))
            feed.update(zip(data_names, data_vals))
            for n, s, dt in extra_shapes:
                # loss-head label vars / generative state vars: the
                # graph evaluator requires every variable bound
                feed[n] = jnp.zeros(s, dt)
            # trace-constant key: inference is deterministic by
            # construction (training=False; Dropout is identity), the
            # key only satisfies ops that demand an rng scope
            with rng_scope(jax.random.PRNGKey(0)):
                outs, _aux_updates = eval_graph(outputs_ref, feed, False)
            return tuple(outs)

        jitted = jax.jit(predict_fn, donate_argnums=(0,))
        data_abs = tuple(
            self._abs((bucket,) + self._sample_shapes[n], self._dtype,
                      self._data_placement(
                          (bucket,) + self._sample_shapes[n]))
            for n in data_names)
        param_abs, aux_abs = self._store_abs()
        with warnings.catch_warnings():
            # most models cannot alias the input buffer into an output
            # buffer; the donation is still correct (the batch is dead),
            # so the advisory is pure noise at compile time
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            return jitted.lower(data_abs, param_abs, aux_abs).compile()

    def program(self, bucket):
        """The compiled program for ``bucket`` (AOT-cached)."""
        if bucket not in self._buckets:
            raise ValueError("no bucket %d (configured: %r)"
                             % (bucket, self._buckets))
        program, _hit = self.cache.get(
            ("predict", bucket), lambda: self._build_program(bucket))
        return program

    def warm(self):
        """Compile every bucket program NOW — serving starts with the
        full menu ready, so no request ever pays a trace. A generative
        model's prefill/decode/adopt menu warms too, so the first
        sequence never pays a trace either."""
        for b in self._buckets:
            self.program(b)
        n = len(self._buckets)
        if self._gen is not None:
            for L in self.gen_prefill_menu():
                self.gen_prefill_program(L)
                n += 1
            K = gen_slots()
            self.gen_decode_program(K)
            self.gen_adopt_program(K)
            n += 2
        return n

    # -- autoregressive generation (ISSUE 17) ------------------------------
    # The generative symbol contract: exactly one data input (the token
    # ids, [batch, time]), an extra var named "pos" (per-slot write
    # offset, declared shape (0,)), and for every remaining extra var
    # ``n`` (a KV/state cache, declared per-sample shape (0, S, ...))
    # an output named ``n + "_next"`` carrying its updated value.
    # ``example/char_lm`` builds it; any symbol shaped this way serves.
    #
    # Each state keeps its OWN length, and says what kind it is through
    # its variable's ``__state_kind__`` attribute:
    #   * ``full`` (the default): row ``s`` holds position ``s``; it grows
    #     with the sequence, so the shortest of them is the sequence
    #     ceiling and clamps the prefill menu;
    #   * ``ring``: a window layer's cache, position ``s`` in row ``s mod
    #     rows``; its length bounds nothing;
    #   * ``sum:<publisher>``: no slot dimension at all. One ``(1, ...)``
    #     array the ENGINE holds; every program adds into it on the device
    #     (a prefill's part is added at adoption), it is never donated, and
    #     ``stats()`` reads it (``ops.nn.SUM_PUBLISHERS`` names what it
    #     feeds in the registry): counts per step without a read per step.
    # An optional extra var ``len`` (declared shape (0,)) is fed the number
    # of TRUE rows of the chunk: the prompt's length in a padded prefill,
    # one in a decode step. A ring needs it to keep padding out.
    def _detect_generate(self):
        if len(self._data_names) != 1:
            return None
        if "pos" not in self._extra_names:
            return None
        out_idx = {n: i for i, n in
                   enumerate(self._symbol.list_outputs())}
        declared = self._declared_var_specs()
        attrs = self._symbol.attr_dict()
        states, kinds = [], []
        for n in self._extra_names:
            if n in ("pos", "len"):
                continue
            i = out_idx.get(n + "_next_output")
            spec = declared.get(n)
            if i is None or spec is None or len(spec[0]) < 2:
                return None
            states.append((n, tuple(spec[0][1:]), spec[1], i))
            kinds.append(attrs.get(n, {}).get("__state_kind__", "full"))
        if not states:
            return None
        grows = [s[1][0] for s, k in zip(states, kinds) if k == "full"] \
            or [s[1][0] for s in states]
        # a lane packs every kind but sums by slot; the engine holds those
        slot = tuple(i for i, k in enumerate(kinds)
                     if not k.startswith("sum"))
        sums = tuple(i for i in range(len(kinds)) if i not in slot)
        return {"tok": self._data_names[0], "pos": "pos",
                "len": "len" if "len" in self._extra_names else None,
                "states": tuple(states), "kinds": tuple(kinds),
                "slot_states": slot, "sum_states": sums,
                "slot_specs": tuple(states[i] for i in slot),
                "sum_specs": tuple(states[i] for i in sums),
                "cache_len": int(min(grows))}

    @property
    def is_generative(self):
        return self._gen is not None

    def generate_spec(self):
        """The wire-visible generation contract (None for one-shot
        models): state names, cache length (the hard sequence-length
        ceiling), the compiled prefill menu and the max_new clamp."""
        if self._gen is None:
            return None
        return {"token_input": self._gen["tok"],
                "states": [n for n, _s, _d, _i in self._gen["states"]],
                "state_rows": {n: s[0] for n, s, _d, _i
                               in self._gen["states"]},
                "state_kinds": dict(zip(
                    (n for n, _s, _d, _i in self._gen["states"]),
                    self._gen["kinds"])),
                "cache_len": self._gen["cache_len"],
                "prefill_buckets": list(self.gen_prefill_menu()),
                "slots": gen_slots(),
                "max_new": gen_max_new()}

    def gen_prefill_menu(self):
        """Prefill prompt-length buckets, clamped to the cache length."""
        if self._gen is None:
            return ()
        S = self._gen["cache_len"]
        menu = tuple(b for b in gen_prefill_buckets() if b <= S)
        return menu or (S,)

    def gen_bucket_for(self, plen):
        for b in self.gen_prefill_menu():
            if plen <= b:
                return b
        raise ValueError(
            "prompt length %d exceeds the largest prefill bucket %d"
            % (plen, self.gen_prefill_menu()[-1]))

    def _store_abs(self):
        # sharded mode: the live store arrays already sit in their
        # per-name NamedShardings, so their .sharding IS the aval
        # placement (single-device mode stays placement-free)
        param_abs = tuple(self._abs(v.shape, v.dtype, v.sharding)
                          for v in self._param_vals)
        aux_abs = tuple(self._abs(v.shape, v.dtype, v.sharding)
                        for v in self._aux_vals)
        return param_abs, aux_abs

    def _gen_state_placements(self, K):
        """Per-state placements for the packed ``K``-slot decode
        caches: rule-matched per name (the slot dim shards when K
        divides its axis — the KV cache's share of the 1/N memory
        win), replicated when unmatched, the lone device when no mesh
        is configured."""
        return tuple(self._placement(n, (K,) + s)
                     for n, s, _dt, _i in self._gen["slot_specs"])

    def _sum_abs(self):
        return tuple(self._abs((1,) + s, dt, self._placement(n, (1,) + s))
                     for n, s, dt, _i in self._gen["sum_specs"])

    def _build_gen_prefill(self, L):
        """Prompt in (padded to bucket ``L``, batch 1) -> (first greedy
        token, per-sequence state rows). The token buffer is donated;
        the logits row the first token comes from is the TRUE last
        prompt position, so padding never leaks into the sample."""
        g = self._gen
        tok_name, pos_name = g["tok"], g["pos"]
        states = g["states"]
        param_names, aux_names = self._param_names, self._aux_names
        outputs_ref = self._symbol._outputs

        def prefill_fn(tokens, length, param_vals, aux_vals):
            feed = dict(zip(param_names, param_vals))
            feed.update(zip(aux_names, aux_vals))
            feed[tok_name] = tokens
            feed[pos_name] = jnp.zeros((1,), jnp.int32)
            if g["len"]:
                feed[g["len"]] = length.astype(jnp.int32)
            for n, s, dt, _i in states:
                feed[n] = jnp.zeros((1,) + s, dt)
            with rng_scope(jax.random.PRNGKey(0)):
                outs, _aux = eval_graph(outputs_ref, feed, False)
            logits = outs[0]
            if logits.ndim == 2:          # flattened head: (L, V)
                logits = logits.reshape(1, L, -1)
            last = jnp.take_along_axis(
                logits,
                (length.astype(jnp.int32) - 1)[:, None, None], axis=1)
            first = jnp.argmax(last[:, 0, :], axis=-1).astype(jnp.int32)
            rows = tuple(outs[i] for _n, _s, _dt, i in states)
            return first, rows

        if self._mesh is None:
            jitted = jax.jit(prefill_fn, donate_argnums=(0,))
        else:
            # explicit out_shardings: the prefill rows feed the adopt
            # program, whose lowered avals pin their placement — the
            # handoff must match exactly or the AOT call is rejected
            repl = self._mesh.replicated()
            row_sh = tuple(self._placement(n, (1,) + s)
                           for n, s, _dt, _i in states)
            jitted = jax.jit(prefill_fn, donate_argnums=(0,),
                             out_shardings=(repl, row_sh))
        param_abs, aux_abs = self._store_abs()
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            with self._noting_attn_path("prefill"):
                lowered = jitted.lower(
                    self._abs((1, L), self._dtype),
                    self._abs((1,), _np.int32),
                    param_abs, aux_abs)
            return lowered.compile()

    def _build_gen_decode(self, K):
        """ONE decode step over the packed ``K``-slot batch: (current
        tokens, positions, packed state) -> (readable next tokens, the
        next step's feed, advanced positions, updated state). Token
        feed, positions and state are DONATED — XLA aliases them into
        the outputs, so per-step cost is one dispatch and the KV state
        never round-trips the host. Inactive slots compute garbage at
        constant cost; adoption overwrites their rows."""
        g = self._gen
        tok_name, pos_name = g["tok"], g["pos"]
        states, sums = g["slot_specs"], g["sum_specs"]
        state_names = tuple(n for n, _s, _d, _i in states)
        sum_names = tuple(n for n, _s, _d, _i in sums)
        param_names, aux_names = self._param_names, self._aux_names
        outputs_ref = self._symbol._outputs

        def decode_fn(tok_feed, pos, state_vals, param_vals, aux_vals,
                      sum_vals):
            feed = dict(zip(param_names, param_vals))
            feed.update(zip(aux_names, aux_vals))
            feed[tok_name] = tok_feed
            feed[pos_name] = pos
            if g["len"]:
                feed[g["len"]] = jnp.ones_like(pos)
            feed.update(zip(state_names, state_vals))
            feed.update(zip(sum_names, sum_vals))
            with rng_scope(jax.random.PRNGKey(0)):
                outs, _aux = eval_graph(outputs_ref, feed, False)
            logits = outs[0]
            if logits.ndim == 3:
                logits = logits[:, -1, :]
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            new_states = tuple(outs[i] for _n, _s, _dt, i in states)
            new_sums = tuple(outs[i] for _n, _s, _dt, i in sums)
            return (nxt, nxt[:, None].astype(tok_feed.dtype),
                    pos + 1, new_states, new_sums)

        state_sh = self._gen_state_placements(K)
        if self._mesh is None:
            jitted = jax.jit(decode_fn, donate_argnums=(0, 1, 2))
        else:
            # out state placement == in state placement: donation
            # carries the sharded KV caches across steps reshard-free
            repl = self._mesh.replicated()
            jitted = jax.jit(decode_fn, donate_argnums=(0, 1, 2),
                             out_shardings=(repl, repl, repl, state_sh,
                                            tuple(repl for _ in sums)))
        param_abs, aux_abs = self._store_abs()
        state_abs = tuple(
            self._abs((K,) + s, dt, sh)
            for (_n, s, dt, _i), sh in zip(states, state_sh))
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            # a sharded engine's mesh is ambient while the decode program
            # is traced, so cached_attention sees it and keeps the dense
            # formula, which GSPMD partitions (the kernel is one device's)
            with self._mesh or contextlib.nullcontext(), \
                    self._noting_attn_path("decode"):
                lowered = jitted.lower(
                    self._abs((K, 1), self._dtype),
                    self._abs((K,), _np.int32),
                    state_abs, param_abs, aux_abs, self._sum_abs())
            return lowered.compile()

    def _build_gen_adopt(self, K):
        """Insert one prefilled sequence into decode slot ``slot`` of
        the packed batch (donated in place) — how a queued sequence
        joins the in-flight batch at a step boundary without draining
        it."""
        states = self._gen["slot_specs"]

        def adopt_fn(tok_feed, pos, state_vals, row_tok, row_pos,
                     row_states, slot, sum_vals, sum_rows):
            slot = slot.astype(jnp.int32)
            tok_feed = lax.dynamic_update_slice(
                tok_feed, row_tok.reshape(1, 1).astype(tok_feed.dtype),
                (slot, 0))
            pos = lax.dynamic_update_slice(
                pos, row_pos.reshape(1).astype(pos.dtype), (slot,))
            new_states = tuple(
                lax.dynamic_update_slice(
                    s, r.astype(s.dtype), (slot,) + (0,) * (s.ndim - 1))
                for s, r in zip(state_vals, row_states))
            # what the prefill counted joins the engine's sums here
            new_sums = tuple(s + r.astype(s.dtype)
                             for s, r in zip(sum_vals, sum_rows))
            return tok_feed, pos, new_states, new_sums

        state_sh = self._gen_state_placements(K)
        if self._mesh is None:
            jitted = jax.jit(adopt_fn, donate_argnums=(0, 1, 2))
        else:
            repl = self._mesh.replicated()
            jitted = jax.jit(adopt_fn, donate_argnums=(0, 1, 2),
                             out_shardings=(repl, repl, state_sh, tuple(
                                 repl for _ in self._gen["sum_specs"])))
        state_abs = tuple(
            self._abs((K,) + s, dt, sh)
            for (_n, s, dt, _i), sh in zip(states, state_sh))
        row_abs = tuple(
            self._abs((1,) + s, dt, self._placement(n, (1,) + s))
            for n, s, dt, _i in states)
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            return jitted.lower(
                self._abs((K, 1), self._dtype),
                self._abs((K,), _np.int32),
                state_abs,
                self._abs((1,), _np.int32),
                self._abs((1,), _np.int32),
                row_abs,
                self._abs((), _np.int32),
                self._sum_abs(), self._sum_abs()).compile()

    def _require_gen(self):
        if self._gen is None:
            raise ValueError(
                "model is not generative: the symbol lacks the "
                "pos/state-next generation contract")

    def gen_prefill_program(self, L):
        self._require_gen()
        program, _hit = self.cache.get(
            ("gen_prefill", L), lambda: self._build_gen_prefill(L))
        return program

    def gen_decode_program(self, K):
        self._require_gen()
        program, _hit = self.cache.get(
            ("gen_decode", K), lambda: self._build_gen_decode(K))
        return program

    def gen_adopt_program(self, K):
        self._require_gen()
        program, _hit = self.cache.get(
            ("gen_adopt", K), lambda: self._build_gen_adopt(K))
        return program

    def gen_state_init(self, K):
        """Fresh packed decode state for ``K`` slots: [token feed
        (K, 1), positions (K,) int32, per-state caches] — the triple a
        decode lane owns and every step donates forward."""
        self._require_gen()
        dev = self._replicated()
        tok_feed = jax.device_put(_np.zeros((K, 1), self._dtype), dev)
        pos = jax.device_put(_np.zeros((K,), _np.int32), dev)
        states = tuple(
            jax.device_put(_np.zeros((K,) + s, dt), sh)
            for (_n, s, dt, _i), sh in zip(
                self._gen["slot_specs"], self._gen_state_placements(K)))
        by_kind = {}
        for (_n, s, dt, _i), kind in zip(self._gen["states"],
                                         self._gen["kinds"]):
            kind = kind.partition(":")[0]
            rows = 1 if kind == "sum" else K
            by_kind[kind] = by_kind.get(kind, 0) + rows * int(
                _np.prod(s)) * _np.dtype(dt).itemsize
        with self._stats_lock:
            self._stats["gen_state_bytes"] = by_kind
        return [tok_feed, pos, states]

    def _sums(self):
        """The engine's device sums (zeros until a program has run)."""
        if self._gen_sums is None:
            self._gen_sums = tuple(
                jax.device_put(_np.zeros((1,) + s, dt),
                               self._placement(n, (1,) + s))
                for n, s, dt, _i in self._gen["sum_specs"])
        return self._gen_sums

    def gen_publish_sums(self):
        """Read the device sums (one small transfer; never inside a step)
        and fold what they gained since the last reading into the
        registry. Returns ``{state: totals}``, the totals since the start
        kept here in int64: the device's int32 sums wrap (after 2^31
        assignments, 20 hours at 30,000 a second), so what a sum gained is
        its difference modulo 2^32. The scheduler's thread replaces
        ``_gen_sums`` with every program it runs and takes no lock for it:
        a sum is never donated, so whichever tuple is read here is whole."""
        if self._gen is None or not self._gen["sum_states"]:
            return {}
        from ..ops.nn import SUM_PUBLISHERS
        with self._sums_lock:
            now = [_np.asarray(v).reshape(-1).astype(_np.int64)
                   for v in jax.device_get(self._sums())]
            out = {}
            for i, value in zip(self._gen["sum_states"], now):
                name = self._gen["states"][i][0]
                seen, total = self._sums_published.get(name, (0, 0))
                delta = (value - seen) & 0xFFFFFFFF
                publish = SUM_PUBLISHERS.get(
                    self._gen["kinds"][i].partition(":")[2])
                if publish is not None:
                    publish(name, delta)
                self._sums_published[name] = (value, total + delta)
                out[name] = (total + delta).tolist()
            return out

    def gen_prefill(self, tokens, param_vals, aux_vals):
        """Prefill one prompt against an explicit store. Returns
        ``(first_token (1,) int32 device array, state rows)`` — the
        caller reads the token and adopts the rows into a slot."""
        self._require_gen()
        arr = _np.asarray(tokens).reshape(-1)
        plen = int(arr.shape[0])
        if plen < 1:
            raise ValueError("empty prompt")
        L = self.gen_bucket_for(plen)
        padded = _np.zeros((1, L), self._dtype)
        padded[0, :plen] = arr
        program = self.gen_prefill_program(L)
        dev = self._replicated()
        first, rows = program(
            jax.device_put(padded, dev),
            jax.device_put(_np.asarray([plen], _np.int32), dev),
            param_vals, aux_vals)
        _obs.device_run("serve.engine.device.prefill", first, rows=L)
        self._note("gen_prefills")
        return first, rows

    def gen_step(self, state, param_vals, aux_vals):
        """One decode step over a lane's packed state; returns
        ``(readable_tokens (K,) int32, new_state)``. The old state is
        donated — dead after this call."""
        self._require_gen()
        K = int(state[0].shape[0])
        program = self.gen_decode_program(K)
        nxt, tok_feed, pos, new_states, self._gen_sums = program(
            state[0], state[1], state[2], param_vals, aux_vals,
            self._sums())
        _obs.device_run("serve.engine.device.decode", nxt, slots=K)
        self._note("gen_steps")
        return nxt, [tok_feed, pos, new_states]

    def gen_adopt(self, state, first_tok, plen, rows, slot):
        """Write a prefilled sequence into ``slot`` of a lane's packed
        state (donated in place); position starts at the prompt
        length."""
        self._require_gen()
        K = int(state[0].shape[0])
        program = self.gen_adopt_program(K)
        g = self._gen
        tok_feed, pos, new_states, self._gen_sums = program(
            state[0], state[1], state[2], first_tok,
            _np.asarray([plen], _np.int32),
            tuple(rows[i] for i in g["slot_states"]), _np.int32(slot),
            self._sums(), tuple(rows[i] for i in g["sum_states"]))
        # every output is donated into the next run: a mark, no array
        _obs.device_run("serve.engine.device.adopt", None)
        return [tok_feed, pos, new_states]

    # -- prewarm: export/import the AOT program menu (ISSUE 16) --------
    def program_fingerprint(self):
        """What makes two engines program-compatible: the wire
        signature plus every store shape the compiled programs were
        lowered against — and, for a sharded engine, the mesh topology
        and sharding rules (an SPMD program for an 8-way mesh must
        never install on a different fleet shape). A prewarm file only
        installs when this matches exactly."""
        import jax as _jax
        fp = {"signature": self.signature(),
              "params": [[list(s), str(d)]
                         for s, d in self._param_shapes],
              "aux": [[list(s), str(d)]
                      for s, d in self._aux_shapes],
              "jax": _jax.__version__}
        if self._mesh is not None:
            fp["mesh"] = {
                "shape": [[a, int(self._mesh.axis_size(a))]
                          for a in self._mesh.axis_names],
                "rules": [[pat.pattern, str(spec)]
                          for pat, spec in self._rules.rules]}
        return fp

    def export_programs(self, path):
        """Serialize the warmed program menu for peers; returns the
        entry count (0 = nothing exportable yet)."""
        return self.cache.export_to(path,
                                    meta=self.program_fingerprint())

    def prewarm_from(self, path):
        """Import a peer's exported programs — the joiner's warm start:
        every imported bucket skips its cold compile (``warm()``
        afterwards only builds what is missing). Refusal-tolerant: a
        missing/mismatched/corrupt file imports 0 and the engine falls
        back to compiling, never serves a wrong program."""
        try:
            return self.cache.import_from(
                path, expect_meta=self.program_fingerprint())
        except (OSError, ValueError, EOFError, ImportError) as e:
            warnings.warn("prewarm import from %s skipped: %s"
                          % (path, e))
            return 0

    # -- execution ---------------------------------------------------------
    def predict(self, arrays, rows=None):
        """Run one (possibly coalesced) batch against the STABLE
        version: pad ``arrays`` into the smallest bucket, dispatch the
        AOT program, return the outputs as numpy arrays sliced back to
        ``rows``."""
        outs, _v = self.predict_versioned(arrays, rows=rows)
        return outs

    def predict_versioned(self, arrays, rows=None, version=None):
        """The version-routed form the batcher drives: dispatch against
        the store of ``version`` (None = stable) and return
        ``(outputs, answered_version)``. The store triple is read once,
        so the whole batch is answered by exactly one coherent weight
        version even when a swap lands concurrently."""
        if rows is None:
            rows = self.check_rows(arrays)
        bucket = self.bucket_for(rows)
        program = self.program(bucket)
        param_vals, aux_vals, answered = self._resolve_store(version)
        data_vals = []
        for name, arr in zip(self._data_names, arrays):
            arr = _np.ascontiguousarray(arr, dtype=self._dtype)
            if rows < bucket:
                padded = _np.zeros((bucket,) + self._sample_shapes[name],
                                   self._dtype)
                padded[:rows] = arr
                arr = padded
            data_vals.append(
                jax.device_put(arr, self._data_placement(arr.shape)))
        outs = program(tuple(data_vals), param_vals, aux_vals)
        with self._stats_lock:
            self._stats["predicts"] += 1
            self._stats["rows"] += rows
            self._stats["pad_rows"] += bucket - rows
        return [_np.asarray(o)[:rows] for o in outs], answered
