"""Executor: compiled symbolic graph execution.

Capability parity with ``src/executor/graph_executor.cc`` (1,892 LoC) —
re-designed for XLA: ``simple_bind`` traces the whole symbol into ONE jitted
computation (forward) and one fused forward+vjp computation (backward).
MXNet's PlanMemory pool, bulk segments, cached engine oprs and per-op async
pushes are all subsumed by the XLA compiler's buffer assignment and fusion;
``is_train`` becomes a static trace argument; randomness (Dropout) is an
explicit PRNG-key input refreshed per forward.
"""
from __future__ import annotations

import functools
import logging

import numpy as _np
import jax
import jax.numpy as jnp

from .base import canonical_dtype, backward_mirror_enabled, maybe_remat
from .context import current_context
from .layout import AutoLayoutStep, MeshStep, auto_format
from .ops.registry import rng_scope, split2 as _split2
from .symbol import eval_graph
from . import ndarray as nd
from .ndarray import NDArray, _wrap

__all__ = ["Executor"]


def _ones_cot(o):
    # integer outputs (argmax/shape_array/casts) take float0 cotangents;
    # a ones_like would make jax.vjp reject the pullback
    if jnp.issubdtype(o.dtype, jnp.inexact):
        return jnp.ones_like(o)
    return _np.zeros(o.shape, jax.dtypes.float0)


def _zeros_cot(o):
    if jnp.issubdtype(o.dtype, jnp.inexact):
        return jnp.zeros_like(o)
    return _np.zeros(o.shape, jax.dtypes.float0)


class Executor:
    """Compiled executor over a Symbol (API parity with mx.executor.Executor)."""

    def __init__(self, sym, ctx, arg_dict, grad_dict, grad_req_dict, aux_dict):
        self._symbol = sym
        self._ctx = ctx
        self.arg_dict = arg_dict
        self.grad_dict = grad_dict
        self.aux_dict = aux_dict
        self._grad_req = grad_req_dict
        self._arg_names = sym.list_arguments()
        self._aux_names = sym.list_auxiliary_states()
        self._grad_args = [n for n in self._arg_names
                           if grad_req_dict.get(n, "null") != "null"]
        self.arg_arrays = [arg_dict[n] for n in self._arg_names]
        self.grad_arrays = [grad_dict.get(n) for n in self._arg_names]
        self.aux_arrays = [aux_dict[n] for n in self._aux_names]
        self._outputs = None
        self._out_shapes = None
        self._key = jax.random.PRNGKey(_np.random.randint(0, 2**31 - 1))
        self._monitor_callback = None

        outputs_ref = sym._outputs
        arg_names = tuple(self._arg_names)
        aux_names = tuple(self._aux_names)
        grad_args = tuple(self._grad_args)

        @functools.partial(jax.jit, static_argnames=("training",))
        def fwd(arg_vals, aux_vals, key, training):
            feed = dict(zip(arg_names, arg_vals))
            feed.update(zip(aux_names, aux_vals))
            with rng_scope(key):
                outs, aux_updates = eval_graph(outputs_ref, feed, training)
            new_aux = tuple(aux_updates.get(n, feed[n]) for n in aux_names)
            return tuple(outs), new_aux

        # MXNET_BACKWARD_DO_MIRROR (read at bind time): checkpoint the
        # differentiated region so the backward recomputes activations
        # instead of storing them (base.maybe_remat).
        self._mirror = backward_mirror_enabled()

        def _vjp_parts(arg_vals, aux_vals, key):
            feed = dict(zip(arg_names, arg_vals))
            feed.update(zip(aux_names, aux_vals))

            def f(gvals):
                local = dict(feed)
                local.update(zip(grad_args, gvals))
                with rng_scope(key):
                    outs, aux_updates = eval_graph(outputs_ref, local, True)
                new_aux = tuple(aux_updates.get(n, local[n]) for n in aux_names)
                return tuple(outs), new_aux

            primals = tuple(feed[n] for n in grad_args)
            return jax.vjp(maybe_remat(f, enabled=self._mirror), primals)

        @jax.jit
        def fwd_bwd(arg_vals, aux_vals, key, cotangents):
            (outs, new_aux), vjp_fn = _vjp_parts(arg_vals, aux_vals, key)
            zero_aux = tuple(_zeros_cot(a) for a in new_aux)
            grads = vjp_fn((cotangents, zero_aux))[0]
            return outs, new_aux, grads

        @jax.jit
        def fwd_bwd_ones(arg_vals, aux_vals, key):
            # Fused train step for the loss-head case (out_grads=None):
            # cotangents are ones, so they can be built inside the trace and
            # the whole forward+backward is ONE compiled computation. This is
            # what lets forward(is_train=True) speculate the backward and
            # Module.fit pay for the forward convolutions exactly once per
            # step (reference runs fwd nodes once and reuses activations,
            # graph_executor.cc:81-109).
            (outs, new_aux), vjp_fn = _vjp_parts(arg_vals, aux_vals, key)
            cot = tuple(_ones_cot(o) for o in outs)
            zero_aux = tuple(_zeros_cot(a) for a in new_aux)
            grads = vjp_fn((cot, zero_aux))[0]
            return outs, new_aux, grads

        self._fwd = fwd
        self._fwd_bwd = fwd_bwd
        self._fwd_bwd_ones = fwd_bwd_ones
        # Backward speculation is earned, not assumed: None = undecided
        # (plain forward), True = this executor proved to be a loss head
        # (its backward arrives with out_grads=None), False = it received
        # explicit head gradients or mutates inputs between forward and
        # backward — speculation would be wasted work. Forward-only
        # executors therefore never pay for a fused pass.
        self._speculate = None
        self._cached_grads = None
        self._state_snapshot = None
        self._grads_served = True

    # -- binding constructors ---------------------------------------------
    @staticmethod
    def _simple_bind(sym, ctx, grad_req, type_dict, shape_kwargs,
                     stype_dict=None):
        arg_names = sym.list_arguments()
        aux_names = sym.list_auxiliary_states()
        arg_shapes, out_shapes, aux_shapes = sym.infer_shape(**shape_kwargs)
        type_dict = type_dict or {}
        # storage types: InferStorageType pass over var declarations,
        # overridden by an explicit stype_dict (reference simple_bind's
        # stype_dict argument). Sparse-typed args materialize as CSR /
        # RowSparse NDArrays so sparse-aware consumers (lazy updates,
        # row_sparse_pull) engage; grads of row_sparse params are
        # row_sparse too (reference: BackwardStorageType of sparse dot).
        arg_stypes, _out_st, _aux_st = sym.infer_storage_type(
            **(stype_dict or {}))
        stype_of = dict(zip(arg_names, arg_stypes))
        arg_dict, grad_dict = {}, {}
        req_dict = _normalize_grad_req(grad_req, arg_names)
        for name, shape in zip(arg_names, arg_shapes):
            if shape is None:
                raise ValueError("could not infer shape for argument %r" % name)
            dt = canonical_dtype(type_dict.get(name, _np.float32))
            st = stype_of.get(name, "default")
            if st != "default":
                from .ndarray import sparse as _sparse
                arg_dict[name] = _sparse.zeros(st, shape, ctx=ctx, dtype=dt)
            else:
                arg_dict[name] = nd.zeros(shape, ctx=ctx, dtype=dt)
            if req_dict.get(name, "null") != "null":
                if st == "row_sparse":
                    from .ndarray import sparse as _sparse
                    grad_dict[name] = _sparse.zeros(st, shape, ctx=ctx,
                                                    dtype=dt)
                else:
                    grad_dict[name] = nd.zeros(shape, ctx=ctx, dtype=dt)
        aux_dict = {}
        for name, shape in zip(aux_names, aux_shapes):
            if shape is None:
                raise ValueError("could not infer shape for aux state %r" % name)
            aux_dict[name] = nd.zeros(shape, ctx=ctx)
        exe = Executor(sym, ctx, arg_dict, grad_dict, req_dict, aux_dict)
        exe._out_shapes = [tuple(s) for s in out_shapes]
        return exe

    @staticmethod
    def _bind(sym, ctx, args, args_grad, grad_req, aux_states):
        arg_names = sym.list_arguments()
        aux_names = sym.list_auxiliary_states()
        if isinstance(args, (list, tuple)):
            arg_dict = dict(zip(arg_names, args))
        else:
            arg_dict = dict(args)
        if args_grad is None:
            grad_dict = {}
        elif isinstance(args_grad, (list, tuple)):
            grad_dict = {n: g for n, g in zip(arg_names, args_grad)
                         if g is not None}
        else:
            grad_dict = dict(args_grad)
        req_dict = _normalize_grad_req(grad_req, arg_names)
        for n in arg_names:
            if n not in grad_dict:
                req_dict[n] = "null"
        if aux_states is None:
            aux_dict = {}
        elif isinstance(aux_states, (list, tuple)):
            aux_dict = dict(zip(aux_names, aux_states))
        else:
            aux_dict = dict(aux_states)
        return Executor(sym, ctx, arg_dict, grad_dict, req_dict, aux_dict)

    # -- execution ---------------------------------------------------------
    def forward(self, is_train=False, **kwargs):
        for k, v in kwargs.items():
            # sparse-aware rebind: same-stype sources hand their
            # compressed metadata over, anything else invalidates it for
            # lazy recompute (NDArray._assign_value)
            self.arg_dict[k]._assign_value(v)
        self._key, sub = _split2(self._key)
        arg_vals = tuple(self.arg_dict[n]._data for n in self._arg_names)
        aux_vals = tuple(self.aux_dict[n]._data for n in self._aux_names)
        if self._arg_names or self._aux_names:
            # params adopted from a mesh-sharded fused store live on every
            # mesh device while freshly-fed data sits on one; replicate the
            # minority so the jit sees one consistent device set (the
            # program then runs as a GSPMD mesh program)
            from .ndarray import _align_devices
            merged = _align_devices(list(arg_vals) + list(aux_vals))
            arg_vals = tuple(merged[:len(arg_vals)])
            aux_vals = tuple(merged[len(arg_vals):])
        if self._cached_grads is not None and not self._grads_served:
            # the previous speculated backward was never consumed (e.g.
            # training-mode prediction loops) — stop paying for it
            self._speculate = False
        self._cached_grads = None
        if is_train and self._grad_args and self._speculate:
            self._grads_served = False
            outs, new_aux, grads = self._fwd_bwd_ones(arg_vals, aux_vals, sub)
            self._cached_grads = grads
        else:
            outs, new_aux = self._fwd(arg_vals, aux_vals, sub, bool(is_train))
        if is_train:
            for n, v in zip(self._aux_names, new_aux):
                self.aux_dict[n]._data = v
        if self._cached_grads is not None:
            # jax.Arrays are immutable, so any in-place NDArray write
            # between forward and backward swaps the _data object —
            # identity-compare against this (post-aux-update) snapshot at
            # backward time to know whether speculated grads are still valid
            self._state_snapshot = arg_vals + tuple(
                self.aux_dict[n]._data for n in self._aux_names)
        else:
            self._state_snapshot = None
        self._last_key = sub
        self._outputs = [_wrap(o, self._ctx) for o in outs]
        if self._monitor_callback is not None:
            for name, arr in zip(self._symbol.list_outputs(), self._outputs):
                self._monitor_callback(name, arr)
        return self._outputs

    def backward(self, out_grads=None, is_train=True):
        if not self._grad_args:
            return
        if self._outputs is None:
            raise RuntimeError("backward called before forward")
        self._grads_served = True
        state_now = tuple(self.arg_dict[n]._data for n in self._arg_names) \
            + tuple(self.aux_dict[n]._data for n in self._aux_names)
        fresh = (self._state_snapshot is not None and
                 all(cur is old for cur, old
                     in zip(state_now, self._state_snapshot)))
        if out_grads is None and self._cached_grads is not None and fresh:
            grads = self._cached_grads
            # drop the references: the optimizer update is about to swap
            # every param's _data, and a kept snapshot would pin the whole
            # forward-time parameter set in device memory between steps
            self._cached_grads = None
            self._state_snapshot = None
        elif out_grads is None:
            if self._cached_grads is not None:
                # caller mutates bound arrays between forward and backward;
                # speculated grads are computed from forward-time values, so
                # recompute from the current state and stop speculating
                self._speculate = False
            elif self._speculate is None:
                # proven loss head: fuse the backward into forward from the
                # next step on (Module.fit steady state = 1 forward/step)
                self._speculate = True
            arg_vals = state_now[:len(self._arg_names)]
            aux_vals = state_now[len(self._arg_names):]
            _outs, _new_aux, grads = self._fwd_bwd_ones(arg_vals, aux_vals,
                                                        self._last_key)
        else:
            # explicit head gradients: this executor sits mid-chain, so
            # speculation can never pay off — stop doing it
            self._speculate = False
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            cotangents = tuple(g._data if g is not None
                               else _zeros_cot(o._data)
                               for g, o in zip(out_grads, self._outputs))
            arg_vals = state_now[:len(self._arg_names)]
            aux_vals = state_now[len(self._arg_names):]
            _outs, _new_aux, grads = self._fwd_bwd(arg_vals, aux_vals,
                                                   self._last_key, cotangents)
        for n, g in zip(self._grad_args, grads):
            tgt = self.grad_dict[n]
            if self._grad_req.get(n) == "add":
                tgt._data = tgt._data + g
            else:
                tgt._data = g
            if hasattr(tgt, "_aux"):
                # sparse gradient slot: XLA computed a dense cotangent
                # (the fused fwd+vjp is one dense program by design);
                # invalidate the compressed metadata so sparse-aware
                # consumers (lazy optimizer updates, row_sparse_pull)
                # lazily recover the true stored rows from the value
                tgt._aux = None

    @property
    def outputs(self):
        return self._outputs if self._outputs is not None else []

    @property
    def output_shapes(self):
        """Inferred output shapes, available before any forward (the
        reference computes these at SimpleBind: graph_executor.cc:512)."""
        if self._out_shapes is None:
            shape_kwargs = {n: tuple(a.shape)
                            for n, a in self.arg_dict.items()}
            _, outs, _ = self._symbol.infer_shape(**shape_kwargs)
            self._out_shapes = [tuple(s) for s in outs]
        return self._out_shapes

    @property
    def output_dict(self):
        return dict(zip(self._symbol.list_outputs(), self.outputs))

    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        for k, v in arg_params.items():
            if k in self.arg_dict:
                dst = self.arg_dict[k]
                if v._data.dtype != dst._data.dtype:
                    v = _wrap(v._data.astype(dst._data.dtype), dst._ctx)
                dst._assign_value(v)
            elif not allow_extra_params:
                raise ValueError("unknown argument %r" % k)
        if aux_params:
            for k, v in aux_params.items():
                if k in self.aux_dict:
                    self.aux_dict[k]._data = v._data
                elif not allow_extra_params:
                    raise ValueError("unknown aux state %r" % k)

    def reshape(self, partial_shaping=False, allow_up_sizing=False,
                shared_args=None, **kwargs):
        """Re-bind with new shapes (cheap: jit re-specialises per shape).

        ``shared_args``: names whose NDArray objects may be shared with
        this executor when the shape is unchanged (None = all, matching
        the reference's memory-sharing reshape). Names outside the set
        get value-preserving copies so in-place writes on one executor
        cannot leak into the other."""
        new_shapes = dict(kwargs)
        arg_shapes, out_shapes, aux_shapes = \
            self._symbol.infer_shape(**new_shapes)
        share_ok = ((lambda n: True) if shared_args is None
                    else set(shared_args).__contains__)
        arg_dict = {}
        for n, s in zip(self._arg_names, arg_shapes):
            old = self.arg_dict[n]
            if tuple(old.shape) == tuple(s):
                arg_dict[n] = old if share_ok(n) else old.copy()
            else:
                arg_dict[n] = nd.zeros(s, ctx=self._ctx, dtype=old.dtype)
        grad_dict = {n: nd.zeros_like(arg_dict[n]) for n in self.grad_dict}
        aux_dict = {}
        for n, s in zip(self._aux_names, aux_shapes):
            old = self.aux_dict[n]
            if tuple(old.shape) == tuple(s):
                aux_dict[n] = old if share_ok(n) else old.copy()
            else:
                aux_dict[n] = nd.zeros(s, ctx=self._ctx)
        new_exe = Executor(self._symbol, self._ctx, arg_dict, grad_dict,
                           self._grad_req, aux_dict)
        new_exe._out_shapes = [tuple(s) for s in out_shapes]
        return new_exe

    # -- fused train step --------------------------------------------------
    @staticmethod
    def _amp_cast(compute_dtype, cast_exclude):
        """The cast-in half of the mixed-precision policy (ISSUE 12,
        ``MXTPU_AMP=bf16``): floating parameters and inputs compute in
        ``compute_dtype``, names in ``cast_exclude`` (labels — their
        values are class indices a bf16 mantissa would corrupt) and
        non-floating inputs pass through untouched. Aux states (BN
        running statistics) are NEVER routed through this cast — they
        stay fp32 in the donated store. The cast sits INSIDE the
        differentiated function, so gradients come back in the master
        dtype (fp32) through the cast VJP."""
        exclude = frozenset(cast_exclude or ())

        def _amp(name, v):
            if compute_dtype is None or name in exclude \
                    or not jnp.issubdtype(v.dtype, jnp.floating):
                return v
            return v.astype(compute_dtype)

        return _amp

    @staticmethod
    def _amp_verdict(grads, loss_scale):
        """Unscale loss-scaled gradients and compute the TrainGuard-style
        finite verdict (fp32 global grad square-sum — NaN/Inf anywhere,
        or a finite-but-exploded norm that overflows the square, flips
        ``ok`` to False). Returns ``(grads_fp32_unscaled, ok)``."""
        inv = jnp.float32(1.0 / loss_scale)
        grads = tuple(g.astype(jnp.float32) * inv
                      if jnp.issubdtype(g.dtype, jnp.floating) else g
                      for g in grads)
        gsq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                  for g in grads)
        return grads, jnp.isfinite(gsq)

    @staticmethod
    def _amp_select(ok, new, old):
        """Overflow skip: hold every piece of persistent state at its
        pre-step value when the verdict is False (nested tuples with
        None leaves — optimizer state trees — supported)."""
        if new is None:
            return None
        if isinstance(new, (tuple, list)):
            return tuple(Executor._amp_select(ok, n, o)
                         for n, o in zip(new, old))
        return jnp.where(ok, new, old)

    def _mesh_plan(self, mesh, rules, train_names, state_trees=None):
        """NamedSharding placement plan for a mesh-compiled fused step
        (ISSUE 20): parameters and aux states place through
        ``rules.sharding_for`` (first match wins, unmatched names
        replicate, non-dividing mesh axes drop per dim); optimizer-state
        leaves inherit their parameter's sharding when param-shaped
        (momenta, adam variance — the ZeRO memory win) and replicate
        otherwise (scalar step counts). Returns ``(param_sh, state_sh,
        aux_sh, repl)``; ``state_sh`` is None when no state trees were
        given."""
        if rules is None:
            from .parallel.mesh import ShardingRules
            rules = ShardingRules([])
        repl = mesh.replicated()
        param_sh = tuple(
            rules.sharding_for(mesh, n, tuple(self.arg_dict[n].shape))
            for n in train_names)
        aux_sh = tuple(
            rules.sharding_for(mesh, n, tuple(self.aux_dict[n].shape))
            for n in self._aux_names)
        state_sh = None
        if state_trees is not None:
            state_sh = tuple(
                jax.tree_util.tree_map(
                    lambda leaf, _p=psh, _w=tuple(
                        self.arg_dict[n].shape):
                        _p if tuple(getattr(leaf, "shape", ())) == _w
                        else repl,
                    st)
                for n, psh, st in zip(train_names, param_sh,
                                      state_trees))
        return param_sh, state_sh, aux_sh, repl

    def _mesh_other_shardings(self, mesh, rules, other_names,
                              batch_names):
        """Placement for the non-donated inputs of a mesh program:
        batch tensors (data/labels) shard dim 0 over the ``data`` axis
        when it exists and divides — the ``_split_input_slice``
        equivalent done by GSPMD instead of host-side np splits — and
        fixed (non-trained) parameters follow the rules like any other
        parameter. Everything the mesh program touches must live on the
        mesh's full device set: replication is the fallback, never a
        single-device placement."""
        from .parallel.mesh import AXIS_DATA
        repl = mesh.replicated()
        batch_set = set(batch_names or ())
        out = []
        for n in other_names:
            shape = tuple(self.arg_dict[n].shape)
            if n in batch_set:
                dsize = mesh.axis_size(AXIS_DATA)
                split = bool(shape) and dsize > 1 and shape[0] % dsize == 0
                if not split and mesh.num_devices > 1:
                    logging.warning(
                        "mesh %s: batch input %r %s is replicated, so all "
                        "%d devices compute the whole batch (needs a "
                        "'%s' axis whose size divides dim 0)",
                        mesh.shape, n, shape, mesh.num_devices, AXIS_DATA)
                out.append(mesh.batch_sharding() if split else repl)
            elif rules is not None:
                out.append(rules.sharding_for(mesh, n, shape))
            else:
                out.append(repl)
        return tuple(out)

    def make_fused_train_step(self, train_names, optimizer, opt_slots,
                              metric_fn=None, donate=True,
                              compute_dtype=None, loss_scale=None,
                              cast_exclude=(), auto_layout=False,
                              mesh=None, rules=None, state_trees=None,
                              batch_names=()):
        """Build ONE donated jitted XLA program running the whole train
        step: forward + backward (ones cotangents, loss-head pattern) +
        the ENTIRE optimizer update as a multi-tensor apply (every
        parameter through :func:`optimizer.functional_optimizer_step`,
        reusing the ``ops/optim_ops.py`` kernels) and, optionally, the
        metric's device-side (sum, count) accumulation.

        ``train_names`` are the arguments updated by the optimizer, in
        slot order; ``opt_slots`` the matching updater indices (so lr/wd
        multipliers and saved optimizer states line up with the eager
        per-param path). Every other argument (data, labels, fixed
        params) rides as a non-donated input in ``other_names`` order =
        ``[n for n in list_arguments() if n not in train_names]``.

        Mixed precision (``MXTPU_AMP=bf16``): ``compute_dtype`` casts
        floating params and inputs (minus ``cast_exclude`` — label
        names) to the compute dtype INSIDE the program, so activations
        and the backward run reduced-precision while the donated store
        keeps fp32 master weights, fp32 optimizer state and fp32 aux
        (BN statistics); gradients return fp32 through the cast VJP and
        :func:`optimizer.functional_optimizer_step` applies in fp32 —
        cast-in/cast-out in the SAME program, zero extra host syncs or
        retraces. ``loss_scale`` additionally scales the head cotangent
        by S, unscales the fp32 gradients by 1/S, and reuses the
        TrainGuard isfinite verdict to SKIP the update in-program on
        overflow (params/state/aux/step-count all held at their
        pre-step values — a skipped step is indistinguishable from one
        that never ran).

        Donation semantics: params (0), optimizer state trees (1), aux
        states (2), rng key (4), step count (5) and the metric
        accumulator (7) are donated — XLA updates the buffers in place,
        and the CALLER'S input arrays are invalidated by the call. The
        Module fused driver rebinds each NDArray's ``_data`` to the
        returned value after every step. Batches (3) and lr (6) are
        deliberately NOT donated: batches may be re-fed (pre-staged
        loops) and lr is a carried constant.

        ``auto_layout`` compiles with XLA-chosen (AUTO) layouts for the
        persistent state (in AND out, so donation carries the chosen
        layouts across steps) and returns an
        :class:`~mxtpu.layout.AutoLayoutStep` that relayouts the donated
        store exactly once at compile, not per call.

        ``mesh`` + ``rules`` (ISSUE 20) compile the SAME program as an
        SPMD mesh program: the donated store is placed with explicit
        ``in_shardings``/``out_shardings`` from
        :meth:`_mesh_plan` (params/aux by rule, optimizer-state leaves
        inheriting their parameter's sharding, scalars replicated) and
        a :class:`~mxtpu.layout.MeshStep` scatters the seed store
        across the mesh on first call — per-device param+opt memory
        ~1/N, zero per-step resharding because out matches in.
        ``state_trees`` supplies the optimizer-state tree structure for
        per-leaf placement; ``batch_names`` are the data/label inputs
        eligible for dim-0 ``data``-axis sharding. Mesh placement wins
        over ``auto_layout`` (AUTO markers don't compose with explicit
        NamedShardings).

        Returns ``(fn, other_names)`` where ``fn(train_vals, state_trees,
        aux_vals, other_vals, key, t, lr, metric_acc) -> (new_vals,
        new_states, new_aux, outs, key', t+1, metric_acc')``.
        """
        from .optimizer import functional_optimizer_step
        outputs_ref = self._symbol._outputs
        arg_names = tuple(self._arg_names)
        aux_names = tuple(self._aux_names)
        train_names = tuple(train_names)
        train_set = set(train_names)
        other_names = tuple(n for n in arg_names if n not in train_set)
        opt_slots = tuple(opt_slots)
        mirror = self._mirror
        amp = self._amp_cast(compute_dtype, cast_exclude)
        scale = float(loss_scale) if loss_scale else None

        def _forward(gvals, other_vals, aux_vals, key):
            local = {n: amp(n, v) for n, v in zip(other_names,
                                                  other_vals)}
            local.update(zip(aux_names, aux_vals))
            local.update((n, amp(n, v)) for n, v in zip(train_names,
                                                        gvals))
            with rng_scope(key):
                outs, aux_updates = eval_graph(outputs_ref, local, True)
            new_aux = tuple(aux_updates.get(n, local[n]) for n in aux_names)
            return tuple(outs), new_aux

        def _head_cot(o):
            if jnp.issubdtype(o.dtype, jnp.inexact):
                ones = jnp.ones_like(o)
                return ones * jnp.asarray(scale, o.dtype) if scale \
                    else ones
            return _np.zeros(o.shape, jax.dtypes.float0)

        donate_argnums = (0, 1, 2, 4, 5, 7) if donate else ()

        def fused(train_vals, state_trees, aux_vals, other_vals, key, t,
                  lr, metric_acc):
            key, sub = _split2(key)
            t = t + 1

            def f(gvals):
                return _forward(gvals, other_vals, aux_vals, sub)

            with jax.named_scope("fwd_bwd"):
                (outs, new_aux), vjp_fn = jax.vjp(
                    maybe_remat(f, enabled=mirror), tuple(train_vals))
                cot = tuple(_head_cot(o) for o in outs)
                zero_aux = tuple(_zeros_cot(a) for a in new_aux)
                grads = vjp_fn((cot, zero_aux))[0]
            ok = None
            if scale:
                with jax.named_scope("amp_guard"):
                    grads, ok = self._amp_verdict(grads, scale)
            new_vals, new_states = [], []
            with jax.named_scope("optimizer"):
                for slot, w, g, st in zip(opt_slots, train_vals, grads,
                                          state_trees):
                    w2, st2 = functional_optimizer_step(
                        optimizer, slot, w, g, st, t, lr)
                    new_vals.append(w2)
                    new_states.append(st2)
            if ok is not None:
                with jax.named_scope("amp_select"):
                    new_vals = [jnp.where(ok, nv, ov)
                                for nv, ov in zip(new_vals, train_vals)]
                    new_states = [self._amp_select(ok, ns, os_)
                                  for ns, os_ in zip(new_states,
                                                     state_trees)]
                    new_aux = tuple(jnp.where(ok, na, oa)
                                    for na, oa in zip(new_aux, aux_vals))
                    t = jnp.where(ok, t, t - 1)
            if metric_fn is not None:
                with jax.named_scope("metric"):
                    m_sum, m_cnt = metric_fn(dict(zip(other_names,
                                                      other_vals)), outs)
                    contrib = jnp.stack([m_sum, m_cnt]).astype(
                        metric_acc.dtype)
                    if ok is not None:
                        # a skipped step contributes nothing — one NaN
                        # batch must not poison the epoch accumulator
                        contrib = jnp.where(ok, contrib,
                                            jnp.zeros_like(contrib))
                    metric_acc = metric_acc + contrib
            return (tuple(new_vals), tuple(new_states), tuple(new_aux),
                    outs, key, t, metric_acc)

        if mesh is not None:
            param_sh, state_sh, aux_sh, repl = self._mesh_plan(
                mesh, rules, train_names, state_trees)
            other_sh = self._mesh_other_shardings(
                mesh, rules, other_names, batch_names)
            jitted = jax.jit(
                fused,
                in_shardings=(param_sh, state_sh, aux_sh, other_sh,
                              repl, repl, repl, repl),
                out_shardings=(param_sh, state_sh, aux_sh, None,
                               repl, repl, repl),
                donate_argnums=donate_argnums)
            sh_map = {0: param_sh, 2: aux_sh, 3: other_sh,
                      4: repl, 5: repl, 6: repl, 7: repl}
            if state_sh is not None:
                sh_map[1] = state_sh
            return MeshStep(jitted, mesh, sh_map), other_names
        if auto_layout:
            auto = auto_format()
            jitted = jax.jit(
                fused,
                in_shardings=tuple(auto if i in (0, 1, 2) else None
                                   for i in range(8)),
                out_shardings=tuple(auto if i in (0, 1, 2) else None
                                    for i in range(7)),
                donate_argnums=donate_argnums)
            return AutoLayoutStep(jitted, state_argnums=(0, 1, 2)), \
                other_names
        return jax.jit(fused, donate_argnums=donate_argnums), other_names

    def make_fused_grad_step(self, train_names, metric_fn=None,
                             donate=True, compute_dtype=None,
                             loss_scale=None, cast_exclude=(),
                             wire_dtype=None, auto_layout=False,
                             sparse_emits=None, mesh=None, rules=None,
                             batch_names=()):
        """Grad-EMITTING mode of the fused train step — the
        kvstore/dist path (ISSUE 10). ONE jitted program runs forward +
        backward (ones cotangents, loss-head pattern) + the optional
        device-side metric accumulation and RETURNS the gradients
        instead of applying an optimizer: the update happens where the
        kvstore says it does — server-side (``update_on_kvstore``) or
        locally through :meth:`make_fused_apply_step` after the pull.

        Sparse embeddings (ISSUE 13): ``sparse_emits`` maps a
        row-sparse parameter name to the tuple of DIRECT-input names
        feeding its Embedding lookups. For those parameters the SAME
        program dedupes the step's indices on device (sort +
        segment-position scatter — the static-shape unique) and
        gathers the touched rows out of the dense VJP gradient, so the
        emitted entry is a ``(row_ids, rows)`` pair instead of the
        full-table gradient: ``row_ids`` is ``(nnz_max,)`` int32
        sorted ascending with the table row count as the padding
        sentinel (``nnz_max`` = total indices fed, a static bound),
        ``rows`` is ``(nnz_max, *row_shape)`` with zero padding — the
        sparse-pushpull wire payload, still ONE XLA program end to
        end. ``wire_dtype`` applies to the gathered rows exactly like
        dense gradients.

        Mixed precision (ISSUE 12): ``compute_dtype`` applies the same
        cast-in policy as :meth:`make_fused_train_step` (bf16 params +
        activations, fp32 aux, fp32 gradients at the cast boundary);
        ``wire_dtype`` casts the EMITTED gradients — the push payload —
        down in the same program, so the kvstore wire carries half-width
        bytes with no extra dispatch (the server's fp32 master table
        upcasts on apply, ``kvstore_async._wire_decode``). With
        ``loss_scale``, an overflow step emits ZERO gradients instead of
        scaled garbage (the server applies a no-op update — the dist
        rendering of the skip, with no extra host sync) and holds the
        aux states at their pre-step values.

        Donation semantics: the parameters are NOT donated — this
        program only reads them, and the kvstore pull rebinds them
        afterwards. Aux states (1), the rng key (3) and the metric
        accumulator (4) are donated; the caller rebinds their wrappers
        every step exactly like the train-step contract.

        ``mesh`` + ``rules`` (ISSUE 20) compile the grad emitter as an
        SPMD mesh program like :meth:`make_fused_train_step`: params
        and aux place by rule, emitted gradients keep unspecified out
        shardings (the pull gathers them host-side either way), and
        the returned :class:`~mxtpu.layout.MeshStep` re-scatters the
        freshly-pulled params each step — inherent to the dist cycle,
        not a retrace. Mesh wins over ``auto_layout``.

        Returns ``(fn, other_names)`` where ``fn(train_vals, aux_vals,
        other_vals, key, metric_acc) -> (grads, new_aux, outs, key',
        metric_acc')``.
        """
        outputs_ref = self._symbol._outputs
        arg_names = tuple(self._arg_names)
        aux_names = tuple(self._aux_names)
        train_names = tuple(train_names)
        train_set = set(train_names)
        other_names = tuple(n for n in arg_names if n not in train_set)
        mirror = self._mirror
        amp = self._amp_cast(compute_dtype, cast_exclude)
        scale = float(loss_scale) if loss_scale else None
        # sparse-emit plan: feed-name -> other_vals position, resolved
        # once at build (eligibility already proved the feeds are
        # direct inputs)
        sparse_pos = {
            name: tuple(other_names.index(f) for f in feeds)
            for name, feeds in (sparse_emits or {}).items()}

        def _forward(gvals, other_vals, aux_vals, key):
            local = {n: amp(n, v) for n, v in zip(other_names,
                                                  other_vals)}
            local.update(zip(aux_names, aux_vals))
            local.update((n, amp(n, v)) for n, v in zip(train_names,
                                                        gvals))
            with rng_scope(key):
                outs, aux_updates = eval_graph(outputs_ref, local, True)
            new_aux = tuple(aux_updates.get(n, local[n]) for n in aux_names)
            return tuple(outs), new_aux

        def _head_cot(o):
            if jnp.issubdtype(o.dtype, jnp.inexact):
                ones = jnp.ones_like(o)
                return ones * jnp.asarray(scale, o.dtype) if scale \
                    else ones
            return _np.zeros(o.shape, jax.dtypes.float0)

        def _wire(g):
            if wire_dtype is not None and \
                    jnp.issubdtype(g.dtype, jnp.floating):
                return g.astype(wire_dtype)
            return g

        def _sparse_emit(name, g, other_vals):
            """(row_ids, rows) out of the dense VJP gradient: static-
            shape unique over the step's fed indices (sort, then
            scatter each run's first element to its segment slot —
            padding tail holds the num_rows sentinel), then one gather
            of the touched rows. Duplicate indices were already
            summed by the VJP's scatter-add, so gather IS the
            segment-sum dedupe."""
            num_rows = g.shape[0]
            ids = jnp.concatenate([
                jnp.reshape(other_vals[p], (-1,)).astype(jnp.int32)
                for p in sparse_pos[name]])
            sids = jnp.sort(ids)
            first = jnp.concatenate([jnp.ones((1,), bool),
                                     sids[1:] != sids[:-1]])
            seg = jnp.cumsum(first) - 1
            uniq = jnp.full(ids.shape, num_rows,
                            jnp.int32).at[seg].set(sids)
            valid = uniq < num_rows
            safe = jnp.where(valid, uniq, 0)
            rows = g[safe] * valid.reshape(
                (-1,) + (1,) * (g.ndim - 1)).astype(g.dtype)
            return uniq, _wire(rows)

        donate_argnums = (1, 3, 4) if donate else ()

        def fused_grads(train_vals, aux_vals, other_vals, key, metric_acc):
            key, sub = _split2(key)

            def f(gvals):
                return _forward(gvals, other_vals, aux_vals, sub)

            with jax.named_scope("fwd_bwd"):
                (outs, new_aux), vjp_fn = jax.vjp(
                    maybe_remat(f, enabled=mirror), tuple(train_vals))
                cot = tuple(_head_cot(o) for o in outs)
                zero_aux = tuple(_zeros_cot(a) for a in new_aux)
                grads = vjp_fn((cot, zero_aux))[0]
            ok = None
            if scale:
                with jax.named_scope("amp_guard"):
                    grads, ok = self._amp_verdict(grads, scale)
                    grads = tuple(jnp.where(ok, g, jnp.zeros_like(g))
                                  for g in grads)
                    new_aux = tuple(jnp.where(ok, na, oa)
                                    for na, oa in zip(new_aux, aux_vals))
            if sparse_pos:
                with jax.named_scope("sparse_emit"):
                    grads = tuple(
                        _sparse_emit(n, g, other_vals)
                        if n in sparse_pos else _wire(g)
                        for n, g in zip(train_names, grads))
            elif wire_dtype is not None:
                grads = tuple(_wire(g) for g in grads)
            if metric_fn is not None:
                with jax.named_scope("metric"):
                    m_sum, m_cnt = metric_fn(dict(zip(other_names,
                                                      other_vals)), outs)
                    contrib = jnp.stack([m_sum, m_cnt]).astype(
                        metric_acc.dtype)
                    if ok is not None:
                        contrib = jnp.where(ok, contrib,
                                            jnp.zeros_like(contrib))
                    metric_acc = metric_acc + contrib
            return grads, tuple(new_aux), outs, key, metric_acc

        if mesh is not None:
            param_sh, _unused, aux_sh, repl = self._mesh_plan(
                mesh, rules, train_names)
            other_sh = self._mesh_other_shardings(
                mesh, rules, other_names, batch_names)
            jitted = jax.jit(
                fused_grads,
                in_shardings=(param_sh, aux_sh, other_sh, repl, repl),
                out_shardings=(None, aux_sh, None, repl, repl),
                donate_argnums=donate_argnums)
            return MeshStep(jitted, mesh, {
                0: param_sh, 1: aux_sh, 2: other_sh,
                3: repl, 4: repl}), other_names
        if auto_layout:
            # AUTO only where donation carries the layout across steps
            # (the aux store); params arrive via the kvstore pull's
            # device_put each step, so AUTO there would relayout per
            # call instead of once
            auto = auto_format()
            jitted = jax.jit(
                fused_grads,
                in_shardings=tuple(auto if i == 1 else None
                                   for i in range(5)),
                out_shardings=tuple(auto if i == 1 else None
                                    for i in range(5)),
                donate_argnums=donate_argnums)
            return AutoLayoutStep(jitted, state_argnums=(1,)), other_names
        return jax.jit(fused_grads, donate_argnums=donate_argnums), \
            other_names

    def make_fused_apply_step(self, train_names, optimizer, opt_slots,
                              donate=True, auto_layout=False,
                              mesh=None, rules=None, state_trees=None):
        """The optimizer half of the fused step on its own — the
        locally-applied update of the kvstore dist path (ISSUE 10,
        ``update_on_kvstore=False``): after the pull returns the merged
        gradients, ONE jitted multi-tensor apply runs every parameter
        through :func:`optimizer.functional_optimizer_step`, with the
        parameters (0), optimizer state trees (1) and step count (3)
        donated so XLA updates the buffers in place. Gradients (2) and
        lr (4) are not donated (grads arrive as freshly-pulled host
        values; lr is a carried constant). Half-precision gradients (a
        bf16 wire pull, ISSUE 12) upcast to the master-weight dtype
        inside ``functional_optimizer_step`` — the apply always runs
        fp32.

        ``mesh`` + ``rules`` (ISSUE 20): params/state place by rule
        like :meth:`make_fused_train_step`; the pulled gradients are
        param-shaped, so they re-scatter into the params' shardings
        each apply (the dist_local rendering of the input pipeline).
        Mesh wins over ``auto_layout``.

        Returns ``fn(train_vals, state_trees, grad_vals, t, lr) ->
        (new_vals, new_states, t+1)``.
        """
        from .optimizer import functional_optimizer_step
        opt_slots = tuple(opt_slots)

        donate_argnums = (0, 1, 3) if donate else ()

        def fused_apply(train_vals, state_trees, grad_vals, t, lr):
            t = t + 1
            new_vals, new_states = [], []
            with jax.named_scope("optimizer"):
                for slot, w, g, st in zip(opt_slots, train_vals,
                                          grad_vals, state_trees):
                    w2, st2 = functional_optimizer_step(
                        optimizer, slot, w, g, st, t, lr)
                    new_vals.append(w2)
                    new_states.append(st2)
            return tuple(new_vals), tuple(new_states), t

        if mesh is not None:
            param_sh, state_sh, _unused, repl = self._mesh_plan(
                mesh, rules, train_names, state_trees)
            jitted = jax.jit(
                fused_apply,
                in_shardings=(param_sh, state_sh, param_sh, repl, repl),
                out_shardings=(param_sh, state_sh, repl),
                donate_argnums=donate_argnums)
            sh_map = {0: param_sh, 2: param_sh, 3: repl, 4: repl}
            if state_sh is not None:
                sh_map[1] = state_sh
            return MeshStep(jitted, mesh, sh_map)
        if auto_layout:
            auto = auto_format()
            jitted = jax.jit(
                fused_apply,
                in_shardings=tuple(auto if i in (0, 1) else None
                                   for i in range(5)),
                out_shardings=tuple(auto if i in (0, 1) else None
                                    for i in range(3)),
                donate_argnums=donate_argnums)
            return AutoLayoutStep(jitted, state_argnums=(0, 1))
        return jax.jit(fused_apply, donate_argnums=donate_argnums)

    def adopt_arrays(self, arg_src, aux_src):
        """Alias this executor's argument/aux slots to the given NDArray
        OBJECTS (same shape+dtype) so a group of executors — the buckets
        of a fused BucketingModule — share ONE device-side parameter
        store: whichever bucket steps rebinds the shared arrays' _data,
        and a bucket switch needs no host round-trip at all."""
        for name, src in arg_src.items():
            dst = self.arg_dict.get(name)
            if dst is not None and dst is not src \
                    and dst.shape == src.shape and dst.dtype == src.dtype:
                self.arg_dict[name] = src
        for name, src in aux_src.items():
            dst = self.aux_dict.get(name)
            if dst is not None and dst is not src \
                    and dst.shape == src.shape and dst.dtype == src.dtype:
                self.aux_dict[name] = src
        self.arg_arrays = [self.arg_dict[n] for n in self._arg_names]
        self.grad_arrays = [self.grad_dict.get(n) for n in self._arg_names]
        self.aux_arrays = [self.aux_dict[n] for n in self._aux_names]

    def set_monitor_callback(self, callback):
        self._monitor_callback = callback

    def debug_str(self):
        return self._symbol.tojson()


def _normalize_grad_req(grad_req, arg_names):
    if isinstance(grad_req, str):
        return {n: grad_req for n in arg_names}
    if isinstance(grad_req, (list, tuple)):
        return dict(zip(arg_names, grad_req))
    out = {n: "null" for n in arg_names}
    out.update(grad_req)
    return out
