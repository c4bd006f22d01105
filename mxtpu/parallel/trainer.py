"""ShardedTrainer: the SPMD training step.

This is the TPU-native rendering of the reference's whole data-parallel
training machinery — ``DataParallelExecutorGroup`` batch slicing
(``python/mxnet/module/executor_group.py:289,422,554``), KVStore gradient
reduce/broadcast (``src/kvstore/comm.h``, ``kvstore_nccl.h``), and the
optimizer ``Updater`` loop (``python/mxnet/optimizer.py`` +
``src/operator/optimizer_op.*``) — collapsed into ONE jitted XLA program
laid out over a named device mesh:

* the batch arrives sharded over the ``data`` axis (no host-side split);
* forward+backward run as a single fused computation; GSPMD inserts the
  psum/reduce-scatter over ICI that CommDevice/NCCL did by hand — and
  because gradients are produced layer-by-layer inside one program, XLA
  overlaps the collectives with remaining backward compute, which is
  exactly the engine-priority overlap trick of ``comm.h``
  (FnProperty::kCPUPrioritized) done by the compiler;
* the optimizer update runs sharded in the same program (the
  "update_on_kvstore" capability: the update happens where the data lives);
* tensor/model parallelism is expressed by parameter ShardingRules
  (mesh.py) — the superset of the reference's group2ctx placement.

Any mxtpu Optimizer works unmodified inside the jitted step: a functional
adapter feeds it traced (t, lr) scalars so Adam bias-correction and LR
schedules stay dynamic across steps without retracing.
"""
from __future__ import annotations

import copy
import os

import numpy as _np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from .. import autograd as _ag
from .. import base as _base
from .. import ndarray as nd
from ..dist_hooks import AsyncPushWindow, kvstore_grad_pusher
from ..layout import AutoLayoutStep, auto_format, auto_layout_enabled
from ..ndarray import NDArray
from .. import optimizer as opt_mod
# the functional (jit-traceable) optimizer adapter lives next to the
# optimizers themselves since the Module fused step shares it
from ..optimizer import (functional_optimizer_step, state_to_tree,
                         tree_to_state)
from ..ops.registry import rng_scope, split2 as _rng_split2
from ..gluon.block import _swap_params, _trace_scope
from ..gluon.loss import Loss
from .mesh import MeshContext, ShardingRules, AXIS_DATA

__all__ = ["ShardedTrainer", "functional_optimizer_step", "state_to_tree",
           "tree_to_state", "device_prefetch"]


# ---------------------------------------------------------------------------
# ShardedTrainer
# ---------------------------------------------------------------------------

def _as_jax(x):
    if isinstance(x, NDArray):
        return x._data
    return jnp.asarray(x)


# the AUTO-layout step wrapper moved to mxtpu/layout.py (ISSUE 12) so
# the fused Module path shares the one implementation; the old private
# name keeps working for existing callers/tests
_AutoLayoutStep = AutoLayoutStep


class ShardedTrainer:
    """Train a Gluon block SPMD over a device mesh.

    Parameters
    ----------
    block : HybridBlock
        The model. Parameters must be initialized (or initializable from
        the first batch's shapes).
    loss : gluon Loss block or callable(pred, label) -> NDArray
    optimizer : str or mxtpu Optimizer
    mesh : MeshContext, optional (defaults to all devices on the data axis)
    rules : ShardingRules, optional — tensor-parallel parameter layouts;
        unmatched parameters are replicated (pure DP).
    zero1 : bool — ZeRO-stage-1 optimizer-state sharding: for pure-DP
        (replicated) parameters whose leading dim divides the data axis,
        optimizer state lives dim-0-sharded across the data axis and the
        update computes on shards; declared via sharding constraints, so
        XLA's SPMD partitioner materializes the reduce_scatter (grads) /
        all_gather (updated weights) pair — no hand-written collectives.
        State memory for those params drops by the data-axis size.

    Example
    -------
    >>> mesh = MeshContext(data=4, model=2)
    >>> st = ShardedTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(),
    ...                     'sgd', {'learning_rate': 0.1}, mesh=mesh,
    ...                     rules=ShardingRules([...]))
    >>> loss = st.step(data, label)
    """

    def __init__(self, block, loss, optimizer, optimizer_params=None,
                 mesh=None, rules=None, donate=True, dtype=None,
                 remat=None, remat_policy=None, zero1=False,
                 auto_layout=None):
        if dtype not in (None, "float32", "bfloat16"):
            # float16 would need loss scaling (reference mp_sgd pairs fp16
            # weights with fp32 master copies + scale); bf16 shares f32's
            # exponent range so no scaling is required on TPU
            raise ValueError("dtype must be None/'float32'/'bfloat16'")
        self._compute_dtype = (jnp.bfloat16 if dtype == "bfloat16"
                               else None)
        self._block = block
        self._loss = loss
        if isinstance(optimizer, opt_mod.Optimizer):
            self._optimizer = optimizer
            if optimizer_params:
                raise ValueError("optimizer_params must be empty when "
                                 "optimizer is an Optimizer instance")
        else:
            self._optimizer = opt_mod.create(optimizer,
                                             **(optimizer_params or {}))
        self._mesh = mesh if mesh is not None else MeshContext()
        self._rules = rules or ShardingRules()
        self._donate = donate
        # rematerialization (the MXNET_BACKWARD_DO_MIRROR capability):
        # checkpoint the loss computation so backward recomputes
        # activations — the standard HBM lever for deep nets / long
        # sequences. remat=None defers to the env knob.
        if remat is None:
            # an explicit policy implies remat; else defer to the env knob
            remat = True if remat_policy is not None \
                else _base.backward_mirror_enabled()
        elif not remat and remat_policy is not None:
            raise ValueError("remat_policy given but remat=False")
        self._remat = bool(remat)
        self._remat_policy = remat_policy
        self._zero1 = bool(zero1)
        # XLA-chosen persistent-state layouts (experimental): compile the
        # train step with AUTO input/output layouts for params/optimizer
        # state/aux so conv weights live in the layout the convolutions
        # want instead of being relaid out every step. Opt-in while the
        # win is unmeasured; numerics are layout-invariant either way.
        self._auto_layout = auto_layout_enabled(auto_layout)
        self._step_fns = {}
        self._placed = False
        self._key = jax.random.PRNGKey(_np.random.randint(0, 2 ** 31 - 1))
        self._num_update = 0
        # guard mode (mxtpu.resilience.TrainGuard): the jitted step also
        # computes isfinite(loss) & isfinite(global grad norm) and
        # SELECTS the old params/opt-state/aux when the step is bad —
        # a NaN gradient can never reach the persistent state. The
        # (loss, ok, grad_norm) triple rides out as ONE packed device
        # vector so the guard's host read costs the single transfer the
        # unguarded step() already pays for the loss.
        self._guard = False
        self._last_metrics = None
        self._deferred_grads = None
        self._guard_lr_scale = 1.0
        # elastic-resume plumbing: state restored before first placement
        # is stashed and applied by _place
        self._pending_opt_state = None
        self._pending_key_dev = None
        # async gradient-push hook (set_grad_push/attach_kvstore): when
        # set, every jitted step also returns its gradients and the hook
        # ships them off-thread — the NEXT step's compute overlaps the
        # previous step's KVStore push. The bounded-inflight
        # backpressure window is the shared dist_hooks implementation
        # (the same one the fused Module dist step rides).
        self._grad_push = None
        self._push_window = AsyncPushWindow(2)
        # on-device step state, materialized at first step_async
        self._key_dev = None
        self._t_dev = None
        self._lr_dev = None
        self._lr_host = None
        # filled at first placement
        self._params = None
        self._train_idx = None
        self._aux_idx = None
        self._param_vals = None
        self._aux_vals = None
        self._opt_states = None
        self._shardings = None
        self._zero1_shardings = None

    # -- placement ---------------------------------------------------------
    def _place(self, args):
        """Finish init, shard every parameter and optimizer state onto the
        mesh per the ShardingRules, create sharded optimizer state."""
        block = self._block
        try:
            for p in block._ordered_params():
                p._finish_deferred_init()
        except Exception:
            block._deferred_infer_shape(*args)
        params = block._ordered_params()
        self._params = params
        self._train_idx = [i for i, p in enumerate(params)
                           if p.grad_req != "null"]
        self._aux_idx = [i for i, p in enumerate(params)
                         if p.grad_req == "null"]
        shardings = [self._rules.sharding_for(self._mesh, p.name, p.shape)
                     for p in params]
        self._shardings = shardings
        vals = [jax.device_put(p.data()._data, s)
                for p, s in zip(params, shardings)]
        self._param_vals = [vals[i] for i in self._train_idx]
        self._aux_vals = [vals[i] for i in self._aux_idx]
        # ZeRO-1: a pure-DP (replicated) param with a dim-0 divisible by
        # the data axis gets its optimizer state dim-0-sharded there
        self._zero1_shardings = []
        ndata = self._mesh.axis_size(AXIS_DATA)
        for i in self._train_idx:
            p = params[i]
            z_sh = None
            if self._zero1 and ndata > 1 and len(p.shape) >= 1 \
                    and p.shape[0] % ndata == 0 \
                    and shardings[i] == self._mesh.replicated():
                z_sh = self._mesh.sharding(
                    AXIS_DATA, *([None] * (len(p.shape) - 1)))
            self._zero1_shardings.append(z_sh)
        # sharded optimizer state: any state leaf with the param's shape
        # inherits the param's sharding — or its ZeRO-1 dim-0 shard
        # (momentum/variance live alongside the weight shard), scalars
        # replicate.
        self._opt_states = []
        for j, i in enumerate(self._train_idx):
            p = params[i]
            st = state_to_tree(
                self._optimizer.create_state_multi_precision(j, p.data()))
            sh = self._zero1_shardings[j] or shardings[i]

            def place_leaf(leaf, sh=sh, shape=p.shape):
                if leaf is None:
                    return None
                tgt = sh if tuple(leaf.shape) == tuple(shape) \
                    else self._mesh.replicated()
                return jax.device_put(leaf, tgt)

            self._opt_states.append(jax.tree_util.tree_map(
                place_leaf, st, is_leaf=lambda x: x is None))
        self._placed = True
        if self._pending_opt_state is not None:
            saved, self._pending_opt_state = self._pending_opt_state, None
            self._apply_opt_state(saved)

    # -- the jitted step ---------------------------------------------------
    def _build_step(self, shapes_key, n_inputs, with_update):
        block = self._block
        loss_blk = self._loss
        params = self._params
        train_idx = self._train_idx
        aux_idx = self._aux_idx
        optimizer = self._optimizer
        mesh = self._mesh

        cdt = self._compute_dtype

        def forward_loss(train_vals, aux_vals, inputs, label, key, training):
            # mixed precision (the reference's mp_sgd capability,
            # optimizer_op-inl.h multi-precision update): params/activations
            # compute in bf16 on the MXU, master weights + optimizer state
            # and BN statistics stay f32 — the cast sits inside the
            # differentiated function so grads come back f32 via the cast
            # VJP.
            full = [None] * len(params)
            for v, i in zip(train_vals, train_idx):
                full[i] = NDArray(v.astype(cdt) if cdt is not None and
                                  jnp.issubdtype(v.dtype, jnp.floating)
                                  else v)
            for v, i in zip(aux_vals, aux_idx):
                full[i] = NDArray(v)
            ins = [NDArray(v.astype(cdt) if cdt is not None and
                           jnp.issubdtype(v.dtype, jnp.floating) else v)
                   for v in inputs]
            with _ag.pause(train_mode=training), rng_scope(key), \
                    _trace_scope(), \
                    _swap_params(block, dict(zip(params, full))):
                out = block._run_hybrid(ins)
                outs = out if isinstance(out, (list, tuple)) else [out]
                if isinstance(loss_blk, Loss):
                    with _swap_params(
                            loss_blk,
                            dict(zip(loss_blk._ordered_params(),
                                     [NDArray(p.data()._data)
                                      for p in loss_blk._ordered_params()]))):
                        l = loss_blk(outs[0], NDArray(label))
                elif callable(loss_blk):
                    l = loss_blk(outs[0], NDArray(label))
                else:
                    raise TypeError("loss must be a Loss block or callable")
            loss_val = jnp.mean(l._data.astype(jnp.float32))
            aux_new = tuple(
                full[i]._data.astype(av.dtype)
                for i, av in zip(aux_idx, aux_vals))
            return loss_val, (aux_new, tuple(o._data for o in outs))

        loss_fn = _base.maybe_remat(
            forward_loss, enabled=self._remat, static_argnums=(5,),
            policy=self._remat_policy)

        # when a gradient-push hook is registered the step also returns
        # its (f32, pre-constraint) gradients so the hook can ship them;
        # baked in at build time — set_grad_push drops cached train fns
        want_grads = self._grad_push is not None
        # guard mode is likewise baked in: set_guard drops cached fns
        want_guard = self._guard

        def train_step(train_vals, states, aux_vals, inputs, label, key,
                       t, lr):
            # rng, step count and lr live on device and are carried through
            # donated buffers: a steady-state step makes ZERO host->device
            # transfers (critical when the host link is thin).
            key, sub = jax.random.split(key)
            t = t + 1
            # named_scope: profiles of this step attribute HLO time to
            # fwd_bwd vs optimizer phases (block-level names come from
            # Block.__call__'s own scopes nested inside)
            with jax.named_scope("fwd_bwd"):
                (loss_val, (aux_new, outs)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(
                        train_vals, aux_vals, inputs, label, sub, True)
            ok = None
            if want_guard:
                with jax.named_scope("guard_check"):
                    # global grad norm in f32: NaN/Inf anywhere — and a
                    # finite-but-exploded norm that overflows the square
                    # — flips ok to False. Fused into THIS program: the
                    # check costs a reduction, never a host round trip.
                    gsq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                              for g in grads)
                    ok = jnp.isfinite(loss_val) & jnp.isfinite(gsq)
            new_vals, new_states = [], []
            zero1_sh = self._zero1_shardings
            with jax.named_scope("optimizer"):
                for j, (w, g, st) in enumerate(zip(train_vals, grads,
                                                   states)):
                    z_sh = zero1_sh[j]
                    if z_sh is not None:
                        # ZeRO-1: pin grad/weight/state to the dim-0
                        # data shard so the update computes on 1/N of
                        # the param per device; the partitioner turns
                        # the replicated-grad dependency into a
                        # reduce_scatter and the new_vals constraint
                        # below into an all_gather
                        g = jax.lax.with_sharding_constraint(g, z_sh)
                        w = jax.lax.with_sharding_constraint(w, z_sh)
                    w2, st2 = functional_optimizer_step(
                        optimizer, j, w, g, st, t, lr)
                    if z_sh is not None:
                        st2 = jax.tree_util.tree_map(
                            lambda leaf, zs=z_sh, pw=w:
                            jax.lax.with_sharding_constraint(leaf, zs)
                            if leaf is not None
                            and tuple(leaf.shape) == tuple(pw.shape)
                            else leaf,
                            st2, is_leaf=lambda x: x is None)
                    new_vals.append(w2)
                    new_states.append(st2)
            if want_guard:
                with jax.named_scope("guard_select"):
                    # bad step: hold EVERY piece of persistent state —
                    # params, optimizer state, aux (BN stats), step
                    # count — at its pre-step value. A skipped step is
                    # indistinguishable from a step that never ran.
                    new_vals = [jnp.where(ok, nv, ov)
                                for nv, ov in zip(new_vals, train_vals)]
                    new_states = [jax.tree_util.tree_map(
                        lambda nl, ol: None if nl is None
                        else jnp.where(ok, nl, ol),
                        ns, os_, is_leaf=lambda x: x is None)
                        for ns, os_ in zip(new_states, states)]
                    aux_new = tuple(jnp.where(ok, na, oa)
                                    for na, oa in zip(aux_new, aux_vals))
                    t = jnp.where(ok, t, t - 1)
                    metrics = jnp.stack([
                        loss_val, ok.astype(jnp.float32), jnp.sqrt(gsq)])
            # pin layouts so donation round-trips buffers in place
            new_vals = [
                jax.lax.with_sharding_constraint(v, s)
                for v, s in zip(new_vals,
                                [self._shardings[i] for i in train_idx])]
            out = (tuple(new_vals), tuple(new_states), tuple(aux_new),
                   loss_val, outs, key, t)
            if want_grads:
                out += (tuple(grads),)
            if want_guard:
                out += (metrics,)
            return out

        def eval_step(train_vals, aux_vals, inputs, label, key):
            loss_val, (aux_new, outs) = forward_loss(
                train_vals, aux_vals, inputs, label, key, False)
            return loss_val, outs

        with mesh.mesh:
            if with_update:
                # donation audit: params(0), optimizer states(1), aux(2),
                # rng key(5) and step count(6) are donated — each is
                # replaced by a same-shaped output, so XLA updates the
                # buffers in place (zero extra HBM for the update).
                # inputs(3)/label(4) are deliberately NOT donated: callers
                # legitimately reuse pre-staged batches across steps
                # (a donated batch buffer would be invalidated after the
                # first step). lr(7) is a
                # carried constant, never replaced, so it must stay live.
                donate = (0, 1, 2, 5, 6) if self._donate else ()
                if self._auto_layout:
                    auto = auto_format()
                    # AUTO only on the persistent state (in AND out, so
                    # the chosen layouts agree with donation aliasing);
                    # batches/key/t/lr keep caller-visible defaults
                    outs_sh = (auto, auto, auto, None, None, None, None)
                    if want_grads:
                        outs_sh += (None,)
                    if want_guard:
                        outs_sh += (None,)
                    jitted = jax.jit(
                        train_step,
                        in_shardings=(auto, auto, auto, None, None,
                                      None, None, None),
                        out_shardings=outs_sh,
                        donate_argnums=donate)
                    return _AutoLayoutStep(jitted, mesh)
                return jax.jit(train_step, donate_argnums=donate)
            return jax.jit(eval_step)

    # -- public API --------------------------------------------------------
    def _shard_batch(self, arrs):
        out = []
        for a in arrs:
            v = _as_jax(a)
            sh = self._mesh.batch_sharding(v.ndim)
            if isinstance(v, jax.Array) and v.sharding == sh:
                out.append(v)  # already staged (prefetching loader path)
            else:
                out.append(jax.device_put(v, sh))
        return out

    def _device_step_state(self):
        """Lazily created on-device (key, t, lr) carried across steps."""
        if self._key_dev is None:
            rep = self._mesh.replicated()
            if self._pending_key_dev is not None:
                # elastic resume: carry on the exact device RNG stream
                # the checkpoint recorded — a respawned worker replays
                # the same draws an uninterrupted run would have made
                dev_key = self._pending_key_dev
                self._pending_key_dev = None
                self._key_dev = jax.device_put(_np.asarray(dev_key), rep)
            else:
                # branch the host chain: the device chain carries one
                # fork (and is donated every step), the host keeps
                # advancing the other for eval-time draws. np copy so
                # donation can't delete the host key's buffer
                # (device_put may alias when shardings match).
                self._key, dev_key = _rng_split2(self._key)
                self._key_dev = jax.device_put(_np.asarray(dev_key), rep)
            self._t_dev = jax.device_put(
                _np.asarray(self._num_update, _np.int32), rep)
            self._lr_host = self._host_lr()
            self._lr_dev = jax.device_put(
                _np.asarray(self._lr_host, _np.float32), rep)
        return self._key_dev, self._t_dev, self._lr_dev

    def step_async(self, data, label):
        """One fused forward/backward/update step. Returns the loss as a
        lazy NDArray (no host sync): dispatches pipeline back-to-back, so
        steady-state throughput is bounded by device compute, not host
        round-trips — the engine-async property of the reference
        (ThreadedEngine returns immediately; sync happens at WaitForVar)."""
        data_list = data if isinstance(data, (list, tuple)) else [data]
        if not self._placed:
            self._place([NDArray(_as_jax(d)) for d in data_list])
        inputs = self._shard_batch(data_list)
        label_j = self._shard_batch([label])[0]
        skey = ("train", tuple(tuple(i.shape) for i in inputs),
                tuple(label_j.shape))
        if skey not in self._step_fns:
            self._step_fns[skey] = self._build_step(skey, len(inputs), True)
        key, t, lr = self._device_step_state()
        self._num_update += 1
        new_lr = self._host_lr()
        if new_lr != self._lr_host:  # scheduler moved: push the new value
            self._lr_host = new_lr
            lr = jax.device_put(_np.asarray(new_lr, _np.float32),
                                self._mesh.replicated())
        res = self._step_fns[skey](
            tuple(self._param_vals), tuple(self._opt_states),
            tuple(self._aux_vals), tuple(inputs), label_j, key, t, lr)
        (new_vals, new_states, aux_new, loss_val, outs, new_key,
         new_t) = res[:7]
        self._param_vals = list(new_vals)
        self._opt_states = list(new_states)
        self._aux_vals = list(aux_new)
        self._last_outputs = outs
        self._key_dev, self._t_dev, self._lr_dev = new_key, new_t, lr
        extra = 7
        if self._grad_push is not None and len(res) > extra:
            grads = res[extra]
            extra += 1
            if self._guard:
                # the guard decides after its finite check whether this
                # step's gradients ship (commit_grad_push) or vanish
                # (drop_grad_push) — a NaN gradient never hits the wire
                self._deferred_grads = grads
            else:
                self._dispatch_grad_push(grads)
        if self._guard and len(res) > extra:
            self._last_metrics = res[extra]
        return NDArray(loss_val)

    def step(self, data, label):
        """Synchronous step: returns the scalar loss as a host float —
        the Module.forward_backward+update equivalent."""
        return float(self.step_async(data, label).asnumpy())

    def compiled_step(self, data, label):
        """AOT-compile the fused training step for these batch shapes and
        return (jax Compiled object, None). Does NOT execute anything:
        use for XLA's own reports — memory_analysis() (the memcost
        example reads peak activation memory per remat setting),
        cost_analysis(), as_text()."""
        data_list = data if isinstance(data, (list, tuple)) else [data]
        if not self._placed:
            self._place([NDArray(_as_jax(d)) for d in data_list])
        inputs = self._shard_batch(data_list)
        label_j = self._shard_batch([label])[0]
        skey = ("train", tuple(tuple(i.shape) for i in inputs),
                tuple(label_j.shape))
        if skey not in self._step_fns:
            self._step_fns[skey] = self._build_step(skey, len(inputs),
                                                    True)
        key, t, lr = self._device_step_state()
        lowered = self._step_fns[skey].lower(
            tuple(self._param_vals), tuple(self._opt_states),
            tuple(self._aux_vals), tuple(inputs), label_j, key, t, lr)
        return lowered.compile(), None

    def forward(self, data, label):
        """Evaluation forward: returns (loss, outputs) without updating."""
        data_list = data if isinstance(data, (list, tuple)) else [data]
        if not self._placed:
            self._place([NDArray(_as_jax(d)) for d in data_list])
        inputs = self._shard_batch(data_list)
        label_j = self._shard_batch([label])[0]
        key, self._key = _rng_split2(self._key)
        skey = ("eval", tuple(tuple(i.shape) for i in inputs),
                tuple(label_j.shape))
        if skey not in self._step_fns:
            self._step_fns[skey] = self._build_step(skey, len(inputs), False)
        loss_val, outs = self._step_fns[skey](
            tuple(self._param_vals), tuple(self._aux_vals),
            tuple(inputs), label_j, key)
        return float(loss_val), [NDArray(o) for o in outs]

    # -- async gradient push -----------------------------------------------
    def set_grad_push(self, push_fn, max_inflight=2):
        """Register an asynchronous gradient-push hook.

        After every :meth:`step_async`, ``push_fn({name: grad, ...})`` is
        called with the step's per-parameter gradients (f32 NDArrays).
        If it returns a future (anything with ``.result()``) the trainer
        tracks it: at most ``max_inflight`` pushes ride outstanding, so
        the NEXT step's compute overlaps the previous step's push while a
        stalled sink applies backpressure instead of piling up memory.
        Failures surface at the backpressure drain or at
        :meth:`flush_grad_pushes` / :meth:`sync_params`.

        ``push_fn=None`` unregisters (after draining)."""
        self.flush_grad_pushes()
        self._grad_push = push_fn
        self._deferred_grads = None
        self._push_window = AsyncPushWindow(max_inflight)
        # cached train fns were built without the grads output
        self._step_fns = {k: v for k, v in self._step_fns.items()
                          if k[0] != "train"}

    def attach_kvstore(self, kv, max_inflight=2):
        """Wire gradient pushes to a (dist_async) KVStore: every step's
        gradients ship via ``kv.push_async`` on the store's worker pool
        — compute overlaps the wire end-to-end, small parameters ride
        the store's coalesced frames. Keys (parameter names) are lazily
        ``kv.init``-ed with zeros on first push (the shared
        ``dist_hooks.kvstore_grad_pusher`` hook). The window's counters
        publish into ``kv.stats()['grad_push_window']``.

        A bf16 trainer (``dtype='bfloat16'``) ships bf16 gradients —
        half the push bytes; the server's fp32 master table upcasts on
        apply — unless the store compresses (2-bit beats bf16)."""
        wire_dtype = None
        if self._compute_dtype is not None and \
                getattr(kv, "_compression", None) is None:
            wire_dtype = self._compute_dtype
        self.set_grad_push(kvstore_grad_pusher(kv, wire_dtype=wire_dtype),
                           max_inflight=max_inflight)
        if hasattr(kv, "add_stats_source"):
            kv.add_stats_source("grad_push_window",
                                lambda: self._push_window.stats())

    # -- guard hooks (mxtpu.resilience.TrainGuard) -------------------------
    def set_guard(self, enabled):
        """Build train steps with the fused finite-check + select (see
        _build_step): the step additionally returns a packed
        (loss, ok, grad_norm) device vector and holds ALL persistent
        state at its pre-step value when ok is False. Drops cached train
        fns — the output signature changes."""
        self.flush_grad_pushes()
        self._guard = bool(enabled)
        self._deferred_grads = None
        self._last_metrics = None
        self._step_fns = {k: v for k, v in self._step_fns.items()
                          if k[0] != "train"}

    def last_metrics(self):
        """Guard mode: the last step's packed (loss, ok, grad_norm)
        device vector — ONE host transfer reads all three."""
        return self._last_metrics

    def commit_grad_push(self):
        """Guard verdict 'good step': ship the deferred gradients."""
        grads, self._deferred_grads = self._deferred_grads, None
        if grads is not None:
            self._dispatch_grad_push(grads)

    def drop_grad_push(self):
        """Guard verdict 'bad step': this step's gradients vanish."""
        self._deferred_grads = None

    def rewind_step(self):
        """Guard hook for a skipped step: the jitted step already held
        the device step count at its pre-step value; pull the host-side
        counter (which drives the LR schedule) back in line."""
        self._num_update -= 1

    def set_guard_lr_scale(self, scale):
        """Multiplier the guard applies on top of the schedule (its
        halve-on-repeated-failure policy); survives checkpoints via
        state_dict."""
        self._guard_lr_scale = float(scale)

    # -- elastic resume ----------------------------------------------------
    def state_dict(self):
        """Everything the jitted step carries besides the parameters
        themselves (those ride CheckpointManager's ``params`` tree):
        step count, host+device RNG keys, optimizer state, LR-scheduler
        progress and the guard LR scale. Outstanding gradient pushes are
        drained first so the snapshot never captures a half-shipped
        window."""
        self.flush_grad_pushes()
        st = {"num_update": int(self._num_update),
              "rng_key": _np.asarray(self._key),
              "guard_lr_scale": float(self._guard_lr_scale),
              "lr": float(self._optimizer.lr)}
        sched = self._optimizer.lr_scheduler
        if sched is not None:
            st["lr_scheduler"] = sched.state_dict()
        if self._placed:
            if self._key_dev is not None:
                st["rng_key_dev"] = _np.asarray(self._key_dev)
            st["opt_state"] = [self._opt_tree_to_np(t)
                               for t in self._opt_states]
        return st

    def load_state_dict(self, state):
        """Restore a :meth:`state_dict`. Parameters must already be back
        in the block (CheckpointManager.restore writes them first); a
        placed trainer re-stages them onto the mesh, an unplaced one
        picks them up at first step."""
        self.flush_grad_pushes()
        self._num_update = int(state["num_update"])
        self._key = jnp.asarray(state["rng_key"])
        self._guard_lr_scale = float(state.get("guard_lr_scale", 1.0))
        if "lr" in state:
            self._optimizer.lr = float(state["lr"])
        sched = self._optimizer.lr_scheduler
        if sched is not None and "lr_scheduler" in state:
            sched.load_state_dict(state["lr_scheduler"])
        self._pending_key_dev = state.get("rng_key_dev")
        # force _device_step_state to rebuild (key from the checkpoint,
        # t from the restored num_update, lr from the restored schedule)
        self._key_dev = self._t_dev = self._lr_dev = None
        self._lr_host = None
        saved_opt = state.get("opt_state")
        if not self._placed:
            self._pending_opt_state = saved_opt
            return
        # re-stage the (already restored) block parameters on the mesh.
        # A parameter whose block-side buffer was donated away (caller
        # round-tripped trainer state WITHOUT restoring params) keeps
        # its live mesh value instead.
        def _stage(j, i, store):
            v = self._params[i].data()._data
            if not (hasattr(v, "is_deleted") and v.is_deleted()):
                store[j] = jax.device_put(v, self._shardings[i])

        for j, i in enumerate(self._train_idx):
            _stage(j, i, self._param_vals)
        for j, i in enumerate(self._aux_idx):
            _stage(j, i, self._aux_vals)
        if saved_opt is not None:
            self._apply_opt_state(saved_opt)

    @staticmethod
    def _opt_tree_to_np(tree):
        """Optimizer-state pytree (nested tuples / None / jax arrays)
        → host numpy with the same structure."""
        if tree is None:
            return None
        if isinstance(tree, (tuple, list)):
            return tuple(ShardedTrainer._opt_tree_to_np(t) for t in tree)
        return _np.asarray(tree)

    def _apply_opt_state(self, saved):
        """Place host-numpy optimizer-state trees back onto the mesh
        with the same sharding _place chooses (param-shaped leaves on
        the param/ZeRO-1 shard, scalars replicated)."""
        placed = []
        for j, (i, tree) in enumerate(zip(self._train_idx, saved)):
            p = self._params[i]
            sh = self._zero1_shardings[j] or self._shardings[i]

            def place(t, sh=sh, shape=p.shape):
                if t is None:
                    return None
                if isinstance(t, (tuple, list)):
                    return tuple(place(x, sh, shape) for x in t)
                tgt = sh if tuple(t.shape) == tuple(shape) \
                    else self._mesh.replicated()
                return jax.device_put(_np.asarray(t), tgt)

            placed.append(place(tree))
        self._opt_states = placed

    def _dispatch_grad_push(self, grads):
        names = [self._params[i].name for i in self._train_idx]
        # the window drains to under its bound BEFORE shipping: a slow
        # sink blocks there (backpressure), never accumulates futures
        payload = {n: NDArray(g) for n, g in zip(names, grads)}
        self._push_window.dispatch(lambda: self._grad_push(payload))

    def flush_grad_pushes(self):
        """Block until every outstanding gradient push has landed,
        surfacing the first failure."""
        self._push_window.flush()

    def _host_lr(self):
        o = self._optimizer
        base = float(o.lr_scheduler(self._num_update)) \
            if o.lr_scheduler is not None else float(o.lr)
        return base * self._guard_lr_scale

    @property
    def learning_rate(self):
        return self._host_lr()

    def set_learning_rate(self, lr):
        self._optimizer.lr = lr

    def sync_params(self):
        """Copy mesh-sharded values back into the block's Parameters so
        save_params / export / eager inference see the trained weights
        (the kv.pull-at-checkpoint equivalent)."""
        self.flush_grad_pushes()   # pushed state must not trail params
        if not self._placed:
            return
        for v, i in zip(self._param_vals, self._train_idx):
            self._params[i].set_data(NDArray(jax.device_get(v)))
        for v, i in zip(self._aux_vals, self._aux_idx):
            self._params[i].set_data(NDArray(jax.device_get(v)))


def device_prefetch(iterator, mesh=None, size=2):
    """Stage upcoming batches onto the mesh ahead of consumption.

    The device-side half of the input pipeline: the host-side prefetchers
    (``io.PrefetchingIter``, the gluon DataLoader workers) overlap decode
    with compute, and this generator overlaps the host->device transfer —
    batches are ``jax.device_put`` onto the mesh's batch sharding ``size``
    steps ahead, so ``ShardedTrainer.step_async`` finds them already
    staged (its ``_shard_batch`` recognizes matching shardings) and the
    steady-state step makes no synchronous transfer at all. This is the
    engine-async PrefetcherIter capability (reference
    ``src/io/iter_prefetcher.h``) extended across the PCIe/host link.

    ``iterator`` yields arrays, (data, label) tuples/lists, or DataBatch
    objects; the same structure is yielded back with device-staged
    contents.

    Example
    -------
    >>> for x, y in device_prefetch(loader, mesh=st._mesh):
    ...     st.step_async(x, y)
    """
    import collections

    mesh = mesh if mesh is not None else MeshContext()

    def stage_arr(a):
        v = _as_jax(a)
        return jax.device_put(v, mesh.batch_sharding(v.ndim))

    def stage(batch):
        if isinstance(batch, (tuple, list)):
            staged = [stage_arr(b) for b in batch]
            # namedtuples construct from positional fields, not an iterable
            if isinstance(batch, tuple) and hasattr(batch, "_fields"):
                return type(batch)(*staged)
            return type(batch)(staged)
        if hasattr(batch, "data") and hasattr(batch, "label"):
            # build a fresh batch object: iterators that recycle one
            # DataBatch across next() calls must not alias buffered entries
            staged = copy.copy(batch)
            staged.data = [NDArray(stage_arr(d)) for d in batch.data]
            if batch.label is not None:  # DataBatch allows label=None
                staged.label = [NDArray(stage_arr(l)) for l in batch.label]
            return staged
        return stage_arr(batch)

    it = iter(iterator)
    buf = collections.deque()
    try:
        while len(buf) < max(1, size):
            buf.append(stage(next(it)))
    except StopIteration:
        pass
    while buf:
        out = buf.popleft()
        try:
            buf.append(stage(next(it)))
        except StopIteration:
            pass
        yield out
