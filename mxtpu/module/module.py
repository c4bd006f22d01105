"""Module: the concrete symbolic training module.

Capability parity with ``python/mxnet/module/module.py`` (bind :363,
init_params :258, init_optimizer :472, forward/backward, update :629-650,
save/load_checkpoint). Gradient sync follows the reference's
update/update_on_kvstore split (``model.py:104-170``); on one host both
paths run the optimizer on-device over XLA-reduced gradients.

Fused train step (``MXTPU_MODULE_FUSED``, default on): on a single
context with a locally-applied optimizer, ``forward_backward`` runs
forward + backward + the ENTIRE optimizer update as ONE donated jitted
XLA program (``module/fused.py``), and ``update()`` becomes a no-op
acknowledging the already-applied step. Donation semantics: each step
invalidates the previous parameter/optimizer-state device buffers and
rebinds every NDArray's ``_data`` to the program's outputs — hold the
NDArray wrappers (``arg_dict`` entries, ``param_arrays``), never raw
``jax.Array`` handles, across steps.

Distributed fused step (``MXTPU_MODULE_FUSED_DIST``, default on): a
kvstore-managed module rides the same one-program contract in its
grad-EMITTING form — forward+backward(+device metric) in one program,
then ``update()`` pushes the gradients and applies the update per
kvstore mode (server-side for ``update_on_kvstore``, a donated local
apply program otherwise). ``MXTPU_MODULE_DIST_MODE=async`` pipelines
push+pull on the store's worker pool under a bounded-inflight window
(``MXTPU_MODULE_PUSH_INFLIGHT``); the default ``sync`` matches the
eager dist loop bit-for-bit. Monitors, custom updaters, sparse
parameters, ``inputs_need_grad`` and multi-context groups still fall
back to the eager path, logging the reason once at warning level
(``fused._fused_eligible``).

Mixed precision (``MXTPU_AMP=bf16``, ISSUE 12): a MODE of the fused
path — bf16 compute params/activations with fp32 master weights,
optimizer state and BN statistics living in the donated store; the
cast-in/cast-out happens inside the one program, gradients apply in
fp32, and on the dist modes the emitted gradients ship bf16 (half the
``pushpull`` wire bytes; the server's fp32 master table upcasts on
apply). ``MXTPU_AMP_LOSS_SCALE`` adds an in-program overflow skip.
AMP-ineligible setups (non-fp32 params) log once at warning level and
keep the fp32 fused step (``module/fused.py`` docstring, "Mixed
precision" in docs/perf_analysis.md).
"""
from __future__ import annotations

import logging
import warnings

from .. import context as ctx_mod
from .. import ndarray as nd
from .. import optimizer as opt
from ..context import cpu
from ..initializer import Uniform, InitDesc
from ..model import (_create_kvstore, _initialize_kvstore, _update_params,
                     _update_params_on_kvstore, load_checkpoint,
                     BatchEndParam)
from . import fused as fused_mod
from .base_module import BaseModule, _check_input_names, _parse_data_desc
from .executor_group import DataParallelExecutorGroup

__all__ = ["Module"]

_ALREADY_INIT = ("%s already initialized and force_init=False. "
                 "%s call ignored.")


class Module(BaseModule):
    """High-level computation machine over a Symbol
    (reference module/module.py:51)."""

    def __init__(self, symbol, data_names=("data",),
                 label_names=("softmax_label",), logger=logging,
                 context=None, work_load_list=None, fixed_param_names=None,
                 state_names=None):
        super().__init__(logger=logger)
        ctxs = context if context is not None else ctx_mod.current_context()
        self._context = [ctxs] if isinstance(ctxs, ctx_mod.Context) else ctxs
        self._work_load_list = (work_load_list if work_load_list is not None
                                else [1] * len(self._context))
        assert len(self._work_load_list) == len(self._context)

        self._symbol = symbol
        name_groups = {
            "data": list(data_names or []),
            "label": list(label_names or []),
            "state": list(state_names or []),
            "fixed_param": list(fixed_param_names or []),
        }
        for kind, names in name_groups.items():
            _check_input_names(symbol, names, kind, kind != "label")
        self._data_names = name_groups["data"]
        self._label_names = name_groups["label"]
        self._state_names = name_groups["state"]
        self._fixed_param_names = name_groups["fixed_param"]

        # everything the graph consumes that the iterator doesn't feed is
        # a learnable parameter
        fed = set(self._data_names + self._label_names + self._state_names)
        self._param_names = [a for a in symbol.list_arguments()
                             if a not in fed]
        self._aux_names = list(symbol.list_auxiliary_states())
        self._output_names = list(symbol.list_outputs())

        self._arg_params = self._aux_params = None
        self._params_dirty = False
        # optimizer wiring, filled by init_optimizer
        self._optimizer = self._kvstore = self._updater = None
        self._update_on_kvstore = self._preload_opt_states = None
        self._grad_req = None
        # executor state, filled by bind
        self._exec_group = self._data_shapes = self._label_shapes = None
        # fused train step (module/fused.py), filled by init_optimizer
        self._fused = None
        self._fused_update_pending = False
        # mesh sharding (ISSUE 20, set_sharding / MXTPU_MESH)
        self._mesh_ctx = None
        self._sharding_rules = None

    # -- state guards (the reference inlines these asserts at each site) --
    def _require(self, params=False, optimizer=False):
        assert self.binded, "call bind first"
        if params:
            assert self.params_initialized, "call init_params first"
        if optimizer:
            assert self.optimizer_initialized, "call init_optimizer first"

    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        """Create a model from a checkpoint (reference module.py:146)."""
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params, mod._aux_params = args, auxs
        mod.params_initialized = True
        if load_optimizer_states:
            mod._preload_opt_states = "%s-%04d.states" % (prefix, epoch)
        return mod

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False):
        """Save symbol+params[+opt states] (reference module.py:173)."""
        self._symbol.save("%s-symbol.json" % prefix)
        param_name = "%s-%04d.params" % (prefix, epoch)
        self.save_params(param_name)
        logging.info('Saved checkpoint to "%s"', param_name)
        if save_optimizer_states:
            state_name = "%s-%04d.states" % (prefix, epoch)
            self.save_optimizer_states(state_name)
            logging.info('Saved optimizer state to "%s"', state_name)

    def _reset_bind(self):
        self.binded = False
        self._exec_group = self._data_shapes = self._label_shapes = None
        self._fused = None
        self._fused_update_pending = False

    # -- properties --------------------------------------------------------
    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        self._require()
        return self._data_shapes

    @property
    def label_shapes(self):
        self._require()
        return self._label_shapes

    @property
    def output_shapes(self):
        self._require()
        outputs = self._exec_group.get_outputs()
        if outputs:
            return list(zip(self._output_names,
                            [o.shape for o in outputs]))
        # before the first forward: infer from the symbol like the
        # reference (executor_group.py binds with inferred shapes, so
        # output_shapes is valid right after bind — SequentialModule
        # wires module N+1's data_shapes from it)
        known = {name: shape
                 for name, shape in (self._data_shapes or []) +
                 (self._label_shapes or [])}
        _, out_shapes, _ = self._symbol.infer_shape(**known)
        return list(zip(self._output_names, out_shapes))

    # -- params ------------------------------------------------------------
    def get_params(self):
        self._require(params=True)
        if self._params_dirty:
            self._sync_params_from_devices()
        return (self._arg_params, self._aux_params)

    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False,
                    allow_extra=False):
        """Initialize parameters (reference module.py:258)."""
        if self.params_initialized and not force_init:
            warnings.warn(_ALREADY_INIT % ("Parameters", "init_params"),
                          stacklevel=2)
            return
        assert self.binded, "call bind before initializing the parameters"

        def host_mirror(names, group_arrays):
            return {name: nd.zeros(arr[0].shape, dtype=arr[0].dtype)
                    for name, arr in zip(names, group_arrays)}

        if self._arg_params is None:
            self._arg_params = host_mirror(self._param_names,
                                           self._exec_group.param_arrays)
        if self._aux_params is None:
            self._aux_params = host_mirror(self._aux_names,
                                           self._exec_group.aux_arrays)

        attrs = self._symbol.attr_dict()

        def fill(desc, arr, provided):
            """provided value wins; else the initializer; missing provided
            entries error unless allow_missing. (InitDesc IS the name —
            a str subclass carrying attrs.)"""
            if provided is None:
                if initializer is not None:
                    initializer(desc, arr)
            elif desc in provided:
                src = provided[desc]
                if src is not arr:
                    src.copyto(arr)
            elif not allow_missing:
                raise RuntimeError("%s is not presented" % desc)
            elif initializer is not None:
                initializer(desc, arr)

        for table, provided in ((self._arg_params, arg_params),
                                (self._aux_params, aux_params)):
            for name in sorted(table):
                fill(InitDesc(name, attrs.get(name)), table[name], provided)

        self.params_initialized = True
        self._params_dirty = False
        self._exec_group.set_params(self._arg_params, self._aux_params,
                                    allow_extra=allow_extra)

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        """Assign parameters directly (reference module.py:327)."""
        if not allow_missing:
            self.init_params(initializer=None, arg_params=arg_params,
                             aux_params=aux_params,
                             allow_missing=allow_missing,
                             force_init=force_init, allow_extra=allow_extra)
            return
        if self.params_initialized and not force_init:
            warnings.warn(_ALREADY_INIT % ("Parameters", "set_params"),
                          stacklevel=2)
            return
        self._exec_group.set_params(arg_params, aux_params,
                                    allow_extra=allow_extra)
        self._params_dirty = True
        self.params_initialized = True

    # -- bind --------------------------------------------------------------
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        """Bind executors (reference module.py:363)."""
        if force_rebind:
            self._reset_bind()
        if self.binded:
            self.logger.warning("Already binded, ignoring bind()")
            return
        self.for_training, self.inputs_need_grad = (for_training,
                                                    inputs_need_grad)
        self._grad_req = grad_req
        assert for_training or not inputs_need_grad

        self._data_shapes, self._label_shapes = _parse_data_desc(
            self.data_names, self.label_names, data_shapes, label_shapes)

        shared_group = None
        if shared_module is not None:
            assert isinstance(shared_module, Module)
            shared_module._require(params=True)
            shared_group = shared_module._exec_group

        self._exec_group = DataParallelExecutorGroup(
            self._symbol, self._context, self._work_load_list,
            self._data_shapes, self._label_shapes, self._param_names,
            for_training, inputs_need_grad, shared_group,
            logger=self.logger, fixed_param_names=self._fixed_param_names,
            grad_req=grad_req, state_names=self._state_names)
        self.binded = True

        if shared_module is not None and shared_module.params_initialized:
            self.set_params(*shared_module.get_params())
        elif self._arg_params is not None:
            # params were loaded before bind (Module.load)
            self._exec_group.set_params(self._arg_params, self._aux_params,
                                        allow_extra=True)
            self.params_initialized = True

    def reshape(self, data_shapes, label_shapes=None):
        """Reshape for new batch shapes (reference module.py:450)."""
        self._require()
        # executors are rebuilt from host params below; pull the latest
        # device-side values first or optimizer progress would be reverted
        if self._params_dirty:
            self._sync_params_from_devices()
        self._data_shapes, self._label_shapes = _parse_data_desc(
            self.data_names, self.label_names, data_shapes, label_shapes)
        self._exec_group.reshape(self._data_shapes, self._label_shapes)
        if self.params_initialized:
            self._exec_group.set_params(self._arg_params, self._aux_params,
                                        allow_extra=True)
        if self._fused is not None:
            # rebinding built fresh arrays; re-alias them to the group's
            # shared device store so bucket modules stay coherent
            self._fused.adopt_store()

    # -- optimizer ---------------------------------------------------------
    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        """Install optimizer (reference module.py:472)."""
        self._require(params=True)
        if self.optimizer_initialized and not force_init:
            self.logger.warning("optimizer already initialized, ignoring...")
            return
        if self._params_dirty:
            self._sync_params_from_devices()

        kvstore, update_on_kvstore = _create_kvstore(
            kvstore, len(self._context), self._arg_params)
        batch_size = self._exec_group.batch_size
        if kvstore and "dist" in kvstore.type and "_sync" in kvstore.type:
            batch_size *= kvstore.num_workers
        rescale_grad = 1.0 / batch_size

        # slot index -> param name, for per-param lr/wd multipliers: one
        # slot per param when the store updates, one per (param, device)
        # replica otherwise
        names = self._exec_group.param_names
        n_dev = len(self._context)
        if update_on_kvstore:
            idx2name = dict(enumerate(names))
        else:
            idx2name = {i * n_dev + k: n
                        for i, n in enumerate(names) for k in range(n_dev)}

        if isinstance(optimizer, str):
            conf = dict(optimizer_params)
            conf.setdefault("rescale_grad", rescale_grad)
            optimizer = opt.create(optimizer, sym=self.symbol,
                                   param_idx2name=idx2name, **conf)
        else:
            assert isinstance(optimizer, opt.Optimizer)
            if optimizer.rescale_grad != rescale_grad:
                warnings.warn(
                    "Optimizer created manually outside Module but "
                    "rescale_grad is not normalized to 1.0/batch_size/"
                    "num_workers (%s vs. %s). Is this intended?" % (
                        optimizer.rescale_grad, rescale_grad), stacklevel=2)
            if not optimizer.idx2name:
                optimizer.idx2name = idx2name.copy()

        self._optimizer = optimizer
        self._kvstore = kvstore
        self._update_on_kvstore = update_on_kvstore

        if kvstore:
            compression = getattr(self._exec_group, "_compression_params",
                                  None)
            if compression:
                kvstore.set_gradient_compression(compression)
            _initialize_kvstore(kvstore=kvstore,
                                param_arrays=self._exec_group.param_arrays,
                                arg_params=self._arg_params,
                                param_names=self._param_names,
                                update_on_kvstore=update_on_kvstore)
        # either the store applies updates where the weights live, or this
        # module keeps its own updater closure
        if update_on_kvstore:
            self._updater = None
            kvstore.set_optimizer(self._optimizer)
        else:
            self._updater = opt.get_updater(optimizer)

        self.optimizer_initialized = True
        if self._preload_opt_states is not None:
            self.load_optimizer_states(self._preload_opt_states)
            self._preload_opt_states = None
        self._fused = fused_mod.maybe_create(self)

    def set_sharding(self, mesh, rules=None):
        """Engage mesh-sharded training for this module (ISSUE 20):
        ``mesh`` is a :class:`~mxtpu.parallel.mesh.MeshContext`,
        ``rules`` a :class:`~mxtpu.parallel.mesh.ShardingRules` /
        :class:`~mxtpu.partition.PartitionRules` naming each
        parameter's placement (None = FSDP-style default: dim 0 over
        the first mesh axis where it divides). The fused train step
        then compiles as an SPMD mesh program with the donated
        param/opt-state/aux store sharded by rule — per-device memory
        ~1/N. Call before ``init_optimizer``; calling after re-creates
        the fused trainer with the new placement (parameter values are
        preserved — the first sharded step scatters them)."""
        self._mesh_ctx = mesh
        self._sharding_rules = rules
        if self.optimizer_initialized and self._fused is not None:
            self._fused.flush()
            self._fused = fused_mod.maybe_create(self)
        return self

    def borrow_optimizer(self, shared_module):
        """Share optimizer with another module (reference module.py:546)."""
        assert shared_module.optimizer_initialized
        for attr in ("_optimizer", "_kvstore", "_update_on_kvstore",
                     "_updater"):
            setattr(self, attr, getattr(shared_module, attr))
        self.optimizer_initialized = True
        # join the lender's fused group: buckets alias one device-side
        # parameter store, so a bucket switch is a cache hit
        self._fused = fused_mod.attach_borrowed(self, shared_module)

    # -- computation -------------------------------------------------------
    def forward_backward(self, data_batch):
        """One train step. On the fused path this dispatches ONE jitted
        program covering forward + backward + optimizer update (+ metric
        accumulation); ``update()`` then just acknowledges it."""
        if self._fused is not None and self._fused.step(data_batch):
            self._fused_update_pending = True
            return
        self.forward(data_batch, is_train=True)
        self.backward()

    def forward(self, data_batch, is_train=None):
        """Forward computation (reference module.py:563)."""
        self._require(params=True)
        if self._fused is not None:
            self._fused.note_eager_forward()
        curr_data_shapes = tuple(i.shape for i in self._data_shapes)
        if isinstance(data_batch, list):
            # the reference guards `is not None` here, which a [] passes —
            # catch the empty batch it actually means to reject
            assert data_batch, "Encountered empty data batch"
            new_data_shapes = tuple(i.data[0].shape for i in data_batch)
        else:
            new_data_shapes = tuple(i.shape for i in data_batch.data)
        if curr_data_shapes != new_data_shapes:
            self.reshape(*self._shapes_for_batch(data_batch,
                                                 new_data_shapes))
        self._exec_group.forward(data_batch, is_train)

    def _shapes_for_batch(self, data_batch, new_data_shapes):
        """(data descs, label descs) matching a batch whose shapes differ
        from the bound ones (bucketing-style late reshape)."""
        def redescribe(descs, shapes):
            return [type(d)(d.name, s) if hasattr(d, "name") else (d[0], s)
                    for d, s in zip(descs, shapes)]

        if getattr(data_batch, "provide_data", None):
            new_dshape = data_batch.provide_data
        else:
            new_dshape = redescribe(self._data_shapes, new_data_shapes)
        if getattr(data_batch, "provide_label", None):
            new_lshape = data_batch.provide_label
        elif getattr(data_batch, "label", None):
            new_lshape = redescribe(self._label_shapes,
                                    [j.shape for j in data_batch.label])
        else:
            new_lshape = None
        return new_dshape, new_lshape

    def backward(self, out_grads=None):
        """Backward computation (reference module.py:603)."""
        self._require(params=True)
        self._exec_group.backward(out_grads=out_grads)

    def update(self):
        """Apply optimizer to gradients (reference module.py:629)."""
        self._require(params=True, optimizer=True)
        self._params_dirty = True
        if self._fused_update_pending:
            # the fused forward_backward either applied this step's
            # update inside its one donated program (local mode) or
            # emitted gradients that finish_update now ships through
            # the kvstore (dist modes: push+pull inline, or pipelined
            # on the store's pool under the bounded-inflight window)
            self._fused_update_pending = False
            if self._fused is not None:
                self._fused.finish_update()
            return
        group = self._exec_group
        if self._update_on_kvstore:
            _update_params_on_kvstore(group.param_arrays, group.grad_arrays,
                                      self._kvstore, group.param_names)
        else:
            _update_params(group.param_arrays, group.grad_arrays,
                           self._updater, len(self._context),
                           kvstore=self._kvstore,
                           param_names=group.param_names)

    def get_outputs(self, merge_multi_context=True):
        self._require(params=True)
        return self._exec_group.get_outputs(
            merge_multi_context=merge_multi_context)

    def get_input_grads(self, merge_multi_context=True):
        self._require(params=True)
        assert self.inputs_need_grad
        return self._exec_group.get_input_grads(
            merge_multi_context=merge_multi_context)

    def get_states(self, merge_multi_context=True):
        self._require(params=True)
        return self._exec_group.get_states(
            merge_multi_context=merge_multi_context)

    def set_states(self, states=None, value=None):
        self._require(params=True)
        self._exec_group.set_states(states, value)

    def update_metric(self, eval_metric, labels):
        if self._fused is not None and self._fused.note_metric(eval_metric):
            return  # accumulated device-side inside the fused step
        self._exec_group.update_metric(eval_metric, labels)

    def _sync_params_from_devices(self):
        """Synchronize parameters from devices to host copies
        (reference module.py:697)."""
        if self._fused is not None:
            # async dist mode: outstanding push/pull windows must land
            # before the host mirrors are read
            self._fused.flush()
        self._exec_group.get_params(self._arg_params, self._aux_params)
        if self._kvstore and self._update_on_kvstore:
            for param_name, param_val in sorted(self._arg_params.items()):
                rank = (self._param_names.index(param_name)
                        if param_name in self._param_names else 0)
                self._kvstore.pull(param_name, param_val, priority=-rank)
        self._params_dirty = False

    def save_optimizer_states(self, fname):
        """Save optimizer states (reference module.py:712)."""
        assert self.optimizer_initialized
        if self._fused is not None:
            self._fused.flush()   # server state must include every push
        if self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname)
            return
        with open(fname, "wb") as fout:
            fout.write(self._updater.get_states())

    def load_optimizer_states(self, fname):
        """Load optimizer states (reference module.py:727)."""
        assert self.optimizer_initialized
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
            return
        with open(fname, "rb") as f:
            self._updater.set_states(f.read())

    def install_monitor(self, mon):
        self._require()
        self._exec_group.install_monitor(mon)

    def prepare(self, data_batch, sparse_row_id_fn=None):
        """Pre-step hook: row-sparse pull (reference module.py:744), and
        on the fused path, async device staging of the upcoming batch so
        the next step's transfer overlaps the in-flight program."""
        self._require()
        if sparse_row_id_fn is None:
            if self._fused is not None:
                from ..io import stage_batch
                stage_batch(data_batch, self._context[0])
            return
        if not (self._kvstore and self._update_on_kvstore):
            warnings.warn(UserWarning(
                "Parameters are not updated in the KVStore. No need to "
                "call sparse_row_id_fn."))
            return
        for param_name, row_id in sparse_row_id_fn(data_batch).items():
            param_idx = self._exec_group.param_names.index(param_name)
            param_val = self._exec_group.param_arrays[param_idx]
            self._kvstore.row_sparse_pull(param_name, param_val,
                                          row_ids=row_id,
                                          priority=-param_idx)
