"""Checkpointing and KVStore training glue.

Capability parity with ``python/mxnet/model.py`` (994 LoC): BatchEndParam,
save_checkpoint/load_checkpoint (``model.py:367,397``), and the kvstore
helpers ``_create_kvstore/_initialize_kvstore/_update_params[_on_kvstore]``
(``model.py:59-170``) used by Module and Trainer. Checkpoints are
``prefix-symbol.json`` + ``prefix-%04d.params`` exactly like the reference;
the params container is the framework's NDArray save format.
"""
from __future__ import annotations

import logging
import os
from collections import namedtuple

import numpy as np

from . import ndarray as nd
from . import symbol as sym
from .ndarray import NDArray

__all__ = ["BatchEndParam", "save_checkpoint", "load_checkpoint",
           "load_params"]

BatchEndParam = namedtuple("BatchEndParams",
                           ["epoch", "nbatch", "eval_metric", "locals"])


def _module_fused_enabled():
    """MXTPU_MODULE_FUSED gate for the fused Module train step
    (``module/fused.py``, ``docs/env_vars.md``): default ON; ``0`` keeps
    the eager forward/backward/per-param-update loop everywhere."""
    return os.environ.get("MXTPU_MODULE_FUSED", "1").strip().lower() \
        not in ("0", "false", "off")


def _create_kvstore(kvstore, num_device, arg_params):
    """Create kvstore from --kv-store style spec (reference model.py:59)."""
    from . import kvstore as kvs
    update_on_kvstore = True
    if kvstore is None:
        kv = None
    elif isinstance(kvstore, kvs.KVStore):
        # a single-process store over one device with no compression set
        # has nothing to reduce: same as the string form below, so the
        # Module step stays fused (example/image-classification passes
        # the instance it read rank/num_workers from)
        plain = type(kvstore) is kvs.KVStore \
            and kvstore.gradient_compression is None
        kv = None if num_device == 1 and plain else kvstore
    elif isinstance(kvstore, str):
        if num_device == 1 and "dist" not in kvstore:
            kv = None
        else:
            kv = kvs.create(kvstore)
            if kvstore == "local":
                max_size = max(np.prod(param.shape)
                               for param in arg_params.values()) \
                    if arg_params else 0
                if max_size > 1024 * 1024 * 16:
                    update_on_kvstore = False
    else:
        raise TypeError("kvstore must be KVStore, str or None")
    if kv is None:
        update_on_kvstore = False
    elif os.environ.get("MXTPU_UPDATE_ON_KVSTORE", "1").strip().lower() \
            in ("0", "false", "off"):
        # the reference's MXNET_UPDATE_ON_KVSTORE escape: the store only
        # merges gradients (push + pull), the worker applies the
        # optimizer locally — Module's fused dist path renders this as
        # the grad-emitting program + donated local apply
        update_on_kvstore = False
    return (kv, update_on_kvstore)


def _initialize_kvstore(kvstore, param_arrays, arg_params, param_names,
                        update_on_kvstore):
    """Init kvstore entries from parameters (reference model.py:86)."""
    for idx, param_on_devs in enumerate(param_arrays):
        name = param_names[idx]
        kvstore.init(name, arg_params[name])
        if update_on_kvstore:
            kvstore.pull(name, param_on_devs, priority=-idx)


def _update_params_on_kvstore(param_arrays, grad_arrays, kvstore, param_names):
    """Push grads / pull updated weights (reference model.py:104)."""
    for index, pair in enumerate(zip(param_arrays, grad_arrays)):
        arg_list, grad_list = pair
        if grad_list[0] is None:
            continue
        name = param_names[index]
        kvstore.push(name, grad_list, priority=-index)
        kvstore.pull(name, arg_list, priority=-index)


def _update_params(param_arrays, grad_arrays, updater, num_device,
                   kvstore=None, param_names=None):
    """Aggregate grads (optionally via kvstore) and run updater locally
    (reference model.py:118)."""
    for index, pair in enumerate(zip(param_arrays, grad_arrays)):
        arg_list, grad_list = pair
        if grad_list[0] is None:
            continue
        if kvstore:
            name = param_names[index]
            kvstore.push(name, grad_list, priority=-index)
            kvstore.pull(name, grad_list, priority=-index)
        for k, p in enumerate(zip(arg_list, grad_list)):
            w, g = p
            updater(index * num_device + k, g, w)


def save_checkpoint(prefix, epoch, symbol, arg_params, aux_params):
    """Save ``prefix-symbol.json`` + ``prefix-%04d.params``
    (reference model.py:367)."""
    if symbol is not None:
        symbol.save("%s-symbol.json" % prefix)
    save_dict = {("arg:%s" % k): v for k, v in arg_params.items()}
    save_dict.update({("aux:%s" % k): v for k, v in aux_params.items()})
    param_name = "%s-%04d.params" % (prefix, epoch)
    nd.save(param_name, save_dict)
    logging.info('Saved checkpoint to "%s"', param_name)


def load_params(prefix, epoch):
    """Load params file into (arg_params, aux_params) dicts."""
    save_dict = nd.load("%s-%04d.params" % (prefix, epoch))
    arg_params, aux_params = {}, {}
    for k, v in save_dict.items():
        tp, name = k.split(":", 1)
        if tp == "arg":
            arg_params[name] = v
        elif tp == "aux":
            aux_params[name] = v
    return arg_params, aux_params


def load_checkpoint(prefix, epoch):
    """Load symbol + params saved by save_checkpoint (reference model.py:397)."""
    symbol = sym.load("%s-symbol.json" % prefix)
    arg_params, aux_params = load_params(prefix, epoch)
    return (symbol, arg_params, aux_params)


class FeedForward:
    """Legacy training front-end (reference ``python/mxnet/model.py``
    FeedForward, model.py:419-994; deprecated there in favour of Module,
    kept for API parity). Wraps a Module and exposes the numpy-friendly
    fit/predict/score/save/load surface."""

    def __init__(self, symbol, ctx=None, num_epoch=None, epoch_size=None,
                 optimizer="sgd", initializer=None, numpy_batch_size=128,
                 arg_params=None, aux_params=None, allow_extra_params=False,
                 begin_epoch=0, **kwargs):
        from . import initializer as init_mod
        self.symbol = symbol
        self.ctx = ctx
        self.num_epoch = num_epoch
        self.epoch_size = epoch_size
        self.optimizer = optimizer
        self.initializer = initializer or init_mod.Uniform(0.01)
        self.numpy_batch_size = numpy_batch_size
        self.arg_params = arg_params
        self.aux_params = aux_params
        self.allow_extra_params = allow_extra_params
        self.begin_epoch = begin_epoch
        self.kwargs = dict(kwargs)
        self._module = None

    # -- helpers -----------------------------------------------------------
    def _as_iter(self, X, y=None, batch_size=None, shuffle=False):
        from . import io
        if hasattr(X, "provide_data"):
            return X
        return io.NDArrayIter(X, y, batch_size or self.numpy_batch_size,
                              shuffle=shuffle)

    def _ensure_module(self):
        from . import module as mod
        if self._module is None:
            self._module = mod.Module(self.symbol, context=self.ctx)
        return self._module

    # -- training ----------------------------------------------------------
    def fit(self, X, y=None, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            logger=None, work_load_list=None, monitor=None,
            eval_end_callback=None, eval_batch_end_callback=None):
        train = self._as_iter(X, y, shuffle=True)
        if eval_data is not None and not hasattr(eval_data, "provide_data"):
            eval_data = self._as_iter(eval_data[0], eval_data[1])
        m = self._ensure_module()
        # a prior predict/score bound the module for inference; Module.bind
        # silently ignores rebinds, so force one to get backward graphs
        rebind = m.binded and not m.for_training
        m.fit(train, eval_data=eval_data, eval_metric=eval_metric,
              force_rebind=rebind,
              epoch_end_callback=epoch_end_callback,
              batch_end_callback=batch_end_callback, kvstore=kvstore,
              optimizer=self.optimizer,
              optimizer_params=self.kwargs or {"learning_rate": 0.01},
              initializer=self.initializer,
              arg_params=self.arg_params, aux_params=self.aux_params,
              allow_missing=True,
              begin_epoch=self.begin_epoch,
              num_epoch=self.num_epoch or 1, monitor=monitor)
        self.arg_params, self.aux_params = m.get_params()
        return self

    def predict(self, X, num_batch=None, return_data=False, reset=True):
        data = self._as_iter(X)
        m = self._ensure_module()
        if not m.binded:
            m.bind(data_shapes=data.provide_data, for_training=False)
            m.init_params(self.initializer, arg_params=self.arg_params,
                          aux_params=self.aux_params, allow_missing=True,
                          allow_extra=self.allow_extra_params)
        if reset:
            data.reset()
        if not return_data:
            out = m.predict(data, num_batch=num_batch)
            if isinstance(out, (list, tuple)):
                return [o.asnumpy() for o in out]
            return out.asnumpy()
        # reference model.py:predict(return_data=True) returns the triple
        # (outputs, data, label) with padding trimmed
        outs, datas, labels = [], [], []
        for nbatch, batch in enumerate(data):
            if num_batch is not None and nbatch == num_batch:
                break
            m.forward(batch, is_train=False)
            pad = getattr(batch, "pad", 0) or 0
            n = batch.data[0].shape[0] - pad
            outs.append(m.get_outputs()[0].asnumpy()[:n])
            datas.append(batch.data[0].asnumpy()[:n])
            if batch.label:
                labels.append(batch.label[0].asnumpy()[:n])
        cat = np.concatenate
        return (cat(outs), cat(datas),
                cat(labels) if labels else None)

    def score(self, X, eval_metric="acc", num_batch=None,
              batch_end_callback=None, reset=True):
        from . import metric as metric_mod
        data = self._as_iter(X)
        if reset:
            data.reset()
        m = self._ensure_module()
        if not m.binded:
            m.bind(data_shapes=data.provide_data,
                   label_shapes=data.provide_label, for_training=False)
            m.init_params(self.initializer, arg_params=self.arg_params,
                          aux_params=self.aux_params, allow_missing=True,
                          allow_extra=self.allow_extra_params)
        metric = metric_mod.create(eval_metric)
        res = m.score(data, metric, num_batch=num_batch)
        return dict(res)[metric.name]

    # -- persistence -------------------------------------------------------
    def save(self, prefix, epoch=None):
        if epoch is None:
            epoch = self.num_epoch or 0
        save_checkpoint(prefix, epoch, self.symbol,
                        self.arg_params or {}, self.aux_params or {})

    @staticmethod
    def load(prefix, epoch, ctx=None, **kwargs):
        symbol, arg_params, aux_params = load_checkpoint(prefix, epoch)
        return FeedForward(symbol, ctx=ctx, arg_params=arg_params,
                           aux_params=aux_params, begin_epoch=epoch,
                           **kwargs)

    @staticmethod
    def create(symbol, X, y=None, ctx=None, num_epoch=None, epoch_size=None,
               optimizer="sgd", initializer=None, eval_data=None,
               eval_metric="acc", epoch_end_callback=None,
               batch_end_callback=None, kvstore="local", logger=None,
               work_load_list=None, eval_end_callback=None,
               eval_batch_end_callback=None, **kwargs):
        """Train a new model from scratch (reference model.py:create)."""
        model = FeedForward(symbol, ctx=ctx, num_epoch=num_epoch,
                            epoch_size=epoch_size, optimizer=optimizer,
                            initializer=initializer, **kwargs)
        model.fit(X, y, eval_data=eval_data, eval_metric=eval_metric,
                  epoch_end_callback=epoch_end_callback,
                  batch_end_callback=batch_end_callback, kvstore=kvstore,
                  logger=logger, work_load_list=work_load_list,
                  eval_end_callback=eval_end_callback,
                  eval_batch_end_callback=eval_batch_end_callback)
        return model


__all__ += ["FeedForward"]
