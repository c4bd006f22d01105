"""Shared training harness for the image-classification scripts.

Capability parity with the reference's common/fit.py (the Module.fit
assembly: kvstore, lr schedule, checkpoint/resume, Speedometer, metrics,
monitor, test-io mode — example/image-classification/common/fit.py:145-312)
plus a TPU-first engine: ``--engine sharded`` trains the same workload
through ShardedTrainer (one fused SPMD step over the device mesh with
device_prefetch staging) instead of the per-executor Module loop.
"""
from __future__ import annotations

import logging
import os
import re
import time

import mxtpu as mx


def _get_lr_scheduler(args, kv):
    if args.lr_factor is None or args.lr_factor >= 1:
        return args.lr, None
    epoch_size = args.num_examples / args.batch_size
    if "dist" in args.kv_store:
        epoch_size /= kv.num_workers
    begin_epoch = args.load_epoch or 0
    if "pow" in (args.lr_step_epochs or ""):
        pwr = float(re.sub("pow[- ]*", "", args.lr_step_epochs))
        max_up = args.num_epochs * epoch_size
        return args.lr, mx.lr_scheduler.PolyScheduler(int(max_up), args.lr,
                                                      pwr)
    step_epochs = [int(x) for x in args.lr_step_epochs.split(",")]
    lr = args.lr
    for s in step_epochs:
        if begin_epoch >= s:
            lr *= args.lr_factor
    if lr != args.lr:
        logging.info("Adjust learning rate to %e for epoch %d",
                     lr, begin_epoch)
    steps = [int(epoch_size * (x - begin_epoch))
             for x in step_epochs if x - begin_epoch > 0]
    if not steps:
        return lr, None
    return lr, mx.lr_scheduler.MultiFactorScheduler(step=steps,
                                                    factor=args.lr_factor)


def _load_model(args, rank=0):
    if args.load_epoch is None:
        return None, None, None
    assert args.model_prefix is not None
    prefix = args.model_prefix
    if rank > 0 and os.path.exists("%s-%d-symbol.json" % (prefix, rank)):
        prefix += "-%d" % rank
    sym, arg_params, aux_params = mx.model.load_checkpoint(
        prefix, args.load_epoch)
    logging.info("Loaded model %s_%04d.params", prefix, args.load_epoch)
    return sym, arg_params, aux_params


def _save_model(args, rank=0):
    if args.model_prefix is None:
        return None
    dst_dir = os.path.dirname(args.model_prefix)
    if dst_dir and not os.path.isdir(dst_dir):
        os.makedirs(dst_dir, exist_ok=True)
    prefix = args.model_prefix if rank == 0 \
        else "%s-%d" % (args.model_prefix, rank)
    return mx.callback.do_checkpoint(prefix)


def add_fit_args(parser):
    train = parser.add_argument_group("Training", "model training")
    train.add_argument("--network", type=str,
                       help="the neural network to use")
    train.add_argument("--num-layers", type=int,
                       help="number of layers, required by e.g. resnet")
    train.add_argument("--engine", type=str, default="module",
                       choices=["module", "sharded"],
                       help="module = MXNet-parity symbolic Module path; "
                            "sharded = fused SPMD ShardedTrainer path")
    train.add_argument("--kv-store", type=str, default="device",
                       help="key-value store type")
    train.add_argument("--num-epochs", type=int, default=100,
                       help="max num of epochs")
    train.add_argument("--lr", type=float, default=0.1,
                       help="initial learning rate")
    train.add_argument("--lr-factor", type=float, default=0.1,
                       help="the ratio to reduce lr on each step")
    train.add_argument("--lr-step-epochs", type=str,
                       help="the epochs to reduce the lr, e.g. 30,60")
    train.add_argument("--initializer", type=str, default="default",
                       help="the initializer type")
    train.add_argument("--optimizer", type=str, default="sgd",
                       help="the optimizer type")
    train.add_argument("--mom", type=float, default=0.9,
                       help="momentum for sgd")
    train.add_argument("--wd", type=float, default=0.0001,
                       help="weight decay for sgd")
    train.add_argument("--batch-size", type=int, default=128,
                       help="the batch size")
    train.add_argument("--disp-batches", type=int, default=20,
                       help="show progress for every n batches")
    train.add_argument("--model-prefix", type=str, help="model prefix")
    train.add_argument("--monitor", dest="monitor", type=int, default=0,
                       help="log network parameters every N iters if >0")
    train.add_argument("--load-epoch", type=int,
                       help="load the model saved at this epoch from "
                            "--model-prefix")
    train.add_argument("--top-k", type=int, default=0,
                       help="report the top-k accuracy; 0 disables")
    train.add_argument("--loss", type=str, default="",
                       help="extra loss metrics: ce and/or nll")
    train.add_argument("--test-io", type=int, default=0,
                       help="1 means test reading speed without training")
    train.add_argument("--dtype", type=str, default="float32",
                       help="precision: float32, float16 or bfloat16")
    train.add_argument("--gc-type", type=str, default="none",
                       help="gradient compression type: 2bit or none")
    train.add_argument("--gc-threshold", type=float, default=0.5,
                       help="threshold for 2bit gradient compression")
    return train


def _select_initializer(args):
    if args.initializer == "default":
        if args.network == "alexnet":
            return mx.init.Normal()
        if "vgg" in (args.network or ""):
            return mx.init.Xavier()
        return mx.init.Xavier(rnd_type="gaussian", factor_type="in",
                              magnitude=2)
    table = {"xavier": mx.init.Xavier, "msra": mx.init.MSRAPrelu,
             "orthogonal": mx.init.Orthogonal, "normal": mx.init.Normal,
             "uniform": mx.init.Uniform, "one": mx.init.One,
             "zero": mx.init.Zero}
    return table[args.initializer]()


def _eval_metrics(args, network=None):
    metrics = [mx.metric.create("accuracy")]
    if args.top_k > 0:
        metrics.append(mx.metric.create("top_k_accuracy", top_k=args.top_k))
    for loss_type in filter(None,
                            (s.strip() for s in args.loss.split(","))):
        if loss_type == "nll":
            loss_type = "nll_loss"
        if loss_type in ("ce", "nll_loss"):
            metrics.append(mx.metric.create(loss_type))
        else:
            logging.warning("%s is not a valid loss type", loss_type)
    return metrics


def _run_test_io(args, train):
    tic = time.time()
    for i, batch in enumerate(train):
        for d in batch.data:
            d.wait_to_read()
        if (i + 1) % args.disp_batches == 0:
            logging.info("Batch [%d]\tSpeed: %.2f samples/sec", i,
                         args.disp_batches * args.batch_size
                         / (time.time() - tic))
            tic = time.time()


def fit(args, network, data_loader, **kwargs):
    """Train a model.

    args : parsed CLI args
    network : Symbol (engine=module) or Gluon block (engine=sharded)
    data_loader : fn(args, kv) -> (train_iter, val_iter)

    Returns the trained Module (None for the sharded engine / test-io).
    """
    kv = mx.kvstore.create(args.kv_store)
    if args.gc_type != "none":
        kv.set_gradient_compression({"type": args.gc_type,
                                     "threshold": args.gc_threshold})
    head = "%(asctime)-15s Node[" + str(kv.rank) + "] %(message)s"
    logging.basicConfig(level=logging.DEBUG, format=head)
    logging.info("start with arguments %s", args)
    logging.getLogger("jax").setLevel(logging.INFO)  # keep DEBUG for our own lines
    logging.info("device: %s", mx.context.describe_device())

    train, val = data_loader(args, kv)
    if args.test_io:
        _run_test_io(args, train)
        return

    if args.engine == "sharded":
        _fit_sharded(args, network, train, val, kv)
        return

    if "arg_params" in kwargs and "aux_params" in kwargs:
        arg_params, aux_params = kwargs["arg_params"], kwargs["aux_params"]
    else:
        sym, arg_params, aux_params = _load_model(args, kv.rank)
        if sym is not None:
            assert sym.tojson() == network.tojson()

    checkpoint = _save_model(args, kv.rank)
    lr, lr_scheduler = _get_lr_scheduler(args, kv)
    model = mx.mod.Module(symbol=network)

    optimizer_params = {"learning_rate": lr, "wd": args.wd,
                        "lr_scheduler": lr_scheduler,
                        "multi_precision": True}
    if args.optimizer in ("sgd", "dcasgd", "nag"):
        optimizer_params["momentum"] = args.mom

    monitor = mx.mon.Monitor(args.monitor, pattern=".*") \
        if args.monitor > 0 else None
    batch_end_callbacks = [mx.callback.Speedometer(args.batch_size,
                                                   args.disp_batches)]
    if "batch_end_callback" in kwargs:
        cbs = kwargs["batch_end_callback"]
        batch_end_callbacks += cbs if isinstance(cbs, list) else [cbs]

    model.fit(train,
              begin_epoch=args.load_epoch or 0,
              num_epoch=args.num_epochs,
              eval_data=val,
              eval_metric=_eval_metrics(args, network),
              kvstore=kv,
              optimizer=args.optimizer,
              optimizer_params=optimizer_params,
              initializer=_select_initializer(args),
              arg_params=arg_params,
              aux_params=aux_params,
              batch_end_callback=batch_end_callbacks,
              epoch_end_callback=checkpoint,
              allow_missing=True,
              monitor=monitor)
    return model


# -- TPU-first engine ------------------------------------------------------

def _fit_sharded(args, net, train, val, kv):
    """One fused SPMD train step per batch over the device mesh."""
    import jax
    from mxtpu import gluon
    from mxtpu.parallel import MeshContext, ShardedTrainer, device_prefetch

    lr, lr_scheduler = _get_lr_scheduler(args, kv)
    begin_epoch = args.load_epoch or 0
    # rank-suffix checkpoints like the module path's _save_model, so
    # workers sharing a filesystem never race on one file
    prefix = args.model_prefix
    if prefix and kv.rank > 0:
        prefix = "%s-%d" % (prefix, kv.rank)
    if begin_epoch:
        assert prefix is not None
        net.load_params("%s-%04d.params" % (prefix, begin_epoch))
    else:
        net.initialize(_select_initializer(args))

    optimizer_params = {"learning_rate": lr, "wd": args.wd,
                        "lr_scheduler": lr_scheduler}
    if args.optimizer in ("sgd", "dcasgd", "nag"):
        optimizer_params["momentum"] = args.mom
    mesh = MeshContext()
    trainer = ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), args.optimizer,
        optimizer_params, mesh=mesh,
        dtype="bfloat16" if args.dtype == "bfloat16" else None)

    metrics = _eval_metrics(args)
    for epoch in range(begin_epoch, args.num_epochs):
        tic = time.time()
        nbatch = 0
        losses = []
        train.reset()
        for batch in device_prefetch(train, mesh=mesh):
            losses.append(trainer.step_async(batch.data[0]._data,
                                             batch.label[0]._data))
            nbatch += 1
            if nbatch % args.disp_batches == 0:
                losses[-1].wait_to_read()  # bound async depth
                speed = args.disp_batches * args.batch_size \
                    / (time.time() - tic)
                logging.info(
                    "Epoch[%d] Batch [%d]\tSpeed: %.2f samples/sec\t"
                    "loss=%.5f", epoch, nbatch, speed,
                    float(losses[-1].asnumpy()))
                tic = time.time()
        if losses:
            losses[-1].wait_to_read()
        logging.info("Epoch[%d] Train-batches=%d", epoch, nbatch)

        if val is not None:
            for m in metrics:
                m.reset()
            val.reset()
            for batch in val:
                _, outs = trainer.forward(batch.data[0]._data,
                                          batch.label[0]._data)
                # block outputs are logits (the loss applies softmax);
                # normalize for probability-based metrics like 'ce'
                preds = [mx.nd.softmax(outs[0])]
                for m in metrics:
                    m.update(batch.label, preds)
            for m in metrics:
                for name, v in zip(*[_as_list(x) for x in m.get()]):
                    logging.info("Epoch[%d] Validation-%s=%f",
                                 epoch, name, v)

        if prefix:
            trainer.sync_params()
            dst_dir = os.path.dirname(prefix)
            if dst_dir and not os.path.isdir(dst_dir):
                os.makedirs(dst_dir, exist_ok=True)
            net.save_params("%s-%04d.params" % (prefix, epoch + 1))
            logging.info('Saved checkpoint to "%s-%04d.params"',
                         prefix, epoch + 1)


def _as_list(x):
    return x if isinstance(x, (list, tuple)) else [x]
