#!/usr/bin/env python
"""DLRM-style click model on the fused sparse dist path (ISSUE 13).

The recommender workload the row-sparse machinery exists for (Naumov et
al., 2019, in the lineage of the OSDI'14 parameter server): categorical
features look up rows of LARGE embedding tables declared
``stype='row_sparse'``, dense features ride a bottom MLP, and the
concatenated features feed a top MLP predicting click/no-click. Each
step touches only ``batch x lookups`` embedding rows, so training runs
as

* ONE XLA program per step — forward + backward + device-side
  unique/gather of the touched rows (``(row_ids, rows)`` out);
* ONE ``sparse_push_pull`` round trip per table — only touched rows on
  the wire, the server applying the ROW-WISE optimizer
  (``Optimizer.update_host_rows``), the reply scattering straight back
  into the device store;
* wire bytes and server optimizer cost that scale with rows touched,
  never with table size (``ci/check_embedding_perf.py`` pins it).

Synthetic click data with planted preferences keeps it CPU-runnable;
the click signal depends on (user-bucket, item-bucket) affinity so the
model genuinely has to learn the embeddings.

Run: JAX_PLATFORMS=cpu python example/dlrm_click/dlrm_click.py
     [--users 200] [--items 300] [--dim 8] [--epochs 4]
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import mxtpu as mx          # noqa: E402


def build_net(n_users, n_items, dim, dense_dim):
    """Two sparse embedding towers + a dense bottom MLP -> top MLP."""
    user = mx.sym.var("user")
    item = mx.sym.var("item")
    dense = mx.sym.var("dense")
    u_w = mx.sym.var("user_emb_weight", stype="row_sparse")
    i_w = mx.sym.var("item_emb_weight", stype="row_sparse")
    u = mx.sym.Embedding(user, weight=u_w, input_dim=n_users,
                         output_dim=dim, name="user_emb")
    i = mx.sym.Embedding(item, weight=i_w, input_dim=n_items,
                         output_dim=dim, name="item_emb")
    u = mx.sym.Reshape(u, shape=(-1, dim))
    i = mx.sym.Reshape(i, shape=(-1, dim))
    bot = mx.sym.FullyConnected(dense, num_hidden=dim, name="bot_fc")
    bot = mx.sym.Activation(bot, act_type="relu")
    # feature interaction: the DLRM dot-interaction rendered as concat
    # of towers + elementwise user*item product
    inter = u * i
    feat = mx.sym.Concat(u, i, bot, inter, dim=1)
    top = mx.sym.FullyConnected(feat, num_hidden=16, name="top_fc1")
    top = mx.sym.Activation(top, act_type="relu")
    top = mx.sym.FullyConnected(top, num_hidden=2, name="top_fc2")
    return mx.sym.SoftmaxOutput(top, name="softmax")


def synth_clicks(n, n_users, n_items, dense_dim, seed=0):
    """Clicks from a planted (user-bucket x item-bucket) affinity."""
    r = np.random.RandomState(seed)
    users = r.randint(0, n_users, n)
    items = r.randint(0, n_items, n)
    dense = r.rand(n, dense_dim).astype("f")
    affinity = r.rand(8, 8)
    p = affinity[users % 8, items % 8] + 0.1 * dense[:, 0]
    clicks = (p > np.median(p)).astype("f")
    return (users.astype("f")[:, None], items.astype("f")[:, None],
            dense, clicks)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--users", type=int, default=200)
    ap.add_argument("--items", type=int, default=300)
    ap.add_argument("--dim", type=int, default=8)
    ap.add_argument("--dense-dim", type=int, default=4)
    ap.add_argument("--samples", type=int, default=1024)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--epochs", type=int, default=4)
    args = ap.parse_args(argv)

    os.environ.setdefault("MXTPU_PS_HEARTBEAT", "0")
    mx.random.seed(0)
    np.random.seed(0)

    users, items, dense, clicks = synth_clicks(
        args.samples, args.users, args.items, args.dense_dim)
    it = mx.io.NDArrayIter(
        {"user": users, "item": items, "dense": dense},
        {"softmax_label": clicks},
        batch_size=args.batch_size, shuffle=True)

    net = build_net(args.users, args.items, args.dim, args.dense_dim)
    mod = mx.mod.Module(net, context=mx.cpu(),
                        data_names=["user", "item", "dense"],
                        label_names=["softmax_label"])
    mod.fit(it, num_epoch=args.epochs, kvstore="dist_async",
            optimizer="adagrad",
            optimizer_params={"learning_rate": 0.1},
            initializer=mx.init.Xavier(),
            eval_metric="acc")

    assert mod._fused is not None and mod._fused.mode == "dist", \
        "the fused sparse dist path must engage"
    assert set(mod._fused._sparse_feeds) == {"user_emb_weight",
                                             "item_emb_weight"}
    stats = mod._kvstore.stats()
    steps = args.epochs * (args.samples // args.batch_size)
    # one sparse push per table per step; rows bounded by the batch,
    # never the table
    assert stats["sparse_pushes"] == 2 * steps, stats["sparse_pushes"]
    assert stats["sparse_rows"] <= 2 * steps * args.batch_size

    it.reset()
    acc = dict(mod.score(it, "acc"))["accuracy"]
    print("click accuracy: %.3f  (sparse pushes: %d, rows touched: %d)"
          % (acc, stats["sparse_pushes"], stats["sparse_rows"]))
    assert acc > 0.7, acc
    mod._kvstore.close()
    return acc


if __name__ == "__main__":
    main()
