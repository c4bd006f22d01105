#!/usr/bin/env python
"""Transformer-with-MoE, trained AND served sharded over a device mesh.

The ISSUE 20 open workload: a model laid out by ONE `PartitionRules`
list across every layer of the stack — the pjit-sharded fused train
step (``Module.set_sharding``), the sharded checkpoint layout, and the
sharded AOT serving menu (``InferenceEngine(mesh=, rules=)``) — on 8
emulated CPU devices. The expert weights shard over the ``expert``
mesh axis (one expert's FFN per device; under a real jit GSPMD lowers
the ``parallel/moe.py`` dispatch einsums to the expert all-to-all),
everything else rides the FSDP-style dim-0 rule, and the whole run is
numerics-parity with the plain single-device path.

Model: token embedding -> causal self-attention (``cached_attention``
at pos=0) -> mixture-of-experts FFN (``sym.moe_ffn`` wrapping
``parallel/moe.py``) -> vocab head; task is next-token prediction on a
periodic synthetic stream (predictable after one period), so learning
proves routing + experts train end to end.

Run (8 virtual devices):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      python example/moe_transformer/moe_transformer.py
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import mxtpu as mx          # noqa: E402

V, D, H, E, FF = 16, 32, 2, 8, 16
T, PERIOD = 16, 4


def build_model(seq_len):
    """2x [cached_attention] -> MoE FFN -> head (two attention layers
    so the copy task's induction circuit can form). The caches /
    ``pos`` are zero-fed data inputs at training (pos=0 degenerates to
    dense causal attention); the MoE weights are declared vars so
    shape inference knows E/FF without a checkpoint."""
    data = mx.sym.Variable("data")
    pos = mx.sym.Variable("pos", shape=(0,), dtype="int32")
    x = mx.sym.Embedding(data=data, input_dim=V, output_dim=D,
                         name="tok_emb")
    for li in range(2):
        kc = mx.sym.Variable("kc%d" % li, shape=(0, seq_len, D))
        vc = mx.sym.Variable("vc%d" % li, shape=(0, seq_len, D))
        q = mx.sym.FullyConnected(data=x, num_hidden=D, flatten=False,
                                  name="l%d_q" % li)
        k = mx.sym.FullyConnected(data=x, num_hidden=D, flatten=False,
                                  name="l%d_k" % li)
        v = mx.sym.FullyConnected(data=x, num_hidden=D, flatten=False,
                                  name="l%d_v" % li)
        att = mx.sym.cached_attention(q, k, v, kc, vc, pos, num_heads=H,
                                      alibi=True, name="l%d_att" % li)
        o = mx.sym.FullyConnected(data=att[0], num_hidden=D,
                                  flatten=False, name="l%d_o" % li)
        x = x + o
    gate = mx.sym.Variable("moe_gate", shape=(D, E))
    w1 = mx.sym.Variable("moe_w1", shape=(E, D, FF))
    b1 = mx.sym.Variable("moe_b1", shape=(E, FF))
    w2 = mx.sym.Variable("moe_w2", shape=(E, FF, D))
    b2 = mx.sym.Variable("moe_b2", shape=(E, D))
    moe = mx.sym.moe_ffn(x, gate, w1, b1, w2, b2,
                         capacity_factor=2.0, num_selected=1,
                         name="moe")
    x = x + moe[0]
    logits = mx.sym.FullyConnected(data=x, num_hidden=V, flatten=False,
                                   name="head")
    flat = mx.sym.Reshape(logits, shape=(-1, V))
    return mx.sym.SoftmaxOutput(flat, name="softmax")


def moe_init_params(seed=11):
    """Explicit init for the declared MoE vars (3-D expert stacks are
    outside the name-pattern initializers' vocabulary)."""
    rng = np.random.RandomState(seed)
    s = 0.1
    host = {"moe_gate": rng.randn(D, E).astype(np.float32) * s,
            "moe_w1": rng.randn(E, D, FF).astype(np.float32) * s,
            "moe_b1": np.zeros((E, FF), np.float32),
            "moe_w2": rng.randn(E, FF, D).astype(np.float32) * s,
            "moe_b2": np.zeros((E, D), np.float32)}
    return {k: mx.nd.array(v) for k, v in host.items()}


def sharding_rules():
    """One rule list, every layout (PartitionRules' contract): expert
    stacks over the ``expert`` axis (dim 0 = expert index), everything
    else FSDP-style dim-0 over the same devices where it divides."""
    from mxtpu.parallel import PartitionSpec as P
    from mxtpu.partition import PartitionRules
    return PartitionRules([
        (r"moe_(w|b)[12]$", P("expert")),
        (r"moe_gate$", P(None, "expert")),
        (r".*", P("expert")),
    ])


def stream_batches(n=256, seed=3):
    """Periodic token stream: position t repeats t - PERIOD, so the
    next token is predictable from attention over the window."""
    rng = np.random.RandomState(seed)
    head = rng.randint(0, V, size=(n, PERIOD))
    reps = (T + 1 + PERIOD - 1) // PERIOD + 1
    full = np.tile(head, (1, reps))[:, :T + 1]
    return full[:, :T].astype("f"), full[:, 1:].astype("f")


def train(mesh=None, rules=None, epochs=6):
    np.random.seed(0)
    mx.random.seed(0)
    X, Y = stream_batches()
    feed = {"data": X, "pos": np.zeros((len(X),), "f")}
    for li in range(2):
        feed["kc%d" % li] = np.zeros((len(X), T, D), "f")
        feed["vc%d" % li] = np.zeros((len(X), T, D), "f")
    it = mx.io.NDArrayIter(feed, {"softmax_label": Y}, batch_size=32,
                           shuffle=True)
    mod = mx.mod.Module(build_model(T), data_names=sorted(feed),
                        label_names=["softmax_label"])
    if mesh is not None:
        mod.set_sharding(mesh, rules)
    mod.fit(it, num_epoch=epochs, optimizer="adam",
            optimizer_params={"learning_rate": 1e-2},
            initializer=mx.init.Xavier(),
            arg_params=moe_init_params(), allow_missing=True,
            eval_metric=mx.metric.Perplexity(ignore_label=None))
    it.reset()
    ppl = dict(mod.score(
        it, mx.metric.Perplexity(ignore_label=None)))["perplexity"]
    args, auxs = mod.get_params()
    return mod, ppl, {k: v.asnumpy().copy() for k, v in args.items()}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=20)
    ap.add_argument("--expert-axis", type=int, default=0,
                    help="expert mesh axis size (0 = all devices)")
    args = ap.parse_args(argv)
    os.environ.setdefault("MXTPU_PS_HEARTBEAT", "0")
    import jax
    from mxtpu.parallel import MeshContext
    n = args.expert_axis or len(jax.devices())
    mesh = MeshContext({"expert": n})
    rules = sharding_rules()
    print("mesh:", mesh)

    # -- parity: a short run, single-device vs mesh, same seeds ------------
    # (kept short on purpose: the router's argmax amplifies float noise,
    # so long runs legitimately drift at expert-assignment boundaries)
    _, ppl0, p0 = train(epochs=3)
    _, _, p1 = train(mesh=mesh, rules=rules, epochs=3)
    worst = max(float(np.max(np.abs(p0[k] - p1[k]))) for k in p0)
    print("train parity (3 epochs): worst param maxdiff %.3g" % worst)
    # bound sized to a couple of adam steps (lr 1e-2): float noise at an
    # expert-assignment boundary can flip one token's route, a genuine
    # layout bug shifts every parameter by O(0.1)
    assert worst < 5e-3, "sharded training diverged from single-device"

    # -- learn: the full run, sharded end to end ---------------------------
    mod1, ppl1, p1 = train(mesh=mesh, rules=rules, epochs=args.epochs)
    store = mod1._fused._group.param_store
    ndev = len(store["moe_w1"]._data.sharding.device_set)
    spec = store["moe_w1"]._data.sharding.spec
    print("moe_w1 store: devices=%d spec=%s" % (ndev, spec))
    assert ndev == mesh.num_devices, "expert stack not on the mesh"
    print("perplexity start=%.3f (3 epochs) final=%.3f (%d epochs)"
          % (ppl0, ppl1, args.epochs))
    assert ppl1 < 2.5, "sharded MoE did not learn the stream"

    # -- serve it sharded: same rules place the AOT predict menu -----------
    from mxtpu.serving import InferenceEngine
    arg_params, aux_params = mod1.get_params()
    host = {k: v.asnumpy() for k, v in arg_params.items()}
    shapes = {"data": (T,), "pos": ()}
    for li in range(2):
        shapes["kc%d" % li] = (T, D)
        shapes["vc%d" % li] = (T, D)
    e0 = InferenceEngine(build_model(T), host, {}, shapes,
                         buckets=(1, 8), warm=True)
    e1 = InferenceEngine(build_model(T), host, {}, shapes,
                         buckets=(1, 8), warm=True, mesh=mesh,
                         rules=rules)
    x = stream_batches(n=8, seed=9)[0]
    n8 = len(x)
    zeros = {"pos": np.zeros((n8,), np.int32),
             "data": x}
    for li in range(2):
        zeros["kc%d" % li] = np.zeros((n8, T, D), "f")
        zeros["vc%d" % li] = np.zeros((n8, T, D), "f")
    feed = [zeros[n] for n in sorted(shapes)]  # data_names sorted order
    o0 = e0.predict(feed)[0]
    o1 = e1.predict(feed)[0]
    d = float(np.max(np.abs(o0 - o1)))
    print("serve parity: predict maxdiff %.3g" % d)
    assert d < 1e-5, "sharded serving diverged"
    compiles = e1.stats()["compiles"]
    e1.predict(feed)
    assert e1.stats()["compiles"] == compiles, "per-request recompile"
    v = e1.swap_weights(host)
    assert v == 1 and e1.stats()["compiles"] == compiles, \
        "swap_weights must not retrace"
    print("sharded serve: %d programs, 0 per-request recompiles, "
          "swap ok" % compiles)
    return 0


if __name__ == "__main__":
    sys.exit(main())
