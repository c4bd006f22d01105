#!/usr/bin/env python
"""Char-level transformer LM: train -> save_checkpoint -> serve generate.

The autoregressive serving workload (ISSUE 17) end to end on CPU, out
of machinery the tree already trusts:

* ONE builder emits both symbols. The TRAIN symbol runs
  ``cached_attention`` with cache length T and the caches/``pos`` fed
  as zero data inputs — at ``pos=0`` the op is exactly dense causal
  self-attention, and it is differentiable, so ``Module.fit`` trains
  it like any other graph. The GEN symbol is the same stack with a
  LARGER cache (the serving context window), cache variables declared
  ``(0, S, D)`` and every cache returning a ``*_next`` output — the
  KV-cache contract :class:`~mxtpu.serving.InferenceEngine` detects
  and AOT-compiles into donated prefill/decode programs.
* ``save_checkpoint`` writes the GEN symbol + the trained params; the
  serving replica loads it with ``InferenceEngine.from_checkpoint``
  exactly like every other model (``tools/launch.py --serve`` works on
  the same artifact).
* ``ServingClient.generate`` streams tokens from the continuous
  scheduler; greedy decode over the memorized corpus must reproduce
  the training text, and the steady-state decode loop must be
  retrace-free (the compiles counter is pinned).

Run: JAX_PLATFORMS=cpu python example/char_lm/char_lm.py
     [--dim 32] [--layers 2] [--epochs 8] [--seq-len 48]

Long-context training (ISSUE 20): ``--mesh-seq N`` builds an N-way
``seq`` mesh axis and trains the same symbols with attention routed
through ``parallel/ring_attention.py`` — each device holds T/N query
rows, K/V blocks rotate via ppermute, attention memory is O(T/N) per
device — while the fused train step runs as a pjit mesh program
(``Module.set_sharding``). Serving is untouched: decode steps are
T=1 and never route.

Run: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
     python example/char_lm/char_lm.py --mesh-seq 8
"""
import argparse
import os
import shutil
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import mxtpu as mx          # noqa: E402

TEXT = "the quick brown fox jumps over the lazy dog. " * 40
CHARS = sorted(set(TEXT))
C2I = {c: i for i, c in enumerate(CHARS)}
VOCAB = len(CHARS)


def build_lm(dim, heads, layers, cache_len, vocab=VOCAB):
    """One transformer stack, both lives: with ``cache_len=T`` and
    zero-fed caches it is the training graph; with a bigger cache and
    the ``*_next`` outputs grouped in, it is the serving contract."""
    data = mx.sym.Variable("data")
    pos = mx.sym.Variable("pos", shape=(0,), dtype="int32")
    x = mx.sym.Embedding(data=data, input_dim=vocab, output_dim=dim,
                         name="tok_emb")
    cache_next = []
    for li in range(layers):
        kc = mx.sym.Variable("kc%d" % li, shape=(0, cache_len, dim))
        vc = mx.sym.Variable("vc%d" % li, shape=(0, cache_len, dim))
        q = mx.sym.FullyConnected(data=x, num_hidden=dim, flatten=False,
                                  name="l%d_q" % li)
        k = mx.sym.FullyConnected(data=x, num_hidden=dim, flatten=False,
                                  name="l%d_k" % li)
        v = mx.sym.FullyConnected(data=x, num_hidden=dim, flatten=False,
                                  name="l%d_v" % li)
        att = mx.sym.cached_attention(q, k, v, kc, vc, pos,
                                      num_heads=heads, alibi=True,
                                      name="l%d_att" % li)
        o = mx.sym.FullyConnected(data=att[0], num_hidden=dim,
                                  flatten=False, name="l%d_o" % li)
        x = x + o
        f = mx.sym.FullyConnected(data=x, num_hidden=2 * dim,
                                  flatten=False, name="l%d_f1" % li)
        f = mx.sym.Activation(f, act_type="relu")
        f = mx.sym.FullyConnected(data=f, num_hidden=dim, flatten=False,
                                  name="l%d_f2" % li)
        x = x + f
        cache_next.append(mx.sym.identity(att[1], name="kc%d_next" % li))
        cache_next.append(mx.sym.identity(att[2], name="vc%d_next" % li))
    logits = mx.sym.FullyConnected(data=x, num_hidden=vocab,
                                   flatten=False, name="head")
    return logits, cache_next


def train_symbol(dim, heads, layers, seq_len):
    logits, _ = build_lm(dim, heads, layers, seq_len)
    flat = mx.sym.Reshape(logits, shape=(-1, VOCAB))
    return mx.sym.SoftmaxOutput(flat, name="softmax")


def gen_symbol(dim, heads, layers, cache_len):
    logits, cache_next = build_lm(dim, heads, layers, cache_len)
    return mx.sym.Group([logits] + cache_next)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--heads", type=int, default=2)
    ap.add_argument("--layers", type=int, default=2)
    # Train windows must cover the positions decode will visit (prompt
    # 16 + 40 generated = pos 55); ALiBi extrapolates the last few.
    ap.add_argument("--seq-len", type=int, default=48)
    ap.add_argument("--cache-len", type=int, default=64)
    ap.add_argument("--epochs", type=int, default=8)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--model-prefix", default=None,
                    help="checkpoint prefix (default: a temp dir)")
    ap.add_argument("--mesh-seq", type=int, default=0,
                    help="sequence-parallel mesh axis size: train with "
                         "ring attention over N devices (0 = off)")
    args = ap.parse_args(argv)

    os.environ.setdefault("MXTPU_PS_HEARTBEAT", "0")
    mx.random.seed(0)
    np.random.seed(0)
    T, D = args.seq_len, args.dim

    # -- train: sliding next-char windows over the corpus ------------------
    ids = np.asarray([C2I[c] for c in TEXT], np.int32)
    starts = np.arange(0, len(ids) - T - 1, 3)
    X = np.stack([ids[s:s + T] for s in starts]).astype("f")
    Y = np.stack([ids[s + 1:s + T + 1] for s in starts]).astype("f")
    feed = {"data": X, "pos": np.zeros((len(X),), "f")}
    for li in range(args.layers):
        feed["kc%d" % li] = np.zeros((len(X), T, D), "f")
        feed["vc%d" % li] = np.zeros((len(X), T, D), "f")
    it = mx.io.NDArrayIter(feed, {"softmax_label": Y},
                           batch_size=args.batch_size, shuffle=True)
    mod = mx.mod.Module(train_symbol(D, args.heads, args.layers, T),
                        data_names=sorted(feed),
                        label_names=["softmax_label"])
    import contextlib
    train_scope = contextlib.nullcontext()
    if args.mesh_seq > 1:
        # the long-context lever: seq-parallel ring attention inside a
        # pjit mesh train program (attention memory O(T/N) per device)
        from mxtpu.parallel import MeshContext
        from mxtpu.ops.nn import seq_parallel
        if T % args.mesh_seq:
            raise SystemExit("--seq-len %d not divisible by --mesh-seq"
                             " %d" % (T, args.mesh_seq))
        mesh = MeshContext({"seq": args.mesh_seq})
        mod.set_sharding(mesh)
        train_scope = seq_parallel(mesh)
        print("mesh:", mesh, "— attention rides the seq ring")
    with train_scope:
        mod.fit(it, num_epoch=args.epochs, optimizer="adam",
                optimizer_params={"learning_rate": 3e-3},
                initializer=mx.init.Xavier(),
                eval_metric=mx.metric.Perplexity(ignore_label=None))
        it.reset()
        ppl = dict(mod.score(
            it, mx.metric.Perplexity(ignore_label=None)))["perplexity"]
    assert ppl < 1.35, "corpus not learned (perplexity %.3f)" % ppl

    # -- save the GENERATION artifact (bigger cache, same params) ----------
    tmp = None
    prefix = args.model_prefix
    if prefix is None:
        tmp = tempfile.mkdtemp(prefix="char_lm_")
        prefix = os.path.join(tmp, "char_lm")
    arg_params, aux_params = mod.get_params()
    from mxtpu.model import save_checkpoint
    save_checkpoint(prefix, 0,
                    gen_symbol(D, args.heads, args.layers,
                               args.cache_len),
                    arg_params, aux_params)

    # -- serve it: continuous-batching generate over the wire --------------
    from mxtpu.serving import InferenceEngine, ModelServer, ServingClient
    engine = InferenceEngine.from_checkpoint(
        prefix, 0, {"data": (1,)}, buckets=(1,))
    assert engine.is_generative, "gen symbol must declare the KV contract"
    srv = ModelServer(engine, port=0, model_name="char_lm").start()
    try:
        cli = ServingClient(addrs=[srv.address])
        seed = "the quick brown "
        prompt = np.asarray([C2I[c] for c in seed], np.int32)
        toks, info = cli.generate2(prompt, max_new=40, model="char_lm")
        text = "".join(CHARS[t] for t in toks)
        print("seed    : %r" % seed)
        print("generate: %r  (version %s, reason %s)"
              % (text, info["version"], info["reason"]))
        want = "fox jumps over the lazy dog."
        assert text.startswith(want), \
            "memorized corpus not reproduced: %r" % text
        # steady state is retrace-free: a second sequence through the
        # warmed prefill/decode menu must compile NOTHING new
        before = engine.cache.compiles
        toks2, _ = cli.generate2(prompt, max_new=40, model="char_lm")
        assert toks2 == toks, "greedy decode must be deterministic"
        assert engine.cache.compiles == before, \
            "decode retraced (%d -> %d compiles)" \
            % (before, engine.cache.compiles)
        sched = srv.stats()["models"]["char_lm"]["scheduler"]
        print("scheduler: %d sequence(s), %d decode step(s), "
              "%d token(s), 0 retraces"
              % (sched["sequences"], sched["steps"], sched["tokens"]))
    finally:
        srv.stop()
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)
    return ppl


if __name__ == "__main__":
    main()
