#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the main path once, through the entry points a user
calls, at the full width of ResNet-50 (weights random, from a seed):

* device  — JAX's default device is a TPU, or the script exits non-zero;
* train   — ``example/image-classification/train_imagenet.py --benchmark 1``
            (``common/fit.py::fit`` -> ``Module.fit``) for a few steps with
            ``MXTPU_AMP=bf16``: fused step engaged, everything on the TPU,
            one compile, loss falling on the repeated synthetic batch;
* serve   — the checkpoint that run saved, through ``InferenceEngine`` ->
            ``ModelServer`` -> ``ServingClient``: answers equal
            ``Module.predict``; then the generate menu (prefill / decode /
            adopt, donated KV) on ``example/char_lm`` at its own size — no
            full-width language model exists in the repo yet;
* kernels — Pallas flash attention forward+backward compiled by Mosaic at
            T=8192 and equal to the einsum reference at T=2048; ``sym.RNN``
            LSTM and GRU at the PTB-large hidden size through the default
            op path, equal to the plain ``lax.scan`` reference;
* mesh    — with >= 4 devices: the train leg as one SPMD program over
            ``MXTPU_MESH=data=4``.

Each leg prints one line saying what it asserted; a failed assertion ends
the run non-zero. The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``. Nothing here is a
benchmark: the step time is printed for information only.

``--legs a,b`` runs a subset while debugging (the JSON then says
``"ok": false, "partial": true``).
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import tempfile
import time
import types

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
for _p in (os.path.join(ROOT, "example", "char_lm"),
           os.path.join(ROOT, "example", "image-classification"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

LEGS = ("train", "serve", "generate", "kernels", "mesh")

# The width the contract asks for: ResNet-50, 1000 classes, 3x224x224,
# batch 32; char_lm at the example's own defaults; the kernels at the
# sizes the compiler refused before this script existed.
FULL = types.SimpleNamespace(
    layers=50, classes=1000, image=(3, 224, 224), batch=32, steps=8,
    buckets=(1, 8, 32),
    requests=(1, 3, 8, 5, 32, 2, 8, 1, 17, 4, 32, 6, 1, 8, 9, 2),
    lm_dim=32, lm_heads=2, lm_layers=2, lm_cache=64, lm_prompt=16,
    lm_decode=32,
    flash_t=8192, flash_t_ref=2048, flash_heads=8, flash_dims=(64, 128),
    rnn_t=35, rnn_n=32, rnn_h=1500)

# The same legs at a size the CPU tests can afford
# (tests/test_device_selection.py).
TOY = types.SimpleNamespace(
    layers=8, classes=12, image=(3, 16, 16), batch=8, steps=4,
    buckets=(1, 4, 8), requests=(1, 3, 8, 2),
    lm_dim=16, lm_heads=2, lm_layers=1, lm_cache=32, lm_prompt=8,
    lm_decode=8,
    flash_t=256, flash_t_ref=128, flash_heads=2, flash_dims=(64,),
    rnn_t=5, rnn_n=4, rnn_h=16)


def _check(cond, msg):
    if not cond:
        raise AssertionError(msg)


@contextlib.contextmanager
def _env(**pairs):
    old = {k: os.environ.get(k) for k in pairs}
    os.environ.update(pairs)
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _leaves(x):
    """Raw jax arrays under an NDArray / tuple / None optimizer state."""
    if x is None:
        return []
    if isinstance(x, (tuple, list)):
        return [leaf for y in x for leaf in _leaves(y)]
    return [x._data if hasattr(x, "_data") else x]


def _store(mod):
    """Every persistent device buffer of the train step: parameters,
    aux states, optimizer-state leaves."""
    ex = mod._exec_group.execs[0]
    params = [ex.arg_dict[n]._data for n in mod._exec_group.param_names]
    aux = [a._data for a in ex.aux_dict.values()]
    opt = [leaf for st in mod._updater.states.values()
           for leaf in _leaves(st)]
    return params, aux, opt


def _rel_err(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-12))


# -- device ----------------------------------------------------------------

_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": 0,
                 "/jax/compilation_cache/cache_misses": 0}


def _count_cache_event(event, **_kw):
    if event in _CACHE_EVENTS:
        _CACHE_EVENTS[event] += 1


def leg_device():
    import jax
    import mxtpu  # noqa: F401  (places the compile cache)
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print("device: platform=%s device_kind=%r count=%d jax=%s "
          "compile_cache=%s" % (info["platform"], info["kind"],
                                info["count"], jax.__version__,
                                jax.config.jax_compilation_cache_dir),
          flush=True)
    return info


# -- train -----------------------------------------------------------------

def leg_train(sz, platform, workdir, mesh=None):
    """``train_imagenet.py --benchmark 1`` for ``sz.steps`` steps. Returns
    what the later legs need: the Module, the checkpoint prefix, the
    per-step losses and the last batch."""
    import jax
    import mxtpu as mx
    import train_imagenet
    from mxtpu.module import fused

    tag = "mesh" if mesh else "train"
    prefix = os.path.join(workdir, tag, "resnet")
    argv = ["--benchmark", "1", "--num-layers", str(sz.layers),
            "--num-classes", str(sz.classes),
            "--image-shape", ",".join(str(d) for d in sz.image),
            "--batch-size", str(sz.batch),
            "--num-examples", str(sz.batch * sz.steps),
            "--num-epochs", "1", "--lr", "0.02",
            "--disp-batches", str(sz.steps), "--model-prefix", prefix]
    losses, stamps, compiles, last = [], [], [], {}

    def on_batch(param):
        mod = param.locals["self"]
        probs = mod.get_outputs()[0]
        jax.block_until_ready(probs._data)
        stamps.append(time.perf_counter())
        batch = param.locals["batch"]
        y = batch.label[0].asnumpy().astype(np.int64)
        p = probs.asnumpy().astype(np.float64)
        losses.append(float(-np.log(np.maximum(
            p[np.arange(len(y)), y], 1e-30)).mean()))
        compiles.append(mod._fused._group.stats["compiles"]
                        if mod._fused is not None else -1)
        last["batch"] = batch

    mx.random.seed(0)
    np.random.seed(0)
    steps0 = fused._M_STEPS.default().value
    env = {"MXTPU_AMP": "bf16"}
    if mesh:
        env["MXTPU_MESH"] = mesh
    with _env(**env):
        mod = train_imagenet.main(argv, batch_end_callback=on_batch)
    steps = fused._M_STEPS.default().value - steps0

    _check(mod._fused is not None, "%s: fused step not engaged" % tag)
    fs = mod._fused._group
    _check(fs.amp == "bf16", "%s: AMP is %r, not bf16" % (tag, fs.amp))
    _check(steps == sz.steps == fs.stats["steps"] == len(losses),
           "%s: %d steps taken, module.steps counted %d, fused group %d "
           "(an eager fallback ran)" % (tag, sz.steps, steps,
                                        fs.stats["steps"]))
    programs = len(mod._fused._cache)
    _check(compiles[0] == compiles[-1] == programs,
           "%s: compiles per step %r for %d program signature(s) — a "
           "compile after step 1" % (tag, compiles, programs))
    params, aux, opt = _store(mod)
    devs = set()
    for a in params + aux + opt:
        devs |= a.devices()
    _check(opt and all(d.platform == platform for d in devs),
           "%s: store lives on %r, wanted only %s devices"
           % (tag, sorted(str(d) for d in devs), platform))
    _check(all(np.isfinite(losses)) and losses[-1] < losses[0],
           "%s: loss did not fall on the repeated batch: %r"
           % (tag, losses))
    step_ms = float(np.median(np.diff(stamps)[1:])) * 1e3
    print("%s: resnet-%d b%d bf16 %d fused steps, 0 eager; %d compile(s) "
          "for %d program(s), none after step 1; %d param + %d aux + %d "
          "optimizer buffers on %s; loss %.4f -> %.4f; step %.1f ms "
          "(host clock after block_until_ready, information only)"
          % (tag, sz.layers, sz.batch, steps, compiles[-1], programs,
             len(params), len(aux), len(opt),
             ",".join(sorted(str(d) for d in devs)), losses[0],
             losses[-1], step_ms), flush=True)
    return {"mod": mod, "prefix": prefix, "losses": losses,
            "batch": last["batch"]}


# -- serve -----------------------------------------------------------------

def leg_serve(sz, platform, trained):
    import mxtpu as mx
    from mxtpu.serving import InferenceEngine, ModelServer, ServingClient

    mod = trained["mod"]
    rng = np.random.RandomState(1)
    x = rng.uniform(-1, 1, (sz.batch,) + sz.image).astype(np.float32)
    want = mod.predict(mx.io.NDArrayIter(x, batch_size=sz.batch)).asnumpy()

    engine = InferenceEngine.from_checkpoint(
        trained["prefix"], 1, {"data": sz.image}, buckets=sz.buckets)
    _check(engine.cache.compiles == len(sz.buckets),
           "serve: %d compiles for %d buckets"
           % (engine.cache.compiles, len(sz.buckets)))
    devs = engine.store_devices()
    _check(all(d.platform == platform for d in devs),
           "serve: weight store on %r" % (devs,))
    srv = ModelServer(engine, port=0, model_name="resnet").start()
    cli = ServingClient(addrs=[srv.address])
    try:
        worst = 0.0
        for rows in sz.requests:
            out = cli.predict(x[:rows])[0]
            _check(out.shape == (rows, sz.classes) and
                   np.isfinite(out).all(),
                   "serve: %d-row request answered %r" % (rows, out.shape))
            worst = max(worst, _rel_err(out, want[:rows]))
    finally:
        cli.close()
        srv.stop()
    tol = 2e-2
    _check(worst <= tol, "serve: outputs differ from Module.predict by "
           "%.3g of the largest probability (tolerance %.3g)"
           % (worst, tol))
    _check(engine.cache.compiles == len(sz.buckets),
           "serve: a request compiled (%d programs now)"
           % engine.cache.compiles)
    print("serve: %d requests of %s rows answered through ModelServer/"
          "ServingClient; max |p - Module.predict| = %.2g of the largest "
          "probability (tolerance %.0e); %d compiles = %d buckets, 0 "
          "during requests; weight store on %s"
          % (len(sz.requests), "/".join(str(r) for r in sz.requests),
             worst, tol, engine.cache.compiles, len(sz.buckets),
             ",".join(str(d) for d in devs)), flush=True)


# -- generate --------------------------------------------------------------

def _lm_reference_logits(params, tokens, heads, layers):
    """example/char_lm's stack as plain float32 jax.numpy over the whole
    sequence at once: dense causal multi-head attention with the ALiBi
    bias, no KV cache. Returns logits [T, vocab]."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST

    def fc(x, name):
        return jnp.dot(x, params[name + "_weight"].T, precision=hi) \
            + params[name + "_bias"]

    x = jnp.asarray(params["tok_emb_weight"])[jnp.asarray(tokens)]
    t, d = x.shape
    hd = d // heads
    pos = jnp.arange(t)
    dist = (pos[:, None] - pos[None, :]).astype(jnp.float32)
    slopes = jnp.asarray([2.0 ** (-8.0 * (i + 1) / heads)
                          for i in range(heads)], jnp.float32)
    for li in range(layers):
        q, k, v = (fc(x, "l%d_%s" % (li, n)).reshape(t, heads, hd)
                   for n in "qkv")
        s = jnp.einsum("thd,shd->hts", q, k, precision=hi) / np.sqrt(hd) \
            - slopes[:, None, None] * dist[None]
        s = jnp.where(dist[None] >= 0, s, -1e30)
        att = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v,
                         precision=hi).reshape(t, d)
        x = x + fc(att, "l%d_o" % li)
        x = x + fc(jax.nn.relu(fc(x, "l%d_f1" % li)), "l%d_f2" % li)
    return fc(x, "head")


def leg_generate(sz, platform, workdir):
    import mxtpu as mx
    import char_lm
    from mxtpu.model import save_checkpoint
    from mxtpu.serving import InferenceEngine, ModelServer, ServingClient

    # random weights from a seed, through the example's own builders
    mx.random.seed(0)
    np.random.seed(0)
    t = sz.lm_prompt
    shapes = [("data", (1, t)), ("pos", (1,))]
    for li in range(sz.lm_layers):
        shapes += [("kc%d" % li, (1, t, sz.lm_dim)),
                   ("vc%d" % li, (1, t, sz.lm_dim))]
    mod = mx.mod.Module(
        char_lm.train_symbol(sz.lm_dim, sz.lm_heads, sz.lm_layers, t),
        data_names=[n for n, _ in shapes], label_names=["softmax_label"])
    mod.bind(data_shapes=shapes, label_shapes=[("softmax_label", (1, t))],
             for_training=False)
    mod.init_params(mx.init.Xavier(magnitude=6.0))
    arg_params, aux_params = mod.get_params()
    prefix = os.path.join(workdir, "char_lm")
    save_checkpoint(prefix, 0,
                    char_lm.gen_symbol(sz.lm_dim, sz.lm_heads,
                                       sz.lm_layers, sz.lm_cache),
                    arg_params, aux_params)

    engine = InferenceEngine.from_checkpoint(
        prefix, 0, {"data": (1,)}, buckets=(1,))
    _check(engine.is_generative, "generate: KV contract not detected")
    devs = engine.store_devices()
    _check(all(d.platform == platform for d in devs),
           "generate: weight store on %r" % (devs,))
    prompt = np.random.RandomState(2).randint(
        0, char_lm.VOCAB, sz.lm_prompt).astype(np.int32)
    srv = ModelServer(engine, port=0, model_name="char_lm").start()
    cli = ServingClient(addrs=[srv.address])
    try:
        before = engine.cache.compiles
        toks, _info = cli.generate2(prompt, max_new=sz.lm_decode + 1,
                                    model="char_lm")
        sched = srv.stats()["models"]["char_lm"]["scheduler"]
    finally:
        cli.close()
        srv.stop()
    _check(len(toks) == sz.lm_decode + 1 and sched["steps"] >= sz.lm_decode,
           "generate: %d tokens, %d decode steps"
           % (len(toks), sched["steps"]))
    _check(engine.cache.compiles == before,
           "generate: decode retraced (%d -> %d programs)"
           % (before, engine.cache.compiles))
    # greedy decode of the reference reproduces the served tokens iff, at
    # every position, the served token is the reference's argmax — ties
    # within the stated tolerance count as equal
    seq = np.concatenate([prompt, np.asarray(toks, np.int32)])
    logits = np.asarray(_lm_reference_logits(
        {k: v.asnumpy() for k, v in arg_params.items()}, seq[:-1],
        sz.lm_heads, sz.lm_layers))[len(prompt) - 1:]
    tol = 2e-2 * float(np.abs(logits).max())
    gap = logits.max(axis=-1) - logits[np.arange(len(toks)), toks]
    exact = int((gap == 0).sum())
    _check((gap <= tol).all(),
           "generate: served tokens leave the reference greedy decode "
           "(logit gaps %r, tolerance %.3g)" % (gap.tolist(), tol))
    print("generate: char_lm d%d x%d layers, cache %d (the example's own "
          "size — no full-width language model exists in the repo yet); "
          "prefill + %d decode steps through ModelServer, 0 retraces over "
          "%d programs; %d/%d tokens are the plain jax.numpy reference's "
          "argmax, the rest within %.2g logits"
          % (sz.lm_dim, sz.lm_layers, sz.lm_cache, sched["steps"], before,
             exact, len(toks), tol), flush=True)


# -- kernels ---------------------------------------------------------------

def _flash_check(sz, d, platform):
    import jax
    import jax.numpy as jnp
    from mxtpu.ops.pallas_attention import (flash_attention,
                                            flash_attention_reference)

    def both(attn):
        def f(q, k, v):
            o = attn(q, k, v, causal=True)
            return o.astype(jnp.float32).sum(), o
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                          has_aux=True))

    flash, ref = both(flash_attention), both(flash_attention_reference)
    key = jax.random.PRNGKey(d)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i),
                                 (1, sz.flash_heads, sz.flash_t, d),
                                 jnp.bfloat16) for i in range(3))
    if platform == "tpu":
        _check("tpu_custom_call" in flash.lower(q, k, v).as_text(),
               "kernels: flash d%d did not lower to Mosaic" % d)
    (_, o), grads = flash(q, k, v)
    _check(all(bool(jnp.isfinite(a.astype(jnp.float32)).all())
               for a in (o,) + grads),
           "kernels: flash d%d T=%d produced non-finite values"
           % (d, sz.flash_t))
    qs, ks, vs = (a[:, :, :sz.flash_t_ref] for a in (q, k, v))
    (_, o_f), g_f = flash(qs, ks, vs)
    (_, o_r), g_r = ref(qs, ks, vs)
    return max(_rel_err(a, b) for a, b in zip((o_f,) + g_f, (o_r,) + g_r))


def _rnn_check(sz, mode, platform):
    import jax.numpy as jnp
    import mxtpu as mx
    from mxtpu.ops import pallas_rnn, rnn as rnn_ops

    t, n, h = sz.rnn_t, sz.rnn_n, sz.rnn_h
    psize = rnn_ops.rnn_param_size(mode, h, h, 1, False)
    args = {"data": (t, n, h), "parameters": (psize,), "state": (1, n, h)}
    syms = {name: mx.sym.Variable(name) for name in args}
    if mode == "lstm":
        args["state_cell"] = (1, n, h)
        syms["state_cell"] = mx.sym.Variable("state_cell")
    out = mx.sym.RNN(state_size=h, num_layers=1, mode=mode, **syms)
    ex = out.simple_bind(grad_req="write", **args)
    rng = np.random.RandomState(3)
    x = rng.standard_normal((t, n, h)).astype(np.float32)
    w = rng.uniform(-1, 1, psize).astype(np.float32) / np.sqrt(h)
    h0 = (rng.standard_normal((1, n, h)) * 0.1).astype(np.float32)
    ex.arg_dict["data"][:] = x
    ex.arg_dict["parameters"][:] = w
    ex.arg_dict["state"][:] = h0
    if mode == "lstm":
        ex.arg_dict["state_cell"][:] = h0
    got = ex.forward(is_train=True)[0]
    _check(all(d.platform == platform for d in got._data.devices()),
           "kernels: %s ran on %r" % (mode, got._data.devices()))
    ex.backward(out_grads=mx.nd.ones(got.shape))
    _check(np.isfinite(ex.grad_dict["parameters"].asnumpy()).all(),
           "kernels: %s gradient is not finite" % mode)

    ((wi, wh),), ((bi, bh),) = rnn_ops._unpack_params(
        jnp.asarray(w), mode, h, h, 1, 1)
    xp = jnp.einsum("tni,gi->tng", jnp.asarray(x), wi) + bi
    if mode == "lstm":
        want = pallas_rnn._scan_reference(xp + bh, h0[0], h0[0], wh.T)[0]
    else:
        xp = xp.at[:, :, :2 * h].add(bh[:2 * h])
        want = pallas_rnn._gru_scan_reference(
            xp, h0[0], wh[:2 * h].T, wh[2 * h:].T, bh[2 * h:])[0]
    return _rel_err(got.asnumpy(), want)


def leg_kernels(sz, platform):
    tol = 3e-2
    flash = {d: _flash_check(sz, d, platform) for d in sz.flash_dims}
    rnn = {m: _rnn_check(sz, m, platform) for m in ("lstm", "gru")}
    for name, err in list(flash.items()) + list(rnn.items()):
        _check(err <= tol, "kernels: %s differs from its reference by "
               "%.3g (tolerance %.3g)" % (name, err, tol))
    print("kernels: flash attention fwd+bwd causal bf16 (1, %d, %d, d) at "
          "default blocks %s, finite; vs einsum reference at T=%d: %s; "
          "sym.RNN default path T=%d N=%d H=%d vs lax.scan reference: %s "
          "(relative to the largest value, tolerance %.0e)"
          % (sz.flash_heads, sz.flash_t,
             "compiled by Mosaic (tpu_custom_call)" if platform == "tpu"
             else "through the Pallas interpreter", sz.flash_t_ref,
             ", ".join("d%d %.2g" % kv for kv in flash.items()),
             sz.rnn_t, sz.rnn_n, sz.rnn_h,
             ", ".join("%s %.2g" % kv for kv in rnn.items()), tol),
          flush=True)


# -- mesh ------------------------------------------------------------------

def leg_mesh(sz, platform, workdir, first_loss):
    """The train leg as one SPMD program over four devices."""
    n = 4
    run = leg_train(sz, platform, workdir, mesh="data=%d" % n)
    mod = run["mod"]
    _check(mod._fused._group.mesh is not None, "mesh: group has no mesh")
    params, _aux, opt = _store(mod)
    per_dev, total = {}, 0
    for a in params + opt:
        total += a.nbytes
        for s in a.addressable_shards:
            per_dev[s.device.id] = per_dev.get(s.device.id, 0) \
                + s.data.nbytes
    _check(len(per_dev) == n and
           all(abs(b - total / n) <= 0.01 * total / n
               for b in per_dev.values()),
           "mesh: per-device param+optimizer bytes %r, wanted %d/%d"
           % (per_dev, total, n))
    compiled = mod._fused.compiled_step(run["batch"])
    # positional argument 3 holds the non-donated inputs, data first
    data_sh = compiled.input_shardings[0][3][0]
    rows = data_sh.shard_shape((sz.batch,) + sz.image)[0]
    _check(rows == sz.batch // n,
           "mesh: each device gets %d batch rows, wanted %d"
           % (rows, sz.batch // n))
    text = compiled.as_text()
    coll = [c for c in ("all-reduce", "reduce-scatter") if c in text]
    _check(coll, "mesh: no all-reduce or reduce-scatter in the compiled "
           "step")
    tol = 2e-2
    err = abs(run["losses"][0] - first_loss) / abs(first_loss)
    _check(err <= tol, "mesh: first-step loss %.5f vs %.5f on one device "
           "(relative %.3g, tolerance %.3g)"
           % (run["losses"][0], first_loss, err, tol))
    print("mesh: data=%d — each device holds %d of %d param+optimizer "
          "bytes (1/%d within 1%%) and %d of %d batch rows; %s in the "
          "compiled step; first-step loss %.5f vs %.5f on one device "
          "(relative %.2g, tolerance %.0e)"
          % (n, max(per_dev.values()), total, n, rows, sz.batch,
             " and ".join(coll), run["losses"][0], first_loss, err, tol),
          flush=True)


# -- main ------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--legs", default=",".join(LEGS),
                    help="comma list of %s (debugging aid; the device leg "
                         "always runs)" % "/".join(LEGS))
    legs = [leg for leg in ap.parse_args(argv).legs.split(",") if leg]
    unknown = sorted(set(legs) - set(LEGS))
    if unknown:
        ap.error("unknown legs %r" % (unknown,))

    import jax
    jax.monitoring.register_event_listener(_count_cache_event)
    device = leg_device()
    if device["platform"] != "tpu":
        print("chip_smoke: JAX found no TPU (default platform is %r); "
              "nothing was run" % device["platform"], file=sys.stderr)
        return 1

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        trained = None
        if {"train", "serve", "mesh"} & set(legs):
            trained = leg_train(FULL, "tpu", workdir)
        if "serve" in legs:
            leg_serve(FULL, "tpu", trained)
        if "generate" in legs:
            leg_generate(FULL, "tpu", workdir)
        if "kernels" in legs:
            leg_kernels(FULL, "tpu")
        if "mesh" in legs:
            if device["count"] >= 4:
                leg_mesh(FULL, "tpu", workdir, trained["losses"][0])
            else:
                print("mesh: not run, %d device" % device["count"],
                      flush=True)
    hits, misses = _CACHE_EVENTS.values()
    print("compile cache: %d hit(s), %d miss(es) in %s"
          % (hits, misses, jax.config.jax_compilation_cache_dir),
          flush=True)
    result = {"ok": set(legs) == set(LEGS), "device": device}
    if not result["ok"]:
        result["partial"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
