#!/usr/bin/env python
"""Inference throughput benchmark across the model zoo (reference
example/image-classification/benchmark_score.py:45-84 — the source of the
docs/faq/perf.md inference tables). Prints images/sec per (model, batch).
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np  # noqa: E402
import mxtpu as mx  # noqa: E402
from mxtpu.gluon.model_zoo import vision  # noqa: E402

MODELS = {
    "alexnet": vision.alexnet,
    "vgg16": lambda **kw: vision.get_vgg(16, **kw),
    "resnet-50": lambda **kw: vision.get_resnet(1, 50, **kw),
    "resnet-152": lambda **kw: vision.get_resnet(1, 152, **kw),
    "inception-v3": vision.inception_v3,
    "mobilenet": lambda **kw: vision.get_mobilenet(1.0, **kw),
    "squeezenet": vision.squeezenet1_0,
    "densenet121": vision.densenet121,
}

# models that exist as symbol builders rather than gluon zoo blocks
# (the reference scored Inception-BN from its symbol library too)
SYMBOL_MODELS = {"inception-bn": "inception_bn"}


def _score_symbol(model_name, batch, hw, n_iter):
    from importlib import import_module
    here = os.path.dirname(os.path.abspath(__file__))
    if here not in sys.path:   # callers import this module by file path
        sys.path.insert(0, here)
    mod = import_module("symbols." + SYMBOL_MODELS[model_name])
    sym = mod.get_symbol(1000, "3,%d,%d" % (hw, hw))
    ex = sym.simple_bind(grad_req="null",
                         data=(batch, 3, hw, hw),
                         softmax_label=(batch,))
    ex.arg_dict["data"][:] = np.random.uniform(
        size=(batch, 3, hw, hw)).astype(np.float32)
    from mxtpu.benchmarking import timed_steps
    sec, _ = timed_steps(lambda _s: ex.forward(is_train=False)[0],
                         warmup=2, iters=n_iter)
    return batch / sec


def score(model_name, batch, hw, n_iter=10, dtype="float32"):
    mx.random.seed(0)
    if model_name in SYMBOL_MODELS:
        assert dtype == "float32", \
            "symbol-path scoring is fp32 (the reference methodology)"
        return _score_symbol(model_name, batch, hw, n_iter)
    net = MODELS[model_name]()
    net.initialize(mx.init.Xavier(), force_reinit=True)
    if dtype != "float32":
        net.cast(dtype)
    net.hybridize()
    x = mx.nd.array(np.random.uniform(
        size=(batch, 3, hw, hw)).astype(np.float32))
    if dtype != "float32":
        x = x.astype(dtype)
    from mxtpu.benchmarking import timed_steps
    sec, _ = timed_steps(lambda _s: net(x), warmup=2, iters=n_iter)
    return batch / sec


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--models", default="resnet-50")
    p.add_argument("--batch-sizes", default="1,8,32")
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--iters", type=int, default=10)
    args = p.parse_args()
    for name in args.models.split(","):
        hw = 299 if name == "inception-v3" else args.image_size
        for b in (int(x) for x in args.batch_sizes.split(",")):
            img_s = score(name, b, hw, args.iters)
            print("network: %-14s batch: %3d  images/sec: %.2f"
                  % (name, b, img_s))


if __name__ == "__main__":
    main()
