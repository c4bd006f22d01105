"""The operation and parameter counts the MFU metrics stand on, and the
reference's leaves against the program's own symbols."""
import json
import os

import numpy as np

from conftest import ROOT


def cfg(name):
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as f:
        return json.load(f)


def test_resnet50_multiply_adds():
    from benchmarks.reference import resnet
    assert resnet.macs_per_image(cfg("resnet50")) == 4_089_184_256


def test_bloom_parameters():
    from benchmarks.reference import bloom
    c = cfg("bloom-1b7")
    assert bloom.parameter_count(c) == 1_722_408_960
    assert bloom.ops_per_token(c) == 2 * 1_722_408_960
    # weights once, plus live key/value rows: 24 layers x 2 x 2048 x 2 bytes
    assert bloom.decode_step_bytes(c, 1000) == 2 * 1_722_408_960 + 1000 * 196_608


def test_resnet_leaves_match_symbol():
    from benchmarks.models import resnet as model
    from benchmarks.reference import resnet
    c = cfg("resnet50")
    sym = model.symbol(c)
    args, _outs, aux = sym.infer_shape(data=(2, 3, 224, 224))
    got = dict(zip(sym.list_arguments(), args))
    params, aux_l, _convs = resnet.layout(c)
    assert {n: tuple(s) for n, s, _k in params} == {
        n: tuple(s) for n, s in got.items()
        if n not in ("data", "softmax_label")}
    assert {n: tuple(s) for n, s, _k in aux_l} == dict(
        zip(sym.list_auxiliary_states(), map(tuple, aux)))
    assert sum(int(np.prod(s)) for _n, s, _k in params) == 25_549_486


def test_bloom_leaves_match_symbol():
    from benchmarks.models import bloom as model
    from benchmarks.reference import bloom
    c = cfg("bloom-1b7")
    args = set(model.symbol(c).list_arguments())
    leaves = {n for n, _s, _k in bloom.layout(c)}
    assert leaves <= args
    rest = args - leaves
    assert rest == {"data", "pos"} | {"%sc%d" % (k, i) for k in "kv"
                                      for i in range(c["n_layer"])}


def test_traffic_blocks_hold_the_same_work():
    from benchmarks import traffic
    mix = json.load(open(os.path.join(ROOT, "benchmarks", "traffic",
                                      "closed-32.json")))
    mix.update(loop="open", rate_per_s=2.0)
    sums = []
    for seed in (1, 2**31 + 5):
        s = traffic.Stream(mix, 1000, seed)
        block = [s.next() for _ in range(mix["pool"])]
        sums.append((round(sum(b[0] for b in block), 9),
                     sum(len(b[1]) for b in block), sum(b[2] for b in block)))
        assert all(mix["prompt_len"]["min"] <= len(b[1])
                   <= mix["prompt_len"]["max"] for b in block)
    assert sums[0] == sums[1]
    assert abs(sums[0][0] - mix["pool"] / mix["rate_per_s"]) < 1e-6
