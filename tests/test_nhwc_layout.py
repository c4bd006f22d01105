"""MXTPU_CONV_LAYOUT=NHWC — the channels-last experiment knob must be
bit-compatible with the default NCHW path."""
import numpy as np
import pytest

import mxtpu.ndarray as nd


def _both(fn, monkeypatch):
    monkeypatch.delenv("MXTPU_CONV_LAYOUT", raising=False)
    base = fn()
    monkeypatch.setenv("MXTPU_CONV_LAYOUT", "NHWC")
    alt = fn()
    monkeypatch.delenv("MXTPU_CONV_LAYOUT", raising=False)
    return base, alt


def test_conv_nhwc_matches(monkeypatch):
    r = np.random.RandomState(0)
    x = nd.array(r.randn(2, 3, 8, 8).astype("f"))
    w = nd.array(r.randn(4, 3, 3, 3).astype("f"))
    b = nd.array(r.randn(4).astype("f"))

    def run():
        return nd.Convolution(x, w, b, kernel=(3, 3), num_filter=4,
                              stride=(2, 2), pad=(1, 1)).asnumpy()
    base, alt = _both(run, monkeypatch)
    np.testing.assert_allclose(base, alt, rtol=1e-5, atol=1e-5)


def test_grouped_conv_nhwc_matches(monkeypatch):
    r = np.random.RandomState(1)
    x = nd.array(r.randn(1, 4, 6, 6).astype("f"))
    w = nd.array(r.randn(8, 2, 3, 3).astype("f"))

    def run():
        return nd.Convolution(x, w, kernel=(3, 3), num_filter=8,
                              num_group=2, no_bias=True).asnumpy()
    base, alt = _both(run, monkeypatch)
    np.testing.assert_allclose(base, alt, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("pool_type", ["max", "avg", "sum"])
def test_pooling_nhwc_matches(monkeypatch, pool_type):
    r = np.random.RandomState(2)
    x = nd.array(r.randn(2, 3, 7, 7).astype("f"))

    def run():
        return nd.Pooling(x, kernel=(3, 3), pool_type=pool_type,
                          stride=(2, 2), pad=(1, 1),
                          count_include_pad=False).asnumpy()
    base, alt = _both(run, monkeypatch)
    np.testing.assert_allclose(base, alt, rtol=1e-5, atol=1e-5)


def test_pooling_full_convention_and_global(monkeypatch):
    r = np.random.RandomState(3)
    x = nd.array(r.randn(1, 2, 9, 9).astype("f"))

    def run_full():
        return nd.Pooling(x, kernel=(3, 3), pool_type="max", stride=(2, 2),
                          pooling_convention="full").asnumpy()

    def run_global():
        return nd.Pooling(x, pool_type="avg", global_pool=True,
                          kernel=(1, 1)).asnumpy()
    for fn in (run_full, run_global):
        base, alt = _both(fn, monkeypatch)
        np.testing.assert_allclose(base, alt, rtol=1e-5, atol=1e-5)


def test_resnet_block_nhwc_matches(monkeypatch):
    """A conv->pool->conv chain end to end through gluon."""
    import mxtpu as mx
    from mxtpu.gluon.model_zoo import vision
    r = np.random.RandomState(4)
    x = r.randn(1, 3, 32, 32).astype("f")

    def run():
        mx.random.seed(0)
        net = vision.get_resnet(1, 18)
        net.initialize(mx.init.Xavier(), force_reinit=True)
        return net(mx.nd.array(x)).asnumpy()
    base, alt = _both(run, monkeypatch)
    np.testing.assert_allclose(base, alt, rtol=1e-4, atol=1e-4)
