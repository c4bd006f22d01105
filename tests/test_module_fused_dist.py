"""Fused DISTRIBUTED Module train step (ISSUE 10): the kvstore-managed
fast path — sync-mode bit-for-bit parity with the eager dist loop
(sgd + adam, optimizer-state round-trip through the server), async-mode
loss band + bounded push window, the dist_local (merged-gradient) mode,
and the narrowed fallback predicate with its one-shot debug log."""
import logging

import numpy as np
import pytest

import mxtpu as mx
from mxtpu.module import fused as fused_mod


def _toy_problem(n=192, seed=5, classes=4):
    r = np.random.RandomState(seed)
    y = (r.rand(n) * classes).astype("f")
    x = r.rand(n, 16).astype("f") * 0.1
    for i in range(n):
        x[i, int(y[i]) * 4:int(y[i]) * 4 + 4] += 1.0
    return x, y


def _mlp(classes=4):
    data = mx.sym.var("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu", name="relu1")
    net = mx.sym.FullyConnected(net, num_hidden=classes, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _dist_fit(monkeypatch, fused_dist, mode="sync", optimizer="sgd",
              opt_params=None, epochs=3, keep_module=False):
    """One Module.fit through an in-process dist_async store; returns
    (module-or-None, params, kv stats, engaged mode)."""
    monkeypatch.setenv("MXTPU_MODULE_FUSED", "1")
    monkeypatch.setenv("MXTPU_MODULE_FUSED_DIST",
                       "1" if fused_dist else "0")
    monkeypatch.setenv("MXTPU_MODULE_DIST_MODE", mode)
    monkeypatch.setenv("MXTPU_PS_HEARTBEAT", "0")
    np.random.seed(7)
    mx.random.seed(7)
    x, y = _toy_problem()
    it = mx.io.NDArrayIter(x, y, batch_size=32, shuffle=True,
                           label_name="softmax_label")
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.fit(it, optimizer=optimizer,
            optimizer_params=opt_params or {"learning_rate": 0.1,
                                            "momentum": 0.9},
            num_epoch=epochs, kvstore="dist_async", eval_metric="acc")
    engaged = mod._fused.mode if mod._fused is not None else None
    args, _ = mod.get_params()
    params = {k: v.asnumpy().copy() for k, v in args.items()}
    stats = mod._kvstore.stats()
    if keep_module:
        return mod, params, stats, engaged
    mod._kvstore.close()
    return None, params, stats, engaged


@pytest.mark.parametrize("optimizer,opt_params", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4}),
    ("adam", {"learning_rate": 0.01}),
])
def test_fused_dist_sync_parity_bitwise(monkeypatch, optimizer,
                                        opt_params):
    """Sync-mode fused dist fit must match the eager dist path
    BIT-FOR-BIT: same grads (one fused program vs speculative
    fwd+bwd), same server-side update sequence per key."""
    _, fused, _, m1 = _dist_fit(monkeypatch, True, "sync", optimizer,
                                opt_params)
    _, eager, _, m2 = _dist_fit(monkeypatch, False, "sync", optimizer,
                                opt_params)
    assert m1 == "dist", "fused dist path must engage"
    assert m2 is None, "eager run must not engage the fused path"
    assert fused.keys() == eager.keys()
    for k in fused:
        assert np.array_equal(fused[k], eager[k]), \
            "%s differs between fused and eager dist paths" % k


def test_fused_dist_optimizer_state_roundtrip_server(monkeypatch,
                                                     tmp_path):
    """save/load_optimizer_states ride the SERVER (update_on_kvstore):
    the fused dist path must round-trip them and keep training fused."""
    mod, _, _, engaged = _dist_fit(monkeypatch, True, "sync", "adam",
                                   {"learning_rate": 0.01},
                                   keep_module=True)
    try:
        assert engaged == "dist"
        fname = str(tmp_path / "dist_opt.states")
        mod.save_optimizer_states(fname)
        mod.load_optimizer_states(fname)
        x, y = _toy_problem()
        batch = mx.io.DataBatch([mx.nd.array(x[:32])],
                                [mx.nd.array(y[:32])])
        mod.forward_backward(batch)
        mod.update()
        assert mod._fused is not None and mod._fused.mode == "dist"
    finally:
        mod._kvstore.close()


def test_fused_dist_async_loss_band_and_window(monkeypatch):
    """Async mode: same model converges (loss band = final accuracy),
    pushes ride the bounded-inflight window whose counters surface in
    kv.stats()['module_fused_dist']."""
    _, params, stats, engaged = _dist_fit(
        monkeypatch, True, "async", "sgd", {"learning_rate": 0.5})
    assert engaged == "dist"
    for v in params.values():
        assert np.isfinite(v).all()
    win = stats["module_fused_dist"]
    assert 1 <= win["inflight_hwm"] <= win["window"]
    assert win["dispatched"] >= 6          # epochs * batches shipped
    assert win["inflight"] == 0            # flushed at get_params
    assert win["completed"] == win["dispatched"]
    # accuracy band vs the eager dist run
    _, eparams, _, _ = _dist_fit(monkeypatch, False, "sync", "sgd",
                                 {"learning_rate": 0.5})
    for k in params:
        # async staleness means not bitwise, but the same neighborhood
        assert np.allclose(params[k], eparams[k], rtol=0.3, atol=0.3), k


def test_fused_dist_local_mode_parity(monkeypatch):
    """MXTPU_UPDATE_ON_KVSTORE=0: the store only merges gradients and
    the worker applies the optimizer — the fused path renders this as
    grad program + donated local apply. Parity is the PR-5 fused-apply
    tolerance (one fusion boundary differs from the eager per-param
    op), not bitwise; the bit-for-bit contract is the server-side
    (update_on_kvstore) sync mode above."""
    monkeypatch.setenv("MXTPU_UPDATE_ON_KVSTORE", "0")
    _, fused, _, m1 = _dist_fit(monkeypatch, True, "sync", "sgd",
                                {"learning_rate": 0.1, "momentum": 0.9},
                                epochs=2)
    _, eager, _, m2 = _dist_fit(monkeypatch, False, "sync", "sgd",
                                {"learning_rate": 0.1, "momentum": 0.9},
                                epochs=2)
    assert m1 == "dist_local" and m2 is None
    for k in fused:
        np.testing.assert_allclose(fused[k], eager[k], rtol=5e-4,
                                   atol=1e-6, err_msg=k)


def test_fused_dist_kill_switch_logs_reason(monkeypatch, caplog):
    """MXTPU_MODULE_FUSED_DIST=0 keeps kvstore modules eager, and the
    silent fallback names its reason ONCE at warning level."""
    with caplog.at_level(logging.DEBUG):
        _, _, _, engaged = _dist_fit(monkeypatch, False, "sync")
    assert engaged is None
    msgs = [r.message for r in caplog.records
            if "fused train step not engaged" in r.message]
    assert msgs, "fallback must be logged"
    assert "MXTPU_MODULE_FUSED_DIST=0" in msgs[0]
    assert len(msgs) == 1, "the fallback log is one-shot per module"


def test_fallback_reasons_are_named(monkeypatch, caplog):
    """The narrowed predicate: every silent fallback (inputs_need_grad
    here) is diagnosable through the warning log."""
    monkeypatch.setenv("MXTPU_MODULE_FUSED", "1")
    x, y = _toy_problem()
    it = mx.io.NDArrayIter(x, y, batch_size=32,
                           label_name="softmax_label")
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.bind(it.provide_data, it.provide_label, inputs_need_grad=True)
    mod.init_params(mx.initializer.Xavier())
    with caplog.at_level(logging.DEBUG):
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.05})
    assert mod._fused is None
    assert any("inputs_need_grad" in r.message for r in caplog.records)


def test_fused_eligible_modes():
    """_fused_eligible's (mode, reason) contract on a plain local
    module."""
    x, y = _toy_problem()
    it = mx.io.NDArrayIter(x, y, batch_size=32,
                           label_name="softmax_label")
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.bind(it.provide_data, it.provide_label)
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05})
    mode, reason = fused_mod._fused_eligible(mod)
    assert mode == "local" and reason is None


def test_fused_dist_monitor_falls_back_mid_run(monkeypatch):
    """A Monitor install mid-run disables the dist fast path with the
    usual one warning and drains the window first."""
    monkeypatch.setenv("MXTPU_MODULE_FUSED", "1")
    monkeypatch.setenv("MXTPU_MODULE_DIST_MODE", "async")
    monkeypatch.setenv("MXTPU_PS_HEARTBEAT", "0")
    x, y = _toy_problem()
    it = mx.io.NDArrayIter(x, y, batch_size=32,
                           label_name="softmax_label")
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.bind(it.provide_data, it.provide_label)
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(kvstore="dist_async", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05})
    kv = mod._kvstore
    try:
        assert mod._fused is not None and mod._fused.mode == "dist"
        batch = next(iter(it))
        mod.forward_backward(batch)
        mod.update()
        mod.install_monitor(mx.monitor.Monitor(1))
        with pytest.warns(UserWarning, match="fused train step disabled"):
            mod.forward_backward(batch)
        mod.update()
        assert mod._fused is None
        win = kv.stats()["module_fused_dist"]
        assert win["inflight"] == 0, "disable must drain the window"
    finally:
        kv.close()
