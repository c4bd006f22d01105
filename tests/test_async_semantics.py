"""Async-dispatch semantics stress tier.

The reference proves its dependency engine with randomized dependency
graphs compared against serial execution
(tests/cpp/engine/threaded_engine_test.cc:124-278 RandSumExpr) and
transports kernel exceptions to the WaitForVar sync point
(docs/architecture/exception_handling.md). mxtpu's equivalents:

* random in-place NDArray mutation/dependency chains executed under the
  default async dispatch must produce bitwise-identical results to the
  same program under NaiveEngine (every op synchronous);
* an error raised inside compiled device code (a host callback in a
  jitted graph, the only runtime-raising path on this backend) must NOT
  fire at dispatch — it must surface at the sync point (`asnumpy` /
  `wait_to_read` / `waitall`) with the op's message intact.
"""
import numpy as np
import pytest

import mxtpu as mx
from mxtpu import engine


def _random_program(seed, sync):
    """Run a randomized mutation/dependency chain; return final states.

    Mixes the hazard classes the reference engine test exercises:
    read-after-write (use a freshly assigned array), write-after-read
    (mutate an array another op just consumed), write-after-write
    (reassign the same slot twice), plus views/slices, accumulation
    (+=), cross-array reductions and an executor in the middle.
    """
    engine.set_engine_type("NaiveEngine" if sync
                           else "ThreadedEnginePerDevice")
    try:
        rng = np.random.RandomState(seed)
        n, shape = 6, (4, 4)
        arrs = [mx.nd.array(rng.randn(*shape).astype("f"))
                for _ in range(n)]
        for _ in range(120):
            op = rng.randint(7)
            i, j, k = rng.randint(n, size=3)
            if op == 0:      # WAW + RAW: full reassignment from two reads
                arrs[i][:] = arrs[j] + 0.5 * arrs[k]
            elif op == 1:    # accumulation (kAddTo-style)
                arrs[i] += arrs[j]
            elif op == 2:    # matmul dependency
                arrs[i][:] = mx.nd.dot(arrs[j], arrs[k]) * 0.1
            elif op == 3:    # slice-view write (partial mutation)
                r = rng.randint(shape[0])
                arrs[i][r] = arrs[j][shape[0] - 1 - r]
            elif op == 4:    # reduce -> broadcast back in
                s = mx.nd.sum(arrs[j], axis=0, keepdims=True)
                arrs[i][:] = mx.nd.broadcast_to(s, shape) / shape[0]
            elif op == 5:    # elementwise chain with a copy hazard
                tmp = arrs[j].copy()
                arrs[j][:] = -arrs[j]
                arrs[i][:] = tmp * 2.0 + arrs[k]
            else:            # scalar mutation everyone downstream reads
                arrs[i] *= 0.9
        return [a.asnumpy().copy() for a in arrs]
    finally:
        engine.set_engine_type("ThreadedEnginePerDevice")


@pytest.mark.parametrize("seed", [0, 7, 1234])
def test_random_mutation_chains_async_matches_naive(seed):
    async_out = _random_program(seed, sync=False)
    sync_out = _random_program(seed, sync=True)
    for a, b in zip(async_out, sync_out):
        np.testing.assert_array_equal(a, b)


def _failing_custom_net():
    class FailingOp(mx.operator.CustomOp):
        def forward(self, is_train, req, in_data, out_data, aux):
            x = in_data[0].asnumpy()
            if np.any(x > 1e5):
                raise ValueError("poisoned activation in failing_op")
            self.assign(out_data[0], req[0], in_data[0])

        def backward(self, req, out_grad, in_grad, out_data, in_data, aux):
            self.assign(in_grad[0], req[0], out_grad[0])

    @mx.operator.register("failing_op_async_test")
    class FailingProp(mx.operator.CustomOpProp):
        def list_arguments(self):
            return ["data"]

        def infer_shape(self, in_shape):
            return in_shape, [in_shape[0]], []

        def create_operator(self, ctx, shapes, dtypes):
            return FailingOp()

    return FailingProp


def test_async_error_surfaces_at_sync_point():
    _failing_custom_net()
    data = mx.sym.var("data")
    net = mx.sym.Custom(data, op_type="failing_op_async_test")
    net = net * 2.0
    exe = net.simple_bind(mx.cpu(), grad_req="null", data=(2, 3))

    # healthy input: flows through
    exe.arg_dict["data"][:] = np.ones((2, 3), "f")
    out = exe.forward(is_train=False)[0]
    np.testing.assert_allclose(out.asnumpy(), 2 * np.ones((2, 3)))

    # poisoned input: the raise happens inside the compiled graph's host
    # callback; it must surface at the value sync with the message
    exe.arg_dict["data"][:] = np.full((2, 3), 1e6, "f")
    with pytest.raises(Exception, match="poisoned activation"):
        out = exe.forward(is_train=False)[0]
        out.asnumpy()


def test_async_error_surfaces_at_waitall():
    """Engine::WaitForAll is also a sync point for pending failures."""
    _ = _failing_custom_net  # registered by the test above or here
    try:
        prop = _failing_custom_net()
    except Exception:
        prop = None  # already registered under this op_type
    x = mx.nd.array(np.full((2, 3), 1e6, "f"))
    with pytest.raises(Exception, match="poisoned activation"):
        y = mx.nd.Custom(x, op_type="failing_op_async_test")
        y = y + 1.0
        engine.waitall()
        y.asnumpy()


# ---------------------------------------------------------------------------
# no hidden host syncs in steady-state dispatch paths
# ---------------------------------------------------------------------------

class _iter_trap:
    """Fail the test if anything iterates a concrete jax.Array.

    Array.__iter__ materializes chunks on the host — a silent
    async-queue drain per call that serializes dispatch; tuple-unpacking
    jax.random.split's result did exactly this in every hybridized
    forward (fix: ops.registry.split2). Steady-state hot
    paths must never iterate concrete arrays; this trap pins that."""

    def __enter__(self):
        import jax._src.array as jarray
        self._mod = jarray
        self._orig = jarray.ArrayImpl.__iter__

        def trap(_self):
            raise AssertionError(
                "jax.Array.__iter__ in a steady-state dispatch path "
                "(host-sync hazard; use ops.registry.split2-style "
                "indexing instead of unpacking/iterating)")
        jarray.ArrayImpl.__iter__ = trap
        return self

    def __exit__(self, *a):
        self._mod.ArrayImpl.__iter__ = self._orig


def test_hybrid_forward_iterates_no_concrete_arrays():
    from mxtpu.gluon import nn
    import mxtpu as mx2
    net = nn.HybridSequential()
    net.add(nn.Conv2D(4, 3, padding=1), nn.Flatten(), nn.Dense(8))
    net.initialize(mx2.init.Xavier())
    net.hybridize()
    x = mx2.nd.array(np.random.rand(2, 3, 8, 8).astype("f"))
    net(x)  # compile outside the trap
    with _iter_trap():
        for _ in range(3):
            out = net(x)
    out.wait_to_read()


def test_sharded_trainer_step_iterates_no_concrete_arrays():
    import jax
    from mxtpu import gluon
    from mxtpu.gluon import nn
    from mxtpu.parallel import MeshContext, ShardedTrainer
    import mxtpu as mx2
    net = nn.HybridSequential()
    net.add(nn.Dense(16), nn.Activation("relu"), nn.Dense(4))
    net.initialize(mx2.init.Xavier())
    x = np.random.rand(8, 8).astype("f")
    y = np.random.randint(0, 4, (8,)).astype("f")
    st = ShardedTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                        {"learning_rate": 0.1},
                        mesh=MeshContext(jax.devices()[:1], data=1))
    st.step(x, y)  # compile + materialize device step state
    xd = st._shard_batch([x])[0]
    yd = st._shard_batch([y])[0]
    with _iter_trap():
        for _ in range(3):
            loss = st.step_async(xd, yd)
    float(loss.asnumpy())


def test_iter_trap_catches_the_old_pattern():
    import jax
    with _iter_trap():
        with pytest.raises(AssertionError, match="host-sync hazard"):
            _a, _b = jax.random.split(jax.random.PRNGKey(0))


def test_split2_matches_unpack_values():
    """split2 replaced 'a, b = jax.random.split(k)' in eager paths for
    dispatch-async reasons; the VALUES must be identical or every
    seeded model in the zoo quietly reproduces differently."""
    import jax
    from mxtpu.ops.registry import split2
    k = jax.random.PRNGKey(42)
    ks = np.asarray(jax.random.split(k))
    a, b = split2(k)
    np.testing.assert_array_equal(np.asarray(a), ks[0])
    np.testing.assert_array_equal(np.asarray(b), ks[1])
