"""Compact O(nnz_max) row-sparse storage (reference row_sparse's memory
contract, include/mxnet/ndarray.h:61-66: a table bigger than device
memory, accessed row-wise — SparseEmbedding fwd/bwd, lazy optimizer
updates on stored rows, kvstore row_sparse_pull without densifying)."""
import numpy as np
import pytest

import mxtpu as mx
from mxtpu import gluon
from mxtpu.ndarray import sparse


VOCAB, DIM = 300_000, 16  # dense would be ~19 MB; compact is ~KBs


def _nbytes(arr):
    total = arr._data.size * arr._data.dtype.itemsize
    for v in arr._aux.values():
        total += v._data.size * v._data.dtype.itemsize
    return total


def test_device_memory_proportional_to_nnz_max():
    a = sparse.zeros("row_sparse", (VOCAB, DIM), nnz_max=32)
    assert a.shape == (VOCAB, DIM)
    assert a._data.shape == (32, DIM)
    dense_bytes = VOCAB * DIM * 4
    assert _nbytes(a) < dense_bytes / 1000
    # value round-trip through the host
    a._set_rows(np.array([7, 100_000]),
                a._data[:2] + 1.0)
    host = a.asnumpy()
    assert host.shape == (VOCAB, DIM)
    assert host[7, 0] == 1.0 and host[100_000, 0] == 1.0
    assert host.sum() == 2 * DIM
    # dense materialization on device is refused
    with pytest.raises(Exception, match="nnz_max rows"):
        a.todense()


def test_compact_constructors_merge_retain():
    a = sparse.compact_row_sparse_array(
        (np.array([[1.0] * DIM, [2.0] * DIM], "f"), np.array([10, 3])),
        shape=(VOCAB, DIM), nnz_max=8)
    np.testing.assert_array_equal(a.indices.asnumpy(), [3, 10])
    b = sparse.compact_row_sparse_array(
        (np.array([[5.0] * DIM], "f"), np.array([10])),
        shape=(VOCAB, DIM), nnz_max=4)
    m = sparse.compact_merge([a, b])
    np.testing.assert_array_equal(m.indices.asnumpy(), [3, 10])
    np.testing.assert_allclose(m.data.asnumpy()[1], [6.0] * DIM)
    r = m.retain([3, 77])
    np.testing.assert_array_equal(r.indices.asnumpy(), [3])
    np.testing.assert_allclose(r.data.asnumpy()[0], [2.0] * DIM)


def test_sparse_embedding_grad_matches_dense_gradcheck():
    """The compact sparse-embedding backward must equal the dense
    Embedding autograd gradient on the touched rows (and be zero-free
    elsewhere by construction)."""
    np.random.seed(0)
    vocab, dim, batch = 50, 4, 6
    ids = np.array([3, 7, 3, 49, 0, 7], "f")
    w0 = np.random.randn(vocab, dim).astype("f")
    head = np.random.randn(batch, dim).astype("f")

    # dense reference: plain take under autograd
    wd = mx.nd.array(w0)
    gd = mx.nd.zeros((vocab, dim))
    mx.autograd.mark_variables([wd], [gd])
    with mx.autograd.record():
        out = mx.nd.take(wd, mx.nd.array(ids).astype("int32"), axis=0)
        loss = mx.nd.sum(out * mx.nd.array(head))
    loss.backward()
    dense_grad = gd.asnumpy()

    # compact path through the gluon block
    emb = gluon.contrib.nn.SparseEmbedding(vocab, dim, nnz_max=8)
    emb.initialize()
    emb.weight.set_data(mx.nd.array(w0))
    with mx.autograd.record():
        out2 = emb(mx.nd.array(ids))
        loss2 = mx.nd.sum(out2 * mx.nd.array(head))
    loss2.backward()
    g = emb.weight._grad
    assert isinstance(g, sparse.CompactRowSparseNDArray)
    np.testing.assert_array_equal(g.indices.asnumpy(), [0, 3, 7, 49])
    np.testing.assert_allclose(g.asnumpy(), dense_grad, rtol=1e-5,
                               atol=1e-6)
    # forward values match the dense take
    np.testing.assert_allclose(out2.asnumpy(), out.asnumpy())


def test_sparse_embedding_trains_with_lazy_sgd():
    """End to end: SparseEmbedding + Trainer(sgd) converges on a toy
    classification task; the optimizer touches stored rows only."""
    np.random.seed(1)
    mx.random.seed(1)   # the initialiser's draw: else whatever the worker's
                        # earlier tests left decides a bound met by 1%
    vocab, dim, classes = 120, 8, 4
    net = gluon.nn.Sequential()
    emb = gluon.contrib.nn.SparseEmbedding(vocab, dim, nnz_max=32)
    net.add(emb)
    net.add(gluon.nn.Dense(classes))
    net.initialize(mx.init.Xavier())
    ids = np.random.randint(0, 40, (128,)).astype("f")  # rows 40+ untouched
    labels = (ids % classes).astype("f")
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.5})
    w_before = emb.weight.data().asnumpy().copy()
    losses = []
    for _ in range(60):
        with mx.autograd.record():
            out = net(mx.nd.array(ids))
            loss = loss_fn(out, mx.nd.array(labels))
        loss.backward()
        trainer.step(len(ids))
        losses.append(float(mx.nd.mean(loss).asscalar()))
    assert losses[-1] < losses[0] * 0.2, (losses[0], losses[-1])
    w_after = emb.weight.data().asnumpy()
    # untouched rows were never updated (lazy semantics)
    np.testing.assert_array_equal(w_before[60:], w_after[60:])
    assert np.abs(w_before[:40] - w_after[:40]).sum() > 0


def test_kvstore_compact_pull_push_no_densify():
    """row_sparse_pull from a compact store moves rows compactly; pushes
    of compact grads union-merge without a dense buffer."""
    kv = mx.kv.create("local")
    table = sparse.compact_row_sparse_array(
        (np.arange(3 * DIM, dtype="f").reshape(3, DIM),
         np.array([5, 900, 200_000])),
        shape=(VOCAB, DIM), nnz_max=16)
    kv.init(0, table)
    dst = sparse.zeros("row_sparse", (VOCAB, DIM), nnz_max=8)
    kv.row_sparse_pull(0, out=dst, row_ids=mx.nd.array([900, 5]))
    np.testing.assert_array_equal(dst.indices.asnumpy(), [5, 900])
    np.testing.assert_allclose(dst.data.asnumpy()[0], np.arange(DIM))
    # a dense pull of the compact table is refused
    with pytest.raises(TypeError, match="row_sparse_pull"):
        kv.pull(0, out=mx.nd.zeros((VOCAB, DIM)))
    # compact push merge
    g1 = sparse.compact_row_sparse_array(
        (np.ones((1, DIM), "f"), np.array([900])),
        shape=(VOCAB, DIM), nnz_max=4)
    g2 = sparse.compact_row_sparse_array(
        (np.ones((2, DIM), "f"), np.array([900, 7])),
        shape=(VOCAB, DIM), nnz_max=4)
    seen = {}

    def updater(key, recv, local):
        seen["recv"] = recv

    kv._set_updater(updater)
    kv.push(0, [g1, g2])
    recv = seen["recv"]
    assert isinstance(recv, sparse.CompactRowSparseNDArray)
    np.testing.assert_array_equal(recv.indices.asnumpy(), [7, 900])
    np.testing.assert_allclose(recv.data.asnumpy()[1], [2.0] * DIM)


def test_lazy_update_on_compact_weight():
    """SGD on a compact weight updates resident rows in place; rows not
    in the gradient keep their value; non-resident gradient rows raise."""
    w = sparse.compact_row_sparse_array(
        (np.ones((3, DIM), "f"), np.array([2, 50, 9000])),
        shape=(VOCAB, DIM), nnz_max=8)
    g = sparse.compact_row_sparse_array(
        (np.full((2, DIM), 0.5, "f"), np.array([50, 9000])),
        shape=(VOCAB, DIM), nnz_max=4)
    opt = mx.optimizer.SGD(learning_rate=1.0, rescale_grad=1.0, wd=0.0)
    opt.update(0, w, g, opt.create_state(0, w))
    out = w.asnumpy()
    np.testing.assert_allclose(out[2], np.ones(DIM))          # untouched
    np.testing.assert_allclose(out[50], np.full(DIM, 0.5))    # 1 - 0.5
    np.testing.assert_allclose(out[9000], np.full(DIM, 0.5))
    bad = sparse.compact_row_sparse_array(
        (np.ones((1, DIM), "f"), np.array([77])),
        shape=(VOCAB, DIM), nnz_max=2)
    with pytest.raises(KeyError, match="not resident"):
        opt.update(0, w, bad, None)


def test_sparse_embedding_shared_weight_sums_in_one_pass():
    """A SparseEmbedding applied twice inside one recorded graph must sum
    both contributions (grad_req='write' replaces only across passes)."""
    vocab, dim = 30, 4
    emb = gluon.contrib.nn.SparseEmbedding(vocab, dim, nnz_max=8)
    emb.initialize(mx.init.One())
    ids_a = mx.nd.array(np.array([1, 2], "f"))
    ids_b = mx.nd.array(np.array([2, 5], "f"))
    with mx.autograd.record():
        loss = mx.nd.sum(emb(ids_a)) + mx.nd.sum(emb(ids_b))
    loss.backward()
    g = emb.weight._grad
    np.testing.assert_array_equal(g.indices.asnumpy(), [1, 2, 5])
    dense = g.asnumpy()
    np.testing.assert_allclose(dense[2], np.full(dim, 2.0))  # both calls
    np.testing.assert_allclose(dense[1], np.ones(dim))
    # second backward pass with grad_req='write' replaces, not accumulates
    with mx.autograd.record():
        loss = mx.nd.sum(emb(ids_a))
    loss.backward()
    g2 = emb.weight._grad
    np.testing.assert_array_equal(g2.indices.asnumpy(), [1, 2])
    np.testing.assert_allclose(g2.asnumpy()[2], np.ones(dim))


def test_sparse_embedding_batch_exceeding_nnz_max_grows():
    """More unique ids in a batch than nnz_max must lose NO gradient —
    the grad buffer grows instead of truncating."""
    vocab, dim = 100, 4
    emb = gluon.contrib.nn.SparseEmbedding(vocab, dim, nnz_max=2)
    emb.initialize(mx.init.One())
    ids = mx.nd.array(np.arange(10, dtype="f"))
    with mx.autograd.record():
        loss = mx.nd.sum(emb(ids))
    loss.backward()
    g = emb.weight._grad
    assert g.nnz == 10
    np.testing.assert_array_equal(g.indices.asnumpy(), np.arange(10))
    np.testing.assert_allclose(g.data.asnumpy(), np.ones((10, dim)))


def test_stateful_optimizer_on_compact_weight_refused():
    w = sparse.compact_row_sparse_array(
        (np.ones((1, DIM), "f"), np.array([3])), shape=(VOCAB, DIM),
        nnz_max=2)
    g = sparse.compact_row_sparse_array(
        (np.ones((1, DIM), "f"), np.array([3])), shape=(VOCAB, DIM),
        nnz_max=2)
    opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9,
                           rescale_grad=1.0)
    with pytest.raises(NotImplementedError, match="full table lives"):
        opt.update(0, w, g, opt.create_state(0, w))


def test_kvstore_compact_push_into_dense_store_refused():
    """A compact gradient pushed at a dense-initialised key without an
    updater must raise instead of installing the (nnz_max, row) buffer
    as the store's full value (pull already guards the mirror case)."""
    kv = mx.kv.create("local")
    kv.init("w", mx.nd.zeros((50, 4)))
    g = sparse.compact_row_sparse_array(
        (np.ones((2, 4), "f"), np.array([3, 7])), shape=(50, 4),
        nnz_max=8)
    with pytest.raises(TypeError):
        kv.push("w", g)
