"""The BLOOM symbol against its plain reference at a small size, in float32:
a prefill and then decoding one token at a time through the cache have to
give the logits of the reference's one full forward pass. 16 heads, so that
the ALiBi slopes are the published ones for 16."""
import numpy as np

CFG = {"hidden_size": 64, "n_layer": 2, "n_head": 16, "vocab_size": 97,
       "layer_norm_epsilon": 1e-5, "initializer_range": 0.02,
       "block_init_std": 0.2, "cache_len": 24, "cache_dtype": "float32"}


def test_alibi_slopes_for_16_heads():
    from benchmarks.reference.bloom import alibi_slopes
    s = alibi_slopes(16)
    assert np.allclose(s[0], 2 ** -0.5) and np.allclose(s[-1], 2 ** -8)
    assert np.allclose(s[1:] / s[:-1], 2 ** -0.5)


def test_prefill_then_decode_matches_full_forward():
    import jax
    import jax.numpy as jnp
    from mxtpu.ops.registry import rng_scope
    from mxtpu.symbol import eval_graph
    from benchmarks.models import bloom as model
    from benchmarks.reference import bloom as ref

    weights = {k: v.astype(jnp.float32)
               for k, v in ref.init_weights(CFG, 5).items()}
    sym = model.symbol(CFG)
    n_state = 2 * CFG["n_layer"]
    rng = np.random.default_rng(0)
    seq = rng.integers(0, CFG["vocab_size"], size=14)
    plen = 9

    def step(tokens, pos, caches):
        feed = dict(weights)
        feed["data"] = jnp.asarray(tokens, jnp.float32)[None, :]
        feed["pos"] = jnp.asarray([pos], jnp.int32)
        for i in range(CFG["n_layer"]):
            feed["kc%d" % i], feed["vc%d" % i] = caches[2 * i], caches[2 * i + 1]
        with rng_scope(jax.random.PRNGKey(0)):
            outs, _aux = eval_graph(sym._outputs, feed, False)
        return np.asarray(outs[0])[0], list(outs[1:1 + n_state])

    zeros = [jnp.zeros((1, CFG["cache_len"], CFG["hidden_size"]), jnp.float32)
             for _ in range(n_state)]
    got, caches = step(seq[:plen], 0, zeros)
    rows = [got[-1]]
    for p in range(plen, len(seq)):
        lg, caches = step(seq[p:p + 1], p, caches)
        rows.append(lg[-1])
    want = np.asarray(ref.logits(CFG, weights, seq,
                                 np.arange(plen - 1, len(seq))))
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(np.stack(rows), want, atol=2e-5, rtol=1e-4)
