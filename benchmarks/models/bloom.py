"""The program's side of the ``bloom`` family: the generation symbol, built
from registry ops the way ``example/char_lm/char_lm.py::build_lm`` is, with
the ``kc*/vc*/pos`` inputs and ``*_next`` outputs that ``InferenceEngine``
detects. Leaf names are the reference's, so its weights go in as they are."""
from __future__ import annotations


def _gelu(x, mx):
    return x * 0.5 * (1.0 + mx.sym.tanh(
        0.79788456 * x * (1.0 + 0.044715 * x * x)))


def symbol(cfg):
    import mxtpu as mx
    d, heads = int(cfg["hidden_size"]), int(cfg["n_head"])
    vocab, eps = int(cfg["vocab_size"]), float(cfg["layer_norm_epsilon"])
    cache_len, cache_dtype = int(cfg["cache_len"]), cfg["cache_dtype"]
    fc = mx.sym.FullyConnected
    data = mx.sym.Variable("data")
    pos = mx.sym.Variable("pos", shape=(0,), dtype="int32")
    emb = mx.sym.Variable("tok_emb_weight")
    x = mx.sym.Embedding(data=data, weight=emb, input_dim=vocab,
                         output_dim=d, name="tok_emb")
    x = mx.sym.LayerNorm(x, eps=eps, name="emb_ln")
    cache_next = []
    for i in range(int(cfg["n_layer"])):
        p = "l%d_" % i
        kc = mx.sym.Variable("kc%d" % i, shape=(0, cache_len, d),
                             dtype=cache_dtype)
        vc = mx.sym.Variable("vc%d" % i, shape=(0, cache_len, d),
                             dtype=cache_dtype)
        h = mx.sym.LayerNorm(x, eps=eps, name=p + "ln1")
        q, k, v = (fc(data=h, num_hidden=d, flatten=False, name=p + n)
                   for n in ("q", "k", "v"))
        att = mx.sym.cached_attention(q, k, v, kc, vc, pos, num_heads=heads,
                                      alibi=True, name=p + "att")
        x = x + fc(data=att[0], num_hidden=d, flatten=False, name=p + "o")
        h = mx.sym.LayerNorm(x, eps=eps, name=p + "ln2")
        h = _gelu(fc(data=h, num_hidden=4 * d, flatten=False,
                     name=p + "f1"), mx)
        x = x + fc(data=h, num_hidden=d, flatten=False, name=p + "f2")
        cache_next.append(mx.sym.identity(att[1], name="kc%d_next" % i))
        cache_next.append(mx.sym.identity(att[2], name="vc%d_next" % i))
    x = mx.sym.LayerNorm(x, eps=eps, name="ln_f")
    logits = fc(data=x, weight=emb, num_hidden=vocab, no_bias=True,
                flatten=False, name="head")
    return mx.sym.Group([logits] + cache_next)
