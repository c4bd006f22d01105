"""Plain float32 reference of the BLOOM decoder (BigScience, arXiv:2211.05100;
``huggingface.co/bigscience/bloom-1b7``): token embedding followed by a
LayerNorm, pre-LayerNorm blocks of multi-head attention with ALiBi (no
position embedding) and a GELU MLP of 4 x hidden, biases everywhere, a final
LayerNorm and a head tied to the embedding.

Straight ``jax.numpy`` in float32 at ``Precision.HIGHEST`` over the whole
sequence at once: no cache, no batching, no kernels. Imports nothing of
``mxtpu``. It owns the weights: ``init_weights`` makes them from the seed in
bfloat16, the type the configuration serves them in, and the program is
handed the same values. The forward pass runs layer by layer, each layer's
weights raised to float32 as it is reached, so that it fits beside nothing.

Departures: the fused query-key-value matrix of the checkpoint format is
three matrices here (the same arithmetic and parameter count); ALiBi is
written as ``-slope * (query position - key position)``, which differs from
the published ``slope * key position`` by a constant per row that softmax
removes.

``quant`` is the control: matrix-multiplication inputs rounded to scaled
float8 (e4m3), the nearest precision below bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..common import seed_key

HI = lax.Precision.HIGHEST


def layout(cfg):
    """Every weight as ``(name, shape, kind)``; kind is matrix, bias, gamma
    or beta."""
    d, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    out = [("tok_emb_weight", (v, d), "matrix"),
           ("emb_ln_gamma", (d,), "gamma"), ("emb_ln_beta", (d,), "beta")]
    for i in range(int(cfg["n_layer"])):
        p = "l%d_" % i
        out += [(p + "ln1_gamma", (d,), "gamma"), (p + "ln1_beta", (d,), "beta")]
        for n in ("q", "k", "v", "o"):
            out += [(p + n + "_weight", (d, d), "matrix"),
                    (p + n + "_bias", (d,), "bias")]
        out += [(p + "ln2_gamma", (d,), "gamma"), (p + "ln2_beta", (d,), "beta"),
                (p + "f1_weight", (4 * d, d), "matrix"),
                (p + "f1_bias", (4 * d,), "bias"),
                (p + "f2_weight", (d, 4 * d), "matrix"),
                (p + "f2_bias", (d,), "bias")]
    out += [("ln_f_gamma", (d,), "gamma"), ("ln_f_beta", (d,), "beta")]
    return out


def parameter_count(cfg):
    return sum(int(np.prod(s)) for _n, s, _k in layout(cfg))


@functools.partial(jax.jit, static_argnames=("leaves", "std", "block_std"))
def _make_group(key, leaves, std, block_std):
    out = {}
    for i, (name, shape, kind) in enumerate(leaves):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.bfloat16)
        if kind == "gamma":
            z = 1.0 + 0.1 * z
        elif kind == "beta":
            z = 0.1 * z
        elif name == "tok_emb_weight":
            z = std * z
        else:
            z = block_std * z
        out[name] = z.astype(jnp.bfloat16)
    return out


def init_weights(cfg, seed):
    """All weights made on the device from the seed, in bfloat16: one jitted
    call for the leaves outside the blocks and one for each block (the same
    compiled program every time). The embedding is N(0, initializer_range);
    block matrices and biases N(0, block_init_std); LayerNorm gains
    1 + N(0, 0.1) and shifts N(0, 0.1), so that none is a no-op."""
    std = float(cfg["initializer_range"])
    block_std = float(cfg.get("block_init_std", std))
    key = seed_key(seed)
    groups = {}
    for name, shape, kind in layout(cfg):
        layer, _, leaf = name.partition("_")
        if not (layer[0] == "l" and layer[1:].isdigit()):
            layer, leaf = "", name
        groups.setdefault(layer, []).append((leaf, tuple(shape), kind))
    out = {}
    for n, (layer, leaves) in enumerate(groups.items()):
        made = _make_group(jax.random.fold_in(key, n), tuple(leaves), std,
                           block_std)
        prefix = layer + "_" if layer else ""
        out.update({prefix + k: v for k, v in made.items()})
    return out


def alibi_slopes(n_head):
    return np.asarray([2.0 ** (-8.0 * (i + 1) / n_head)
                       for i in range(n_head)], np.float32)


def gelu(x):
    """BLOOM's GELU: the tanh approximation."""
    return x * 0.5 * (1.0 + jnp.tanh(0.79788456 * x * (1.0 + 0.044715 * x * x)))


def _ln(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * g + b


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale


def _mm(x, w, quant):
    """``x @ w.T`` with the weight stored (out, in)."""
    if quant:
        x, w = _fp8(x), _fp8(w)
    return jnp.dot(x, w.T, precision=HI)


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "quant"))
def block(x, w, n_head, eps, quant=False):
    """One decoder block over a whole sequence ``x [T, D]``; ``w`` maps the
    layer's leaf names without their prefix to bfloat16 arrays."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    t, d = x.shape
    hd = d // n_head
    h = _ln(x, w["ln1_gamma"], w["ln1_beta"], eps)
    q = (_mm(h, w["q_weight"], quant) + w["q_bias"]).reshape(t, n_head, hd)
    k = (_mm(h, w["k_weight"], quant) + w["k_bias"]).reshape(t, n_head, hd)
    v = (_mm(h, w["v_weight"], quant) + w["v_bias"]).reshape(t, n_head, hd)
    scores = jnp.einsum("thd,shd->hts", q, k, precision=HI) / np.sqrt(hd)
    pos = jnp.arange(t)
    dist = (pos[:, None] - pos[None, :]).astype(jnp.float32)
    scores = scores - jnp.asarray(alibi_slopes(n_head))[:, None, None] * dist
    scores = jnp.where(dist[None] >= 0, scores, -jnp.inf)
    att = jax.nn.softmax(scores, axis=-1)
    a = jnp.einsum("hts,shd->thd", att, v, precision=HI).reshape(t, d)
    x = x + _mm(a, w["o_weight"], quant) + w["o_bias"]
    h = _ln(x, w["ln2_gamma"], w["ln2_beta"], eps)
    h = gelu(_mm(h, w["f1_weight"], quant) + w["f1_bias"])
    return x + _mm(h, w["f2_weight"], quant) + w["f2_bias"]


@functools.partial(jax.jit, static_argnames=("eps",))
def embed(tokens, emb, g, b, eps):
    x = jnp.take(emb, tokens, axis=0).astype(jnp.float32)
    return _ln(x, g.astype(jnp.float32), b.astype(jnp.float32), eps)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def head(x, emb, g, b, eps, quant=False):
    h = _ln(x, g.astype(jnp.float32), b.astype(jnp.float32), eps)
    return _mm(h, emb.astype(jnp.float32), quant)


def logits(cfg, weights, tokens, positions, quant=False):
    """Logits ``[len(positions), vocab]`` of the full forward pass over
    ``tokens`` (1-D, padded as the caller likes: the pass is causal), at the
    positions asked for."""
    n_head, eps = int(cfg["n_head"]), float(cfg["layer_norm_epsilon"])
    x = embed(jnp.asarray(tokens, jnp.int32), weights["tok_emb_weight"],
              weights["emb_ln_gamma"], weights["emb_ln_beta"], eps)
    for i in range(int(cfg["n_layer"])):
        p = "l%d_" % i
        w = {k[len(p):]: v for k, v in weights.items() if k.startswith(p)}
        x = block(x, w, n_head, eps, quant)
    x = jnp.take(x, jnp.asarray(positions, jnp.int32), axis=0)
    return head(x, weights["tok_emb_weight"], weights["ln_f_gamma"],
                weights["ln_f_beta"], eps, quant)


def decode_step_bytes(cfg, live_positions, weight_bytes=2, cache_bytes=2):
    """Bytes one decode step has to read: every weight once, and the key and
    value rows of the live positions of the live slots. Not the reserved
    cache, and not how the program happens to read it."""
    d, layers = int(cfg["hidden_size"]), int(cfg["n_layer"])
    return (parameter_count(cfg) * weight_bytes
            + int(live_positions) * layers * 2 * d * cache_bytes)


def ops_per_token(cfg):
    """Operations of one token's forward pass, at two a parameter: the
    matrices dominate, and the tied head counts once, as a head."""
    return 2 * parameter_count(cfg)
