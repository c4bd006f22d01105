"""Plain float32 reference of the ``exaone_moe`` decoder (LG AI Research,
K-EXAONE-236B-A23B, ``config.json`` on huggingface.co/LGAI-EXAONE), as ONE
chip of an expert-parallel deployment holds it.

Pre-norm residual blocks with RMSNorm. Attention: 64 query heads over 8
key/value heads of 128, queries and keys RMS-normalised per head, rotary
positions (theta 1e6, half-split pairs) on ``sliding_attention`` layers,
which see the last 128 positions; ``full_attention`` layers see everything
and carry no rotary positions. Layer 0 has a dense SiLU-gated MLP; every
other layer routes: sigmoid scores over all 128 experts, the 8 largest of
``score + selection bias`` chosen, their scores renormalised and scaled by
2.5, one shared expert beside them. The chip holds ``num_experts`` of the
128 (experts ``expert_first ..``) and ``vocab_size`` of the 153,600 rows of
the embedding and of the untied head: it adds its own experts' terms and the
shared expert, and what the absent experts would have added is left out,
here exactly as in the program.

Departures-if-wrong (the catalog's config does not state them; they are the
EXAONE-4.0 / ``exaone_moe`` family's published choices as the issue's
author knows them, listed under ``assumed`` in the configuration): norms sit
before attention and MLP (pre-norm); queries and keys are normalised per
head; full-attention layers carry no rotary positions; the router has a
selection bias. The multi-token-prediction module is not loaded.

Straight ``jax.numpy`` in float32 under ``default_matmul_precision
("highest")`` over the whole sequence at once: no cache, no batching, no
kernels, nothing of ``mxtpu``. It owns the weights: ``init_weights`` returns
a mapping that makes each leaf from the seed when it is asked for (the same
seed, the same values, on one device kind), rounded to bfloat16 as the
configuration serves it, so the pass below makes and
drops a layer at a time and neither host nor chip ever holds the model in
float32. The program is handed the same mapping.

``quant`` is the control: matrix-product inputs in scaled float8 (e4m3),
the nearest precision below bfloat16.
"""
from __future__ import annotations

import collections.abc
import functools

import jax
import jax.numpy as jnp
import numpy as np


def _layers(cfg):
    n = int(cfg["num_hidden_layers"])
    return list(zip(cfg["layer_types"][:n], cfg["mlp_layer_types"][:n]))


def layout(cfg):
    """Every weight as ``(name, shape, kind)``; kind is matrix, one of
    out_attn / out_dense / out_expert / out_shared (the matrices that write
    into the residual stream), emb, gamma, qk_gamma, router or bias."""
    d, hd = int(cfg["hidden_size"]), int(cfg["head_dim"])
    hq, hk = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    v, held = int(cfg["vocab_size"]), int(cfg["num_experts"])
    wide, f = int(cfg["router_width"]), int(cfg["moe_intermediate_size"])
    dense = int(cfg["intermediate_size"])
    out = [("tok_emb_weight", (v, d), "emb")]
    for i, (_att, mlp) in enumerate(_layers(cfg)):
        p = "l%d_" % i
        out += [(p + "an_gamma", (d,), "gamma"),
                (p + "q_weight", (hq * hd, d), "matrix"),
                (p + "k_weight", (hk * hd, d), "matrix"),
                (p + "v_weight", (hk * hd, d), "matrix"),
                (p + "qn_gamma", (hd,), "qk_gamma"),
                (p + "kn_gamma", (hd,), "qk_gamma"),
                (p + "o_weight", (d, hq * hd), "out_attn"),
                (p + "mn_gamma", (d,), "gamma")]
        if mlp == "dense":
            out += [(p + "g_weight", (dense, d), "matrix"),
                    (p + "u_weight", (dense, d), "matrix"),
                    (p + "d_weight", (d, dense), "out_dense")]
        else:
            out += [(p + "router_weight", (wide, d), "router"),
                    (p + "router_bias", (wide,), "bias"),
                    (p + "eg_weight", (held, d, f), "matrix"),
                    (p + "eu_weight", (held, d, f), "matrix"),
                    (p + "ed_weight", (held, f, d), "out_expert"),
                    (p + "sg_weight", (f, d), "matrix"),
                    (p + "su_weight", (f, d), "matrix"),
                    (p + "sd_weight", (d, f), "out_shared")]
    out += [("fn_gamma", (d,), "gamma"), ("head_weight", (v, d), "matrix")]
    return out


def parameter_count(cfg):
    return sum(int(np.prod(s)) for _n, s, _k in layout(cfg))


@functools.partial(jax.jit, static_argnames=("shape", "kind", "std"))
def _make_leaf(key, shape, kind, std):
    if kind == "bias":          # the selection bias rides in float32
        return std * jax.random.normal(key, shape, jnp.float32)
    z = jax.random.normal(key, shape, jnp.bfloat16)
    if kind in ("gamma", "qk_gamma"):    # std is the gain's mean here
        z = std * (1.0 + 0.1 * z)
    else:
        z = std * z
    return z.astype(jnp.bfloat16)


class Weights(collections.abc.Mapping):
    """The model's leaves by name, each made from the seed when it is asked
    for and kept nowhere: N(0, ``init_std`` of its kind) in bfloat16, gains
    their mean x (1 + N(0, 0.1)), the selection bias N(0, ``init_std.bias``)
    in float32 and then, where the configuration has ``balance``, moved
    until seeded random text loads the experts evenly (``balanced_bias``:
    those seven vectors of 128 are the only leaves kept)."""

    def __init__(self, cfg, seed):
        self._leaves = {n: (i, tuple(s), k)
                        for i, (n, s, k) in enumerate(layout(cfg))}
        # the device's own bit generator: threefry takes a minute for the
        # 6e9 values, and the comparison makes them again a layer at a time
        seed = int(seed)
        self._key = jax.random.fold_in(
            jax.random.key(seed & 0x7FFFFFFF, impl="rbg"), seed >> 31)
        self._std = {"gamma": 1.0, **{k: float(v) for k, v in
                                      cfg["init_std"].items()}}
        self._kept = {}
        if cfg.get("balance"):
            balanced_bias(cfg, self, seed, self._kept)

    def __getitem__(self, name):
        if name in self._kept:
            return self._kept[name]
        i, shape, kind = self._leaves[name]
        return _make_leaf(jax.random.fold_in(self._key, i), shape, kind,
                          self._std[kind])

    def __contains__(self, name):
        return name in self._leaves

    def __iter__(self):
        return iter(self._leaves)

    def __len__(self):
        return len(self._leaves)

    def layer(self, i):
        p = "l%d_" % i
        return {n[len(p):]: self[n] for n in self._leaves if n.startswith(p)}


def init_weights(cfg, seed):
    return Weights(cfg, seed)


# -- the forward pass -------------------------------------------------------

def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale


def _mm(x, w, quant, spec="td,od->to"):
    """``x @ w.T`` with the weight stored (out, in), or ``spec``."""
    if quant:
        x, w = _fp8(x), _fp8(w)
    return jnp.einsum(spec, x, w)


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x, theta):
    """``x [T, heads, hd]`` at positions ``0 .. T-1``, half-split pairs."""
    t, _h, hd = x.shape
    half = hd // 2
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * theta ** (
        -jnp.arange(half, dtype=jnp.float32) / half)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def gated(h, wg, wu, wd, quant):
    return _mm(jax.nn.silu(_mm(h, wg, quant)) * _mm(h, wu, quant), wd, quant)


def attention(x, w, hq, hk, hd, window, theta, eps, quant):
    t = x.shape[0]
    h = rms(x, w["an_gamma"], eps)
    q = rms(_mm(h, w["q_weight"], quant).reshape(t, hq, hd), w["qn_gamma"], eps)
    k = rms(_mm(h, w["k_weight"], quant).reshape(t, hk, hd), w["kn_gamma"], eps)
    v = _mm(h, w["v_weight"], quant).reshape(t, hk, hd)
    if window:
        q, k = rope(q, theta), rope(k, theta)
    scores = jnp.einsum("tkgd,skd->kgts", q.reshape(t, hk, hq // hk, hd),
                        k) / np.sqrt(hd)
    dist = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    seen = dist >= 0
    if window:
        seen &= dist < window
    att = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    a = jnp.einsum("kgts,skd->tkgd", att, v).reshape(t, hq * hd)
    return x + _mm(a, w["o_weight"], quant)


def moe(h, w, top_k, scale, expert_first, quant):
    """The held experts' terms and the shared expert. ``eg/eu/ed`` hold the
    experts ``expert_first .. expert_first + held - 1`` of the router's."""
    held = w["eg_weight"].shape[0]
    s = jax.nn.sigmoid(_mm(h, w["router_weight"], quant))
    _top, chosen = jax.lax.top_k(s + w["router_bias"], top_k)
    ws = jnp.take_along_axis(s, chosen, axis=1)
    ws = scale * ws / jnp.sum(ws, axis=1, keepdims=True)
    # [T, held]: a token's weight on each held expert, 0 where not chosen
    on = jnp.sum(ws[:, :, None] * (chosen[:, :, None] - expert_first
                                   == jnp.arange(held)), axis=1)
    act = (jax.nn.silu(_mm(h, w["eg_weight"], quant, "td,edf->etf"))
           * _mm(h, w["eu_weight"], quant, "td,edf->etf"))
    each = _mm(act, w["ed_weight"], quant, "etf,efd->etd")
    return (jnp.einsum("te,etd->td", on, each)
            + gated(h, w["sg_weight"], w["su_weight"], w["sd_weight"], quant))


@functools.partial(jax.jit, static_argnames=(
    "dims", "window", "theta", "eps", "top_k", "scale", "expert_first",
    "quant"))
def block(x, w, dims, window, theta, eps, top_k, scale, expert_first=0,
          quant=False):
    """One decoder layer over a whole sequence ``x [T, D]``; ``w`` maps the
    layer's leaf names without their prefix to arrays as they are served."""
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        x = attention(x, w, *dims, window, theta, eps, quant)
        h = rms(x, w["mn_gamma"], eps)
        if "g_weight" in w:
            return x + gated(h, w["g_weight"], w["u_weight"], w["d_weight"],
                             quant)
        return x + moe(h, w, top_k, scale, expert_first, quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def head(x, g, w, eps, quant=False):
    with jax.default_matmul_precision("highest"):
        return _mm(rms(x, g.astype(jnp.float32), eps),
                   w.astype(jnp.float32), quant)


def logits(cfg, weights, tokens, positions, quant=False):
    """Logits ``[len(positions), vocab]`` of the full forward pass over
    ``tokens`` (1-D, padded as the caller likes: the pass is causal), at the
    positions asked for. A layer's weights exist only while it runs."""
    dims = (int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]),
            int(cfg["head_dim"]))
    eps = float(cfg["rms_norm_eps"])
    theta = float(cfg["rope_parameters"]["rope_theta"])
    x = jnp.take(weights["tok_emb_weight"], jnp.asarray(tokens, jnp.int32),
                 axis=0).astype(jnp.float32)
    for i, (att, _mlp) in enumerate(_layers(cfg)):
        window = int(cfg["sliding_window"]) if att == "sliding_attention" else 0
        x = block(x, weights.layer(i), dims, window, theta, eps,
                  int(cfg["num_experts_per_tok"]),
                  float(cfg["routed_scaling_factor"]),
                  int(cfg.get("expert_first", 0)), quant)
    x = jnp.take(x, jnp.asarray(positions, jnp.int32), axis=0)
    return head(x, weights["fn_gamma"], weights["head_weight"], eps, quant)


# -- the selection bias, balanced on seeded random text --------------------

@functools.partial(jax.jit, static_argnames=("top_k", "steps"))
def _even_out(s, bias, top_k, steps):
    """Move ``bias [E]`` until the ``top_k`` largest of ``s [T, E] + bias``
    fall evenly on the experts: an expert chosen more often than the mean
    loses a step of bias, one chosen less gains it, the step shrinking from
    0.03 to 0.0003 (the rule a trained router of this family is balanced
    by, without a loss term; here run to rest on a fixed sample)."""
    t, e = s.shape

    def step(i, b):
        _top, chosen = jax.lax.top_k(s + b, top_k)
        load = jnp.sum(chosen[:, :, None] == jnp.arange(e), axis=(0, 1))
        rate = 0.03 * 0.01 ** (i / (steps - 1))
        return b - rate * jnp.sign(load - t * top_k / e)
    bias = jax.lax.fori_loop(0, steps, step, bias)
    return bias - jnp.mean(bias)      # a shift of all chooses nothing


def balanced_bias(cfg, weights, seed, kept):
    """A trained router's selection bias keeps its experts evenly loaded;
    random weights with a random bias do not: every layer's attention adds
    its context's mean to each token, so the states of a text share more
    with every layer, and on the chip one held expert of the last layer
    drew 6.5 times the mean. So each MoE layer's bias is balanced, in layer
    order, on the states that ``balance.sequences`` texts of
    ``balance.length`` random tokens (from the seed) have on their way
    through the layers below, and put into ``kept`` under its leaf's
    name."""
    n, length = (int(cfg["balance"][k]) for k in ("sequences", "length"))
    dims = (int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]),
            int(cfg["head_dim"]))
    eps = float(cfg["rms_norm_eps"])
    theta = float(cfg["rope_parameters"]["rope_theta"])
    top_k = int(cfg["num_experts_per_tok"])
    tokens = np.random.default_rng(int(seed)).integers(
        0, int(cfg["vocab_size"]), size=(n, length))
    x = jnp.take(weights["tok_emb_weight"], jnp.asarray(tokens, jnp.int32),
                 axis=0).astype(jnp.float32)
    scale = float(cfg["routed_scaling_factor"])
    first = int(cfg.get("expert_first", 0))
    for i, (att, mlp) in enumerate(_layers(cfg)):
        window = int(cfg["sliding_window"]) if att == "sliding_attention" else 0
        w = weights.layer(i)
        if mlp == "dense":
            x = jax.vmap(lambda rows: block(rows, w, dims, window, theta, eps,
                                            top_k, scale, first))(x)
            continue
        x, bias = _balance_layer(x, w, dims, window, theta, eps, top_k)
        kept["l%d_router_bias" % i] = w["router_bias"] = bias
        x = _routed(x, w, top_k, scale, first, eps)


@functools.partial(jax.jit, static_argnames=(
    "dims", "window", "theta", "eps", "top_k"))
def _balance_layer(x, w, dims, window, theta, eps, top_k):
    """``x [n, T, D]`` through the layer's attention, and the bias that
    evens out its router on what comes out."""
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        x = jax.vmap(lambda rows: attention(rows, w, *dims, window, theta,
                                            eps, False))(x)
        h = rms(x, w["mn_gamma"], eps).reshape(-1, x.shape[-1])
        s = jax.nn.sigmoid(_mm(h, w["router_weight"], False))
        return x, _even_out(s, w["router_bias"], top_k, 200)


@functools.partial(jax.jit, static_argnames=(
    "top_k", "scale", "expert_first", "eps"))
def _routed(x, w, top_k, scale, expert_first, eps):
    """The MLP half of an MoE layer over ``x [n, T, D]`` (its attention is
    already in ``x``)."""
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        rows = x.reshape(-1, x.shape[-1])
        h = rms(rows, w["mn_gamma"], eps)
        return (rows + moe(h, w, top_k, scale, expert_first, False)
                ).reshape(x.shape)


# -- what a token and a decode step cost, from shapes --------------------

def _sizes(cfg):
    """(parameters outside routed experts and embedding, parameters of one
    routed expert, number of MoE layers)."""
    f, d = int(cfg["moe_intermediate_size"]), int(cfg["hidden_size"])
    routed = sum(int(np.prod(s)) for n, s, _k in layout(cfg)
                 if n.endswith(("eg_weight", "eu_weight", "ed_weight")))
    rest = parameter_count(cfg) - routed - int(cfg["vocab_size"]) * d
    n_moe = sum(1 for _a, m in _layers(cfg) if m != "dense")
    return rest, 3 * d * f, n_moe


def ops_per_token(cfg):
    """Operations of one token's forward pass here, at two a parameter it
    touches: everything outside the routed experts (the head's slice too;
    the embedding is a lookup), and of each MoE layer's routed experts the
    ``top_k x held / router_width`` a token meets on this chip on average
    (8 x 16 / 128 = 1)."""
    rest, expert, n_moe = _sizes(cfg)
    met = (int(cfg["num_experts_per_tok"]) * int(cfg["num_experts"])
           / int(cfg["router_width"]))
    return int(2 * (rest + n_moe * met * expert))


def _cache_rows(cfg, live_positions):
    """Live key/value rows a decode step reads, (full layers, window layers),
    summed over slots: all of a slot's positions on a full layer, at most
    ``sliding_window`` of them on a window layer. From the sum over slots
    alone the second is an upper bound, ``min(sum, slots x window)``; at
    this cell's lengths nearly every slot is past its window."""
    live = int(live_positions)
    return live, min(live, int(cfg["slots"]) * int(cfg["sliding_window"]))


def decode_attention_bytes(cfg, live_positions, cache_bytes=2):
    """Bytes the decode step's attention has to read: the key and value
    rows of the live positions, layer by layer."""
    row = 2 * int(cfg["num_key_value_heads"]) * int(cfg["head_dim"]) * cache_bytes
    full, windowed = _cache_rows(cfg, live_positions)
    n_win = sum(1 for a, _m in _layers(cfg) if a == "sliding_attention")
    n_full = int(cfg["num_hidden_layers"]) - n_win
    return (n_full * full + n_win * windowed) * row


def experts_hit_a_step(cfg):
    """Routed experts a decode step reads, summed over the MoE layers: a
    held expert no slot's token chose is not read. The program's own count
    where it keeps one (the registry gauge ``ops.moe_ffn.experts_hit``, a
    layer's mean over the decode steps between the last two readings of its
    device sums); else what evenly routed tokens give, from shapes: each of
    ``slots`` tokens leaves an expert out with ``1 - top_k / router_width``,
    so 16 x (1 - 0.9375^64) = 15.74 of 16 a layer."""
    from benchmarks.layer_metrics.moe_held_share_sat import registry
    hit = registry("ops.moe_ffn.experts_hit")
    if hit:
        return float(sum(hit))
    _rest, _expert, n_moe = _sizes(cfg)
    missed = (1.0 - int(cfg["num_experts_per_tok"])
              / int(cfg["router_width"])) ** int(cfg["slots"])
    return n_moe * int(cfg["num_experts"]) * (1.0 - missed)


def decode_step_bytes(cfg, live_positions, weight_bytes=2, cache_bytes=2):
    """Bytes one decode step has to read: every weight outside the routed
    experts once, the routed experts the step hits (``experts_hit_a_step``;
    with 64 tokens choosing 8 of 128 nearly all 16 a layer), of the
    embedding only the slots' token rows, and the live key and value
    rows."""
    d, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    _rest, expert, n_moe = _sizes(cfg)
    held = n_moe * int(cfg["num_experts"])
    weights = (parameter_count(cfg) - v * d + int(cfg["slots"]) * d
               - held * expert)
    return int(weights * weight_bytes
               + experts_hit_a_step(cfg) * expert * weight_bytes
               + decode_attention_bytes(cfg, live_positions, cache_bytes))


def routed_expert_bytes(cfg, weight_bytes=2, experts=None):
    """Bytes of ``experts`` routed experts' three matrices (all the held
    ones of every MoE layer when not given): what the grouped products of a
    step that hits them have to read."""
    _rest, expert, n_moe = _sizes(cfg)
    if experts is None:
        experts = n_moe * int(cfg["num_experts"])
    return int(experts * expert * weight_bytes)
