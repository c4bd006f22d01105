"""Plain float32 reference of the ``xing4_0`` decoder (XingChen-AGI,
Xing4.0-29B-A4B, ``config.json`` on huggingface.co/XingChen-AGI), as ONE
chip of a pipeline of seven holds it: whole layers, all 64 experts, the
whole vocabulary.

**Streams.** A token's embedding is copied into ``n = hc_mult`` residual
streams, ``X [n, d]``. Every sub-layer ``F`` (attention, then the MLP) sits
inside a hyper-connection with parameters ``phi [n d, n (n + 2)]``, ``alpha
[3]``, ``base [n (n + 2)]``:

1. ``x~ = vec(X) rsqrt(mean(vec(X)^2) + eps)`` (no gain); ``m = x~ phi``;
2. ``a = sigmoid(alpha_0 m[:n] + base[:n])`` (read weights); ``b = 2
   sigmoid(alpha_1 m[n:2n] + base[n:2n])`` (write weights); ``R~ =
   clamp(alpha_2 mat(m[2n:]) + mat(base[2n:]), clamp_min, clamp_max)``;
3. Sinkhorn: ``M = exp(R~)``, then ``hc_sinkhorn_iters`` times ``M <- M /
   (rowsum(M) + eps)``, ``M <- M / (colsum(M) + eps)``; ``R = M``;
4. ``u = sum_j a_j X[j]``; ``y = F(RMSNorm(u))``; ``X'[i] = sum_j R[i, j]
   X[j] + b_i y``.

After the last layer the streams are summed, normalised, and meet the
untied head.

**Latent attention** on ``h = RMSNorm(u)`` at position ``t``: ``cq =
RMSNorm(W_dq h)``; head ``i``'s query ``W_uq,i cq = [q_nope (128) ; q_rope
(64)]``, the second rotated; ``[c ; k_rope] = W_dkv h``, ``c`` normalised
and ``k_rope`` rotated: what a cache holds of a position, 576 values shared
by all heads; ``[k_nope,i ; v_i] = W_ukv,i c``; ``score_i(t, s) = scale
(q_nope,i . k_nope,i(s) + q_rope,i . k_rope(s))`` for ``s <= t``, softmax,
``o_i = sum_s att v_i(s)``, out ``W_o concat(o_i)``. ``scale = 192^-1/2
m^2`` with ``m = 0.1 mscale_all_dim ln(factor) + 1``; rotary frequencies
are YaRN's, fixed (:func:`yarn_freqs`), cos and sin unscaled.

**MLP.** Layer 0 (the dense layers count once here): SiLU-gated, width
9216. Expert layers: sigmoid scores over 64 experts, the 4 largest of
``score + selection bias`` chosen, their scores renormalised and scaled by
2, one shared expert beside them.

Departures-if-wrong (the catalog's config does not state them; each is
listed under ``assumed`` in the configuration): the hyper-connection's
form above (rows before columns, where ``eps`` sits, the clamp before
``exp``, ``R``'s second index over the source stream, streams that start as
copies and end as a sum, the pre-norm on ``u``); rotary pairs half-split;
the multi-token-prediction module is not loaded.

Straight ``jax.numpy`` in float32 under ``default_matmul_precision
("highest")`` over the whole sequence at once: no cache, no absorbed
products, no kernels, nothing of ``mxtpu``. It owns the weights:
``init_weights`` returns a mapping that makes each leaf from the seed when
it is asked for, rounded to bfloat16 as the configuration serves it (the
hyper-connections' parameters and the selection bias are float32), so the
pass below makes and drops a layer at a time. The program is handed the same
mapping.

``quant`` is the control: matrix-product inputs in scaled float8 (e4m3),
the nearest precision below bfloat16; what the program keeps in float32
(the stream mixing) stays float32.
"""
from __future__ import annotations

import collections.abc
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .exaone_moe import _even_out, _fp8, _mm, gated, rms

FLOAT32_KINDS = ("phi", "alpha", "base", "bias")


def _n_layers(cfg):
    return int(cfg["num_hidden_layers"])


def _is_dense(cfg, i):
    return i < int(cfg["first_k_dense_replace"])


def layout(cfg):
    """Every weight as ``(name, shape, kind)``. Kinds: matrix (fan-in last),
    q_up, out_attn / out_dense / out_expert / out_shared (the matrices that
    write into the streams), expert_in (``[E, fan-in, F]``), emb, head,
    gamma, router, bias, and phi / alpha / base of a hyper-connection."""
    d, n = int(cfg["hidden_size"]), int(cfg["hc_mult"])
    heads = int(cfg["num_attention_heads"])
    nope, rope = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    vd, rq = int(cfg["v_head_dim"]), int(cfg["q_lora_rank"])
    rank = int(cfg["kv_lora_rank"])
    v, e = int(cfg["vocab_size"]), int(cfg["n_routed_experts"])
    f, dense = int(cfg["moe_intermediate_size"]), int(cfg["intermediate_size"])
    shared = f * int(cfg["n_shared_experts"])

    def hyper(p):
        return [(p + "phi", (n * d, n * (n + 2)), "phi"),
                (p + "alpha", (3,), "alpha"),
                (p + "base", (n * (n + 2),), "base")]

    out = [("tok_emb_weight", (v, d), "emb")]
    for i in range(_n_layers(cfg)):
        p = "l%d_" % i
        out += hyper(p + "ah_") + [
            (p + "an_gamma", (d,), "gamma"),
            (p + "dq_weight", (rq, d), "matrix"),
            (p + "qn_gamma", (rq,), "gamma"),
            (p + "uq_weight", (heads * (nope + rope), rq), "q_up"),
            (p + "dkv_weight", (rank + rope, d), "matrix"),
            (p + "cn_gamma", (rank,), "gamma"),
            (p + "ukv_weight", (heads * (nope + vd), rank), "matrix"),
            (p + "o_weight", (d, heads * vd), "out_attn")]
        out += hyper(p + "mh_") + [(p + "mn_gamma", (d,), "gamma")]
        if _is_dense(cfg, i):
            out += [(p + "g_weight", (dense, d), "matrix"),
                    (p + "u_weight", (dense, d), "matrix"),
                    (p + "d_weight", (d, dense), "out_dense")]
        else:
            out += [(p + "router_weight", (e, d), "router"),
                    (p + "router_bias", (e,), "bias"),
                    (p + "eg_weight", (e, d, f), "expert_in"),
                    (p + "eu_weight", (e, d, f), "expert_in"),
                    (p + "ed_weight", (e, f, d), "out_expert"),
                    (p + "sg_weight", (shared, d), "matrix"),
                    (p + "su_weight", (shared, d), "matrix"),
                    (p + "sd_weight", (d, shared), "out_shared")]
    out += [("fn_gamma", (d,), "gamma"), ("head_weight", (v, d), "head")]
    return out


def parameter_count(cfg):
    return sum(int(np.prod(s)) for _n, s, _k in layout(cfg))


def _fan_in(shape, kind):
    return shape[1] if kind in ("expert_in", "out_expert") else shape[-1]


@functools.partial(jax.jit, static_argnames=("shape", "kind", "std", "n"))
def _make_leaf(key, shape, kind, std, n):
    if kind == "alpha":
        return jnp.full(shape, std, jnp.float32)
    if kind == "base":
        # read and write logits 0; the stream matrix's logits ``std`` on the
        # diagonal: each stream mostly keeps itself
        return jnp.concatenate([jnp.zeros((2 * n,), jnp.float32),
                                std * jnp.eye(n, dtype=jnp.float32).ravel()])
    if kind in ("phi", "bias"):
        return std * jax.random.normal(key, shape, jnp.float32)
    z = jax.random.normal(key, shape, jnp.bfloat16)
    z = std * (1.0 + 0.1 * z) if kind == "gamma" else std * z
    return z.astype(jnp.bfloat16)


class Weights(collections.abc.Mapping):
    """The model's leaves by name, each made from the seed when it is asked
    for and kept nowhere. A matrix is N(0, ``init_gain`` of its kind over
    the square root of its fan-in) in bfloat16, the embedding N(0,
    ``init_gain.emb``); a norm's gain 1 + N(0, 0.1); ``phi`` N(0,
    ``init_gain.phi`` / sqrt(n d)), ``alpha`` and the diagonal of ``base``'s
    stream matrix the configuration's constants, the selection bias N(0,
    ``init_gain.bias``), these four in float32. Where the configuration has
    ``balance`` the selection bias is then moved until seeded random text
    loads the experts evenly (:func:`balanced_bias`: those vectors of 64 are
    the only leaves kept)."""

    def __init__(self, cfg, seed):
        self._leaves = {n: (i, tuple(s), k)
                        for i, (n, s, k) in enumerate(layout(cfg))}
        seed = int(seed)
        self._key = jax.random.fold_in(
            jax.random.key(seed & 0x7FFFFFFF, impl="rbg"), seed >> 31)
        self._gain = {"gamma": 1.0, **{k: float(v) for k, v in
                                       cfg["init_gain"].items()}}
        self._n = int(cfg["hc_mult"])
        self._kept = {}
        if cfg.get("balance"):
            balanced_bias(cfg, self, seed, self._kept)

    def __getitem__(self, name):
        if name in self._kept:
            return self._kept[name]
        i, shape, kind = self._leaves[name]
        std = self._gain[kind]
        if kind not in ("emb", "gamma", "alpha", "base", "bias"):
            std /= math.sqrt(_fan_in(shape, kind))
        return _make_leaf(jax.random.fold_in(self._key, i), shape, kind,
                          std, self._n)

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

def softmax_scale(cfg):
    """``(nope + rope)^-1/2 m^2``, ``m = 0.1 mscale_all_dim ln(factor) + 1``
    (YaRN's attention temperature folded into the scale, as DeepSeek-V2/V3
    do when ``mscale_all_dim`` is set)."""
    rs = cfg["rope_scaling"]
    m = 0.1 * float(rs["mscale_all_dim"]) * math.log(float(rs["factor"])) + 1.0
    width = int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"])
    return width ** -0.5 * m * m


def yarn_freqs(cfg):
    """The rotary pairs' angular frequencies ``w_j``, ``j = 0 .. rope/2 -
    1``: ``f_j = theta^(-2j / rope)``, blended toward ``f_j / factor`` by a
    ramp ``g_j`` that runs from pair ``low`` to pair ``high`` (the pairs
    that turn ``beta_fast`` and ``beta_slow`` times over the original
    context): ``w_j = f_j ((1 - g_j) + g_j / factor)``."""
    rs = cfg["rope_scaling"]
    rope, theta = int(cfg["qk_rope_head_dim"]), float(cfg["rope_theta"])
    orig = float(rs["original_max_position_embeddings"])

    def pair_turning(turns):
        return (rope * math.log(orig / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_turning(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(pair_turning(float(rs["beta_slow"]))), rope // 2 - 1)
    j = np.arange(rope // 2, dtype=np.float64)
    f = theta ** (-2.0 * j / rope)
    g = np.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (f * ((1.0 - g) + g / float(rs["factor"]))).astype(np.float32)


def rotate(x, freqs):
    """``x [T, ..., rope]`` at positions ``0 .. T-1``, half-split pairs."""
    half = x.shape[-1] // 2
    ang = (jnp.arange(x.shape[0], dtype=jnp.float32).reshape(
        (-1,) + (1,) * (x.ndim - 1)) * jnp.asarray(freqs))
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def sinkhorn(logits, iters, eps):
    """``logits [..., n, n]`` to a matrix whose rows and columns sum to one:
    ``exp``, then ``iters`` times rows, then columns."""
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def hyper_weights(X, phi, alpha, base, hc):
    """``X [T, n, d]`` to the read weights ``a [T, n]``, the write weights
    ``b [T, n]`` and the stream matrix ``R [T, n, n]`` (row: the stream
    written, column: the stream read)."""
    iters, eps, lo, hi = hc
    t, n, d = X.shape
    flat = X.reshape(t, n * d)
    flat = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True) + eps)
    m = flat @ phi
    a = jax.nn.sigmoid(alpha[0] * m[:, :n] + base[:n])
    b = 2.0 * jax.nn.sigmoid(alpha[1] * m[:, n:2 * n] + base[n:2 * n])
    logits = jnp.clip(alpha[2] * m[:, 2 * n:] + base[2 * n:], lo, hi)
    return a, b, sinkhorn(logits.reshape(t, n, n), iters, eps)


def hyper(X, w, p, gamma, hc, eps, F):
    """One sub-layer ``F`` inside its hyper-connection (leaves ``p + phi /
    alpha / base``); the pre-norm's gain is ``w[gamma]``."""
    a, b, R = hyper_weights(X, w[p + "phi"], w[p + "alpha"], w[p + "base"], hc)
    u = jnp.einsum("tj,tjd->td", a, X)
    y = F(rms(u, w[gamma], eps))
    return jnp.einsum("tij,tjd->tid", R, X) + b[:, :, None] * y[:, None, :]


def attention(h, w, dims, scale, freqs, eps, quant):
    heads, nope, rope, vd = dims
    t = h.shape[0]
    rank = w["cn_gamma"].shape[0]
    cq = rms(_mm(h, w["dq_weight"], quant), w["qn_gamma"], eps)
    q = _mm(cq, w["uq_weight"], quant).reshape(t, heads, nope + rope)
    q_nope, q_rope = q[..., :nope], rotate(q[..., nope:], freqs)
    row = _mm(h, w["dkv_weight"], quant)
    c = rms(row[:, :rank], w["cn_gamma"], eps)
    k_rope = rotate(row[:, rank:], freqs)
    kv = _mm(c, w["ukv_weight"], quant).reshape(t, heads, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scores = scale * (jnp.einsum("thd,shd->hts", q_nope, k_nope)
                      + jnp.einsum("thd,sd->hts", q_rope, k_rope))
    seen = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    att = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hts,shd->thd", att, v).reshape(t, heads * vd)
    return _mm(o, w["o_weight"], quant)


def moe(h, w, top_k, scale, quant):
    """The routed experts' terms and the shared expert's."""
    e = w["eg_weight"].shape[0]
    s = jax.nn.sigmoid(_mm(h, w["router_weight"], quant))
    _top, chosen = jax.lax.top_k(s + w["router_bias"], top_k)
    ws = jnp.take_along_axis(s, chosen, axis=1)
    ws = scale * ws / jnp.sum(ws, axis=1, keepdims=True)
    # [T, E]: a token's weight on each expert, 0 where not chosen
    on = jnp.sum(ws[:, :, None] * (chosen[:, :, None] == jnp.arange(e)),
                 axis=1)
    act = (jax.nn.silu(_mm(h, w["eg_weight"], quant, "td,edf->etf"))
           * _mm(h, w["eu_weight"], quant, "td,edf->etf"))
    down = w["ed_weight"]
    if quant:
        act, down = _fp8(act), _fp8(down)
    # weighted before the last product, so no [E, T, D] array exists
    routed = jnp.einsum("etf,efd->td", act * on.T[:, :, None], down)
    return routed + gated(h, w["sg_weight"], w["su_weight"], w["sd_weight"],
                          quant)


def _static(cfg):
    """What :func:`block` needs of the configuration, hashable."""
    dims = (int(cfg["num_attention_heads"]), int(cfg["qk_nope_head_dim"]),
            int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"]))
    hc = (int(cfg["hc_sinkhorn_iters"]), float(cfg["hc_eps"]),
          float(cfg["mhc_h_res_clamp_min"]), float(cfg["mhc_h_res_clamp_max"]))
    return (dims, hc, softmax_scale(cfg), tuple(yarn_freqs(cfg).tolist()),
            float(cfg["rms_norm_eps"]), int(cfg["num_experts_per_tok"]),
            float(cfg["routed_scaling_factor"]))


@functools.partial(jax.jit, static_argnames=("static", "quant", "half"))
def block(X, w, static, quant=False, half="both"):
    """One decoder layer over a whole sequence's streams ``X [T, n, d]``;
    ``w`` maps the layer's leaf names without their prefix to arrays as they
    are served. ``half``: ``"attn"`` stops after the attention sub-layer,
    ``"mlp"`` runs the MLP sub-layer alone (for :func:`balanced_bias`)."""
    dims, hc, scale, freqs, eps, top_k, routed_scale = static
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        if half != "mlp":
            X = hyper(X, w, "ah_", "an_gamma", hc, eps, lambda h: attention(
                h, w, dims, scale, np.asarray(freqs, np.float32), eps, quant))
        if half == "attn":
            return X
        if "g_weight" in w:
            return hyper(X, w, "mh_", "mn_gamma", hc, eps, lambda h: gated(
                h, w["g_weight"], w["u_weight"], w["d_weight"], quant))
        return hyper(X, w, "mh_", "mn_gamma", hc, eps, lambda h: moe(
            h, w, top_k, routed_scale, quant))


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def head(X, g, w, eps, quant=False):
    with jax.default_matmul_precision("highest"):
        return _mm(rms(jnp.sum(X, axis=-2), g.astype(jnp.float32), eps),
                   w.astype(jnp.float32), quant)


def embed(cfg, weights, tokens):
    x = jnp.take(weights["tok_emb_weight"], jnp.asarray(tokens, jnp.int32),
                 axis=0).astype(jnp.float32)
    return jnp.repeat(x[..., None, :], int(cfg["hc_mult"]), axis=-2)


def logits(cfg, weights, tokens, positions, quant=False):
    """Logits ``[len(positions), vocab]`` of the full forward pass over
    ``tokens`` (1-D, padded as the caller likes: the pass is causal), at the
    positions asked for. A layer's weights exist only while it runs."""
    static = _static(cfg)
    X = embed(cfg, weights, tokens)
    for i in range(_n_layers(cfg)):
        X = block(X, weights.layer(i), static, quant)
    X = jnp.take(X, jnp.asarray(positions, jnp.int32), axis=0)
    return head(X, weights["fn_gamma"], weights["head_weight"], static[4],
                quant)


# -- the selection bias, balanced on seeded random text --------------------

@functools.partial(jax.jit, static_argnames=("static",))
def _router_scores(X, w, static):
    """Sigmoid scores ``[n T, E]`` of the tokens of ``X [n, T, streams, d]``
    as the expert layer's router sees them."""
    _dims, hc, _scale, _freqs, eps, _k, _s = static
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        rows = X.reshape((-1,) + X.shape[2:])
        a, _b, _R = hyper_weights(rows, w["mh_phi"], w["mh_alpha"],
                                  w["mh_base"], hc)
        h = rms(jnp.einsum("tj,tjd->td", a, rows), w["mn_gamma"], eps)
        return jax.nn.sigmoid(_mm(h, w["router_weight"], False))


def balanced_bias(cfg, weights, seed, kept):
    """A trained router's selection bias keeps its experts evenly loaded;
    random weights with a random bias do not. So each expert layer's bias is
    balanced, in layer order, on the states that ``balance.sequences`` texts
    of ``balance.length`` random tokens (from the seed) have on their way
    through the layers below, by the family's loss-free rule
    (``exaone_moe._even_out``), and put into ``kept`` under its leaf's
    name."""
    n, length = (int(cfg["balance"][k]) for k in ("sequences", "length"))
    static = _static(cfg)
    tokens = np.random.default_rng(int(seed)).integers(
        0, int(cfg["vocab_size"]), size=(n, length))
    X = embed(cfg, weights, tokens)
    for i in range(_n_layers(cfg)):
        w = weights.layer(i)
        if _is_dense(cfg, i):
            X = jax.vmap(lambda rows: block(rows, w, static))(X)
            continue
        X = jax.vmap(lambda rows: block(rows, w, static, half="attn"))(X)
        bias = _even_out(_router_scores(X, w, static), w["router_bias"],
                         static[5], 200)
        kept["l%d_router_bias" % i] = w["router_bias"] = bias
        X = jax.vmap(lambda rows: block(rows, w, static, half="mlp"))(X)


# -- what a token and a decode step cost, from shapes --------------------

def _bytes_of(kind, weight_bytes):
    return 4 if kind in FLOAT32_KINDS else weight_bytes


def _routed(name):
    return name.endswith(("eg_weight", "eu_weight", "ed_weight"))


def _sizes(cfg):
    """(parameters outside routed experts and embedding, parameters of one
    routed expert, number of expert layers)."""
    f, d = int(cfg["moe_intermediate_size"]), int(cfg["hidden_size"])
    routed = sum(int(np.prod(s)) for n, s, _k in layout(cfg) if _routed(n))
    rest = parameter_count(cfg) - routed - int(cfg["vocab_size"]) * d
    n_moe = sum(1 for i in range(_n_layers(cfg)) if not _is_dense(cfg, i))
    return rest, 3 * d * f, n_moe


def ops_per_token(cfg):
    """Operations of one token's forward pass, at two a parameter it
    touches: everything outside the routed experts (the head too; the
    embedding is a lookup), and of each expert layer's 64 routed experts
    the ``num_experts_per_tok`` a token meets. Attention over the context
    and the Sinkhorn iterations are left out: an undercount."""
    rest, expert, n_moe = _sizes(cfg)
    return int(2 * (rest + n_moe * int(cfg["num_experts_per_tok"]) * expert))


def decode_attention_bytes(cfg, live_positions, cache_bytes=2):
    """Bytes the decode step's attention has to read: one latent row of
    ``kv_lora_rank + qk_rope_head_dim`` values a live position and layer,
    once, though it serves as key and as value and whatever padding the
    cache's layout adds."""
    row = (int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"])) * cache_bytes
    return int(live_positions) * _n_layers(cfg) * row


def experts_hit_a_step(cfg):
    """Routed experts a decode step reads, summed over the expert layers:
    an expert no slot's token chose is not read. The program's own count
    where it keeps one (the registry gauge ``ops.moe_ffn.experts_hit``);
    else what evenly routed tokens give, from shapes: each of ``slots``
    tokens leaves an expert out with ``1 - top_k / experts``."""
    from benchmarks.layer_metrics.moe_held_share_sat import registry
    hit = registry("ops.moe_ffn.experts_hit")
    if hit:
        return float(sum(hit))
    _rest, _expert, n_moe = _sizes(cfg)
    e = int(cfg["n_routed_experts"])
    missed = (1.0 - int(cfg["num_experts_per_tok"]) / e) ** int(cfg["slots"])
    return n_moe * e * (1.0 - missed)


def decode_step_bytes(cfg, live_positions, weight_bytes=2, cache_bytes=2):
    """Bytes one decode step has to read: every leaf outside the routed
    experts and the embedding once (the hyper-connections' and the
    selection bias at 4 bytes), the routed experts the step hits, of the
    embedding only the slots' token rows, and the live latent rows."""
    d = int(cfg["hidden_size"])
    _rest, expert, _n_moe = _sizes(cfg)
    fixed = sum(int(np.prod(s)) * _bytes_of(k, weight_bytes)
                for n, s, k in layout(cfg)
                if not _routed(n) and n != "tok_emb_weight")
    return int(fixed + int(cfg["slots"]) * d * weight_bytes
               + experts_hit_a_step(cfg) * expert * weight_bytes
               + decode_attention_bytes(cfg, live_positions, cache_bytes))


def routed_expert_bytes(cfg, weight_bytes=2, experts=None):
    """Bytes of ``experts`` routed experts' three matrices (all of every
    expert layer when not given): what the grouped products of a step that
    hits them have to read."""
    _rest, expert, n_moe = _sizes(cfg)
    if experts is None:
        experts = n_moe * int(cfg["n_routed_experts"])
    return int(experts * expert * weight_bytes)
