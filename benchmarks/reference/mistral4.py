"""Plain float32 reference of the ``mistral4`` decoder (Mistral AI,
Mistral-Small-4-119B-2603, ``config.json`` on huggingface.co/mistralai: the
language model; the catalog gives no key of the vision tower, which is not
loaded), as ONE chip of an 8-way expert-parallel group holds it.

Pre-norm, one residual stream, RMSNorm with ``eps`` 1e-6: ``x += Attn(
RMSNorm(x))``, ``x += MoE(RMSNorm(x))`` in every layer (``first_k_dense_
replace`` 0: ``intermediate_size`` belongs to no layer), a final RMSNorm and
an untied head.

**Latent attention** on ``h`` at position ``t``: ``cq = RMSNorm(W_dq h)``;
head ``i``'s query ``W_uq,i cq = [q_nope (64) ; q_rope (64)]``, the second
rotated; ``[c (256) ; k_rope (64)] = W_dkv h``, ``c`` normalised and
``k_rope`` rotated: what a cache holds of a position, 320 values shared by
all heads; ``[k_nope,i (64) ; v_i (128)] = W_ukv,i c``; ``score_i(t, s) =
g(t) scale (q_nope,i . k_nope,i(s) + q_rope,i . k_rope(s))`` for ``s <= t``,
float32 softmax, ``o_i = sum_s att v_i(s)``, out ``W_o concat(o_i)``.
``scale = 128^-1/2 m^2`` with ``m = 0.1 mscale_all_dim ln(factor) + 1``
(0.19497 as published); rotary frequencies are YaRN's, fixed
(:func:`yarn_freqs`: the ramp runs from pair 12 to pair 25 of 32), cos and
sin unscaled because ``mscale`` equals ``mscale_all_dim``; ``g(t) = 1 +
llama_4_scaling_beta ln(1 + floor(t / original_max_position_embeddings))``
(:func:`position_scale`). Computed a block of queries at a time
(``ATTN_BLOCK``), so that 10,240 positions fit a chip: every block sees all
keys and is masked, nothing is carried between blocks.

**Experts.** Router logits ``W_r h`` over all 128 experts in float32; the
scores are their SOFTMAX over the 128; the 4 largest are chosen (``n_group``
1: no group limit; no selection bias: the config has none), their scores
renormalised to sum to one and times ``routed_scaling_factor`` (1). Expert
``(silu(h W_g) * (h W_u)) W_d`` of width 2,048; one shared expert of the
same width is added for every token. The chip holds ``n_routed_experts`` of
the ``router_width`` (experts ``expert_first ..``) and ``vocab_size`` of the
131,072 rows of the embedding and of the untied head: it adds its own
experts' terms and the shared expert, and what the absent experts would have
added is left out, here exactly as in the program.

Departures-if-wrong (the catalog's config does not state them; each is
listed under ``assumed`` in the configuration): the router's scores are a
softmax (the family's convention: Mixtral's and ``mistral3``'s routers;
there is no ``scoring_func`` key); the form of ``g`` (the catalog has the
keys ``llama_4_scaling_beta`` and ``original_max_position_embeddings`` and
not the formula: the one Hugging Face ``transformers`` applies to the
``ministral3`` / ``mistral4`` queries, ``get_llama_4_attn_scale``, as the
builder recalls it); rotary pairs half-split, a fixed permutation of
``rope_interleave``'s order; the vision tower and the multi-token-prediction
modules are not loaded.

Straight ``jax.numpy`` in float32 under ``default_matmul_precision
("highest")``: no cache, no absorbed products, no kernels, no batching,
nothing of ``mxtpu``. It owns the weights: ``init_weights`` returns a
mapping that makes each leaf from the seed when it is asked for, rounded to
bfloat16 as the configuration serves it, so the pass below makes and drops a
layer at a time. The program is handed the same mapping.

``quant`` is the control: matrix-product inputs in scaled float8 (e4m3), the
nearest precision below bfloat16.
"""
from __future__ import annotations

import collections.abc
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import xing4_0
from .exaone_moe import _fp8, _mm, gated, rms
from .xing4_0 import rotate

ATTN_BLOCK = 1024      # query rows a block of the attention holds scores of


def _n_layers(cfg):
    return int(cfg["num_hidden_layers"])


def layout(cfg):
    """Every weight as ``(name, shape, kind)``. Kinds: matrix (fan-in last),
    q_up, out_attn / out_expert / out_shared (the matrices that write into
    the residual stream), expert_in (``[held, fan-in, F]``), emb, head,
    gamma, router. There is no selection bias: the model has none."""
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    nope, rope = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    vd, rq = int(cfg["v_head_dim"]), int(cfg["q_lora_rank"])
    rank = int(cfg["kv_lora_rank"])
    v, held = int(cfg["vocab_size"]), int(cfg["n_routed_experts"])
    wide, f = int(cfg["router_width"]), int(cfg["moe_intermediate_size"])
    shared = f * int(cfg["n_shared_experts"])
    out = [("tok_emb_weight", (v, d), "emb")]
    for i in range(_n_layers(cfg)):
        p = "l%d_" % i
        out += [(p + "an_gamma", (d,), "gamma"),
                (p + "dq_weight", (rq, d), "matrix"),
                (p + "qn_gamma", (rq,), "gamma"),
                (p + "uq_weight", (heads * (nope + rope), rq), "q_up"),
                (p + "dkv_weight", (rank + rope, d), "matrix"),
                (p + "cn_gamma", (rank,), "gamma"),
                (p + "ukv_weight", (heads * (nope + vd), rank), "matrix"),
                (p + "o_weight", (d, heads * vd), "out_attn"),
                (p + "mn_gamma", (d,), "gamma"),
                (p + "router_weight", (wide, d), "router"),
                (p + "eg_weight", (held, d, f), "expert_in"),
                (p + "eu_weight", (held, d, f), "expert_in"),
                (p + "ed_weight", (held, f, d), "out_expert"),
                (p + "sg_weight", (shared, d), "matrix"),
                (p + "su_weight", (shared, d), "matrix"),
                (p + "sd_weight", (d, shared), "out_shared")]
    out += [("fn_gamma", (d,), "gamma"), ("head_weight", (v, d), "head")]
    return out


def parameter_count(cfg):
    return sum(int(np.prod(s)) for _n, s, _k in layout(cfg))


def _fan_in(shape, kind):
    return shape[1] if kind in ("expert_in", "out_expert") else shape[-1]


@functools.partial(jax.jit, static_argnames=("shape", "kind", "std"))
def _make_leaf(key, shape, kind, std):
    z = jax.random.normal(key, shape, jnp.bfloat16)
    z = std * (1.0 + 0.1 * z) if kind == "gamma" else std * z
    return z.astype(jnp.bfloat16)


class Weights(collections.abc.Mapping):
    """The model's leaves by name, each made from the seed when it is asked
    for and kept nowhere. A matrix is N(0, ``init_gain`` of its kind over
    the square root of its fan-in) in bfloat16, the embedding N(0,
    ``init_gain.emb``), a norm's gain 1 + N(0, 0.1)."""

    def __init__(self, cfg, seed):
        self._leaves = {n: (i, tuple(s), k)
                        for i, (n, s, k) in enumerate(layout(cfg))}
        # the device's own bit generator: threefry takes a minute for the
        # 3e9 values, and the comparison makes them again a layer at a time
        seed = int(seed)
        self._key = jax.random.fold_in(
            jax.random.key(seed & 0x7FFFFFFF, impl="rbg"), seed >> 31)
        self._gain = {"gamma": 1.0, **{k: float(v) for k, v in
                                       cfg["init_gain"].items()}}

    def __getitem__(self, name):
        i, shape, kind = self._leaves[name]
        std = self._gain[kind]
        if kind not in ("emb", "gamma"):
            std /= math.sqrt(_fan_in(shape, kind))
        return _make_leaf(jax.random.fold_in(self._key, i), shape, kind, std)

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
    do when ``mscale_all_dim`` is set): 0.19497 as published."""
    rs = cfg["rope_parameters"]
    m = 0.1 * float(rs["mscale_all_dim"]) * math.log(float(rs["factor"])) + 1.0
    width = int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"])
    return width ** -0.5 * m * m


def yarn_freqs(cfg):
    """The rotary pairs' angular frequencies ``w_j``, ``j = 0 .. rope/2 -
    1`` (``xing4_0.yarn_freqs``, which reads the older key names): ``f_j =
    theta^(-2j / rope)``, blended toward ``f_j / factor`` by a ramp that
    runs from pair ``low`` to pair ``high`` (the pairs that turn
    ``beta_fast`` and ``beta_slow`` times over the original context)."""
    rs = cfg["rope_parameters"]
    return xing4_0.yarn_freqs({"rope_scaling": rs,
                               "rope_theta": rs["rope_theta"],
                               "qk_rope_head_dim": cfg["qk_rope_head_dim"]})


def position_scale(cfg, positions):
    """``g(t) = 1 + beta ln(1 + floor(t / original_max_position_embeddings))``:
    1 inside the first original context, 1 + beta ln 2 in the second."""
    rs = cfg["rope_parameters"]
    t = np.asarray(positions, np.float64)
    return 1.0 + float(rs["llama_4_scaling_beta"]) * np.log1p(np.floor(
        t / float(rs["original_max_position_embeddings"])))


def attention(h, w, dims, scale, freqs, pos_scale, eps, quant):
    """``pos_scale = (beta, original context)``: ``g`` is made here from the
    row's index, which is its position."""
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
    beta, orig = pos_scale
    at = jnp.arange(t)
    g = 1.0 + beta * jnp.log1p(jnp.floor(at.astype(jnp.float32) / orig))

    def rows(first):
        """The block of ``block`` queries from row ``first``: scores against
        every key, masked; nothing passes from block to block."""
        qn = jax.lax.dynamic_slice_in_dim(q_nope, first, block)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, first, block)
        mine = jax.lax.dynamic_slice_in_dim(at, first, block)
        scores = (jnp.einsum("thd,shd->hts", qn, k_nope)
                  + jnp.einsum("thd,sd->hts", qr, k_rope))
        scores = scores * (scale * jax.lax.dynamic_slice_in_dim(
            g, first, block))[None, :, None]
        seen = mine[:, None] >= at[None, :]
        att = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", att, v).reshape(block, heads * vd)

    block = math.gcd(t, ATTN_BLOCK)
    o = jax.lax.map(rows, jnp.arange(0, t, block)).reshape(t, heads * vd)
    return _mm(o, w["o_weight"], quant)


def moe(h, w, top_k, scale, expert_first, quant):
    """The held experts' terms and the shared expert. ``eg/eu/ed`` hold the
    experts ``expert_first .. expert_first + held - 1`` of the router's."""
    held = w["eg_weight"].shape[0]
    s = jax.nn.softmax(_mm(h, w["router_weight"], quant), axis=-1)
    ws, chosen = jax.lax.top_k(s, top_k)
    ws = scale * ws / jnp.sum(ws, axis=1, keepdims=True)
    # [T, held]: a token's weight on each held expert, 0 where not chosen
    on = jnp.sum(ws[:, :, None] * (chosen[:, :, None] - expert_first
                                   == jnp.arange(held)), axis=1)
    act = (jax.nn.silu(_mm(h, w["eg_weight"], quant, "td,edf->etf"))
           * _mm(h, w["eu_weight"], quant, "td,edf->etf"))
    down = w["ed_weight"]
    if quant:
        act, down = _fp8(act), _fp8(down)
    # weighted before the last product, so no [held, T, D] array exists
    routed = jnp.einsum("etf,efd->td", act * on.T[:, :, None], down)
    return routed + gated(h, w["sg_weight"], w["su_weight"], w["sd_weight"],
                          quant)


def _static(cfg):
    """What :func:`block` needs of the configuration, hashable."""
    rs = cfg["rope_parameters"]
    dims = (int(cfg["num_attention_heads"]), int(cfg["qk_nope_head_dim"]),
            int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"]))
    pos_scale = (float(rs["llama_4_scaling_beta"]),
                 float(rs["original_max_position_embeddings"]))
    return (dims, softmax_scale(cfg), tuple(yarn_freqs(cfg).tolist()),
            pos_scale, float(cfg["rms_norm_eps"]),
            int(cfg["num_experts_per_tok"]),
            float(cfg["routed_scaling_factor"]),
            int(cfg.get("expert_first", 0)))


@functools.partial(jax.jit, static_argnames=("static", "quant", "parts"))
def block(x, w, static, quant=False, parts=False):
    """One decoder layer over a whole sequence ``x [T, D]``; ``w`` maps the
    layer's leaf names without their prefix to arrays as they are served.
    ``parts``: the two terms the layer adds, ``(attention, experts)``, in
    place of the state."""
    dims, scale, freqs, pos_scale, eps, top_k, routed_scale, first = static
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        a = attention(rms(x, w["an_gamma"], eps), w, dims, scale,
                      np.asarray(freqs, np.float32), pos_scale, eps, quant)
        x = x + a
        m = moe(rms(x, w["mn_gamma"], eps), w, top_k, routed_scale, first,
                quant)
        return (a, m) if parts else x + m


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def head(x, g, w, eps, quant=False):
    with jax.default_matmul_precision("highest"):
        return _mm(rms(x, g.astype(jnp.float32), eps),
                   w.astype(jnp.float32), quant)


def logits(cfg, weights, tokens, positions, quant=False):
    """Logits ``[len(positions), vocab]`` of the full forward pass over
    ``tokens`` (1-D, padded as the caller likes: the pass is causal), at the
    positions asked for. A layer's weights exist only while it runs."""
    static = _static(cfg)
    x = jnp.take(weights["tok_emb_weight"], jnp.asarray(tokens, jnp.int32),
                 axis=0).astype(jnp.float32)
    for i in range(_n_layers(cfg)):
        x = block(x, weights.layer(i), static, quant)
    x = jnp.take(x, jnp.asarray(positions, jnp.int32), axis=0)
    return head(x, weights["fn_gamma"], weights["head_weight"], static[4],
                quant)


# -- what a token, a decode step and a prefill cost, from shapes -----------

def _routed(name):
    return name.endswith(("eg_weight", "eu_weight", "ed_weight"))


def _sizes(cfg):
    """(parameters outside routed experts and embedding, parameters of one
    routed expert)."""
    f, d = int(cfg["moe_intermediate_size"]), int(cfg["hidden_size"])
    routed = sum(int(np.prod(s)) for n, s, _k in layout(cfg) if _routed(n))
    rest = parameter_count(cfg) - routed - int(cfg["vocab_size"]) * d
    return rest, 3 * d * f


def ops_per_token(cfg):
    """Operations of one token's forward pass here, at two a parameter it
    touches: everything outside the routed experts (the head's slice too;
    the embedding is a lookup), and of each layer's routed experts the
    ``top_k x held / router_width`` a token meets on this chip on average
    (4 x 16 / 128 = 0.5). Attention over the context is left out: an
    undercount, by a wide margin at these contexts
    (:func:`prefill_attention_ops` counts a prefill's)."""
    rest, expert = _sizes(cfg)
    met = (int(cfg["num_experts_per_tok"]) * int(cfg["n_routed_experts"])
           / int(cfg["router_width"]))
    return int(2 * (rest + _n_layers(cfg) * met * expert))


def decode_attention_bytes(cfg, live_positions, cache_bytes=2):
    """Bytes the decode step's attention has to read: one latent row of
    ``kv_lora_rank + qk_rope_head_dim`` values (640 bytes) a live position
    and layer, once, though it serves as key and as value and whatever
    padding the cache's layout adds (the state's rows are 384 columns)."""
    row = (int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"])) * cache_bytes
    return int(live_positions) * _n_layers(cfg) * row


def experts_hit_a_step(cfg):
    """Routed experts a decode step reads, summed over the layers: a held
    expert no slot's token chose is not read. The program's own count where
    it keeps one (the registry gauge ``ops.moe_ffn.experts_hit``); else what
    evenly routed tokens give, from shapes: each of ``slots`` tokens leaves
    an expert out with ``1 - top_k / router_width``, so 16 x (1 -
    0.96875^96) = 15.24 of 16 a layer."""
    from benchmarks.layer_metrics.moe_held_share_sat import registry
    hit = registry("ops.moe_ffn.experts_hit")
    if hit:
        return float(sum(hit))
    missed = (1.0 - int(cfg["num_experts_per_tok"])
              / int(cfg["router_width"])) ** int(cfg["slots"])
    return _n_layers(cfg) * int(cfg["n_routed_experts"]) * (1.0 - missed)


def decode_step_bytes(cfg, live_positions, weight_bytes=2, cache_bytes=2):
    """Bytes one decode step has to read: every weight outside the routed
    experts once, the routed experts the step hits, of the embedding only
    the slots' token rows, and the live latent rows."""
    d, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    _rest, expert = _sizes(cfg)
    held = _n_layers(cfg) * int(cfg["n_routed_experts"])
    weights = (parameter_count(cfg) - v * d + int(cfg["slots"]) * d
               - held * expert)
    return int(weights * weight_bytes
               + experts_hit_a_step(cfg) * expert * weight_bytes
               + decode_attention_bytes(cfg, live_positions, cache_bytes))


def routed_expert_bytes(cfg, weight_bytes=2, experts=None):
    """Bytes of ``experts`` routed experts' three matrices (all the held
    ones of every layer when not given): what the grouped products of a step
    that hits them have to read."""
    _rest, expert = _sizes(cfg)
    if experts is None:
        experts = _n_layers(cfg) * int(cfg["n_routed_experts"])
    return int(experts * expert * weight_bytes)


def prefill_attention_ops(cfg, lengths):
    """Useful operations of the causal attention of prefills of these TRUE
    prompt lengths, whatever computes it: a query-key pair is one product
    of ``nope + rope`` columns for the score and one of ``v_head_dim`` for
    the output in each head, two operations a column; a prompt of ``n`` rows
    has ``n (n + 1) / 2`` pairs a layer. A bucket's padding and a tile's
    masked half are no work."""
    pair = 2 * int(cfg["num_attention_heads"]) * (
        int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"])
        + int(cfg["v_head_dim"]))
    return int(sum(int(n) * (int(n) + 1) // 2 for n in lengths)
               * pair * _n_layers(cfg))
