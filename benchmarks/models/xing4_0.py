"""The program's side of the ``xing4_0`` family: the generation symbol,
built from registry ops, with the ``lc*/pos`` inputs and ``*_next`` outputs
that ``InferenceEngine`` detects. A layer keeps ONE state, its latent cache
``lc<i>`` (kind ``full``): a row of ``cache_row`` columns a position, the
576 published values ``[c_kv ; k_rope]`` and zeros up to whole 128-lane
slabs. The residual state between layers is ``[B, T, hc_mult, d]``; every
sub-layer sits between ``hyper_mix`` and ``hyper_merge``. Each expert layer
adds its routing counts into a device sum (kind ``sum:moe_load``); ``len``
carries a padded prompt's true length to the expert layers. Leaf names are
the reference's, so its weights go in as they are."""
from __future__ import annotations

import math


def attention_scale(cfg):
    """The softmax scale the symbol hands ``latent_attention``, from the
    configuration's keys: YaRN's temperature ``1 + 0.1 mscale_all_dim
    ln(factor)``, squared, over the root of a query head's width (0.14468 as
    published). The reference computes its own; a tier-1 test holds the two
    and the stated constant together."""
    rs = cfg["rope_scaling"]
    temperature = 1.0 + 0.1 * float(rs["mscale_all_dim"]) * math.log(
        float(rs["factor"]))
    return temperature ** 2 / math.sqrt(
        int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"]))


def symbol(cfg):
    import mxtpu as mx
    d, n = int(cfg["hidden_size"]), int(cfg["hc_mult"])
    heads = int(cfg["num_attention_heads"])
    nope, rope = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    vd, rq = int(cfg["v_head_dim"]), int(cfg["q_lora_rank"])
    rank = int(cfg["kv_lora_rank"])
    vocab, eps = int(cfg["vocab_size"]), float(cfg["rms_norm_eps"])
    experts, f = int(cfg["n_routed_experts"]), int(cfg["moe_intermediate_size"])
    shared = f * int(cfg["n_shared_experts"])
    rs = cfg["rope_scaling"]
    var = mx.sym.Variable

    def fc(x, width, name):
        return mx.sym.FullyConnected(data=x, num_hidden=width, no_bias=True,
                                     flatten=False, name=name)

    def norm(x, name):
        return mx.sym.RMSNorm(x, gamma=var(name + "_gamma"), eps=eps,
                              name=name)

    def gated(h, width, p, names):
        g, u, dn = names
        act = mx.sym.Activation(fc(h, width, p + g), act_type="silu")
        return fc(act * fc(h, width, p + u), d, p + dn)

    def hyper(streams, p, gamma, sublayer):
        """``sublayer`` (a symbol of the normalised read) inside the
        hyper-connection whose leaves are ``p + phi / alpha / base``."""
        mix = mx.sym.hyper_mix(
            streams, var(p + "phi"),
            var(p + "alpha"),
            var(p + "base"),
            sinkhorn_iters=int(cfg["hc_sinkhorn_iters"]),
            eps=float(cfg["hc_eps"]),
            clamp_min=float(cfg["mhc_h_res_clamp_min"]),
            clamp_max=float(cfg["mhc_h_res_clamp_max"]), name=p + "mix")
        return mx.sym.hyper_merge(mix[1], mix[2], sublayer(norm(mix[0], gamma)),
                                  name=p + "merge")

    data = var("data")
    pos = var("pos", shape=(0,), dtype="int32")
    true_len = var("len", shape=(0,), dtype="int32")
    x = mx.sym.Embedding(data=data, weight=var("tok_emb_weight"),
                         input_dim=vocab, output_dim=d, name="tok_emb")
    # the streams start as copies
    x = mx.sym.broadcast_axis(mx.sym.expand_dims(x, axis=2), axis=2, size=n,
                              name="streams")
    state_next = []
    for i in range(int(cfg["num_hidden_layers"])):
        p = "l%d_" % i
        cache = var("lc%d" % i, dtype=cfg["cache_dtype"],
                    shape=(0, int(cfg["cache_len"]), int(cfg["cache_row"])))

        def attend(h, p=p, cache=cache):
            q = fc(norm(fc(h, rq, p + "dq"), p + "qn"),
                   heads * (nope + rope), p + "uq")
            att = mx.sym.latent_attention(
                q, fc(h, rank + rope, p + "dkv"), var(p + "cn_gamma"),
                var(p + "ukv_weight"), cache, pos, num_heads=heads,
                nope_dim=nope, rope_dim=rope, v_dim=vd,
                scale=attention_scale(cfg), rope_theta=float(cfg["rope_theta"]),
                rope_factor=float(rs["factor"]),
                rope_beta_fast=float(rs["beta_fast"]),
                rope_beta_slow=float(rs["beta_slow"]),
                rope_orig_len=int(rs["original_max_position_embeddings"]),
                norm_eps=eps, name=p + "att")
            state_next.append(mx.sym.identity(att[1], name="lc%d_next" % i))
            return fc(att[0], d, p + "o")

        x = hyper(x, p + "ah_", p + "an", attend)
        if i < int(cfg["first_k_dense_replace"]):
            x = hyper(x, p + "mh_", p + "mn", lambda h, p=p: gated(
                h, int(cfg["intermediate_size"]), p, "gud"))
            continue

        def experts_of(h, p=p, i=i):
            load = var("moe_load%d" % i, shape=(0, experts + 5), dtype="int32",
                       attr={"__state_kind__": "sum:moe_load"})
            routed = mx.sym.moe_ffn_held(
                h, var(p + "router_weight"), var(p + "router_bias"),
                var(p + "eg_weight"), var(p + "eu_weight"),
                var(p + "ed_weight"), load=load, valid_len=true_len,
                top_k=int(cfg["num_experts_per_tok"]), expert_first=0,
                scale=float(cfg["routed_scaling_factor"]), name=p + "moe")
            state_next.append(mx.sym.identity(routed[1],
                                              name="moe_load%d_next" % i))
            return routed[0] + gated(h, shared, p, ("sg", "su", "sd"))

        x = hyper(x, p + "mh_", p + "mn", experts_of)
    # and end as a sum
    x = norm(mx.sym.sum(x, axis=2), "fn")
    return mx.sym.Group([fc(x, vocab, "head")] + state_next)
