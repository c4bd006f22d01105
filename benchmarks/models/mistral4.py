"""The program's side of the ``mistral4`` family: the generation symbol,
built from registry ops, with the ``lc*/pos`` inputs and ``*_next`` outputs
that ``InferenceEngine`` detects. A layer keeps ONE state, its latent cache
``lc<i>`` (kind ``full``): a row of ``cache_row`` columns a position, the 320
published values ``[c_kv (256) ; k_rope (64)]`` and zeros up to whole
128-lane slabs (384). One residual stream; every layer is an expert layer
that is told which of the router's experts it holds (``moe_ffn_held``,
``scoring="softmax"``, no selection bias: the operand is zeros) and adds its
routing counts into a device sum (kind ``sum:moe_load``); ``len`` carries a
padded prompt's true length to the expert layers. Leaf names are the
reference's, so its weights go in as they are."""
from __future__ import annotations

from . import xing4_0


def attention_scale(cfg):
    """The softmax scale the symbol hands ``latent_attention``, from the
    configuration's keys: YaRN's temperature ``1 + 0.1 mscale_all_dim
    ln(factor)``, squared, over the root of a query head's width (0.19497 as
    published; ``xing4_0.attention_scale`` under this family's key names).
    The reference computes its own; a tier-1 test holds the two and the
    stated constant together."""
    return xing4_0.attention_scale(
        dict(cfg, rope_scaling=cfg["rope_parameters"]))


def symbol(cfg):
    import mxtpu as mx
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    nope, rope = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    vd, rq = int(cfg["v_head_dim"]), int(cfg["q_lora_rank"])
    rank = int(cfg["kv_lora_rank"])
    vocab, eps = int(cfg["vocab_size"]), float(cfg["rms_norm_eps"])
    held, wide = int(cfg["n_routed_experts"]), int(cfg["router_width"])
    f = int(cfg["moe_intermediate_size"])
    shared = f * int(cfg["n_shared_experts"])
    rs = cfg["rope_parameters"]
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

    data = var("data")
    pos = var("pos", shape=(0,), dtype="int32")
    true_len = var("len", shape=(0,), dtype="int32")
    x = mx.sym.Embedding(data=data, weight=var("tok_emb_weight"),
                         input_dim=vocab, output_dim=d, name="tok_emb")
    # the model has no selection bias; the op's operand is zeros
    no_bias = mx.sym._zeros(shape=(wide,), dtype="float32", name="no_bias")
    state_next = []
    for i in range(int(cfg["num_hidden_layers"])):
        p = "l%d_" % i
        cache = var("lc%d" % i, dtype=cfg["cache_dtype"],
                    shape=(0, int(cfg["cache_len"]), int(cfg["cache_row"])))
        h = norm(x, p + "an")
        q = fc(norm(fc(h, rq, p + "dq"), p + "qn"), heads * (nope + rope),
               p + "uq")
        att = mx.sym.latent_attention(
            q, fc(h, rank + rope, p + "dkv"), var(p + "cn_gamma"),
            var(p + "ukv_weight"), cache, pos, num_heads=heads,
            nope_dim=nope, rope_dim=rope, v_dim=vd,
            scale=attention_scale(cfg), rope_theta=float(rs["rope_theta"]),
            rope_factor=float(rs["factor"]),
            rope_beta_fast=float(rs["beta_fast"]),
            rope_beta_slow=float(rs["beta_slow"]),
            rope_orig_len=int(rs["original_max_position_embeddings"]),
            norm_eps=eps, pos_scale_beta=float(rs["llama_4_scaling_beta"]),
            name=p + "att")
        state_next.append(mx.sym.identity(att[1], name="lc%d_next" % i))
        x = x + fc(att[0], d, p + "o")
        h = norm(x, p + "mn")
        load = var("moe_load%d" % i, shape=(0, held + 5), dtype="int32",
                   attr={"__state_kind__": "sum:moe_load"})
        routed = mx.sym.moe_ffn_held(
            h, var(p + "router_weight"), no_bias, var(p + "eg_weight"),
            var(p + "eu_weight"), var(p + "ed_weight"), load=load,
            valid_len=true_len, top_k=int(cfg["num_experts_per_tok"]),
            expert_first=int(cfg.get("expert_first", 0)),
            scale=float(cfg["routed_scaling_factor"]), scoring="softmax",
            name=p + "moe")
        state_next.append(mx.sym.identity(routed[1],
                                          name="moe_load%d_next" % i))
        x = x + routed[0] + gated(h, shared, p, ("sg", "su", "sd"))
    return mx.sym.Group([fc(norm(x, "fn"), vocab, "head")] + state_next)
