"""The program's side of the ``exaone_moe`` family: the generation symbol,
built from registry ops, with the ``kc*/vc*/pos`` inputs and ``*_next``
outputs that ``InferenceEngine`` detects. A window layer's cache is a ring
of ``ring_rows`` rows (state kind ``ring``), a full layer's has
``cache_len`` rows; ``len`` carries a padded prompt's true length to the
rings; each expert layer adds its routing counts into a device sum (state
kind ``sum:moe_load``). Leaf names are the reference's, so its weights go
in as they are."""
from __future__ import annotations


def symbol(cfg):
    import mxtpu as mx
    d, hd = int(cfg["hidden_size"]), int(cfg["head_dim"])
    hq, hk = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    vocab, eps = int(cfg["vocab_size"]), float(cfg["rms_norm_eps"])
    held, f = int(cfg["num_experts"]), int(cfg["moe_intermediate_size"])
    window = int(cfg["sliding_window"])
    theta = float(cfg["rope_parameters"]["rope_theta"])
    cache_dtype = cfg["cache_dtype"]
    n = int(cfg["num_hidden_layers"])
    var = mx.sym.Variable

    def fc(x, width, name):
        return mx.sym.FullyConnected(data=x, num_hidden=width, no_bias=True,
                                     flatten=False, name=name)

    def gated(h, width, p, names):
        g, u, dn = names
        act = mx.sym.Activation(fc(h, width, p + g), act_type="silu")
        return fc(act * fc(h, width, p + u), d, p + dn)

    data = var("data")
    pos = var("pos", shape=(0,), dtype="int32")
    true_len = var("len", shape=(0,), dtype="int32")
    x = mx.sym.Embedding(data=data, weight=var("tok_emb_weight"),
                         input_dim=vocab, output_dim=d, name="tok_emb")
    state_next = []
    for i in range(n):
        p = "l%d_" % i
        ring = cfg["layer_types"][i] == "sliding_attention"
        rows = int(cfg["ring_rows"]) if ring else int(cfg["cache_len"])
        kind = {"__state_kind__": "ring"} if ring else None
        kc, vc = (var("%sc%d" % (c, i), shape=(0, rows, hk * hd),
                      dtype=cache_dtype, attr=kind) for c in "kv")
        h = mx.sym.RMSNorm(x, gamma=var(p + "an_gamma"), eps=eps,
                           name=p + "an")
        att = mx.sym.cached_attention(
            fc(h, hq * hd, p + "q"), fc(h, hk * hd, p + "k"),
            fc(h, hk * hd, p + "v"), kc, vc, pos, valid_len=true_len,
            q_gain=var(p + "qn_gamma"), k_gain=var(p + "kn_gamma"),
            num_heads=hq, num_kv_heads=hk, window=window if ring else 0,
            rope_theta=theta if ring else 0.0, norm_eps=eps, name=p + "att")
        x = x + fc(att[0], d, p + "o")
        state_next += [mx.sym.identity(att[1], name="kc%d_next" % i),
                       mx.sym.identity(att[2], name="vc%d_next" % i)]
        h = mx.sym.RMSNorm(x, gamma=var(p + "mn_gamma"), eps=eps,
                           name=p + "mn")
        if cfg["mlp_layer_types"][i] == "dense":
            x = x + gated(h, int(cfg["intermediate_size"]), p, "gud")
            continue
        load = var("moe_load%d" % i, shape=(0, held + 5), dtype="int32",
                   attr={"__state_kind__": "sum:moe_load"})
        routed = mx.sym.moe_ffn_held(
            h, var(p + "router_weight"), var(p + "router_bias"),
            var(p + "eg_weight"), var(p + "eu_weight"), var(p + "ed_weight"),
            load=load, valid_len=true_len,
            top_k=int(cfg["num_experts_per_tok"]),
            expert_first=int(cfg.get("expert_first", 0)),
            scale=float(cfg["routed_scaling_factor"]), name=p + "moe")
        x = x + routed[0] + gated(h, f, p, ("sg", "su", "sd"))
        state_next.append(mx.sym.identity(routed[1],
                                          name="moe_load%d_next" % i))
    x = mx.sym.RMSNorm(x, gamma=var("fn_gamma"), eps=eps, name="fn")
    return mx.sym.Group([fc(x, vocab, "head")] + state_next)
