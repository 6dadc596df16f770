"""Model work of DeepSeek-V2-Lite at one chip's expert share, from the
sizes of its configuration file.

Forward FLOPs per token, with d the hidden size, h heads, dn/dr/dv the
nope, rope and value head dims, r the kv latent rank, E the router's
published experts, k experts per token, held the experts held here:

    attention projections  2 * layers * (d h (dn + dr) + d r + d dr
                                         + r h dn + r h dv + h dv d)
    attention scores       2 * layers * h * (dn + dr + dv) * (seq + 1) / 2
                           (causal: a token sees itself and what precedes it)
    dense MLP              6 * d * intermediate        per dense layer
    router                 2 * d * E                   per MoE layer
    shared experts         6 * d * n_shared * moe_intermediate
    routed experts         6 * d * moe_intermediate * k * held / E
                           (the held share of the routed work: 0.75
                           experts a token at 6 of 64 with 8 held)
    head                   2 * d * vocab               (untied, the slice)

Training FLOPs per token are three times the forward (forward, and the
backward's two products per forward product); recomputation in the
backward pass and the empty rows of the dropless expert buffers are not
counted.
"""


def sizes(cfg: dict) -> dict:
    dense = cfg["first_k_dense_replace"]
    return {"d": cfg["hidden_size"], "layers": cfg["num_hidden_layers"], "dense": dense,
            "moe": cfg["num_hidden_layers"] - dense, "h": cfg["num_attention_heads"],
            "dn": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
            "dv": cfg["v_head_dim"], "r": cfg["kv_lora_rank"], "ff": cfg["intermediate_size"],
            "eff": cfg["moe_intermediate_size"],
            "shared": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            "experts": cfg["published"]["n_routed_experts"], "held": cfg["n_routed_experts"],
            "k": cfg["num_experts_per_tok"], "vocab": cfg["vocab_size"]}


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    z = sizes(cfg)
    d, h = z["d"], z["h"]
    proj = d * h * (z["dn"] + z["dr"]) + d * z["r"] + d * z["dr"] \
        + z["r"] * h * z["dn"] + z["r"] * h * z["dv"] + h * z["dv"] * d
    attention = 2.0 * z["layers"] * (proj + h * (z["dn"] + z["dr"] + z["dv"]) * (seq + 1) / 2)
    dense = 6.0 * z["dense"] * d * z["ff"]
    routed = z["k"] * z["held"] / z["experts"]
    moe = z["moe"] * (2.0 * d * z["experts"] + 6.0 * d * z["shared"]
                      + 6.0 * d * z["eff"] * routed)
    return attention + dense + moe + 2.0 * d * z["vocab"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    return 3.0 * forward_flops_per_token(cfg, seq)


def matmuls(cfg: dict, batch: int, seq: int) -> list:
    """(M, K, N) of every ``x @ w`` that runs through masked_matmul in one
    forward pass: per layer the q, kv-down, rope-key and output
    projections, then layer 0's MLP or the MoE layer's shared experts and
    each held expert's gate, up and down on its dropless buffer of
    batch * seq rows.  The latent up-projections, the router and the head
    are plain einsums."""
    z = sizes(cfg)
    t, d, h = batch * seq, z["d"], z["h"]
    attn = [(t, d, h * (z["dn"] + z["dr"])), (t, d, z["r"]), (t, d, z["dr"]),
            (t, h * z["dv"], d)]
    out = []
    for _ in range(z["dense"]):
        out += attn + [(t, d, z["ff"]), (t, d, z["ff"]), (t, z["ff"], d)]
    for _ in range(z["moe"]):
        out += attn + [(t, d, z["shared"]), (t, d, z["shared"]), (t, z["shared"], d)]
        out += [(t, d, z["eff"]), (t, d, z["eff"]), (t, z["eff"], d)] * z["held"]
    return out
