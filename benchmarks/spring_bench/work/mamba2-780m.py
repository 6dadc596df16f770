"""Model work of mamba2-780m, from the sizes of its configuration file.

Forward FLOPs per token (no attention; the SSD scan in its recurrent
form, the least work the layer needs):

    matmuls  2 * n_layer * (d_model * proj + d_inner * d_model)
             + 2 * d_model * vocab                    (tied head)
             with vocab the embedding's rows, padded to
             pad_vocab_size_multiple
             with proj = 2 d_inner + 2 ngroups d_state + heads
    conv     2 * n_layer * d_conv * (d_inner + 2 ngroups d_state)
    scan     4 * n_layer * heads * d_state * headdim  (dt B x^T, C h)

Training FLOPs per token are three times the forward (forward, and the
backward's two products per forward product); recomputation in the
backward pass is not counted.
"""


def sizes(cfg: dict) -> dict:
    s = cfg["ssm_cfg"]
    d = cfg["d_model"]
    di = s["expand"] * d
    heads = di // s["headdim"]
    pad = cfg.get("pad_vocab_size_multiple", 1)
    return {"d": d, "di": di, "heads": heads, "n": s["d_state"], "p": s["headdim"],
            "g": s["ngroups"], "k": s["d_conv"], "layers": cfg["n_layer"],
            "vocab": -(-cfg["vocab_size"] // pad) * pad,
            "proj": 2 * di + 2 * s["ngroups"] * s["d_state"] + heads}


def forward_flops_per_token(cfg: dict) -> float:
    z = sizes(cfg)
    matmul = 2.0 * z["layers"] * (z["d"] * z["proj"] + z["di"] * z["d"]) \
        + 2.0 * z["d"] * z["vocab"]
    conv = 2.0 * z["layers"] * z["k"] * (z["di"] + 2 * z["g"] * z["n"])
    scan = 4.0 * z["layers"] * z["heads"] * z["n"] * z["p"]
    return matmul + conv + scan


def train_flops_per_token(cfg: dict, seq: int) -> float:
    del seq  # no attention: the work per token does not grow with context
    return 3.0 * forward_flops_per_token(cfg)


def matmuls(cfg: dict, batch: int, seq: int) -> list:
    """(M, K, N) of every ``x @ w`` that runs through masked_matmul in one
    training step: in_proj and out_proj of every layer.  The tied head is
    a plain einsum."""
    z = sizes(cfg)
    t = batch * seq
    return [(t, z["d"], z["proj"]), (t, z["di"], z["d"])] * z["layers"]


def ssd_scans(cfg: dict, batch: int, seq: int) -> list:
    """(batch, seq, heads, headdim, d_state) of every ssd_scan in one
    forward pass."""
    z = sizes(cfg)
    return [(batch, seq, z["heads"], z["p"], z["n"])] * z["layers"]
