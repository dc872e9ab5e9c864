"""The whole LM fit's model FLOPs against the chip's bf16 peak: the FLOPs a
fit needs over (wall time per fit x peak), in percent.

Per organization and round: ``local_steps`` training steps, each a forward
and a backward pass (6 P S per sequence of S tokens, attention 3 x 2 S^2 d
per layer), then one forward pass for the fitted logits (2 P S, attention
2 S^2 d per layer). P counts the parameters that take part in matrix
products: every layer's projections and MLP, and the output head; not the
embedding lookup. Causal attention needs half of the S^2 scores, 2 S^2 d
FLOPs a layer forward. The weight fit and line search are not model FLOPs.
"""


def matmul_params(c):
    hd = c["d_model"] // c["n_heads"]
    per_layer = (2 * c["d_model"] * c["n_heads"] * hd
                 + 2 * c["d_model"] * c["n_kv_heads"] * hd
                 + 3 * c["d_model"] * c["d_ff"])
    return c["n_layers"] * per_layer + c["d_model"] * c["vocab"]


def fit_flops(c):
    p, s, b = matmul_params(c), c["seq"], c["batch"]
    attn_fwd = b * c["n_layers"] * 2 * s * s * c["d_model"]
    step = 6 * p * b * s + 3 * attn_fwd
    predict = 2 * p * b * s + attn_fwd
    return c["n_orgs"] * c["rounds"] * (c["local_steps"] * step + predict)


def read(ctx):
    k = ctx["counters"]
    if not k.get("fits"):
        return None
    wall_per_fit = k["elapsed_s"] / k["fits"]
    peak = ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * fit_flops(ctx["config"]) / (wall_per_fit * peak)
