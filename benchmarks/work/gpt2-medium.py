"""Work that `gpt2-medium` requires, from its shapes alone: the same number
whatever implements a layer. A multiply-add is 2 FLOP; the backward pass costs
twice the forward; causal attention is counted once (half the square); work
that an implementation recomputes is not counted."""


def matmul_params(cfg):
    d = cfg["n_embd"]
    return cfg["n_layer"] * 12 * d * d + d * cfg["vocab_size"]


def attention_flops_fwd(cfg, seq_len):
    """QK^T and PV of one sequence in one layer, causal."""
    return 2 * (2 * seq_len * seq_len * cfg["n_embd"]) // 2


def train_flops_per_sample(cfg, traffic):
    t = traffic["seq_len"]
    fwd = 2 * matmul_params(cfg) * t + cfg["n_layer"] * attention_flops_fwd(cfg, t)
    return 3 * fwd


def flash_attention_step(cfg, traffic):
    """(flops, bytes) of attention forward and backward in one optimizer
    step, over all layers: forward 2 products and q, k, v in, o out; backward
    4 products (dv, dp, dq, dk) and q, k, v, o, do in, dq, dk, dv out, all in
    the compute type (2 bytes). Returned per direction, so that each can meet
    its own bound."""
    b, t, d, n = traffic["batch"], traffic["seq_len"], cfg["n_embd"], cfg["n_layer"]
    fwd = attention_flops_fwd(cfg, t) * b
    tensor = b * t * d * 2
    return [(n * fwd, n * 4 * tensor), (n * 2 * fwd, n * 8 * tensor)]
