"""Work that `deepseek-v2-lite` requires, from its shapes alone: the same
number whatever implements a layer. A multiply-add is 2 FLOP; the backward
pass costs twice the forward; work that an implementation recomputes is not
counted (a flash backward's second `q k^T` among it). A sample is one sequence
of `seq_len` tokens.

Counted at what the configuration holds here: the leading dense layer once,
the routed layers with the 8 experts held of 64 at the balanced expectation
(of a position's 6 experts, 8/64 are held: three quarters of a pair a
position) and the two shared experts for every position, latent attention
over the causal mask's live pairs with the query/key head (`qk_nope +
qk_rope`, 192) and the value head (128) at their own widths, and the head
over the vocabulary's slice.
"""


def _widths(cfg):
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return d, heads, qk, cfg["v_head_dim"]


def attention_params(cfg):
    """Latent attention's projections and the latent's gain: 13,763,072."""
    d, heads, qk, hv = _widths(cfg)
    latent = cfg["kv_lora_rank"]
    return (d * heads * qk + d * (latent + cfg["qk_rope_head_dim"])
            + latent * heads * (cfg["qk_nope_head_dim"] + hv) + heads * hv * d + latent)


def _expert(cfg, width):
    return 3 * cfg["hidden_size"] * width


def layer_params(cfg):
    """Parameters of one routed layer as this chip holds it: 100,405,760."""
    d, h = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return (attention_params(cfg) + cfg["held"][1] * _expert(cfg, h)
            + _expert(cfg, cfg["n_shared_experts"] * h) + d * cfg["router_experts"] + 2 * d)


def dense_layer_params(cfg):
    """Parameters of the leading dense layer: 81,007,104."""
    return attention_params(cfg) + _expert(cfg, cfg["intermediate_size"]) \
        + 2 * cfg["hidden_size"]


def live_pairs(length):
    """(query, key) pairs the causal mask lets through: 134,225,920 at
    16,384."""
    return length * (length + 1) // 2


def held_pairs(cfg, positions):
    """(position, expert) pairs that reach a held expert, at the balanced
    expectation."""
    return positions * cfg["num_experts_per_tok"] * cfg["held"][1] \
        // cfg["router_experts"]


def attention_flops_fwd(cfg, length):
    """`q k^T` at the query/key head and `p v` at the value head over the
    live pairs of one sequence in one layer."""
    _, heads, qk, hv = _widths(cfg)
    return live_pairs(length) * 2 * heads * (qk + hv)


def matmul_flops_fwd(cfg, length):
    """The matrix products of one sequence outside attention's two: every
    layer's projections, the dense layer's feed-forward, the routed layers'
    router, shared experts and held experts, and the head (257,949,696
    parameters a token)."""
    d, h = cfg["hidden_size"], cfg["moe_intermediate_size"]
    k = cfg["first_k_dense_replace"]
    projections = attention_params(cfg) - cfg["kv_lora_rank"]
    dense = projections + _expert(cfg, cfg["intermediate_size"])
    routed = (projections + d * cfg["router_experts"]
              + _expert(cfg, cfg["n_shared_experts"] * h))
    per_token = k * dense + (cfg["num_hidden_layers"] - k) * routed \
        + d * cfg["vocab_size"]
    experts = 2 * held_pairs(cfg, length) * _expert(cfg, h)
    return 2 * length * per_token + (cfg["num_hidden_layers"] - k) * experts


def train_flops_per_sample(cfg, traffic):
    length = traffic["seq_len"]
    attention = cfg["num_hidden_layers"] * attention_flops_fwd(cfg, length)
    return 3 * (matmul_flops_fwd(cfg, length) + attention)


def latent_attention_step(cfg, traffic):
    """(flops, bytes) of the flash kernels forward and backward in one
    optimizer step, a pair a layer: forward 2 products over the live pairs,
    q and k in at the query/key head, v in and o out at the value head;
    backward 4 products (`dv` and `dp` at the value head, `dq` and `dk` at
    the query/key head), q, k, v, o, dO in and dq, dk, dv out, in the compute
    type (2 bytes)."""
    b, length = traffic["batch"], traffic["seq_len"]
    _, heads, qk, hv = _widths(cfg)
    rows = b * length * heads * 2
    fwd = attention_flops_fwd(cfg, length) * b
    per_layer = [(fwd, rows * (2 * qk + 2 * hv)), (2 * fwd, 2 * rows * (2 * qk + 2 * hv))]
    return per_layer * cfg["num_hidden_layers"]
