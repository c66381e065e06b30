"""Work that `sdar-30b-a3b` requires, from its shapes alone: the same number
whatever implements a layer. A multiply-add is 2 FLOP; the backward pass costs
twice the forward; work that an implementation recomputes is not counted.
A sample is one clean sequence of `seq_len` tokens, which the model sees as
`2 * seq_len` positions (its noised copy in front).

Counted at what the configuration holds here: the 16 experts held of 128 at
the balanced expectation (of a position's 8 experts, 16/128 are held: one pair
a position), the live pairs of the block-diffusion mask alone, and the head
over the vocabulary's slice and the noised half only.
"""


def layer_params(cfg):
    """Parameters of one layer as this chip holds it: 94,638,336."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    attention = 2 * d * heads * hd + 2 * d * kv * hd
    experts = cfg["held"][1] * 3 * d * cfg["moe_intermediate_size"]
    norms = 2 * d + 2 * hd
    return attention + d * cfg["router_experts"] + experts + norms


def live_pairs(length, block):
    """(query, key) pairs the block-diffusion mask lets through over the
    `2 * length` positions of one sequence: a noised query sees its own block
    (`block` keys) and the clean blocks before it, a clean query the clean
    blocks up to its own. 16,793,600 at 4096 and 4: 25.0% of the square."""
    blocks = length // block
    return length * block + block * block * blocks * blocks


def held_pairs(cfg, positions):
    """(position, expert) pairs that reach a held expert, at the balanced
    expectation."""
    return positions * cfg["num_experts_per_tok"] * cfg["held"][1] // cfg["router_experts"]


def attention_flops_fwd(cfg, length):
    """QK^T and PV over the live pairs of one sequence in one layer."""
    return live_pairs(length, cfg["block_length"]) * 4 * cfg["num_attention_heads"] \
        * cfg["head_dim"]


def experts_flops_fwd(cfg, positions):
    """Gate, up and down of the held experts in one layer."""
    return 2 * held_pairs(cfg, positions) * 3 * cfg["hidden_size"] \
        * cfg["moe_intermediate_size"]


def train_flops_per_sample(cfg, traffic):
    length = traffic["seq_len"]
    t, d = 2 * length, cfg["hidden_size"]
    hd, heads, kv = cfg["head_dim"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    projections = 2 * t * (2 * d * heads * hd + 2 * d * kv * hd)
    router = 2 * t * d * cfg["router_experts"]
    layer = projections + router + attention_flops_fwd(cfg, length) \
        + experts_flops_fwd(cfg, t)
    head = 2 * length * d * cfg["vocab_size"]
    return 3 * (cfg["num_hidden_layers"] * layer + head)


def blockdiff_attention_step(cfg, traffic):
    """(flops, bytes) of attention forward and backward in one optimizer
    step, over all layers: forward 2 products over the live pairs, q, k, v in
    and o out; backward 4 products and q, k, v, o, do in, dq, dk, dv out, in
    the compute type (2 bytes), keys and values at their own head count.
    Returned per direction, so that each can meet its own bound."""
    b, length, n = traffic["batch"], traffic["seq_len"], cfg["num_hidden_layers"]
    fwd = attention_flops_fwd(cfg, length) * b
    q = b * 2 * length * cfg["num_attention_heads"] * cfg["head_dim"] * 2
    kv = b * 2 * length * cfg["num_key_value_heads"] * cfg["head_dim"] * 2
    return [(n * fwd, n * (2 * q + 2 * kv)), (n * 2 * fwd, n * (4 * q + 4 * kv))]


def grouped_matmul_step(cfg, traffic):
    """(flops, bytes) of the held experts' grouped products in one optimizer
    step, over all layers, at the balanced expectation: forward the rows in
    (hidden), gate and up out, the gated rows in, the rows out, and the held
    experts' matrices read once; backward the same products twice (for the
    rows and for the matrices), each operand read and each gradient written
    once."""
    positions = traffic["batch"] * 2 * traffic["seq_len"]
    n, d, h = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["moe_intermediate_size"]
    pairs = held_pairs(cfg, positions)
    fwd = experts_flops_fwd(cfg, positions)
    rows = pairs * (d + 2 * h + h + d) * 2
    weights = cfg["held"][1] * 3 * d * h * 2
    return [(n * fwd, n * (rows + weights)),
            (n * 2 * fwd, n * (3 * rows + 2 * weights))]
