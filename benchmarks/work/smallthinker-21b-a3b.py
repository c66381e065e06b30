"""Work that `smallthinker-21b-a3b` requires, from its shapes alone: the same
number whatever implements a layer. A multiply-add is 2 FLOP; the backward
pass costs twice the forward; work that an implementation recomputes is not
counted (a flash backward's second `q k^T` among it). A sample is one sequence
of `seq_len` tokens.

Counted at what the configuration holds here: the 8 experts held of 64 at the
balanced expectation (of a position's 6 experts, 8/64 are held: three
quarters of a pair a position), the live pairs of each layer's own mask
(causal, or causal within the window) alone, and the head over the
vocabulary's slice.
"""


def layer_params(cfg):
    """Parameters of one layer as this chip holds it: 68,326,400."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    attention = 2 * d * heads * hd + 2 * d * kv * hd
    experts = cfg["held"][1] * 3 * d * cfg["moe_ffn_hidden_size"]
    return attention + d * cfg["router_experts"] + experts + 2 * d


def windows(cfg):
    """Each layer's window, `None` for a layer that sees every earlier key."""
    return [cfg["sliding_window_size"] if w else None
            for w in cfg["sliding_window_layout"][:cfg["num_hidden_layers"]]]


def live_pairs(length, window=None):
    """(query, key) pairs a causal mask lets through over `length` positions,
    query `i` seeing the keys `j` with `0 <= i - j < window`: 134,225,920 at
    16,384 without a window, 58,722,304 with one of 4,096."""
    w = length if window is None else min(window, length)
    return w * (w + 1) // 2 + (length - w) * w


def held_pairs(cfg, positions):
    """(position, expert) pairs that reach a held expert, at the balanced
    expectation."""
    return positions * cfg["moe_num_active_primary_experts"] * cfg["held"][1] \
        // cfg["router_experts"]


def attention_flops_fwd(cfg, length, window=None):
    """QK^T and PV over the live pairs of one sequence in one layer."""
    return live_pairs(length, window) * 4 * cfg["num_attention_heads"] * cfg["head_dim"]


def experts_flops_fwd(cfg, positions):
    """Gate, up and down of the held experts in one layer."""
    return 2 * held_pairs(cfg, positions) * 3 * cfg["hidden_size"] \
        * cfg["moe_ffn_hidden_size"]


def matmul_flops_fwd(cfg, length):
    """The matrix products of one sequence outside attention's two: the
    projections, the router and the held experts of every layer, and the
    head (150,855,680 parameters a token)."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    projections = 2 * length * (2 * d * heads * hd + 2 * d * kv * hd)
    router = 2 * length * d * cfg["router_experts"]
    layer = projections + router + experts_flops_fwd(cfg, length)
    return cfg["num_hidden_layers"] * layer + 2 * length * d * cfg["vocab_size"]


def train_flops_per_sample(cfg, traffic):
    length = traffic["seq_len"]
    attention = sum(attention_flops_fwd(cfg, length, w) for w in windows(cfg))
    return 3 * (matmul_flops_fwd(cfg, length) + attention)


def mixed_attention_step(cfg, traffic):
    """(flops, bytes) of attention forward and backward in one optimizer
    step, over all layers, each under its own mask: forward 2 products over
    the layer's live pairs, q, k, v in and o out; backward 4 products and q,
    k, v, o, do in, dq, dk, dv out, in the compute type (2 bytes), keys and
    values at their own head count. Returned per layer and direction, so that
    each can meet its own bound."""
    b, length = traffic["batch"], traffic["seq_len"]
    q = b * length * cfg["num_attention_heads"] * cfg["head_dim"] * 2
    kv = b * length * cfg["num_key_value_heads"] * cfg["head_dim"] * 2
    parts = []
    for w in windows(cfg):
        fwd = attention_flops_fwd(cfg, length, w) * b
        parts += [(fwd, 2 * q + 2 * kv), (2 * fwd, 4 * q + 4 * kv)]
    return parts


def grouped_matmul_step(cfg, traffic):
    """(flops, bytes) of the held experts' grouped products in one optimizer
    step, over all layers, at the balanced expectation: forward the rows in
    (hidden), gate and up out, the gated rows in, the rows out, and the held
    experts' matrices read once; backward the same products twice (for the
    rows and for the matrices), each operand read and each gradient written
    once."""
    positions = traffic["batch"] * traffic["seq_len"]
    n, d, h = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["moe_ffn_hidden_size"]
    pairs = held_pairs(cfg, positions)
    fwd = experts_flops_fwd(cfg, positions)
    rows = pairs * (d + 2 * h + h + d) * 2
    weights = cfg["held"][1] * 3 * d * h * 2
    return [(n * fwd, n * (rows + weights)),
            (n * 2 * fwd, n * (3 * rows + 2 * weights))]
