"""Work that `nemotron-3-super-120b-a12b` requires, from its shapes alone: the
same number whatever implements a layer. A multiply-add is 2 FLOP; the
backward pass costs twice the forward; work that an implementation recomputes
is not counted. A sample is one sequence of `seq_len` tokens.

Counted at what the configuration holds here: the 5 Mamba layers at this
chip's 16 heads (one group), the attention layer at its 4 query heads and 1
key/value head over the causal mask's live pairs, the 5 expert layers with
the router and the latent projections for every position, the 8 experts held
of 512 at the balanced expectation (of a position's 22 experts, 8/512 are
held: 0.34375 of a pair a position) in the latent space, the shared expert for
every position, and the head over the vocabulary's slice. The chunked scan is
counted as the chunked algorithm computes it (`ssd_step`).
"""


def _counts(cfg):
    kept = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]
    return kept.count("M"), kept.count("*"), kept.count("E")


def mamba_params(cfg):
    """A Mamba layer's projections, the only products outside the scan:
    13,697,024 (4,096 x 2,320 in, 1,024 x 4,096 out)."""
    d, heads = cfg["hidden_size"], cfg["mamba_num_heads"]
    inner = heads * cfg["mamba_head_dim"]
    conv = inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    return d * (inner + conv + heads) + inner * d


def attention_params(cfg):
    """Attention's projections: 5,242,880."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    return d * hd * (2 * cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"])


def held_pairs(cfg, positions):
    """(position, expert) pairs that reach a held expert, at the balanced
    expectation."""
    return positions * cfg["num_experts_per_tok"] * cfg["held"][1] \
        // cfg["router_experts"]


def _expert(cfg):
    return 2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]


def grouped_matmul_step(cfg, traffic):
    """(flops, bytes) of the held experts' grouped products in one optimizer
    step, over the expert layers, at the balanced expectation, in the latent
    space: forward the rows in (latent), the expert's width out, the squared
    rows in, the rows out, and the held experts' two matrices read once (the
    experts are not gated: one input matrix); backward the same products
    twice (for the rows and for the matrices), each operand read and each
    gradient written once."""
    pairs = held_pairs(cfg, traffic["batch"] * traffic["seq_len"])
    lat, h, n = cfg["moe_latent_size"], cfg["moe_intermediate_size"], _counts(cfg)[2]
    fwd = 2 * pairs * _expert(cfg)
    rows = pairs * (lat + h + h + lat) * 2
    weights = cfg["held"][1] * _expert(cfg) * 2
    return [(n * fwd, n * (rows + weights)),
            (n * 2 * fwd, n * (3 * rows + 2 * weights))]


def expert_layer_params_a_token(cfg):
    """The products an expert layer makes for one position at the balanced
    expectation: router 2,097,152, latent projections 8,388,608, the shared
    expert 44,040,192, and 0.34375 of a held expert's 5,505,024: 56,418,304."""
    d = cfg["hidden_size"]
    shared = 2 * d * cfg["n_shared_experts"] * cfg["moe_shared_expert_intermediate_size"]
    routed = _expert(cfg) * cfg["num_experts_per_tok"] * cfg["held"][1] / cfg["router_experts"]
    return d * cfg["router_experts"] + 2 * d * cfg["moe_latent_size"] + shared + routed


def matmul_params_a_token(cfg):
    """Every layer's products and the head: 422,928,384 a position."""
    n_m, n_a, n_e = _counts(cfg)
    return (n_m * mamba_params(cfg) + n_a * attention_params(cfg)
            + n_e * expert_layer_params_a_token(cfg) + cfg["hidden_size"] * cfg["vocab_size"])


def live_pairs(length):
    """(query, key) pairs the causal mask lets through."""
    return length * (length + 1) // 2


def attention_flops_fwd(cfg, length):
    """`q k^T` and `p v` over the live pairs of one sequence in one layer."""
    return live_pairs(length) * 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"]


def _chunks(cfg, length):
    return -(-length // cfg["chunk_size"])


def ssd_flops_fwd(cfg, length):
    """The chunked scan's products over one sequence in one Mamba layer: in
    each chunk of `L` positions `C B^T` (a group's `L x L` over its state),
    the masked scores times `dt x` (a head's `L x L` over its channels), each
    chunk's own state (`B^T` times `dt x`) and the entering states' part of
    the output (`C` times the state): 6,710,886,400 at 8,192 positions."""
    lc, k = cfg["chunk_size"], _chunks(cfg, length)
    heads, hd = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, s = cfg["n_groups"], cfg["ssm_state_size"]
    return 2 * k * (g * lc * lc * s + heads * lc * lc * hd + 2 * heads * lc * hd * s)


def ssd_bytes_fwd(cfg, length, batch=1):
    """What the scan reads and writes at the least, forward: `x`, `B`, `C` in
    the compute type (2 bytes), `dt` in fp32 in, `y` in fp32 out."""
    heads, hd = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, s = cfg["n_groups"], cfg["ssm_state_size"]
    return batch * length * (2 * (heads * hd + 2 * g * s) + 4 * heads + 4 * heads * hd)


def ssm_scan_step(cfg, traffic):
    """(flops, bytes) of the chunked scan forward and backward in one
    optimizer step, a pair a Mamba layer: the backward makes twice the
    forward's products, and reads the forward's inputs and the output's
    gradient and writes the inputs' gradients (twice the forward's bytes)."""
    b, length = traffic["batch"], traffic["seq_len"]
    fwd = (b * ssd_flops_fwd(cfg, length), ssd_bytes_fwd(cfg, length, b))
    return [fwd, (2 * fwd[0], 2 * fwd[1])] * _counts(cfg)[0]


def train_flops_per_sample(cfg, traffic):
    """20.79 TFLOP of products, 0.21 of attention and 0.10 of the scan at
    8,192 positions."""
    length = traffic["seq_len"]
    n_m, n_a, _ = _counts(cfg)
    forward = (2 * length * matmul_params_a_token(cfg)
               + n_a * attention_flops_fwd(cfg, length) + n_m * ssd_flops_fwd(cfg, length))
    return 3 * forward
