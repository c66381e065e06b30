"""``ConfigDecoder`` built from Nemotron-H's keys: one block a layer read from
``hybrid_override_pattern`` (Mamba-2 mixers, attention, LatentMoE expert
layers) under one scan, against the benchmark's plain reference for
``nemotron-3-super-120b-a12b`` at a tiny size; the sigmoid router; the
attention and expert layers' shares adding up to the whole layers; and a
configuration without the pattern building the parameter tree it did."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmarks")
NAME = "nemotron-3-super-120b-a12b"
TRAFFIC = dict(batch=2, seq_len=40, n_batches=2)


@pytest.fixture(scope="module")
def bench():
    sys.path.insert(0, BENCH)
    import harness
    yield harness
    sys.path.remove(BENCH)


def _cell(bench, name=NAME, **over):
    cfg = json.load(open(os.path.join(BENCH, "configs", name + ".json")))
    cfg.update(json.load(open(os.path.join(BENCH, "tests", "tiny", name + ".json")))["config"])
    cfg.update(over)
    return (cfg, bench.load_module(os.path.join(BENCH, "configs", name + ".py")),
            bench.load_module(os.path.join(BENCH, "reference", name + ".py")))


def _weights(bench, cfg, mod, scale=4.0):
    """Seeded weights, the matrices widened so that every part of a layer
    tells in the loss (the embedding stays N(0, 1) as seeded), the Mamba
    leaves as seeded and the gains off 1."""
    w = mod.make_weights(cfg, bench.seed_key(2 ** 31 + 5))
    key = jax.random.PRNGKey(7)
    return {k: v + 0.1 * jax.random.normal(jax.random.fold_in(key, i), v.shape)
            if k.endswith(".g") else
            v if k == "embed" or k.startswith("mamba.") and "." in k[6:] or
            k in ("mamba.a_log", "mamba.d", "mamba.dt_bias") else v * scale
            for i, (k, v) in enumerate(sorted(w.items()))}


def _bias(cfg, seed=11):
    """A correction bias that moves the choice: each expert layer's bias
    lifts a few experts, held ones among them, by about a score's spread."""
    n = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]].count("E")
    return 0.3 * jax.random.normal(jax.random.PRNGKey(seed), (n, cfg["router_experts"]))


def _loss_and_grads(model, criterion, params, state, x, y):
    def loss(p):
        out, new = model.apply(p, state, jnp.asarray(x), training=True)
        return criterion.apply(out, jnp.asarray(y)), new
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss, has_aux=True)(params)


@pytest.mark.parametrize("remat", [True, False])
def test_the_pattern_under_one_scan_matches_the_references_equations(bench, remat):
    """The tiny cut's 11 layers (``MEMEMEM*EME``) with a correction bias that
    moves the routing: the loss to 1e-5 and every leaf's gradient to 1e-3 of
    its norm (fp32 both; the program's chunked scan, flash-less attention and
    grouped products against the reference's recurrence, blocks and gathers),
    and the held pairs the reference's routing counts."""
    cfg, mod, ref = _cell(bench)
    model, criterion = mod.build(cfg, dict(TRAFFIC, seq_len=40))
    model.remat = remat
    assert model.pattern == "MEMEMEM*EME" and "".join(model.period) == model.pattern
    params = model.get_params()
    assert params["mamba"]["mixer"]["in"].shape[0] == 5
    assert params["experts"]["mixer"]["w_in"].shape == (5, 4, 16, 24)
    assert params["attention"]["mixer"]["q_weight"].shape == (1, 64, 64)
    names = mod.names(cfg)
    weights = _weights(bench, cfg, mod)
    params = bench.tree_from_names(params, names, weights)
    bias = _bias(cfg)
    state = dict(model.get_state(), router_bias=bias)
    x, y = mod.make_batches(cfg, TRAFFIC, np.random.default_rng(5))[0]
    (got, new), grads = _loss_and_grads(model, criterion, params, state, x, y)
    with jax.default_matmul_precision("highest"):
        want, want_grads = ref.make_loss_and_grad(cfg, bias=bias)(
            weights, jnp.asarray(x), jnp.asarray(y))
        top_e = ref.routing(weights, jnp.asarray(x), cfg, bias)
        unbiased = ref.routing(weights, jnp.asarray(x), cfg)
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want))
    first, count = cfg["held"]
    assert int(((top_e >= first) & (top_e < first + count)).sum()) == int(new["pairs_held"])
    assert bool(jnp.any(top_e != unbiased))         # the bias moved the choice
    np.testing.assert_array_equal(new["router_bias"], bias)
    grads = bench.names_from_tree(grads, names)
    for k in want_grads:
        scale = float(jnp.linalg.norm(want_grads[k])) + 1e-12
        assert float(jnp.linalg.norm(grads[k] - want_grads[k])) <= 1e-3 * scale, k
    # the controls: a bfloat16 reference, or one without the bias, misses
    bf16 = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        for q, b in ((bf16, bias), (lambda a: a, None)):
            miss, miss_grads = ref.make_loss_and_grad(cfg, q, bias=b)(
                weights, jnp.asarray(x), jnp.asarray(y))
            assert max(float(jnp.linalg.norm(grads[k] - miss_grads[k]))
                       / (float(jnp.linalg.norm(miss_grads[k])) + 1e-12)
                       for k in miss_grads) > 1e-2


def test_the_sigmoid_router_chooses_by_score_and_bias_and_weights_by_score():
    """Every expert held, against the equations written out: the choice is
    the 22-of-64 largest of ``s + b``, the weights ``s`` alone over their sum,
    times ``routed_scaling_factor`` 5, the experts ReLU squared in a latent
    space between a down and an up projection, and the shared expert beside.
    The bias moves the choice; a layer that chose by ``s`` alone, or weighted
    by ``s + b``, would give another output."""
    from bigdl_tpu.parallel.moe import MoE
    d, h, e, k, lat, s = 32, 16, 64, 22, 8, 24
    layer = MoE(d, h, e, router="topk", top_k=k, gate="relu2", shared=s,
                scoring="sigmoid", routed_scale=5.0, latent=lat)
    key = jax.random.PRNGKey(2)
    p = {n: 0.3 * jax.random.normal(jax.random.fold_in(key, i), v.shape)
         for i, (n, v) in enumerate(sorted(layer.get_params().items()))}
    bias = 0.5 * jax.random.normal(jax.random.fold_in(key, 99), (e,))
    x = jax.random.normal(jax.random.fold_in(key, 100), (40, d))
    relu2 = lambda u: jnp.square(jnp.maximum(u, 0.0))

    def plain(choose_by_bias=True, weight_by_bias=False):
        sc = jax.nn.sigmoid(x @ p["w_gate"])
        _, top = jax.lax.top_k(sc + bias if choose_by_bias else sc, k)
        w = jnp.take_along_axis(sc + bias if weight_by_bias else sc, top, -1)
        w = 5.0 * w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
        lt = x @ p["latent_down"]
        experts = jnp.stack([relu2(lt @ p["w_in"][j]) @ p["w_out"][j] for j in range(e)], 1)
        routed = jnp.einsum("tk,tkd->td", w, jnp.take_along_axis(
            experts, top[:, :, None], 1))
        return routed @ p["latent_up"] + relu2(x @ p["shared_in"]) @ p["shared_out"], top

    state = dict(layer.get_state(), router_bias=bias)
    with jax.default_matmul_precision("highest"):
        got, new = layer.apply(p, state, x)
        want, top = plain()
        misses = [plain(choose_by_bias=False), plain(weight_by_bias=True)]
    np.testing.assert_allclose(got, want, atol=1e-4 * float(jnp.max(jnp.abs(want))))
    assert float(new["pairs_held"]) == 40 * k
    assert bool(jnp.any(misses[0][1] != top))
    for miss, _ in misses:
        assert float(jnp.max(jnp.abs(miss - want))) > 1e-2 * float(jnp.max(jnp.abs(want)))
    # no gradient reaches the bias: it is a buffer of the state
    g = jax.grad(lambda b: jnp.sum(layer.apply(p, dict(state, router_bias=b), x)[0]))(bias)
    assert float(jnp.max(jnp.abs(g))) == 0.0


def test_eight_shares_of_the_attention_heads_add_up_to_the_whole(bench):
    """Attention's tensor-parallel cut: 16 query heads on 2 key/value heads,
    each of eight chips holding 2 query heads and the key/value head they
    read, its rows of the query and key/value projections and its columns of
    the output projection. The shares add up to the reference's whole layer."""
    from bigdl_tpu import nn
    cfg, _, ref = _cell(bench, num_attention_heads=16, num_key_value_heads=2, head_dim=8,
                        hidden_size=32)
    heads, kv, hd, d, t = 16, 2, 8, 32, 24
    key = jax.random.PRNGKey(8)
    p = {"q": 0.3 * jax.random.normal(jax.random.fold_in(key, 0), (heads * hd, d)),
         "kv": 0.3 * jax.random.normal(jax.random.fold_in(key, 1), (2 * kv * hd, d)),
         "out": 0.3 * jax.random.normal(jax.random.fold_in(key, 2), (d, heads * hd)),
         "norm.g": jnp.ones((d,))}
    h = jax.random.normal(jax.random.fold_in(key, 3), (t, d))
    x = h * jax.lax.rsqrt(jnp.mean(jnp.square(h), -1, keepdims=True) + cfg["layer_norm_epsilon"])
    total = 0.0
    with jax.default_matmul_precision("highest"):
        want = ref._attention(h, p, cfg, lambda a: a) - h
        for r in range(8):
            qh = np.arange(2 * r * hd, (2 * r + 2) * hd)
            g = r // 4                                   # 8 query heads a key/value head
            k_rows = np.arange(g * hd, (g + 1) * hd)
            share = nn.MultiHeadAttention(d, 2, causal=True, with_bias=False, num_kv_heads=1,
                                          head_dim=hd, attention_impl="full")
            total = total + share.apply(
                {"q_weight": p["q"][qh], "kv_weight": p["kv"][np.concatenate(
                    [k_rows, kv * hd + k_rows])], "out_weight": p["out"][:, qh]},
                {}, x[None])[0][0]
    np.testing.assert_allclose(total, want, atol=1e-4 * float(jnp.max(jnp.abs(want))))


def test_eight_shares_of_the_experts_and_the_shared_expert_once_are_the_whole_layer(bench):
    """LatentMoE's expert-parallel cut: each of eight chips holds 4 of the 32
    experts, routes over all 32 by the same sigmoid router and bias, and
    brings its part up from the latent; the router, the latent projections and
    the shared expert are whole on every chip. The parts, with the shared
    expert counted once, are the reference's whole layer with every expert
    held."""
    from bigdl_tpu.parallel.moe import MoE
    cfg, mod, ref = _cell(bench, held=[0, 32], n_routed_experts=32, num_hidden_layers=2,
                          hybrid_override_pattern="EE")
    d, h, lat, s = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["moe_latent_size"], \
        cfg["moe_shared_expert_intermediate_size"]
    key = jax.random.PRNGKey(4)
    shapes = dict(mod.names(cfg))
    p = {k: 0.3 * jax.random.normal(jax.random.fold_in(key, i), shapes["experts." + k][1:])
         for i, k in enumerate(ref.KEYS["experts"]) if k != "norm.g"}
    p["norm.g"] = jnp.ones((d,))
    bias = _bias(cfg)[0]
    hs = jax.random.normal(jax.random.fold_in(key, 50), (32, d))
    m = hs * jax.lax.rsqrt(jnp.mean(jnp.square(hs), -1, keepdims=True) + cfg["layer_norm_epsilon"])
    total, shared, pairs = 0.0, None, 0.0
    with jax.default_matmul_precision("highest"):
        want, top_e = ref._expert_layer(hs, p, bias, cfg, lambda a: a)
        for r in range(8):
            layer = MoE(d, h, 32, router="topk", top_k=cfg["num_experts_per_tok"],
                        held=(4 * r, 4), gate="relu2", shared=s, scoring="sigmoid",
                        routed_scale=5.0, latent=lat)
            y, state = layer.apply(
                {"w_gate": p["router"], "w_in": p["in"][4 * r:4 * r + 4],
                 "w_out": p["out"][4 * r:4 * r + 4], "latent_down": p["latent_down"],
                 "latent_up": p["latent_up"], "shared_in": p["shared.in"],
                 "shared_out": p["shared.out"]},
                dict(layer.get_state(), router_bias=bias), m)
            total = total + y
            pairs += float(state["pairs_held"])
        shared = jnp.square(jnp.maximum(m @ p["shared.in"], 0.0)) @ p["shared.out"]
    np.testing.assert_allclose(total - 7 * shared, want - hs,
                               atol=1e-4 * float(jnp.max(jnp.abs(want - hs))))
    assert pairs == 32 * cfg["num_experts_per_tok"] and top_e.shape == (32, 6)


@pytest.mark.parametrize("name", ["sdar-30b-a3b", "smallthinker-21b-a3b", "deepseek-v2-lite"])
def test_a_config_without_the_pattern_builds_the_tree_it_did(bench, name):
    """The parameter tree (paths, shapes, and the values drawn from one
    seed) and the state of the routed configurations' tiny cuts, against the
    decoder's ``reset`` as it stood before hybrid patterns, written out here:
    every matrix N(0, ``initializer_range``) from ``fold_in(key, i)`` in the
    tree's order, every gain 1, the health leaves zero."""
    from bigdl_tpu.models.transformerlm.decoder import _HEALTH
    from bigdl_tpu.utils.random_generator import RandomGenerator
    cfg, mod, _ = _cell(bench, name)
    RandomGenerator.set_seed(21)
    model, _ = mod.build(cfg, dict(TRAFFIC, seq_len=32, block_length=4, noise_t=[0.05, 1.0]))
    assert model.pattern is None and not hasattr(model, "mamba")
    groups = {"embed", "final_norm", "head", "layers"} | ({"dense"} if model.dense_layers else set())
    assert set(model.get_params()) == groups
    assert set(model.get_state()) == set(_HEALTH)
    RandomGenerator.set_seed(21)
    model.reset()
    leaves = jax.tree_util.tree_flatten_with_path(model.get_params())[0]
    # (shape, is a gain) a leaf, in the tree's order, as the old spec made them
    spec = [(leaf.shape, leaf.ndim - (path[0].key in ("layers", "dense")) == 1)
            for path, leaf in leaves]

    def draw(key):
        return [jnp.ones(shape, jnp.float32) if gain else
                cfg["initializer_range"] * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
                for i, (shape, gain) in enumerate(spec)]

    RandomGenerator.set_seed(21)
    want = jax.jit(draw)(jax.random.PRNGKey(RandomGenerator.next_salt() & 0x7FFFFFFF))
    for (_, leaf), w in zip(leaves, want):
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(w))
