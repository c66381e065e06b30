"""``ConfigDecoder`` with layers of several kinds under one scan: a period of
four (full attention without positions, then three layers under a causal
window with RoPE), a router that reads the layer's input and ReLU-gated
experts, against the benchmark's plain reference for ``smallthinker-21b-a3b``
at a tiny size; a period of one is the program it was; and the guide's tie of
the chip's share to the model."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmarks")
NAME = "smallthinker-21b-a3b"
TRAFFIC = dict(batch=2, seq_len=32, n_batches=2)


@pytest.fixture(scope="module")
def bench():
    sys.path.insert(0, BENCH)
    import harness
    yield harness
    sys.path.remove(BENCH)


def _cell(bench, name):
    cfg = json.load(open(os.path.join(BENCH, "configs", name + ".json")))
    cfg.update(json.load(open(os.path.join(BENCH, "tests", "tiny", name + ".json")))["config"])
    return (cfg, bench.load_module(os.path.join(BENCH, "configs", name + ".py")),
            bench.load_module(os.path.join(BENCH, "reference", name + ".py")))


def _weights(bench, cfg, mod, scale=5.0):
    """Seeded weights, widened so that every part of a layer matters (the
    embedding is N(0, 1) as seeded), and gains off 1."""
    w = mod.make_weights(cfg, bench.seed_key(2 ** 31 + 5))
    key = jax.random.PRNGKey(7)
    return {k: v + 0.1 * jax.random.normal(key, v.shape) if k.endswith(".g")
            else v * (1.0 if k == "embed" else scale) for k, v in w.items()}


def _loss_and_grads(model, criterion, params, x, y):
    def loss(p):
        out, state = model.apply(p, model.get_state(), jnp.asarray(x), training=True)
        return criterion.apply(out, jnp.asarray(y)), state
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss, has_aux=True)(params)


@pytest.mark.parametrize("remat", [True, False])
def test_a_period_of_four_matches_the_references_equations(bench, remat):
    cfg, mod, ref = _cell(bench, NAME)
    model, criterion = mod.build(cfg, TRAFFIC)
    model.remat = remat
    from bigdl_tpu.models.transformerlm.decoder import LayerKind
    assert model.period == [LayerKind(None, False)] + [LayerKind(8, True)] * 3
    assert [a.window for a in model.attentions] == [None, 8, 8, 8]
    assert [a.rope for a in model.attentions] == [False, True, True, True]
    assert model.experts.gate == "relu" and model.router_input == "layer"
    names = mod.names(cfg)
    weights = _weights(bench, cfg, mod)
    params = bench.tree_from_names(model.get_params(), names, weights)
    x, y = mod.make_batches(cfg, TRAFFIC, np.random.default_rng(5))[0]
    assert x.shape == y.shape == (2, 32) and (x[:, 1:] == y[:, :-1]).all()
    (got, state), grads = _loss_and_grads(model, criterion, params, x, y)
    with jax.default_matmul_precision("highest"):
        want, want_grads = ref.make_loss_and_grad(cfg)(weights, jnp.asarray(x), jnp.asarray(y))
        top_e = ref.routing(weights, jnp.asarray(x), cfg)
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want))
    first, count = cfg["held"]
    pairs = ((top_e >= first) & (top_e < first + count)).sum((1, 2, 3))
    # the reference's count of held pairs, all four layers', is the program's own
    assert pairs.shape == (4,) and int(pairs.sum()) == int(state["pairs_held"])
    assert float(state["dropped_fraction"]) == 0.0 and float(state["row_passes"]) >= 1.0
    grads = bench.names_from_tree(grads, names)
    assert set(grads) == set(want_grads)
    for k in want_grads:
        scale = float(jnp.linalg.norm(want_grads[k]))
        assert scale > 0, k
        assert float(jnp.linalg.norm(grads[k] - want_grads[k])) <= 1e-4 * scale, k


def test_every_kind_of_the_period_tells_in_the_result(bench):
    """The pattern is not decoration: the same weights under other layouts
    (all layers full, all windowed, RoPE on every layer, the router on the
    experts' input, the SiLU gate) give another loss."""
    cfg, mod, _ = _cell(bench, NAME)
    from bigdl_tpu.models.transformerlm import ConfigDecoder
    names = mod.names(cfg)
    weights = _weights(bench, cfg, mod)
    x, y = mod.make_batches(cfg, TRAFFIC, np.random.default_rng(5))[0]
    base = dict(num_experts=cfg["router_experts"], held=tuple(cfg["held"]), qk_norm=False,
                router_input="layer", expert_gate="relu")
    _, criterion = mod.build(cfg, TRAFFIC)

    def loss(**over):
        model = ConfigDecoder.from_config(cfg, **{**base, **over})
        params = bench.tree_from_names(model.get_params(), names, weights)
        return float(_loss_and_grads(model, criterion, params, x, y)[0][0]), model

    kept, model = loss()
    assert len(model.period) == 4
    for over in (dict(sliding_window_layout=[0] * 4), dict(sliding_window_layout=[1] * 4),
                 dict(rope_layout=[1] * 4), dict(router_input="experts"),
                 dict(expert_gate="silu")):
        other, model = loss(**over)
        assert abs(other - kept) > 1e-4 * kept, over
    assert len(loss(sliding_window_layout=[0] * 4, rope_layout=[1] * 4)[1].period) == 1
    assert len(loss(sliding_window_layout=[0, 1, 0, 1], rope_layout=[0, 1] * 2)[1].period) == 2


def test_a_period_of_one_is_the_program_it_was(bench):
    """The SDAR tiny cut, one kind of layer: outputs and gradients bit for bit
    those of the scan as it stood before layers had kinds, written out here
    from the decoder's own templates."""
    from jax.ad_checkpoint import checkpoint_name
    from bigdl_tpu.models.transformerlm.decoder import _HEALTH, KEPT
    cfg, mod, _ = _cell(bench, "sdar-30b-a3b")
    traffic = dict(batch=2, seq_len=32, n_batches=2, block_length=4, noise_t=[0.05, 1.0])
    model, criterion = mod.build(cfg, traffic)
    assert len(model.period) == 1 and len(model.attentions) == 1
    params = bench.tree_from_names(model.get_params(), mod.names(cfg),
                                   _weights(bench, cfg, mod))
    x, y = mod.make_batches(cfg, traffic, np.random.default_rng(6))[0]

    def as_it_was(params, state, input, *, training=False, rng=None):
        self = model
        h = params["embed"][input]
        positions = self._positions(input.shape[1])
        norm, experts_state = self.norm, self.experts.get_state()

        def layer(h, p):
            a, _ = norm.apply({"weight": p["attn_norm"]}, {}, h)
            a, _ = self.attentions[0].apply(p["attn"], {}, (a, positions), training=training)
            h = checkpoint_name(h + a, "decoder_after_attention")
            m, _ = norm.apply({"weight": p["moe_norm"]}, {}, h)
            m, health = self.experts.apply(p["moe"], experts_state, m, training=training)
            return h + m, {k: health[k] for k in _HEALTH}

        layer = jax.checkpoint(
            layer, policy=jax.checkpoint_policies.save_only_these_names(*KEPT))
        h, health = jax.lax.scan(layer, h, params["layers"])
        h = h[:, :self.block_diffusion[0]]
        h, _ = norm.apply({"weight": params["final_norm"]}, {}, h)
        from bigdl_tpu.utils.table import Table
        return Table(h, params["head"]), {
            k: jax.lax.stop_gradient(fold(health[k])) for k, fold in _HEALTH.items()}

    (loss, state), grads = _loss_and_grads(model, criterion, params, x, y)
    model.apply = as_it_was
    (loss_was, state_was), grads_was = _loss_and_grads(model, criterion, params, x, y)
    assert float(loss) == float(loss_was)
    for k in state_was:
        assert float(state[k]) == float(state_was[k]), k
    for a, b in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(grads_was)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_config_keys_in_both_spellings_and_layouts_longer_than_the_depth():
    from bigdl_tpu.models.transformerlm import ConfigDecoder
    from bigdl_tpu.models.transformerlm.decoder import LayerKind
    common = dict(vocab_size=50, hidden_size=32, num_hidden_layers=4,
                  num_attention_heads=4, num_key_value_heads=2, head_dim=16)
    ours = ConfigDecoder.from_config(dict(
        common, moe_intermediate_size=24, num_experts=8, num_experts_per_tok=2))
    theirs = ConfigDecoder.from_config(dict(
        common, moe_ffn_hidden_size=24, moe_num_primary_experts=8,
        moe_num_active_primary_experts=2, sliding_window_size=4,
        sliding_window_layout=[0, 1, 1, 1] * 13, rope_layout=[0, 1, 1, 1] * 13,
        model_name="a key the decoder has no use for"))
    shapes = lambda m: jax.tree_util.tree_map(lambda a: a.shape, m.get_params())
    assert shapes(ours) == shapes(theirs)
    assert ours.period == [LayerKind(None, True)]
    assert theirs.period == [LayerKind(None, False)] + [LayerKind(4, True)] * 3
    assert theirs.experts.top_k == 2 and theirs.experts.n_experts == 8
    with pytest.raises(ValueError, match="sliding_window_size"):
        ConfigDecoder.from_config(dict(common, moe_intermediate_size=24, num_experts=8,
                                       num_experts_per_tok=2, sliding_window_layout=[1] * 4))
    with pytest.raises(ValueError, match="shorter"):
        ConfigDecoder.from_config(dict(common, moe_intermediate_size=24, num_experts=8,
                                       num_experts_per_tok=2, rope_layout=[1, 0]))
    with pytest.raises(ValueError, match="mask stands in causal"):      # no windowed layer
        ConfigDecoder.from_config(dict(                                 # under block diffusion
            common, moe_intermediate_size=24, num_experts=8, num_experts_per_tok=2,
            sliding_window_layout=[0, 1] * 2, sliding_window_size=4), block_diffusion=(8, 4))


def test_a_windowed_layer_sees_its_window_and_no_further():
    """Two layers, both under a window of 4 with RoPE: position 15's logits
    move with a token 6 back (two layers reach twice the window less one) and
    not with one 7 back."""
    from bigdl_tpu.models.transformerlm import ConfigDecoder
    model = ConfigDecoder(vocab_size=50, hidden_size=32, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                          moe_intermediate_size=24, num_experts=8, num_experts_per_tok=2,
                          sliding_window_layout=[1, 1], sliding_window_size=4, remat=False)
    x = jnp.asarray(np.random.default_rng(0).integers(0, 50, (1, 16)), jnp.int32)
    logits = lambda x: model.apply(model.get_params(), model.get_state(), x)[0][0, 15]
    base = logits(x)
    np.testing.assert_allclose(logits(x.at[0, 8].set(3 if x[0, 8] != 3 else 4)), base, atol=1e-5)
    assert float(jnp.max(jnp.abs(logits(x.at[0, 9].set(3 if x[0, 9] != 3 else 4)) - base))) > 1e-4


def test_eight_shares_of_eight_experts_add_up_to_the_uncut_layer(bench):
    """The guide's tie of share to model, on this model's layer: each of
    eight chips holds 8 of the 64 experts, routes over all 64 on the layer's
    input and computes its own part; the parts added to what every chip
    computes alike (the residual stream after attention, counted once) are
    what the reference gives for the whole layer with all 64 held."""
    from bigdl_tpu.models.transformerlm import ConfigDecoder
    cfg, mod, ref = _cell(bench, NAME)
    cfg.update(router_experts=64, moe_num_active_primary_experts=6, num_hidden_layers=1)
    whole = dict(cfg, held=[0, 64], moe_num_primary_experts=64)
    key = jax.random.PRNGKey(3)
    shapes = dict(mod.names(whole))
    p = {k: (1.0 + 0.1 * jax.random.normal(jax.random.fold_in(key, i), shapes["layers." + k][1:])
             if k.endswith(".g") else
             0.1 * jax.random.normal(jax.random.fold_in(key, i), shapes["layers." + k][1:]))
         for i, k in enumerate(ref.LAYER_KEYS)}
    h = jax.random.normal(key, (32, cfg["hidden_size"]))
    for l, kind in ((0, (None, False)), (1, (8, True))):
        layer_cfg = dict(whole, sliding_window_layout=[l], rope_layout=[l])
        with jax.default_matmul_precision("highest"):
            want, top_e = ref._layer(h, p, layer_cfg, kind, lambda a: a)
        total, pairs = None, 0.0
        for rank in range(8):
            model = ConfigDecoder.from_config(
                dict(layer_cfg), num_experts=64, held=(8 * rank, 8), qk_norm=False,
                router_input="layer", expert_gate="relu")
            norm, attention, experts = model.norm, model.attentions[0], model.experts
            assert (attention.window, attention.rope) == kind
            with jax.default_matmul_precision("highest"):
                a, _ = norm.apply({"weight": p["attn_norm.g"]}, {}, h[None])
                a, _ = attention.apply({"kv_weight": p["attn.kv"], "q_weight": p["attn.q"],
                                        "out_weight": p["attn.out"]}, {},
                                       (a, jnp.arange(32)))
                after = h[None] + a                         # alike on every chip
                m, _ = norm.apply({"weight": p["moe_norm.g"]}, {}, after)
                y, state = experts.apply(
                    {"w_gate": p["router"], "w_in": p["experts.in"][8 * rank:8 * rank + 8],
                     "w_out": p["experts.out"][8 * rank:8 * rank + 8]},
                    experts.get_state(), (m, h[None]))
            total = after + y if total is None else total + y
            pairs += float(state["pairs_held"])
        np.testing.assert_allclose(total[0], want, atol=5e-5)
        assert pairs == 32 * 6 and top_e.shape == (32, 6)
