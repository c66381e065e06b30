"""``ConfigDecoder`` built from DeepSeek-V2's keys: latent attention with YaRN
in every layer, one leading dense layer before the scan over the routed ones,
shared experts beside the routed experts, against the benchmark's plain
reference for ``deepseek-v2-lite`` at a tiny size; and the configurations
that name none of those keys build the program they were, bit for bit."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmarks")
NAME = "deepseek-v2-lite"
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
def test_a_dense_layer_then_routed_ones_match_the_references_equations(bench, remat):
    from bigdl_tpu import nn
    cfg, mod, ref = _cell(bench, NAME)
    model, criterion = mod.build(cfg, TRAFFIC)
    model.remat = remat
    assert model.dense_layers == 1 and len(model.attentions) == 1
    assert isinstance(model.attentions[0], nn.LatentAttention)
    assert model.experts.shared == 2 * cfg["moe_intermediate_size"]
    params = model.get_params()
    # the dense layer's weights apart, counted once; the scan over the routed two
    assert params["dense"]["mlp"]["w_in"].shape == (1, 64, 2 * 96)
    assert params["layers"]["attn"]["q_weight"].shape == (2, 4 * 24, 64)
    names = mod.names(cfg)
    weights = _weights(bench, cfg, mod)
    params = bench.tree_from_names(params, names, weights)
    x, y = mod.make_batches(cfg, TRAFFIC, np.random.default_rng(5))[0]
    (got, state), grads = _loss_and_grads(model, criterion, params, x, y)
    with jax.default_matmul_precision("highest"):
        want, want_grads = ref.make_loss_and_grad(cfg)(weights, jnp.asarray(x), jnp.asarray(y))
        top_e = ref.routing(weights, jnp.asarray(x), cfg)
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want))
    first, count = cfg["held"]
    pairs = ((top_e >= first) & (top_e < first + count)).sum((1, 2, 3))
    assert pairs.shape == (2,) and int(pairs.sum()) == int(state["pairs_held"])
    grads = bench.names_from_tree(grads, names)
    for k in want_grads:
        scale = float(jnp.linalg.norm(want_grads[k])) + 1e-12
        assert float(jnp.linalg.norm(grads[k] - want_grads[k])) <= 1e-3 * scale + 1e-7, k


def test_every_new_part_tells_in_the_result(bench):
    """Without the dense layer, without the shared experts, without YaRN (plain
    RoPE) or with the value head as wide as the query/key head, the loss is
    another: each part is in the program."""
    from bigdl_tpu.models.transformerlm import ConfigDecoder
    cfg, mod, _ = _cell(bench, NAME)
    x, y = mod.make_batches(cfg, TRAFFIC, np.random.default_rng(2))[0]

    def loss(**over):
        model = ConfigDecoder.from_config(
            {**cfg, **over}, num_experts=cfg["router_experts"], held=tuple(cfg["held"]),
            qk_norm=False)
        _, criterion = mod.build(cfg, TRAFFIC)
        return float(_loss_and_grads(model, criterion, model.get_params(), x, y)[0][0])

    from bigdl_tpu.utils.random_generator import RandomGenerator
    RandomGenerator.set_seed(3)
    kept = loss()
    for over in (dict(first_k_dense_replace=0), dict(n_shared_experts=0),
                 dict(rope_scaling=None), dict(v_head_dim=24)):
        RandomGenerator.set_seed(3)
        assert abs(loss(**over) - kept) > 1e-6 * kept, over


def test_latent_keys_are_checked():
    from bigdl_tpu.models.transformerlm import ConfigDecoder
    common = dict(vocab_size=50, hidden_size=32, num_hidden_layers=3,
                  num_attention_heads=4, num_key_value_heads=4,
                  moe_intermediate_size=16, n_routed_experts=8, num_experts_per_tok=2,
                  kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8)
    model = ConfigDecoder.from_config(dict(common, first_k_dense_replace=1,
                                           intermediate_size=40, n_shared_experts=1))
    assert model.experts.n_experts == 8 and model.dense_layers == 1
    assert "LatentAttention" in repr(model) and "the first 1 dense" in repr(model)
    with pytest.raises(ValueError, match="q_lora_rank"):
        ConfigDecoder.from_config(dict(common, q_lora_rank=8))
    with pytest.raises(ValueError, match="latent attention is causal"):
        ConfigDecoder.from_config(dict(common, sliding_window_layout=[0, 1, 1],
                                       sliding_window_size=4))
    with pytest.raises(ValueError, match="yarn"):
        ConfigDecoder.from_config(dict(common, rope_scaling={"type": "linear", "factor": 2}))


@pytest.mark.parametrize("name,traffic", [
    ("sdar-30b-a3b", dict(batch=2, seq_len=32, n_batches=2, block_length=4,
                          noise_t=[0.05, 1.0])),
    ("smallthinker-21b-a3b", TRAFFIC)])
def test_the_configurations_without_the_new_keys_are_the_program_they_were(
        bench, name, traffic):
    """The SDAR and SmallThinker tiny cuts: the parameter tree the names
    list, and outputs, state and gradients bit for bit those of the decoder's
    ``apply`` as it stood before latent attention, dense layers and shared
    experts (PR 37), written out here from the decoder's own templates."""
    from jax.ad_checkpoint import checkpoint_name
    from bigdl_tpu.models.transformerlm.decoder import _HEALTH, KEPT
    from bigdl_tpu.obs import trace
    from bigdl_tpu.utils.table import Table
    cfg, mod, _ = _cell(bench, name)
    model, criterion = mod.build(cfg, traffic)
    assert model.dense_layers == 0 and model.experts.shared == 0
    assert "dense" not in model.get_params()
    assert set(model.get_params()["layers"]["moe"]) == {"w_gate", "w_in", "w_out"}
    params = bench.tree_from_names(model.get_params(), mod.names(cfg),
                                   _weights(bench, cfg, mod))
    x, y = mod.make_batches(cfg, traffic, np.random.default_rng(6))[0]

    def as_it_was(params, state, input, *, training=False, rng=None):
        self = model
        h = params["embed"][input]
        positions = self._positions(input.shape[1])
        norm, experts_state = self.norm, self.experts.get_state()

        def layer_of(attention):
            scope = trace.SCOPE_ATTN_FULL if attention.window is None \
                else trace.SCOPE_ATTN_WINDOW

            def layer(h, p):
                a, _ = norm.apply({"weight": p["attn_norm"]}, {}, h)
                with jax.named_scope(scope):
                    a, _ = attention.apply(p["attn"], {}, (a, positions),
                                           training=training)
                after = checkpoint_name(h + a, "decoder_after_attention")
                m, _ = norm.apply({"weight": p["moe_norm"]}, {}, after)
                m, health = self.experts.apply(
                    p["moe"], experts_state,
                    (m, h) if self.router_input == "layer" else m,
                    training=training)
                return after + m, {k: health[k] for k in _HEALTH}

            return jax.checkpoint(layer, policy=jax.checkpoint_policies
                                  .save_only_these_names(*KEPT))

        layers = [layer_of(attention) for attention in self.attentions]
        if len(layers) == 1:
            body, stacked = layers[0], params["layers"]
        else:
            stacked = jax.tree_util.tree_map(
                lambda a: a.reshape((-1, len(layers)) + a.shape[1:]), params["layers"])

            def body(h, period):
                health = []
                for i, layer in enumerate(layers):
                    h, leaves = layer(h, jax.tree_util.tree_map(lambda a: a[i], period))
                    health.append(leaves)
                return h, {k: jnp.stack([leaves[k] for leaves in health])
                           for k in _HEALTH}

        h, health = jax.lax.scan(body, h, stacked)
        if self.block_diffusion:
            h = h[:, :self.block_diffusion[0]]
        h, _ = norm.apply({"weight": params["final_norm"]}, {}, h)
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
