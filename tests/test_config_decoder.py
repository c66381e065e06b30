"""``ConfigDecoder``: a decoder built from a configuration's keys, its layers
one scanned body, against the benchmark's plain reference for
``sdar-30b-a3b`` at two layers, and through ``LocalOptimizer.optimize()``."""

import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmarks")
CUT = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
           num_key_value_heads=2, head_dim=32, moe_intermediate_size=48,
           router_experts=16, held=[4, 4], num_experts=4, num_experts_per_tok=4,
           vocab_size=96, mask_token_id=95)
TRAFFIC = dict(batch=2, seq_len=32, n_batches=2, block_length=4, noise_t=[0.05, 1.0])


@pytest.fixture(scope="module")
def bench():
    sys.path.insert(0, BENCH)
    import harness
    yield harness
    sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def cell(bench):
    cfg = json.load(open(os.path.join(BENCH, "configs", "sdar-30b-a3b.json")))
    cfg.update(CUT)
    mod = bench.load_module(os.path.join(BENCH, "configs", "sdar-30b-a3b.py"))
    ref = bench.load_module(os.path.join(BENCH, "reference", "sdar-30b-a3b.py"))
    return cfg, mod, ref


def _weights(bench, cfg, mod, scale=5.0):
    """Seeded weights, widened so that every part of a layer matters, and
    gains off 1."""
    w = mod.make_weights(cfg, bench.seed_key(2 ** 31 + 5))
    key = jax.random.PRNGKey(7)
    return {k: v + 0.1 * jax.random.normal(key, v.shape) if k.endswith(".g")
            else v * scale for k, v in w.items()}


def test_loss_and_gradients_match_the_reference(bench, cell):
    cfg, mod, ref = cell
    model, criterion = mod.build(cfg, TRAFFIC)
    names = mod.names(cfg)
    weights = _weights(bench, cfg, mod)
    params = bench.tree_from_names(model.get_params(), names, weights)
    x, y = mod.make_batches(cfg, TRAFFIC, np.random.default_rng(5))[0]
    assert x.shape == (2, 64) and y.shape == (2, 2, 32)

    def loss(p):
        out, state = model.apply(p, model.get_state(), jnp.asarray(x), training=True)
        return criterion.apply(out, jnp.asarray(y)), state

    with jax.default_matmul_precision("highest"):
        (got, state), grads = jax.value_and_grad(loss, has_aux=True)(params)
        want, want_grads = ref.make_loss_and_grad(cfg)(weights, jnp.asarray(x), jnp.asarray(y))
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want))
    assert float(state["dropped_fraction"]) == 0.0 and float(state["pairs_held"]) > 0
    assert float(state["row_passes"]) >= 1.0
    grads = bench.names_from_tree(grads, names)
    assert set(grads) == set(want_grads)
    for k in want_grads:
        scale = float(jnp.linalg.norm(want_grads[k]))
        assert scale > 0, k
        assert float(jnp.linalg.norm(grads[k] - want_grads[k])) <= 1e-4 * scale, k


@pytest.mark.parametrize("towards_held", [False, True])
def test_rematerialised_scan_differentiates_through_the_routed_vjp(bench, cell, towards_held):
    """The scanned decoder with every layer recomputed against the one that
    keeps its layers' values: loss and gradients, with the held pairs inside
    one pass of the expert layer and with every pair held (the pass repeated
    inside the hand-written VJP)."""
    cfg, mod, _ = cell
    names = mod.names(cfg)
    weights = _weights(bench, cfg, mod)
    x, y = mod.make_batches(cfg, TRAFFIC, np.random.default_rng(6))[0]
    got = {}
    for remat in (True, False):
        model, criterion = mod.build(cfg, TRAFFIC)
        model.remat = remat
        params = bench.tree_from_names(model.get_params(), names, weights)
        if towards_held:
            # one large channel in every embedding survives the norms as a
            # constant, and the routers' first row turns it into a bias
            first, count = cfg["held"]
            experts = jnp.arange(cfg["router_experts"])
            bias = jnp.where((experts >= first) & (experts < first + count), 6.0, -6.0)
            params["embed"] = params["embed"].at[:, 0].set(50.0)
            params["layers"]["moe"]["w_gate"] = \
                params["layers"]["moe"]["w_gate"].at[:, 0, :].set(bias)

        def loss(p):
            out, state = model.apply(p, model.get_state(), jnp.asarray(x), training=True)
            return criterion.apply(out, jnp.asarray(y)), state

        with jax.default_matmul_precision("highest"):
            got[remat] = jax.value_and_grad(loss, has_aux=True)(params)
    (loss_r, state_r), grads_r = got[True]
    (loss_k, state_k), grads_k = got[False]
    assert float(state_r["row_passes"]) == float(state_k["row_passes"]) == (2.0 if towards_held else 1.0)
    assert float(state_r["dropped_fraction"]) == 0.0
    np.testing.assert_allclose(float(loss_r), float(loss_k), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(grads_r), jax.tree_util.tree_leaves(grads_k)):
        np.testing.assert_allclose(a, b, atol=1e-6 + 1e-5 * float(jnp.max(jnp.abs(b))))


def test_scan_keeps_the_named_values_and_the_layers_input(bench, cell, capsys):
    """What the rematerialised scan holds a layer across the backward pass:
    the values tagged by name where they are made (``RESIDUAL_NAMES``,
    ``ROUTING_NAMES``, the decoder's own) and the layer's input, nothing
    else, and nothing of (tokens * top_k, feature)."""
    from jax.ad_checkpoint import print_saved_residuals
    cfg, mod, _ = cell
    model, criterion = mod.build(cfg, TRAFFIC)
    x, y = mod.make_batches(cfg, TRAFFIC, np.random.default_rng(6))[0]

    def loss(p):
        out, _ = model.apply(p, model.get_state(), jnp.asarray(x), training=True)
        return criterion.apply(out, jnp.asarray(y))

    print_saved_residuals(loss, model.get_params())
    kept = sorted(re.match(r"(\w+\[[\d,]*\])", line).group(1)
                  for line in capsys.readouterr().out.splitlines()
                  if "output of scan" in line)
    layers, n, t, d = cfg["num_hidden_layers"], TRAFFIC["batch"], 2 * TRAFFIC["seq_len"], cfg["hidden_size"]
    heads, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    tokens, k = n * t, cfg["num_experts_per_tok"]
    f32, i32 = "f32[{}]", "i32[{}]"
    shape = lambda *dims: ",".join(str(v) for v in (layers,) + dims)
    want = sorted([
        f32.format(shape(n, t, d)),                 # the layer's input (the carry)
        f32.format(shape(n, heads, t, hd)),         # flash_q
        f32.format(shape(n, kv, t, hd)),            # flash_k
        f32.format(shape(n, kv, t, hd)),            # flash_v
        f32.format(shape(n, heads, t, hd)),         # flash_out (no lse off the chip:
                                                    # the reference path has none)
        f32.format(shape(n, t, d)),                 # decoder_after_attention
        f32.format(shape(tokens, k)),               # moe_top_p
        i32.format(shape(tokens, k)),               # moe_top_e
        i32.format(shape(tokens * k)),              # moe_order
        i32.format(shape(cfg["held"][1])),          # moe_sizes
        i32.format(shape()),                        # moe_passes
    ])
    assert kept == want
    assert not any(f"[{layers},{tokens * k}," in a for a in kept)


def test_names_follow_the_programs_tree(bench, cell):
    cfg, mod, _ = cell
    model, _ = mod.build(cfg, TRAFFIC)
    names = mod.names(cfg)
    back = bench.names_from_tree(model.get_params(), names)
    assert [tuple(back[n].shape) for n, _ in names] == [tuple(s) for _, s in names]
    gains = {n for n, _ in names if n.endswith(".g")}
    assert all(float(jnp.min(back[n])) == 1.0 == float(jnp.max(back[n])) for n in gains)
    assert model.get_grads().keys() == model.get_params().keys()


def test_batches_hold_the_corruption(cell):
    cfg, mod, _ = cell
    x, y = mod.make_batches(cfg, dict(TRAFFIC, seq_len=256), np.random.default_rng(1))[0]
    xt, x0 = x[:, :256], x[:, 256:]
    masked = y[:, 0] >= 0
    assert (xt[masked] == cfg["mask_token_id"]).all() and (xt[~masked] == x0[~masked]).all()
    assert (y[:, 0][masked] == x0[masked]).all() and (x0 < cfg["mask_token_id"]).all()
    weight = y[:, 1]
    assert (weight[~masked] == 0).all()
    for row in range(2):                # one t a sequence, its weight 1/t
        assert len(np.unique(weight[row][masked[row]])) == 1
        assert 1.0 <= weight[row][masked[row]][0] <= 20.0 + 1e-4


def test_causal_decoder_and_evaluation_give_logits():
    from bigdl_tpu.models.transformerlm import ConfigDecoder
    model = ConfigDecoder(vocab_size=50, hidden_size=32, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                          moe_intermediate_size=24, num_experts=8,
                          num_experts_per_tok=2, remat=False)
    x = jnp.asarray(np.random.default_rng(0).integers(0, 50, (2, 16)), jnp.int32)
    logits, _ = model.apply(model.get_params(), model.get_state(), x)
    assert logits.shape == (2, 16, 50)
    # causal: a later token does not move an earlier position's logits
    moved, _ = model.apply(model.get_params(), model.get_state(), x.at[:, 9].set(3))
    np.testing.assert_allclose(logits[:, :9], moved[:, :9], atol=1e-5)
    assert float(jnp.max(jnp.abs(logits[:, 9:] - moved[:, 9:]))) > 1e-4


def test_trains_through_local_optimizer(bench, cell):
    """The normal path: ``LocalOptimizer(...).optimize()`` with Adam, the
    health leaves logged; the loss falls on a batch seen again and again."""
    from bigdl_tpu import Engine, optim
    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.dataset.sample import MiniBatch
    from bigdl_tpu.optim import Trigger
    cfg, mod, _ = cell
    Engine.init(seed=3)
    Engine.set_compute_dtype(jnp.float32)
    model, criterion = mod.build(cfg, TRAFFIC)
    batches = mod.make_batches(cfg, dict(TRAFFIC, n_batches=1), np.random.default_rng(2))
    opt = optim.LocalOptimizer(model, DataSet.array([MiniBatch(x, y) for x, y in batches]),
                               criterion)
    opt.set_optim_method(optim.Adam(learningrate=3e-3))
    losses = {}

    class Summary:
        def add_scalar(self, tag, value, iteration):
            losses.setdefault(tag, []).append(float(value))

        def get_summary_trigger(self, name):
            return None

    opt.set_train_summary(Summary())
    opt.set_end_when(Trigger.max_iteration(12)).optimize()
    assert losses["Loss"][-1] < 0.7 * losses["Loss"][0]
    assert any(tag.endswith("dropped_fraction") for tag in losses)
    assert all(v == 0.0 for tag, vs in losses.items()
               if tag.endswith("dropped_fraction") for v in vs)
    # the passes the expert layer ran ride the same batched fetch
    assert len(losses["State/row_passes"]) == len(losses["Loss"])
    assert all(v >= 1.0 for v in losses["State/row_passes"])
