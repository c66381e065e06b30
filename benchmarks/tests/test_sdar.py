"""`sdar-30b-a3b`: its work functions against counts made by hand, its plain
reference against the program at a tiny size in float32, and the scope reader
its per-layer metrics share."""

import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

import harness
import scope_seconds
import scoped_trace

NAME = "sdar-30b-a3b"
TRAFFIC = {"batch": 2, "seq_len": 4096, "block_length": 4}


def _load(kind):
    return harness.load_module(os.path.join(harness.HERE, kind, NAME + ".py"))


def _config():
    return json.load(open(os.path.join(harness.HERE, "configs", NAME + ".json")))


def test_parameters_and_live_pairs_by_hand():
    cfg, work = _config(), _load("work")
    # q and o 2048 x 4096 each, k and v 2048 x 512 each, router 2048 x 128,
    # two norms of 2048 and two of 128, 16 experts of 3 x 2048 x 768
    assert work.layer_params(cfg) == 2 * 8_388_608 + 2 * 1_048_576 + 262_144 \
        + 4_352 + 16 * 4_718_592 == 94_638_336
    names = _load("configs").names(cfg)
    total = sum(int(np.prod(shape)) for _, shape in names)
    assert total == 5 * 94_638_336 + 2 * 18_992 * 2048 + 2048 == 550_984_960
    assert work.live_pairs(4096, 4) == 16_793_600          # 25.0% of 8192^2
    assert work.held_pairs(cfg, 16_384) == 16_384          # one pair a position


def test_train_flops_per_sample_by_hand():
    cfg, work = _config(), _load("work")
    projections = 2 * 8192 * (2 * 8_388_608 + 2 * 1_048_576)       # 309 GFLOP
    attention = 16_793_600 * 4 * 4096                                # 275
    experts = 2 * 8192 * 3 * 2048 * 768                              # 77
    router = 2 * 8192 * 2048 * 128                                   # 4
    head = 2 * 4096 * 2048 * 18_992                                  # 319
    want = 3 * (5 * (projections + attention + experts + router) + head)
    assert work.train_flops_per_sample(cfg, TRAFFIC) == want
    assert 10.9e12 < want < 11.0e12                 # 21.9 TFLOP a step of two


def test_kernel_work_by_hand():
    cfg, work = _config(), _load("work")
    (f_fwd, b_fwd), (f_bwd, b_bwd) = work.blockdiff_attention_step(cfg, TRAFFIC)
    assert f_fwd == 5 * 2 * 16_793_600 * 4 * 4096 and f_bwd == 2 * f_fwd
    q, kv = 2 * 8192 * 32 * 128 * 2, 2 * 8192 * 4 * 128 * 2
    assert b_fwd == 5 * (2 * q + 2 * kv) and b_bwd == 2 * b_fwd
    (g_fwd, gb_fwd), (g_bwd, gb_bwd) = work.grouped_matmul_step(cfg, TRAFFIC)
    assert g_fwd == 5 * 2 * 16_384 * 3 * 2048 * 768 and g_bwd == 2 * g_fwd
    rows = 16_384 * (2048 + 1536 + 768 + 2048) * 2
    assert gb_fwd == 5 * (rows + 16 * 3 * 2048 * 768 * 2) and gb_bwd > 2 * gb_fwd


def test_reference_matches_program():
    from bigdl_tpu import Engine
    Engine.init(seed=1)
    Engine.set_compute_dtype(jnp.float32)
    cfg = _config()
    cfg.update(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=2, head_dim=32, moe_intermediate_size=48,
               router_experts=16, held=[4, 4], num_experts=4,
               num_experts_per_tok=4, vocab_size=96, mask_token_id=95)
    traffic = {"batch": 2, "seq_len": 32, "n_batches": 1, "block_length": 4,
               "noise_t": [0.05, 1.0]}
    mod, ref = _load("configs"), _load("reference")
    model, criterion = mod.build(cfg, traffic)
    names = mod.names(cfg)
    weights = {k: v if k.endswith(".g") else 5 * v for k, v in
               mod.make_weights(cfg, harness.seed_key(2 ** 31 + 5)).items()}
    params = harness.tree_from_names(model.get_params(), names, weights)
    x, y = mod.make_batches(cfg, traffic, np.random.default_rng(0))[0]

    def program_loss(p):
        out, _ = model.apply(p, model.get_state(), jnp.asarray(x), training=True)
        return criterion.apply(out, jnp.asarray(y))

    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(program_loss)(params)
        lr, gr = ref.make_loss_and_grad(cfg)(weights, jnp.asarray(x), jnp.asarray(y))
    assert abs(float(lp) - float(lr)) <= 1e-5 * abs(float(lr))
    gp = harness.names_from_tree(gp, names)
    for k in gr:
        scale = float(jnp.linalg.norm(gr[k])) + 1e-12
        assert float(jnp.linalg.norm(gp[k] - gr[k])) <= 1e-3 * scale + 1e-7, k


def test_scope_reader_sums_leaves_under_a_scope(monkeypatch):
    """An operation counts under a scope anywhere in its path, forward or
    backward; a `while` that holds others does not; another scope's name
    that only starts alike does not."""
    op = scoped_trace.Op
    ops = [op(0, 10, "fusion.1", "jit(step)/jvp(bigdl_moe)/bigdl_moe_route/sort", "", ""),
           op(10, 30, "custom-call.2", "jit(step)/transpose(jvp(bigdl_moe))/bigdl_moe_experts/bigdl_gmm/pallas_call", "", ""),
           op(0, 100, "while.3", "jit(step)/bigdl_moe/while", "", ""),
           op(30, 70, "fusion.4", "jit(step)/bigdl_moe_other/add", "", ""),
           op(70, 75, "fusion.5", "jit(step)/bigdl_loss/reduce", "", "")]
    monkeypatch.setattr(scoped_trace, "load",
                        lambda run: SimpleNamespace(ops=[ops, ops]))
    assert scope_seconds.seconds(None, "bigdl_moe") == 30e-12
    assert scope_seconds.seconds(None, "bigdl_gmm") == 20e-12
    assert scope_seconds.seconds(None, "bigdl_moe_route", "bigdl_moe_combine") == 10e-12
    monkeypatch.setattr(scoped_trace, "load", lambda run: None)
    assert scope_seconds.seconds(None, "bigdl_moe") is None
