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
        out, state = model.apply(p, model.get_state(), jnp.asarray(x), training=True)
        return criterion.apply(out, jnp.asarray(y)), state

    with jax.default_matmul_precision("highest"):
        (lp, state), gp = jax.value_and_grad(program_loss, has_aux=True)(params)
        lr, gr = ref.make_loss_and_grad(cfg)(weights, jnp.asarray(x), jnp.asarray(y))
        top_e = ref.routing(weights, jnp.asarray(x), cfg)
    first, count = cfg["held"]
    pairs = ((top_e >= first) & (top_e < first + count)).sum((1, 2, 3))
    assert abs(float(lp) - float(lr)) <= 1e-5 * abs(float(lr))
    # the reference's count of held pairs, by layer, is the program's own
    assert pairs.shape == (2,) and int(pairs.sum()) == int(state["pairs_held"])
    assert float(state["row_passes"]) == 1.0
    gp = harness.names_from_tree(gp, names)
    for k in gr:
        scale = float(jnp.linalg.norm(gr[k])) + 1e-12
        assert float(jnp.linalg.norm(gp[k] - gr[k])) <= 1e-3 * scale + 1e-7, k


def test_noise_levels_are_the_same_strata_in_an_order_drawn_from_the_seed():
    traffic = json.load(open(os.path.join(harness.HERE, "traffic", "train-bd4k.json")))
    mod = _load("configs")
    a, b = (mod.noise_levels(traffic, np.random.default_rng(s)) for s in (1, 2 ** 31 + 5))
    assert a.shape == b.shape == (traffic["n_batches"], traffic["batch"])
    lo, hi = traffic["noise_t"]
    strata = lo + (hi - lo) * (np.arange(a.size) + 0.5) / a.size
    assert np.allclose(np.sort(a, None), strata) and np.allclose(np.sort(b, None), strata)
    assert not np.array_equal(a, b)
    # every batch masks as many positions as any other: one sum of t a batch
    assert np.allclose(a.sum(1), a.sum() / len(a)) and np.allclose(b.sum(1), a.sum(1))
    x, y = mod.make_batches(_config(), traffic, np.random.default_rng(7))[0]
    masked = (x[:, :traffic["seq_len"]] == _config()["mask_token_id"])
    assert np.array_equal(masked, y[:, 0] >= 0) and np.array_equal(masked, y[:, 1] > 0)
    assert abs(masked.sum() - a.sum(1)[0] * traffic["seq_len"]) < 200


def test_the_mask_row_routes_as_the_balanced_expectation_says():
    """Of the candidates drawn from the seed, the `[MASK]` row is one whose own
    top experts hold 4 x 4 / 16 = 1 held expert in each layer; every other
    weight is what the seed gives without the choice: the embedding N(0, 1),
    the other matrices at the source's range."""
    cfg = dict(_config(), **json.load(open(os.path.join(
        os.path.dirname(__file__), "tiny", NAME + ".json")))["config"])
    mod = _load("configs")
    for seed in (3, 2 ** 31 + 11):
        key = harness.seed_key(seed)
        w = mod.make_weights(cfg, key)
        row = w["embed"][cfg["mask_token_id"]]
        unit = row / jnp.sqrt(jnp.mean(row ** 2))
        top = jax.lax.top_k(jnp.einsum("d,lde->le", unit, w["layers.router"]), 4)[1]
        first, count = cfg["held"]
        assert ((top >= first) & (top < first + count)).sum(-1).tolist() == [1, 1]
        assert 0.8 < float(jnp.std(row)) < 1.2
        plain = jax.random.normal(jax.random.fold_in(key, 0), w["embed"].shape, jnp.float32)
        assert np.array_equal(w["embed"][:-1], plain[:-1])
        assert not np.array_equal(row, plain[-1])
        assert abs(float(jnp.std(w["head"])) - cfg["initializer_range"]) < 0.002


def _epoch_means(line, epoch=4):
    """Held pairs by epoch of 4 steps: each holds the run's 4 batches once."""
    by_step = dict(zip(line["steps"], line["pairs_held"]))
    return [sum(by_step[s] for s in range(e, e + epoch)) / epoch
            for e in range(5, line["steps"][-1] - epoch + 2, epoch)]


def test_at_the_cells_rate_the_routing_stays_and_at_1e_4_it_drifts(capsys, tiny_bench):
    """`drift.py` over the tiny cut, 40 steps of the cell's own optimizer with
    4 of 16 experts held: at the traffic file's rate the held pairs of an
    epoch stay where the seeded weights put them and no layer repeats its
    pass; at 1e-4, the rate the cell ran at before PR 34, only the held
    experts answer and Adam pulls the router towards them."""
    import drift
    rate = json.load(open(os.path.join(harness.HERE, "traffic", "train-bd4k.json")))[
        "optim_method"]["args"]["learningrate"]
    assert rate <= 1e-5     # guarded by value too: PERF.md, PR 34, has the sweep
    old = {"traffic": {"optim_method": {"args": {"learningrate": 1e-4}}}}
    drift.main(["--workload", "sdar-30b-a3b.train-bd4k", "--seeds", "1", "--steps", "40",
                "--variant", "{}", "--variant", json.dumps(old)],
               bench_dir=tiny_bench, require_chip=False)
    kept, before = (json.loads(l) for l in capsys.readouterr().out.strip().splitlines()[-2:])
    assert kept["variant"] == {} and before["variant"] == old
    means = _epoch_means(kept)
    assert len(means) == 9 and set(kept["row_passes"]) == {1.0}
    assert max(abs(m / means[0] - 1.0) for m in means) < 0.05
    assert max(kept["pairs_held"]) < 1.25 * kept["pairs_held"][0]
    assert _epoch_means(before)[-1] > 1.15 * means[-1]


def test_the_survey_counts_held_pairs_and_distinct_routings_by_layer(capsys, tiny_bench):
    """`drift.py --survey` runs no program: the reference's routing of every
    batch at the seeded weights, a count a layer."""
    import drift
    drift.main(["--workload", "sdar-30b-a3b.train-bd4k", "--seeds", "5", "--survey"],
               bench_dir=tiny_bench, require_chip=False)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    positions = 2 * 2 * 32          # the tiny cut: 2 sequences of 32 tokens and their copies
    assert len(line["layers_seeded"]) == 4 and len(line["layers_seeded"][0]) == 2
    for masked, tokens, pairs, distinct in zip(line["masked"], line["tokens_distinct"],
                                               line["layers_seeded"], line["routings_distinct"]):
        assert 0 < masked < positions // 2 and 1 < tokens <= positions - masked + 1
        assert all(0 <= p <= 4 * positions for p in pairs)
        assert all(1 <= d <= positions for d in distinct)
    assert line["row_bound_a_layer"] == 2 * 4 * positions * 4 // 16


def test_the_row_passes_reader_reads_the_observable_state_or_nothing():
    reader = harness.load_module(os.path.join(harness.HERE, "metrics",
                                              "moe_row_passes.train.py"))
    state = {"row_passes": 1.0, "pairs_held": 9.0, "decoder/moe/row_passes": 2.0}
    assert reader.read(SimpleNamespace(state=state)) == 2.0       # the fullest layer's
    assert reader.read(SimpleNamespace(state={"aux_loss": 0.1})) is None
    assert reader.read(SimpleNamespace(state={})) is None    # a model that routes nothing


def test_a_state_leaf_over_the_traffic_files_bound_is_said_on_standard_error():
    """`state_at_most` in the cell's traffic file: the runner knows no leaf's
    name, and says which leaf read over what the file allows it."""
    runner = harness.load_module(os.path.join(harness.HERE, "runners", "train.py"))
    traffic = json.load(open(os.path.join(harness.HERE, "traffic", "train-bd4k.json")))
    assert traffic["state_at_most"] == {"row_passes": 1.0}
    cell = SimpleNamespace(name="a.cell", traffic=traffic)
    assert runner.over_the_traffics_bounds(cell, {"row_passes": 1.0, "pairs_held": 9e4}) == []
    said = runner.over_the_traffics_bounds(cell, {"moe/row_passes": 2.0, "pairs_held": 9e4})
    assert len(said) == 1 and "moe/row_passes reads 2" in said[0]
    assert "not a sound measurement" in said[0]
    assert runner.over_the_traffics_bounds(SimpleNamespace(name="b", traffic={}),
                                           {"row_passes": 3.0}) == []


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
