"""`smallthinker-21b-a3b`: its work functions against counts made by hand and
against the dense masks' sums, its plain reference against the program at a
tiny size in float32, its cell through `run.py`'s and `calibrate.py`'s paths
on the CPU, and its per-layer readers where there is nothing to read."""

import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import calibrate
import harness
import run
import scope_seconds

NAME = "smallthinker-21b-a3b"
CELL = NAME + ".train-t16k"
TRAFFIC = {"batch": 1, "seq_len": 16384}


def _load(kind):
    return harness.load_module(os.path.join(harness.HERE, kind, NAME + ".py"))


def _config():
    return json.load(open(os.path.join(harness.HERE, "configs", NAME + ".json")))


def _tiny():
    return dict(_config(), **json.load(open(os.path.join(
        os.path.dirname(__file__), "tiny", NAME + ".json")))["config"])


def test_the_file_holds_the_catalogs_numbers_but_the_three_cuts():
    """Every key of the published config is there under its own name and
    value, the lists whole; `reduced` names exactly what differs."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guide here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "SmallThinker-21BA3B-Instruct")
    cfg = _config()
    assert cfg["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if cfg[k] != v)
    assert differs == sorted(cfg["reduced"]) == ["moe_num_primary_experts",
                                                 "num_hidden_layers", "vocab_size"]
    assert cfg["published"] == {k: row["config"][k] for k in differs}
    assert cfg["held"] == [24, 8] and cfg["router_experts"] == 64
    assert cfg["vocab_size"] * 8 == 151_936 and len(cfg["rope_layout"]) == 52
    assert cfg["sliding_window_layout"][:4] == cfg["rope_layout"][:4] == [0, 1, 1, 1]


def test_parameters_by_hand():
    cfg, work = _config(), _load("work")
    # q and o 2560 x 3584 each, k and v 2560 x 512 each, router 2560 x 64,
    # two norms of 2560, 8 experts of 3 x 2560 x 768
    assert work.layer_params(cfg) == 2 * 9_175_040 + 2 * 1_310_720 + 163_840 \
        + 5_120 + 8 * 5_898_240 == 68_326_400
    names = _load("configs").names(cfg)
    total = sum(int(np.prod(shape)) for _, shape in names)
    assert total == 4 * 68_326_400 + 2 * 18_992 * 2560 + 2560 == 370_547_200
    assert work.held_pairs(cfg, 16_384) == 12_288          # three quarters of a pair a position
    assert work.windows(cfg) == [None, 4096, 4096, 4096]


@pytest.mark.parametrize("length,window", [(64, None), (64, 16), (64, 1), (48, 100),
                                           (1000, 384)])
def test_live_pairs_are_the_dense_masks_sum(length, window):
    lead = np.arange(length)[:, None] - np.arange(length)[None, :]
    dense = (lead >= 0) if window is None else (lead >= 0) & (lead < window)
    assert _load("work").live_pairs(length, window) == int(dense.sum())


def test_train_flops_per_sample_by_hand():
    cfg, work = _config(), _load("work")
    t = 16_384
    assert work.live_pairs(t) == 134_225_920 and work.live_pairs(t, 4096) == 58_722_304
    per_token = 4 * (2 * 9_175_040 + 2 * 1_310_720 + 163_840 + 6 * 8 * 5_898_240 // 64) \
        + 2560 * 18_992
    assert per_token == 150_855_680
    assert work.matmul_flops_fwd(cfg, t) == 2 * t * per_token
    attention = (134_225_920 + 3 * 58_722_304) * 4 * 28 * 128
    want = 3 * (2 * t * per_token + attention)
    assert work.train_flops_per_sample(cfg, TRAFFIC) == want
    assert 14.8e12 < 6 * t * per_token < 14.9e12 and 13.3e12 < 3 * attention < 13.4e12
    assert 28.1e12 < want < 28.3e12         # attention is 47% of the step's required work


def test_kernel_work_by_hand():
    cfg, work = _config(), _load("work")
    parts = work.mixed_attention_step(cfg, TRAFFIC)
    assert len(parts) == 8                      # four layers, forward and backward
    q, kv = 16_384 * 28 * 128 * 2, 16_384 * 4 * 128 * 2
    full, window = 134_225_920 * 4 * 3584, 58_722_304 * 4 * 3584
    assert parts[0] == (full, 2 * q + 2 * kv) and parts[1] == (2 * full, 4 * q + 4 * kv)
    assert parts[2:] == [(window, 2 * q + 2 * kv), (2 * window, 4 * q + 4 * kv)] * 3
    # every part is bound by the MXU, not by memory, at the v5e's peaks
    assert all(f / 197e12 > b / 819e9 for f, b in parts)
    (g_fwd, gb_fwd), (g_bwd, gb_bwd) = work.grouped_matmul_step(cfg, TRAFFIC)
    assert g_fwd == 4 * 2 * 12_288 * 3 * 2560 * 768 and g_bwd == 2 * g_fwd
    rows = 12_288 * (2560 + 1536 + 768 + 2560) * 2
    assert gb_fwd == 4 * (rows + 8 * 3 * 2560 * 768 * 2) and gb_bwd > 2 * gb_fwd


def test_reference_matches_program():
    from bigdl_tpu import Engine
    Engine.init(seed=1)
    Engine.set_compute_dtype(jnp.float32)
    cfg = _tiny()
    traffic = {"batch": 2, "seq_len": 32, "n_batches": 1}
    mod, ref = _load("configs"), _load("reference")
    model, criterion = mod.build(cfg, traffic)
    names = mod.names(cfg)
    weights = {k: v if k.endswith(".g") or k == "embed" else 5 * v for k, v in
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
    assert pairs.shape == (4,) and int(pairs.sum()) == int(state["pairs_held"])
    gp = harness.names_from_tree(gp, names)
    for k in gr:
        scale = float(jnp.linalg.norm(gr[k])) + 1e-12
        assert float(jnp.linalg.norm(gp[k] - gr[k])) <= 1e-3 * scale + 1e-7, k


@pytest.mark.parametrize("seed", [3_000_000_007, 11])
def test_a_sound_run_is_correct(capsys, tiny_bench, seed):
    """The cell through `run.py`'s own path: set-up, a window, the state
    leaves, the reference's steps, one line."""
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "1", "--trace", "0"],
                  bench_dir=tiny_bench, require_chip=False)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert line["attempted"] > 0 and line["compared"]["compiles_in_window"]["value"] == 0
    assert set(line["compared"]) == {"change_median", "compiles_in_window"}
    assert line["state"]["row_passes"] == 1.0 and line["state"]["pairs_held"] > 0
    assert "not a sound measurement" not in err


def test_control_and_faults_fail_the_tiny_cuts_limit(capsys, tiny_bench):
    calibrate.main(["--workload", CELL, "--seeds", "7", "--control", "1"],
                   bench_dir=tiny_bench, require_chip=False)
    verdict = json.loads(capsys.readouterr().out.strip().splitlines()[-1])["verdict"]
    assert verdict["program"] == {"correct": True, "failed_by": []}
    for name in ("control_fp8", "fault_half_batch", "fault_state_unchanged"):
        assert verdict[name] == {"correct": False, "failed_by": ["change_median"]}, name


def test_drift_reads_the_cells_routing_leaves(capsys, tiny_bench):
    """`drift.py` on this cell: 12 steps of its own optimizer, the leaves of
    every step, and the reference's count by layer after the last."""
    import drift
    drift.main(["--workload", CELL, "--seeds", "1", "--steps", "12", "--layers"],
               bench_dir=tiny_bench, require_chip=False)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["steps"] == list(range(3, 13)) and set(line["row_passes"]) == {1.0}
    assert len(line["layers_last"]) == 4 and len(line["layers_last"][0]) == 4
    # 2 x 32 positions, 4 of 16 experts held, 4 a token: 64 pairs at the expectation
    assert all(0 < p < 4 * 64 for p in line["pairs_held"])


def test_the_readers_find_nothing_where_there_is_nothing(monkeypatch):
    """On a program without the scopes (the parent's) or a configuration
    without the work function, each reader returns None and does not raise."""
    readers = {n: harness.load_module(os.path.join(harness.HERE, "metrics", n + ".py"))
               for n in ("window_attn_roofline.train", "attn_window_ms.train",
                         "attn_full_ms.train")}
    other = harness.load_module(os.path.join(harness.HERE, "work", "gpt2-medium.py"))
    assert readers["window_attn_roofline.train"].read(SimpleNamespace(work=other)) is None
    for found in (0.0, None):
        monkeypatch.setattr(scope_seconds, "seconds", lambda run, *scopes: found)
        assert readers["attn_window_ms.train"].read(None) is None
        assert readers["attn_full_ms.train"].read(None) is None
    manifest = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    for m in manifest["per_layer"]:
        if m["name"] in readers:
            assert m["workloads"] == [CELL] and m["moves"] == "train_samples_per_s"
