"""`deepseek-v2-lite`: its work functions against the issue's arithmetic, its
plain reference against the program at a tiny size in float32, its cell
through `run.py`'s and `calibrate.py`'s paths on the CPU, its files found by
name, and its per-layer readers where there is nothing to read."""

import json
import math
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

NAME = "deepseek-v2-lite"
CELL = NAME + ".train-t16k"
TRAFFIC = {"batch": 1, "seq_len": 16384}
READERS = ("latent_attn_roofline.train", "attn_latent_ms.train", "shared_mlp_ms.train")


def _load(kind):
    return harness.load_module(os.path.join(harness.HERE, kind, NAME + ".py"))


def _config():
    return json.load(open(os.path.join(harness.HERE, "configs", NAME + ".json")))


def _tiny():
    return dict(_config(), **json.load(open(os.path.join(
        os.path.dirname(__file__), "tiny", NAME + ".json")))["config"])


def test_the_file_holds_the_catalogs_numbers_but_the_three_cuts():
    """Every key of the published config is there under its own name and
    value, the nested `rope_scaling` whole; `reduced` names exactly what
    differs."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guide here")
    row = next(r for r in map(json.loads, open(catalog)) if r["name"] == "DeepSeek-V2-Lite")
    cfg = _config()
    assert cfg["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if cfg[k] != v)
    assert differs == sorted(cfg["reduced"]) == ["n_routed_experts", "num_hidden_layers",
                                                 "vocab_size"]
    assert cfg["published"] == {k: row["config"][k] for k in differs}
    assert cfg["held"] == [24, 8] and cfg["router_experts"] == 64
    assert cfg["vocab_size"] * 8 == 102_400 and cfg["first_k_dense_replace"] == 1


def test_the_cells_files_are_found_by_name():
    cell = harness.Cell(CELL)
    assert cell.config["name"] == NAME and cell.traffic["seq_len"] == 16384
    assert cell.traffic["state_at_most"] == {"row_passes": 1.0}
    assert cell.chips == 1 and hasattr(cell.work, "latent_attention_step")
    assert hasattr(cell.reference(), "routing") and set(cell.limits())
    assert {m["name"] for m in cell.per_layer()} >= set(READERS)
    for name in READERS:
        harness.load_module(cell.path("metrics", name + ".py"))


def test_parameters_by_hand():
    """The issue's arithmetic: attention 13,763,072, a routed layer as one of
    8 chips 100,405,760, the dense layer 81,007,104, the vocabulary's eighth
    52,428,800: 535,060,992 in all."""
    cfg, work = _config(), _load("work")
    attention = 6_291_456 + 1_179_648 + 512 + 2_097_152 + 4_194_304
    assert work.attention_params(cfg) == attention == 13_763_072
    assert work.layer_params(cfg) == 8 * 8_650_752 + 17_301_504 + 131_072 + 4_096 \
        + attention == 100_405_760
    assert work.dense_layer_params(cfg) == attention + 4_096 + 67_239_936 == 81_007_104
    names = _load("configs").names(cfg)
    total = sum(int(np.prod(shape)) for _, shape in names)
    assert total == 81_007_104 + 4 * 100_405_760 + 52_428_800 + 2_048 == 535_060_992
    assert work.held_pairs(cfg, 16_384) == 12_288          # three quarters of a pair a position


def test_train_flops_per_sample_by_hand():
    cfg, work = _config(), _load("work")
    t = 16_384
    assert work.live_pairs(t) == 134_225_920
    per_token = (13_762_560 + 67_239_936) + 4 * (13_762_560 + 131_072 + 17_301_504
                                                 + 6 * 8 * 8_650_752 // 64) + 26_214_400
    assert per_token == 257_949_696
    assert work.matmul_flops_fwd(cfg, t) == 2 * t * per_token
    attention = 5 * 134_225_920 * 2 * 16 * (192 + 128)
    want = 3 * (2 * t * per_token + attention)
    assert work.train_flops_per_sample(cfg, TRAFFIC) == want
    assert 25.3e12 < 6 * t * per_token < 25.4e12 and 20.6e12 < 3 * attention < 20.7e12
    assert 0.44 < 3 * attention / want < 0.46      # latent attention is 45% of the work


def test_kernel_work_by_hand():
    cfg, work = _config(), _load("work")
    parts = work.latent_attention_step(cfg, TRAFFIC)
    assert len(parts) == 10                     # five layers, forward and backward
    fwd = 134_225_920 * 2 * 16 * 320
    rows = 16_384 * 16 * 2
    assert parts[:2] == [(fwd, rows * 640), (2 * fwd, 2 * rows * 640)]
    assert len(set(parts)) == 2
    # every part is bound by the MXU, not by memory, at the v5e's peaks
    assert all(f / 197e12 > b / 819e9 for f, b in parts)


def test_yarn_by_the_published_keys():
    """The correction range and the softmax scale at the published keys: low
    10, high 23, `s = mscale^2 / sqrt(192)` with `mscale = 0.1 * 0.707 ln 40 + 1`."""
    inv_freq, cos_sin, scale, (low, high) = _load("reference").yarn_frequencies(_config())
    assert (low, high) == (10, 23) and cos_sin == 1.0
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert abs(mscale - 1.26080) < 1e-5 and abs(scale - 0.114721) < 1e-6
    extra = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv_freq[:10], extra[:10], rtol=1e-6)
    np.testing.assert_allclose(inv_freq[23:], extra[23:] / 40, rtol=1e-6)


def test_reference_matches_program():
    from bigdl_tpu import Engine
    Engine.init(seed=1)
    Engine.set_compute_dtype(jnp.float32)
    cfg = _tiny()
    traffic = {"batch": 2, "seq_len": 32, "n_batches": 1}
    mod, ref = _load("configs"), _load("reference")
    model, criterion = mod.build(cfg, traffic)
    assert model.dense_layers == 1 and model.experts.shared == 2 * 24
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
    assert pairs.shape == (2,) and int(pairs.sum()) == int(state["pairs_held"])
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


def test_the_readers_find_nothing_where_there_is_nothing(monkeypatch):
    """On a program without the scopes (the parent's) or a configuration
    without the work function, each reader returns None and does not raise."""
    readers = {n: harness.load_module(os.path.join(harness.HERE, "metrics", n + ".py"))
               for n in READERS}
    other = harness.load_module(os.path.join(harness.HERE, "work", "gpt2-medium.py"))
    assert readers["latent_attn_roofline.train"].read(SimpleNamespace(work=other)) is None
    for found in (0.0, None):
        monkeypatch.setattr(scope_seconds, "seconds", lambda run, *scopes: found)
        assert readers["attn_latent_ms.train"].read(None) is None
        assert readers["shared_mlp_ms.train"].read(None) is None
    manifest = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    for m in manifest["per_layer"]:
        if m["name"] in readers:
            assert m["workloads"] == [CELL] and m["moves"] == "train_samples_per_s"
