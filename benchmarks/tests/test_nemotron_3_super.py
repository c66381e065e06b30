"""`nemotron-3-super-120b-a12b`: its cut as its file states it, its work
functions against the arithmetic of the cut, its cell through `run.py`'s and
`calibrate.py`'s paths on the CPU, its files found by name, and its
per-layer readers where there is nothing to read."""

import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

import calibrate
import harness
import run
import scope_seconds

NAME = "nemotron-3-super-120b-a12b"
CELL = NAME + ".train-t8k"
TRAFFIC = {"batch": 1, "seq_len": 8192}
READERS = ("mamba_ms.train", "ssm_scan_roofline.train", "latent_moe_ms.train")


def _load(kind):
    return harness.load_module(os.path.join(harness.HERE, kind, NAME + ".py"))


def _config():
    return json.load(open(os.path.join(harness.HERE, "configs", NAME + ".json")))


def test_the_file_names_each_cut_with_its_published_value():
    """`reduced` names exactly the keys `published` gives, each cut below its
    published value; the widths are the published ones."""
    cfg = _config()
    assert sorted(cfg["published"]) == sorted(cfg["reduced"])
    assert all(cfg[k] < v for k, v in cfg["published"].items())
    assert (cfg["hidden_size"], cfg["mamba_head_dim"], cfg["ssm_state_size"], cfg["head_dim"],
            cfg["moe_intermediate_size"], cfg["moe_latent_size"],
            cfg["moe_shared_expert_intermediate_size"], cfg["num_experts_per_tok"]) \
        == (4096, 64, 128, 128, 2688, 1024, 5376, 22)
    assert cfg["held"] == [216, 8] and cfg["router_experts"] == 512
    assert cfg["vocab_size"] * 8 == 131_072 and cfg["mamba_num_heads"] * 8 == 128


def test_the_cells_files_are_found_by_name():
    cell = harness.Cell(CELL)
    assert cell.config["name"] == NAME and cell.traffic["seq_len"] == 8192
    assert cell.traffic["state_at_most"] == {"row_passes": 1.0}
    assert cell.chips == 1 and hasattr(cell.work, "ssm_scan_step")
    assert hasattr(cell.reference(), "routing") and set(cell.limits())
    assert {m["name"] for m in cell.per_layer()} >= set(READERS)


def test_parameters_by_hand():
    """The cut's arithmetic: a Mamba share 13,708,592, the attention share
    5,246,976, an expert layer 98,570,240, the vocabulary's eighth
    134,217,728 and the final norm: 700,862,960 in all."""
    cfg = _config()
    names = dict(_load("configs").names(cfg))
    size = lambda prefix: sum(int(np.prod(s)) for n, s in names.items() if n.startswith(prefix))
    assert size("mamba.") == 5 * (9_502_720 + 6_400 + 48 + 1_024 + 4_194_304 + 4_096) \
        == 5 * 13_708_592
    assert size("attention.") == 2_097_152 + 2 * 524_288 + 2_097_152 + 4_096 == 5_246_976
    assert size("experts.") == 5 * (2_097_152 + 8_388_608 + 8 * 5_505_024 + 44_040_192 + 4_096) \
        == 5 * 98_570_240
    assert sum(int(np.prod(s)) for s in names.values()) == 700_862_960


def test_train_flops_per_sample_by_hand():
    """422.9M products a position (Mamba projections 68,485,120, attention
    5,242,880, the expert layers 282,091,520 at the balanced expectation of
    0.34375 held pairs, the head 67,108,864): 20.79 TFLOP a sample at 8,192,
    and attention and the scan beside it."""
    cfg, work = _config(), _load("work")
    per_token = 68_485_120 + 5_242_880 + 282_091_520 + 67_108_864
    assert work.matmul_params_a_token(cfg) == per_token == 422_928_384
    assert work.held_pairs(cfg, 8192) == 2_816            # 352 a held expert
    assert 20.78e12 < 6 * 8192 * per_token < 20.80e12
    attention = 8192 * 8193 // 2 * 2 * 2 * 4 * 128
    scan = 2 * 64 * (128 * 128 * 128 + 16 * 128 * 128 * 64 + 2 * 16 * 128 * 64 * 128)
    assert work.ssd_flops_fwd(cfg, 8192) == scan == 6_710_886_400
    assert work.train_flops_per_sample(cfg, TRAFFIC) == \
        3 * (2 * 8192 * per_token + attention + 5 * scan)
    parts = work.ssm_scan_step(cfg, TRAFFIC)
    assert len(parts) == 10 and parts[1] == (2 * parts[0][0], 2 * parts[0][1])
    # the scan's least time is set by its bytes at the v5e's peaks
    assert all(b / 819e9 > f / 197e12 for f, b in parts)
    # the held experts' grouped products: 2,816 pairs a layer in the latent
    # space, two ungated matrices of 1,024 x 2,688 an expert, 5 layers
    (g_fwd, gb_fwd), (g_bwd, gb_bwd) = work.grouped_matmul_step(cfg, TRAFFIC)
    assert g_fwd == 5 * 2 * 2_816 * 2 * 1024 * 2688 and g_bwd == 2 * g_fwd
    rows, weights = 2_816 * (1024 + 2688 + 2688 + 1024) * 2, 8 * 2 * 1024 * 2688 * 2
    assert gb_fwd == 5 * (rows + weights) and gb_bwd == 5 * (3 * rows + 2 * weights)


def test_the_routers_read_no_shared_direction(tiny_bench):
    """`make_weights` clears each router's columns of their part along the
    mean of its normed input over the calibration sequence, a layer at a time
    through the routers before it; every other weight is as drawn."""
    import jax
    import jax.numpy as jnp
    cfg = json.load(open(os.path.join(tiny_bench, "configs", NAME + ".json")))
    mod = harness.load_module(os.path.join(tiny_bench, "configs", NAME + ".py"))
    ref = harness.load_module(os.path.join(tiny_bench, "reference", NAME + ".py"))
    key = harness.seed_key(2 ** 33 + 1)
    w = mod.make_weights(cfg, key)
    x = jax.random.randint(jax.random.fold_in(key, len(mod.names(cfg))),
                           (1, cfg["router_calibration_tokens"]), 0, cfg["vocab_size"])
    seen = 0
    for kind, i, h in ref.layer_inputs(dict(w), x, cfg):
        if kind == "E":
            m = h / jnp.sqrt(jnp.mean(h * h, -1, keepdims=True) + cfg["layer_norm_epsilon"])
            u = jnp.mean(m * w["experts.norm.g"][i], axis=(0, 1))
            along = u @ w["experts.router"][i]
            assert float(jnp.max(jnp.abs(along))) <= 1e-5 * float(
                jnp.linalg.norm(u) * jnp.max(jnp.linalg.norm(w["experts.router"][i], axis=0)))
            seen += 1
    assert seen == 5
    spec = mod.names(cfg)
    drawn = jax.jit(lambda k: {n: mod._draw(cfg, n, jax.random.fold_in(k, j), s)
                               for j, (n, s) in enumerate(spec)})(key)
    for name, _ in spec:
        same = np.array_equal(np.asarray(drawn[name]), np.asarray(w[name]))
        assert same == (name != "experts.router"), name


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
    assert line["state"]["row_passes"] == 1.0 and line["state"]["pairs_held"] > 0
    assert "not a sound measurement" not in err


def test_control_and_faults_fail_the_tiny_cuts_limit(capsys, tiny_bench):
    calibrate.main(["--workload", CELL, "--seeds", "7", "--control", "1"],
                   bench_dir=tiny_bench, require_chip=False)
    verdict = json.loads(capsys.readouterr().out.strip().splitlines()[-1])["verdict"]
    assert verdict["program"] == {"correct": True, "failed_by": []}
    for name in ("control_fp8", "fault_half_batch", "fault_state_unchanged"):
        assert verdict[name]["correct"] is False, name


def test_the_readers_find_nothing_where_there_is_nothing(monkeypatch):
    """On a program without the scopes (the parent's) or a configuration
    without the work function, each reader returns None and does not raise."""
    readers = {n: harness.load_module(os.path.join(harness.HERE, "metrics", n + ".py"))
               for n in READERS}
    other = harness.load_module(os.path.join(harness.HERE, "work", "gpt2-medium.py"))
    assert readers["ssm_scan_roofline.train"].read(SimpleNamespace(work=other)) is None
    mine = SimpleNamespace(work=_load("work"))
    for found in (0.0, None):
        monkeypatch.setattr(scope_seconds, "seconds", lambda run, *scopes: found)
        assert readers["mamba_ms.train"].read(None) is None
        assert readers["latent_moe_ms.train"].read(None) is None
        assert readers["ssm_scan_roofline.train"].read(mine) is None
    manifest = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    for m in manifest["per_layer"]:
        if m["name"] in readers:
            assert m["workloads"] == [CELL] and m["moves"] == "train_samples_per_s"


def test_the_cell_reports_the_accepted_readers_of_its_expert_layer():
    """The accepted metrics of the routed layer list the cell after the cell
    they listed before."""
    listed = {m["name"] for m in harness.Cell(CELL).per_layer()}
    assert listed >= {"moe_share.train", "moe_route_ms.train", "grouped_mm_roofline.train",
                      "moe_row_passes.train"}
    assert not listed & {"attn_window_ms.train", "attn_latent_ms.train",
                         "flash_attn_roofline.train", "blockdiff_attn_roofline.train"}
