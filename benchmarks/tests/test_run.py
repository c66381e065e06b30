"""A whole run on the CPU at tiny sizes, without the harness's look for a
chip: sound runs come out correct, the control and each planted fault do not
(in the per-step program and in the fused window's alike), and off the chip
`run.py` refuses to print a result."""

import json
import os
import subprocess
import sys

import pytest

import calibrate
import check
import harness
import run

CELL = "gpt2-medium.train-t1024"
FUSED = "resnet50.train-stream"
ROUTED = "sdar-30b-a3b.train-bd4k"


def _run(capsys, bench, cell, seed=3_000_000_007, trace=0):
    manifest = harness.load_json(os.path.join(os.path.dirname(bench), "BENCHMARK.json"))
    if cell not in [w["name"] for w in manifest["workloads"]]:
        pytest.skip(f"{cell}: its configuration brings no benchmarks/tests/tiny/"
                    f"<configuration>.json, so the tiny copy has no such cell")
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds", "1",
                   "--trace", str(trace)], bench_dir=bench, require_chip=False)
    out, err = capsys.readouterr()
    return rc, json.loads(out.strip().splitlines()[-1]), err


@pytest.mark.parametrize("cell", [CELL, "gpt2-medium.train-throwaway", FUSED, ROUTED])
def test_a_sound_run_is_correct(capsys, tiny_bench, cell):
    # the throw-away cell exists only in the temporary copy: a new traffic
    # file and a new entry of BENCHMARK.json, no other file touched
    rc, line, err = _run(capsys, tiny_bench, cell)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert line["metrics"]["train_samples_per_s"]["value"] > 0
    assert line["attempted"] > 0
    assert line["compared"]["compiles_in_window"] == {"value": 0, "limit": 0}
    assert list(line)[-1] == "compared" and "compared compiles_in_window: 0" in err
    assert set(line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    # the host's clock at each evaluation of the end trigger, in every line
    assert len(line["evaluated_s"]) > line["steps"] // 8 and line["evaluated_s"][0] >= 0
    # a routed model's last step, in every line: one pass, some pairs held
    assert ("state" in line) == (cell == ROUTED) and "not a sound measurement" not in err
    assert cell != ROUTED or (line["state"]["row_passes"] == 1.0
                              and line["state"]["pairs_held"] > 0)


def test_a_program_compiled_inside_the_window_is_not_correct(
        capsys, tiny_bench, monkeypatch):
    load = harness.load_module

    def load_and_break(path):
        mod = load(path)
        if path.endswith(os.path.join("runners", "train.py")):
            window = mod.window

            def compiling(cell, st, seconds, trace):
                import jax
                jax.jit(lambda x: x * 3 + 1)(jax.numpy.ones(7))     # never warmed up
                return window(cell, st, seconds, trace)
            mod.window = compiling
        return mod
    monkeypatch.setattr(harness, "load_module", load_and_break)
    rc, line, err = _run(capsys, tiny_bench, CELL)
    assert rc == 0 and line["correct"] is False
    assert line["compared"]["compiles_in_window"]["value"] >= 1


def frozen(step):
    def broken(params, mstate, ostate, *rest):
        _, new_ms, _, loss = step(params, mstate, ostate, *rest)
        return params, new_ms, ostate, loss
    return broken


def half(step):
    def broken(params, mstate, ostate, idx, inp, target, rng):
        n = inp.shape[0] // 2
        return step(params, mstate, ostate, idx, inp[:n], target[:n], rng)
    return broken


def _break_step(monkeypatch, wrap, window_only=False):
    """Plant a fault in every step the program compiles or, with
    `window_only`, in the fused window's program alone: the per-step program
    that a run's first step goes through stays sound."""
    from bigdl_tpu.optim.optimizer import Optimizer
    make_step, make_window = Optimizer._make_step_fn, Optimizer._make_window_fn
    if not window_only:
        monkeypatch.setattr(Optimizer, "_make_step_fn", lambda self: wrap(make_step(self)))
        return

    def broken_window(self, k):
        with monkeypatch.context() as m:
            m.setattr(Optimizer, "_make_step_fn", lambda self: wrap(make_step(self)))
            return make_window(self, k)
    monkeypatch.setattr(Optimizer, "_make_window_fn", broken_window)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        capsys, tiny_bench, monkeypatch):
    _break_step(monkeypatch, frozen)
    rc, line, _ = _run(capsys, tiny_bench, CELL)
    assert rc == 0 and line["correct"] is False
    assert line["compared"]["change"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out_is_not_correct(capsys, tiny_bench, monkeypatch):
    _break_step(monkeypatch, half)
    rc, line, _ = _run(capsys, tiny_bench, CELL)
    assert rc == 0 and line["correct"] is False
    assert line["compared"]["grad1"]["value"] > line["compared"]["grad1"]["limit"]


@pytest.mark.parametrize("fault", [frozen, half])
def test_a_fault_in_the_fused_window_alone_is_not_correct(
        capsys, tiny_bench, monkeypatch, fault):
    """The window's program (steps 2 to 9 of set-up, and all of the timed
    window) is broken and the per-step program is sound: the first gradient
    still agrees, and the change and the buffer after the window do not."""
    _break_step(monkeypatch, fault, window_only=True)
    rc, line, _ = _run(capsys, tiny_bench, FUSED)
    assert rc == 0 and line["correct"] is False
    assert line["not_judged"]["grad1_median"] < 0.3    # sound: a fault reads 1 and more
    assert line["compared"]["change_median"]["value"] > 0.5


def test_control_and_faults_fail_the_cells_own_limits(capsys, tiny_bench):
    """The reference in float8 in the program's place, and each fault in the
    reference, judged as a run is, by the cell's limits file (here the tiny
    copy's; the cells' own verdicts are read on the chip by `calibrate.py`)."""
    calibrate.main(["--workload", CELL, "--seeds", "7", "--control", "1"],
                   bench_dir=tiny_bench, require_chip=False)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    verdict = line["verdict"]
    assert verdict["program"] == {"correct": True, "failed_by": []}
    assert verdict["control_fp8"]["correct"] is False
    assert "grad1" in verdict["control_fp8"]["failed_by"]
    assert verdict["fault_half_batch"]["correct"] is False
    assert verdict["fault_state_unchanged"] == {"correct": False, "failed_by": ["change"]}
    assert line["fault_state_unchanged"]["change"] == pytest.approx(1.0)


def test_off_the_chip_there_is_no_result(tiny_bench):
    env = {k: v for k, v in os.environ.items() if not k.startswith("BIGDL_")}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, os.path.join(harness.HERE, "run.py"),
                        "--workload", "resnet50.train-stream", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no accelerator" in p.stderr


def test_a_program_variable_in_the_environment_is_refused(tiny_bench, monkeypatch, capsys):
    monkeypatch.setenv("BIGDL_FUSE_STEPS", "4")
    rc = run.main(["--workload", CELL, "--seed", "1", "--seconds", "1", "--trace", "0"],
                  bench_dir=tiny_bench, require_chip=False)
    out, err = capsys.readouterr()
    assert rc != 0 and out.strip() == "" and "BIGDL_FUSE_STEPS" in err


def test_unknown_names_are_clear_errors(tiny_bench):
    with pytest.raises(harness.BenchError, match="unknown workload"):
        harness.Cell("no-such.cell", tiny_bench)
    path = os.path.join(tiny_bench, "traffic", "train-throwaway.json")
    traffic = json.load(open(path))
    json.dump(dict(traffic, kind="serve"), open(path, "w"))
    try:
        with pytest.raises(harness.BenchError, match="no runners/serve.py"):
            harness.Cell("gpt2-medium.train-throwaway", tiny_bench)
    finally:
        json.dump(traffic, open(path, "w"))
