"""bench.py's contract when the accelerator leg fails: print the reason, exit
non-zero, substitute nothing. No CPU leg the caller did not ask for, no number
carried forward from an earlier round, and an orchestrating parent that never
attaches a JAX backend of its own (the child holds the chip)."""

import argparse
import json
import os
import subprocess
import sys

import pytest

import bigdl_tpu.benchmark as bm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _args(**over):
    base = dict(model="resnet50", batch=256, iters=24, warmup=12,
                dtype="bf16", compare_dtypes=False, streamed=False,
                timeout=5, int8_infer=False, serving=False,
                decode_infer=False, ablate=False, eval_bench=False)
    base.update(over)
    return argparse.Namespace(**base)


@pytest.fixture
def healthy_probe(monkeypatch):
    monkeypatch.setattr(bm, "_probe_backend", lambda env, timeout: None)


def _run(capsys, args=None):
    rc = bm.run_orchestrator(args or _args())
    out = capsys.readouterr()
    return rc, json.loads(out.out.strip().splitlines()[-1]), out


class TestAcceleratorLegFails:
    def test_exit_code_and_null_record(self, monkeypatch, capsys,
                                       healthy_probe):
        monkeypatch.setattr(bm, "_spawn", lambda argv, env, timeout:
                            (None, "backend hang (simulated)"))
        rc, rec, out = _run(capsys)
        assert rc == 1
        assert rec["value"] is None
        assert "backend hang (simulated)" in rec["error"]
        assert "FAILED" in out.err
        assert rec["timestamp"]          # provenance still stamped

    def test_one_attempt_and_never_a_cpu_leg(self, monkeypatch, capsys,
                                             healthy_probe):
        calls = []

        def spawn(argv, env, timeout):
            calls.append((list(argv), env.get("JAX_PLATFORMS")))
            return None, "dead (simulated)"

        monkeypatch.setattr(bm, "_spawn", spawn)
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        _run(capsys)
        assert len(calls) == 1                      # no retry, no fallback
        argv, platforms = calls[0]
        assert argv[argv.index("--model") + 1] == "resnet50"
        assert "lenet" not in argv and platforms != "cpu"

    def test_record_carries_nothing_from_the_past_or_the_parent(
            self, monkeypatch, capsys, healthy_probe):
        monkeypatch.setattr(bm, "_spawn", lambda argv, env, timeout:
                            (None, "dead (simulated)"))
        _, rec, _ = _run(capsys)
        # no carried-forward chip number, no degraded stand-in, and no block
        # the parent could only fill by touching JAX
        assert set(rec) <= {"metric", "value", "vs_baseline", "error",
                            "probe_error", "timestamp", "git_commit"}

    def test_probe_failure_skips_the_measurement(self, monkeypatch, capsys):
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        monkeypatch.setattr(bm, "_probe_backend",
                            lambda env, timeout: "device probe timed out")
        monkeypatch.setattr(bm, "_spawn", lambda *a: pytest.fail(
            "measurement spawned after a failed probe"))
        rc, rec, _ = _run(capsys)
        assert rc == 1 and rec["value"] is None
        assert rec["probe_error"] == "device probe timed out"

    def test_side_leg_failure_names_its_metric(self, monkeypatch, capsys,
                                               healthy_probe):
        monkeypatch.setattr(bm, "_spawn", lambda argv, env, timeout:
                            (None, "dead (simulated)"))
        rc, rec, _ = _run(capsys, _args(model="transformerlm",
                                        serving_bench=True))
        assert rc == 1 and rec["metric"] == "transformerlm_serving_engine"

    def test_main_returns_the_failure(self, monkeypatch, capsys,
                                      healthy_probe):
        monkeypatch.setattr(bm, "_spawn", lambda argv, env, timeout:
                            (None, "dead (simulated)"))
        assert bm.main(["--model", "lenet", "--no-compare-dtypes"]) == 1


def test_healthy_result_passes_through(monkeypatch, capsys, healthy_probe):
    def spawn(argv, env, timeout):
        return {"metric": "resnet50_train_images_per_sec_per_chip",
                "value": 2300.0, "unit": "images/sec",
                "suspect": False, "platform": "tpu"}, None

    monkeypatch.setattr(bm, "_spawn", spawn)
    rc, rec, _ = _run(capsys)
    assert rc == 0 and rec["value"] == 2300.0
    assert rec["timestamp"]  # provenance stamped on every line


def test_side_leg_flags_reach_the_worker(monkeypatch, capsys, healthy_probe):
    seen = {}

    def spawn(argv, env, timeout):
        seen["argv"] = list(argv)
        return {"metric": "x", "value": 1.0}, None

    monkeypatch.setattr(bm, "_spawn", spawn)
    _run(capsys, _args(paging_bench=True, compare_dtypes=True))
    assert "--paging-bench" in seen["argv"] and "--run" in seen["argv"]


def test_worker_refuses_an_unasked_cpu(monkeypatch):
    """JAX falls back to the CPU by itself when it finds no accelerator; the
    measuring child must not take that for an answer."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)   # nobody asked for CPU
    with pytest.raises(SystemExit) as e:
        bm._run_worker_modes(_args(run=True))
    assert "no accelerator" in str(e.value)


def test_peak_tables_know_the_chip_and_reject_strangers():
    assert bm._peak_flops("TPU v5 lite") == 197e12
    assert bm._peak_hbm("TPU v5 lite") == 819e9
    assert bm._peak_flops("cpu") is None and bm._peak_hbm("cpu") is None
    for lookup in (bm._peak_flops, bm._peak_hbm):
        with pytest.raises(ValueError, match="no peak entry"):
            lookup("TPU v9 imaginary")


def test_peak_lookup_ignores_the_gauge_override(monkeypatch):
    monkeypatch.setenv("BIGDL_PEAK_FLOPS", "1e9")
    assert bm._peak_flops("TPU v5 lite") == 197e12


def test_parent_never_attaches_a_backend():
    """A failed run leaves the orchestrating process without a JAX backend:
    it must not take the chip its children failed to reach."""
    code = (
        "import argparse, sys\n"
        "import bigdl_tpu.benchmark as bm\n"
        "bm._probe_backend = lambda env, timeout: None\n"
        "bm._spawn = lambda argv, env, timeout: (None, 'dead (simulated)')\n"
        "rc = bm.main(['--model', 'lenet', '--no-compare-dtypes'])\n"
        "from jax._src import xla_bridge\n"
        "print('RC', rc, 'BACKENDS', xla_bridge.backends_are_initialized())\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-800:]
    assert "RC 1 BACKENDS False" in r.stdout


def test_bench_py_unreachable_backend_exits_nonzero_without_a_number():
    """End to end: ``python bench.py`` against a backend that cannot attach."""
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="no_such_platform",
               BIGDL_BENCH_PROBE_RETRIES="1")
    r = subprocess.run([sys.executable, "bench.py", "--model", "lenet"],
                       cwd=ROOT, capture_output=True, text=True, timeout=300,
                       env=env)
    assert r.returncode != 0
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["value"] is None and rec["probe_error"]
    assert "per_sec" not in r.stdout and "images/sec" not in r.stdout
