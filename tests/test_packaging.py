"""Build/packaging parity (SURVEY.md §2.5 L8: maven multi-module + make-dist.sh
+ bigdl.sh analog): the wheel must build offline and carry the native C++
source and proto schema; the CLI fans out to the training mains."""

import os
import subprocess
import sys
import zipfile

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestWheel:
    @pytest.fixture(scope="class")
    def wheel(self, tmp_path_factory):
        out = tmp_path_factory.mktemp("dist")
        r = subprocess.run(
            [sys.executable, "-m", "pip", "wheel", ".", "--no-deps",
             "--no-build-isolation", "-w", str(out)],
            cwd=ROOT, capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr[-2000:]
        wheels = [f for f in os.listdir(out) if f.endswith(".whl")]
        assert len(wheels) == 1
        return str(out / wheels[0])

    def test_wheel_contents(self, wheel):
        names = zipfile.ZipFile(wheel).namelist()
        # package modules
        assert any(n.endswith("bigdl_tpu/nn/abstractnn.py") for n in names)
        assert any(n.endswith("bigdl_tpu/cli.py") for n in names)
        # native runtime source ships for on-demand compilation
        assert any(n.endswith("native/batchpack.cpp") for n in names)
        # caffe proto schema ships for the importer
        assert any(n.endswith("utils/caffe/caffe_minimal.proto") for n in names)

    def test_entry_point_declared(self, wheel):
        zf = zipfile.ZipFile(wheel)
        meta = [n for n in zf.namelist() if n.endswith("entry_points.txt")]
        assert meta, "wheel missing entry_points.txt"
        text = zf.read(meta[0]).decode()
        assert "bigdl-tpu = bigdl_tpu.cli:main" in text


class TestCli:
    def test_models_listing(self, capsys):
        from bigdl_tpu.cli import main
        assert main(["models"]) == 0
        out = capsys.readouterr().out
        for name in ("lenet", "resnet", "inception", "ncf"):
            assert name in out

    def test_env_listing(self, capsys, monkeypatch):
        from bigdl_tpu.cli import main
        monkeypatch.setenv("BIGDL_PREFETCH", "3")
        assert main(["env"]) == 0
        assert "BIGDL_PREFETCH=3" in capsys.readouterr().out

    def test_train_forwards_args(self):
        from bigdl_tpu.cli import main
        rc = main(["train", "lenet", "--max-epoch", "1",
                   "--batch-size", "8", "--synthetic-size", "16"])
        assert rc == 0

    def test_no_command_prints_help(self, capsys):
        from bigdl_tpu.cli import main
        assert main([]) == 2
        assert "train" in capsys.readouterr().out


class TestLauncherScript:
    def test_launcher_script_syntax(self):
        r = subprocess.run(["bash", "-n", os.path.join(ROOT, "scripts",
                                                       "bigdl-tpu.sh")],
                           capture_output=True)
        assert r.returncode == 0

    def test_conf_sources_cleanly(self):
        """The conf must survive the launcher's actual source-under-strict-mode."""
        conf = os.path.join(ROOT, "conf", "bigdl-tpu.conf")
        r = subprocess.run(
            ["bash", "-c",
             "set -euo pipefail; set -a; "
             f"source <(grep -E '^[A-Z_]+=' '{conf}' || true); set +a; "
             "echo sourced-ok"],
            capture_output=True, text=True)
        assert r.returncode == 0 and "sourced-ok" in r.stdout, r.stderr

    def test_conf_flags_match_code(self):
        """Every flag documented in the conf is actually read by the code."""
        import re
        conf = open(os.path.join(ROOT, "conf", "bigdl-tpu.conf")).read()
        documented = set(re.findall(r"^#?(BIGDL_[A-Z_]+)=", conf, re.M))
        used = set()
        for dirpath, _, files in os.walk(os.path.join(ROOT, "bigdl_tpu")):
            for f in files:
                if f.endswith(".py"):
                    used |= set(re.findall(
                        r"BIGDL_[A-Z_]+",
                        open(os.path.join(dirpath, f)).read()))
        assert documented <= used, f"conf documents unknown flags: {documented - used}"


class TestPackagedContract:
    def test_bench_and_dryrun_are_packaged(self):
        """The console script's bench/dryrun must not depend on repo-root
        modules (the wheel has no bench.py / __graft_entry__.py)."""
        import bigdl_tpu.benchmark
        import bigdl_tpu.dryrun
        assert callable(bigdl_tpu.benchmark.main)
        assert callable(bigdl_tpu.dryrun.dryrun_multichip)

    def test_repo_root_shims_delegate(self):
        import bench
        import __graft_entry__
        import bigdl_tpu.benchmark
        import bigdl_tpu.dryrun
        assert bench.main is bigdl_tpu.benchmark.main
        assert __graft_entry__.dryrun_multichip is bigdl_tpu.dryrun.dryrun_multichip
        assert __graft_entry__.entry is bigdl_tpu.dryrun.entry


class TestCliBench:
    def test_bench_subcommand_parses(self, monkeypatch):
        """`bigdl-tpu bench` must not re-parse sys.argv (review fix)."""
        import bigdl_tpu.benchmark as bm
        from bigdl_tpu.cli import main
        called = {}

        def orchestrator(args):
            called["model"] = args.model
            return 0

        monkeypatch.setattr(bm, "run_orchestrator", orchestrator)
        monkeypatch.setattr("sys.argv", ["bigdl-tpu", "bench"])
        assert main(["bench"]) == 0
        assert called["model"] == "resnet50"

    def test_worker_spawn_sets_pythonpath(self):
        """Spawned workers must import bigdl_tpu from any cwd (review fix)."""
        import json
        import subprocess
        import sys
        env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
        env["JAX_PLATFORMS"] = "cpu"
        # ORCHESTRATOR mode so the `-m bigdl_tpu.benchmark` worker is actually
        # spawned: parent finds bigdl_tpu via sys.path[0] (the script dir); the
        # worker subprocess must get it from _spawn's PYTHONPATH propagation
        r = subprocess.run(
            [sys.executable, os.path.join(ROOT, "bench.py"),
             "--model", "lenet", "--batch", "16", "--iters", "2",
             "--warmup", "1", "--dtype", "fp32", "--no-compare-dtypes",
             "--timeout", "500"],
            cwd="/tmp", capture_output=True, text=True, timeout=600, env=env)
        assert r.returncode == 0, r.stderr[-1500:]
        line = json.loads(r.stdout.strip().splitlines()[-1])
        assert line["value"] is not None

    def test_no_build_artifacts_tracked(self):
        r = subprocess.run(["git", "ls-files", "build", "dist",
                            "bigdl_tpu.egg-info"],
                           cwd=ROOT, capture_output=True, text=True)
        assert r.stdout.strip() == "", "generated artifacts tracked in git"


class TestCliBenchArgs:
    def test_bench_forwards_args(self, monkeypatch):
        import bigdl_tpu.benchmark as bm
        from bigdl_tpu.cli import main
        seen = {}

        def orchestrator(args):
            seen.update(model=args.model, iters=args.iters)
            return 3       # the CLI hands the bench's exit code through

        monkeypatch.setattr(bm, "run_orchestrator", orchestrator)
        assert main(["bench", "--model", "lenet", "--iters", "5"]) == 3
        assert seen == {"model": "lenet", "iters": 5}
