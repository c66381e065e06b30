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

    def test_bench_is_no_subcommand(self, capsys):
        """The package has no benchmark of its own (the repo's is
        ``benchmarks/run.py``): ``bigdl-tpu bench`` is refused as any unknown
        sub-command is, and ``--help`` names none."""
        from bigdl_tpu.cli import main
        with pytest.raises(SystemExit) as e:
            main(["bench"])
        assert e.value.code == 2
        assert "invalid choice: 'bench'" in capsys.readouterr().err
        with pytest.raises(SystemExit) as e:
            main(["--help"])
        assert e.value.code == 0
        assert "bench" not in capsys.readouterr().out


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
    def test_dryrun_is_packaged(self):
        """The console script's dryrun must not depend on repo-root modules
        (the wheel has no __graft_entry__.py)."""
        import bigdl_tpu.dryrun
        assert callable(bigdl_tpu.dryrun.dryrun_multichip)

    def test_repo_root_shim_delegates(self):
        import __graft_entry__
        import bigdl_tpu.dryrun
        assert __graft_entry__.dryrun_multichip is bigdl_tpu.dryrun.dryrun_multichip
        assert __graft_entry__.entry is bigdl_tpu.dryrun.entry

    def test_no_build_artifacts_tracked(self):
        r = subprocess.run(["git", "ls-files", "build", "dist",
                            "bigdl_tpu.egg-info"],
                           cwd=ROOT, capture_output=True, text=True)
        assert r.stdout.strip() == "", "generated artifacts tracked in git"


