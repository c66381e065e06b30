"""Off the chip the repo says so instead of carrying on: ``chip_smoke.py``
refuses to run, ``Engine.init`` names the CPU it landed on, the multichip dry
run refuses a mesh it was not given, and ``launch`` refuses to let N processes
fight over one host's chips."""

import ast
import importlib
import logging
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PASSING = '"ok": true'


def _smoke(cwd, **env_over):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(env_over)
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_refuses_the_cpu():
    r = _smoke(ROOT, JAX_PLATFORMS="cpu")
    assert r.returncode != 0
    assert PASSING not in r.stdout and '"phase"' not in r.stdout
    assert "no TPU" in r.stderr


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    r = _smoke(str(tmp_path), JAX_PLATFORMS="cpu")
    assert r.returncode != 0 and PASSING not in r.stdout


def test_chip_smoke_is_one_process_and_never_imports_tests():
    """... and stands on what the package has: every ``bigdl_tpu`` module it
    imports exists, with the names it asks for. Its imports sit inside its
    stages and nothing runs them off the chip, so a name that went away would
    otherwise be found by the next chip run."""
    text = open(os.path.join(ROOT, "chip_smoke.py")).read()
    assert "subprocess" not in text and "multiprocessing" not in text
    assert "import tests" not in text and "from tests" not in text
    assert "except Exception" not in text and "except:" not in text
    asked = 0
    for node in ast.walk(ast.parse(text)):
        if isinstance(node, ast.Import):
            modules = [(a.name, []) for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            assert node.level == 0, "chip_smoke.py is a script, not a package"
            modules = [(node.module, [a.name for a in node.names])]
        else:
            continue
        for module, names in modules:
            if module.split(".")[0] != "bigdl_tpu":
                continue
            found = importlib.import_module(module)
            for name in names:
                asked += 1
                if not hasattr(found, name):        # a sub-module not yet imported
                    importlib.import_module(f"{module}.{name}")
    assert asked > 20       # the walk saw the imports inside the stages


def test_engine_warns_when_it_lands_on_cpu_unasked(monkeypatch, caplog):
    from bigdl_tpu.utils.engine import Engine
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with caplog.at_level(logging.WARNING, logger="bigdl_tpu"):
        Engine.init()
    assert any("no accelerator" in r.getMessage() for r in caplog.records)


@pytest.mark.parametrize("how", ["env", "backend"])
def test_engine_is_quiet_when_cpu_was_asked_for(monkeypatch, caplog, how):
    from bigdl_tpu.utils.engine import Engine
    if how == "env":
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    else:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with caplog.at_level(logging.WARNING, logger="bigdl_tpu"):
        Engine.init(backend="cpu" if how == "backend" else None)
    assert not any("no accelerator" in r.getMessage() for r in caplog.records)


def test_dryrun_refuses_more_devices_than_it_was_given():
    from bigdl_tpu.dryrun import dryrun_multichip
    with pytest.raises(RuntimeError, match="JAX reports 8 cpu device"):
        dryrun_multichip(16)


def test_launch_refuses_to_share_one_hosts_chips(capsys):
    from bigdl_tpu import cli
    assert cli.main(["launch", "-n", "2", "lenet"]) == 2
    assert "a chip belongs to one process" in capsys.readouterr().err
