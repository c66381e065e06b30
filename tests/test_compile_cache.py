"""The persistent compile cache is placed from outside: where
``JAX_COMPILATION_CACHE_DIR`` says when it is set (the program then sets
nothing), else at ONE fixed path inside the checkout — never under a temp dir,
a pid or a timestamp, because the path is part of what a later process must
reproduce to hit. Every entry point passes the placement on ``import
bigdl_tpu``. (The suite itself runs with the cache off — tests/conftest.py.)"""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child(code: str, cwd: str = ROOT, **env_over) -> str:
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR",
                        "JAX_ENABLE_COMPILATION_CACHE")}
    env["PYTHONPATH"] = ROOT
    env.update(env_over)
    r = subprocess.run([sys.executable, "-c", code], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-1500:]
    return r.stdout.strip()


SHOW = ("import jax, bigdl_tpu\n"
        "print(jax.config.jax_compilation_cache_dir)")


def test_env_set_is_left_alone(tmp_path):
    assert _child(SHOW, JAX_COMPILATION_CACHE_DIR=str(tmp_path)) \
        == str(tmp_path)


def test_unset_goes_to_one_fixed_path_in_the_checkout(tmp_path):
    want = os.path.join(ROOT, ".jax_cache")
    # two processes, two working directories, two pids: one path
    assert _child(SHOW) == want
    assert _child(SHOW, cwd=str(tmp_path)) == want


def test_serving_engine_import_alone_places_it():
    """ServingEngine never touches the Engine singleton, so Engine.init
    cannot be the place."""
    out = _child("import jax\n"
                 "from bigdl_tpu.serving import ServingEngine\n"
                 "print(jax.config.jax_compilation_cache_dir)")
    assert out == os.path.join(ROOT, ".jax_cache")


def test_compiles_land_where_the_env_says(tmp_path):
    cache = tmp_path / "cache"
    _child("import jax, jax.numpy as jnp, bigdl_tpu\n"
           "jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((64, 64)))"
           ".block_until_ready()",
           JAX_COMPILATION_CACHE_DIR=str(cache), JAX_PLATFORMS="cpu",
           JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
           JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    assert any(cache.iterdir()), "no cache entry written under the env's dir"


def test_no_bigdl_twin_of_the_variable():
    import inspect

    from bigdl_tpu.utils.engine import place_compile_cache
    assert "BIGDL_" not in inspect.getsource(place_compile_cache)
