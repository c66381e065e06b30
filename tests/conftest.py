"""Test configuration: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's trick of testing the distributed path with ``local[N]`` Spark masters
inside one JVM (SURVEY.md §4): we fake an 8-chip topology with
``--xla_force_host_platform_device_count=8`` so DistriOptimizer/collective tests exercise real
sharding + collectives without TPU hardware. Must run before jax is imported anywhere.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# The persistent compile cache (bigdl_tpu.utils.engine.place_compile_cache) is
# for chip compiles. Keep the suite out of it: a test must not pass because an
# earlier run left an executable behind, and XLA:CPU's loader logs a
# machine-feature complaint on every hit. Worker processes inherit this.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_engine():
    """Reset Engine + RNG (and the obs tracer/registry sinks) between tests
    for determinism."""
    yield
    from bigdl_tpu.obs import exporter, mfu, slo, trace, watchdog
    from bigdl_tpu.obs.registry import registry as obs_registry
    from bigdl_tpu.utils.engine import Engine
    from bigdl_tpu.utils.random_generator import RandomGenerator

    Engine.reset()
    RandomGenerator.set_seed(1)
    trace.reset()
    obs_registry.reset()
    mfu.reset()
    slo.reset()
    exporter.reset()
    watchdog.clear_context_providers()
