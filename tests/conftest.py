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


@pytest.fixture
def fused_backward_against_reference(monkeypatch):
    """The flash backward kernel (interpret mode) against the VJP of
    ``_reference_attention`` at ``"highest"`` in float32: all three gradients
    of one call. ``tiles`` hands the kernels a ``_Tiles`` of the case's own
    (block unequal to chunk, several spans a head); ``heads`` is (query heads,
    key/value heads). Shared by the three files that hold the masks' cases."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.kernels import flash_attention as fa

    def check(mask, heads, t, d, dtype, tiles=None):
        if tiles is not None:
            monkeypatch.setattr(fa, "_tiles_under",
                                lambda *a: fa._Tiles(*tiles))
        hq, hkv = heads
        key = jax.random.PRNGKey(hq * 1000 + d)
        q, k, v, g = (jax.random.normal(jax.random.fold_in(key, i), (1, h, t, d),
                                        jnp.float32).astype(dtype)
                      for i, h in enumerate((hq, hkv, hkv, hq)))
        got = jax.vjp(lambda *a: fa.flash_attention(*a, False, True, mask),
                      q, k, v)[1](g)
        with jax.default_matmul_precision("highest"):
            want = jax.vjp(lambda *a: fa._reference_attention(*a, mask),
                           *(a.astype(jnp.float32) for a in (q, k, v)))[1](
                               g.astype(jnp.float32))
        assert [a.shape for a in got] == [q.shape, k.shape, v.shape]
        assert [a.dtype for a in got] == [q.dtype] * 3
        errs = [float(jnp.max(jnp.abs(a.astype(jnp.float32) - w))
                      / jnp.max(jnp.abs(w))) for a, w in zip(got, want)]
        assert max(errs) <= (1e-2 if dtype == jnp.bfloat16 else 1e-5), errs

    return check
