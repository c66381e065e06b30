"""bigdl_tpu — a TPU-native deep-learning framework with the capabilities of BigDL classic.

This is a ground-up re-design of the reference framework (skamble91/BigDL, a fork of
intel-analytics/BigDL "classic") for TPU hardware:

- the reference's ``DenseTensor`` + Intel-MKL JNI math becomes ``jax.numpy`` lowered by XLA
  onto the MXU/VPU (the JNI seam is deleted, not bridged);
- its Torch-style mutable module system (``AbstractModule.forward/backward``) keeps its API
  shape but is backed by a pure functional core (pytree params, ``jax.vjp``) so whole training
  steps compile to one XLA program;
- its Spark ``DistriOptimizer`` + BlockManager partitioned all-reduce becomes data-parallel
  ``jit`` over a ``jax.sharding.Mesh`` with ICI collectives (reduce-scatter → sharded optimizer
  update → all-gather, the exact ZeRO-1 structure the reference's ``AllReduceParameter``
  pioneered on Spark);
- ``Engine.init`` selects a device mesh instead of a CPU thread topology.

Reference provenance: the survey of the reference lives in SURVEY.md. NOTE: the reference
mount was empty in rounds 0-1, so reference citations in docstrings give the *expected
upstream path* (e.g. ``<dl>/nn/Linear.scala``) per SURVEY.md §2 and are marked unverified.
"""

__version__ = "0.1.0"

from bigdl_tpu.obs import compile as _obs_compile
from bigdl_tpu.utils.engine import Engine, place_compile_cache
from bigdl_tpu.utils.table import Table, T

place_compile_cache()
# before anything of the package compiles: set-up's compile phases and the
# persistent cache's answers, recorded from the first program on
_obs_compile.install()

__all__ = ["Engine", "Table", "T", "__version__"]
