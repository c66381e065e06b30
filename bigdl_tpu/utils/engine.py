"""Global runtime singleton — the TPU-native analog of the reference's ``Engine``.

Reference parity (SURVEY.md §2.5, expected upstream ``<dl>/utils/Engine.scala`` — unverified,
mount empty): the reference Engine detects/validates ``nodeNumber × coreNumber`` from the Spark
conf, picks an execution engine (MklBlas vs MklDnn), and owns thread pools. On TPU none of that
maps one-to-one: XLA owns intra-chip parallelism and the "engine type" concept collapses into
one compiled path. What survives is the *role*: a process-wide place that

- initialises the accelerator runtime (and, multi-host, ``jax.distributed``),
- discovers the device topology and builds the default ``jax.sharding.Mesh``,
- holds global knobs (compute dtype, seed, failure-retry budget) configured via
  ``bigdl.*``-style properties (here: ``BIGDL_*`` environment variables),
- guards against accidental double-init (the reference's singleton check).

``Engine.init()`` must be called before training, mirroring the reference contract.
"""

from __future__ import annotations

import logging
import os
import threading
from dataclasses import dataclass, field
from typing import Any, Optional, Sequence

logger = logging.getLogger("bigdl_tpu")


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one place and return
    it. ``JAX_COMPILATION_CACHE_DIR`` wins: JAX reads it itself and no
    directory is set here. Otherwise the cache goes to ``.jax_cache`` beside the
    package — a fixed path, because the path is part of what a later process
    must reproduce to hit. Called once, from ``import bigdl_tpu``, so every
    entry point (trainer, serving engine, CLI) passes it before its
    first compile."""
    import jax

    # the key covers the operations' metadata (named scopes, source lines):
    # otherwise a program cached before a scope was renamed is served with
    # its old names, and a profile bills device time to scopes that are gone
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        pkg_parent = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(pkg_parent, ".jax_cache"))
    return jax.config.jax_compilation_cache_dir


def _env(name: str, default: str | None = None) -> str | None:
    """Read a ``BIGDL_*`` property from the environment (the Python-native tier replacing
    the reference's ``bigdl.*`` JVM system properties, SURVEY.md §5.6). ``name`` must
    already be the ``BIGDL_*`` env-var spelling."""
    return os.environ.get(name, default)


@dataclass
class EngineConfig:
    backend: str = "auto"              # "auto" | "tpu" | "cpu" — analog of bigdl.engineType
    node_number: int = 1               # number of hosts (jax processes)
    core_number: int = 1               # local device count (chips, not CPU cores)
    seed: int = 1                      # global RNG seed default (Torch-style determinism)
    compute_dtype: Any = None          # jnp dtype used for matmul/conv compute (None = float32)
    param_dtype: Any = None            # master parameter dtype (None = float32)
    failure_retry_times: int = 5       # bigdl.failure.retryTimes analog
    failure_retry_interval: float = 15.0  # seconds, bigdl.failure.retryTimeInterval analog
    check_singleton: bool = False      # bigdl.check.singleton analog (BIGDL_CHECK_SINGLETON=1)
    extra: dict = field(default_factory=dict)


def _parse_dtype(name: str):
    import jax.numpy as jnp

    table = {"float32": jnp.float32, "fp32": jnp.float32,
             "bfloat16": jnp.bfloat16, "bf16": jnp.bfloat16,
             "float16": jnp.float16, "fp16": jnp.float16}
    if name not in table:
        raise ValueError(f"Unsupported BIGDL_COMPUTE_DTYPE={name!r}; one of {list(table)}")
    return table[name]


class _EngineState:
    def __init__(self) -> None:
        self.initialized = False
        self.config = EngineConfig()
        self.mesh = None               # default data-parallel Mesh
        self.devices = None
        self.distributed_initialized = False
        # the jax.distributed client object outlives Engine.reset() — this
        # flag tracks the CLIENT's lifetime, distributed_initialized tracks
        # whether THIS Engine config brought it up. reset() clears the
        # latter only; shutdown_distributed() clears both.
        self.distributed_client_live = False
        self.auto_initialized = False
        self.lock = threading.Lock()


_STATE = _EngineState()


class Engine:
    """Process-wide runtime. All methods are classmethods; state is a module singleton."""

    DATA_AXIS = "data"    # batch / data-parallel mesh axis
    MODEL_AXIS = "model"  # reserved: tensor-parallel axis
    SEQ_AXIS = "seq"      # reserved: sequence/context-parallel axis (ring attention)
    PIPE_AXIS = "pipe"    # reserved: pipeline-parallel axis

    # ------------------------------------------------------------------ init
    @classmethod
    def init(
        cls,
        backend: str | None = None,
        node_number: int | None = None,
        core_number: int | None = None,
        seed: int | None = None,
        compute_dtype: Any = None,
        mesh_shape: Sequence[int] | None = None,
        mesh_axes: Sequence[str] | None = None,
        coordinator_address: str | None = None,
        process_id: int | None = None,
    ) -> None:
        """Initialise the runtime. Call once per process before building optimizers.

        Single-host: discovers local devices and builds a 1-D ``('data',)`` mesh.
        Multi-host: pass ``coordinator_address``/``node_number``/``process_id`` to bring up
        ``jax.distributed`` first (the analog of the reference's Spark cluster attach).
        """
        import jax

        resolved_backend = backend or _env("BIGDL_BACKEND", "auto")

        with _STATE.lock:
            if _STATE.initialized:
                # an implicit auto-init (from an accessor) never blocks the user's
                # explicit init
                if _STATE.config.check_singleton and not _STATE.auto_initialized:
                    raise RuntimeError(
                        "Engine.init called twice with singleton check enabled "
                        "(BIGDL_CHECK_SINGLETON=1)")
                logger.debug("Engine.init: already initialized; re-init with new config")

            cfg = EngineConfig()
            cfg.backend = resolved_backend
            cfg.seed = int(seed if seed is not None else _env("BIGDL_SEED", "1"))
            cfg.failure_retry_times = int(_env("BIGDL_FAILURE_RETRY_TIMES", "5"))
            cfg.failure_retry_interval = float(_env("BIGDL_FAILURE_RETRY_INTERVAL", "15"))
            cfg.check_singleton = _env("BIGDL_CHECK_SINGLETON", "0") == "1"

            if coordinator_address is not None and not _STATE.distributed_initialized:
                if _STATE.distributed_client_live:
                    # A previous bring-up's client is still attached (reset()
                    # clears the init latch but cannot destroy the client).
                    # Silently skipping here would leave the caller training
                    # against a coordinator/topology it did NOT ask for.
                    raise RuntimeError(
                        "Engine.init: a jax.distributed client from a previous "
                        "init is still live in this process — call "
                        "Engine.shutdown_distributed() before re-initializing "
                        f"with coordinator_address={coordinator_address!r} "
                        "(elastic recovery: survivors usually re-exec instead)")
                # Multi-host control plane: replaces the reference's Spark driver/executor
                # bootstrap (SURVEY.md §5.8) with jax.distributed. Only legal once per
                # process, so re-inits skip it.
                if resolved_backend in (None, "cpu"):
                    # cross-process CPU collectives need the gloo transport
                    jax.config.update(
                        "jax_cpu_collectives_implementation",
                        os.environ.get(
                            "JAX_CPU_COLLECTIVES_IMPLEMENTATION", "gloo"))
                jax.distributed.initialize(
                    coordinator_address=coordinator_address,
                    num_processes=node_number,
                    process_id=process_id,
                )
                _STATE.distributed_initialized = True
                _STATE.distributed_client_live = True

            devices = cls._discover_devices_bounded(cfg.backend)
            if devices[0].platform == "cpu" and cfg.backend != "cpu" \
                    and os.environ.get("JAX_PLATFORMS") != "cpu":
                logger.warning(
                    "Engine.init(backend=%r) found no accelerator and is "
                    "running on %d CPU device(s); set JAX_PLATFORMS=cpu or "
                    "backend='cpu' if that is intended", cfg.backend,
                    len(devices))
            cfg.node_number = node_number or jax.process_count()
            cfg.core_number = core_number or jax.local_device_count()
            if core_number is not None:
                if core_number <= 0 or core_number > jax.local_device_count():
                    raise ValueError(
                        f"core_number={core_number} must be in [1, "
                        f"{jax.local_device_count()}] (local devices)")
                if jax.process_count() > 1:
                    raise ValueError(
                        "core_number restriction is only supported single-host; "
                        "multi-host meshes must cover every process's devices")
                # Restrict to the first core_number local devices (reference semantics:
                # Engine validates and pins the topology it was told to use).
                devices = devices[:core_number]

            cfg.compute_dtype = (compute_dtype if compute_dtype is not None
                                 else _parse_dtype(_env("BIGDL_COMPUTE_DTYPE", "float32")))
            import jax.numpy as jnp
            cfg.param_dtype = jnp.float32

            _STATE.config = cfg
            _STATE.devices = devices
            _STATE.mesh = cls._build_mesh(devices, mesh_shape, mesh_axes)
            _STATE.initialized = True
            _STATE.auto_initialized = False

            from bigdl_tpu.utils.random_generator import RandomGenerator
            RandomGenerator.set_seed(cfg.seed)

            logger.info(
                "Engine initialized: backend=%s processes=%d local_devices=%d mesh=%s",
                cfg.backend, cfg.node_number, cfg.core_number,
                getattr(_STATE.mesh, "shape", None))

    @classmethod
    def _discover_devices_bounded(cls, backend: str | None):
        """Backend discovery under a watchdog. On some deployments TPU runtime
        attach (``jax.devices()`` → PJRT client construction) can hang
        indefinitely; a bare call would freeze every framework entry point with
        no message. Bound it with ``BIGDL_INIT_TIMEOUT`` (seconds, default 120;
        <= 0 disables the watchdog) and fail loudly with a remediation hint."""
        import jax

        timeout = float(_env("BIGDL_INIT_TIMEOUT", "120"))

        def _discover():
            if backend not in ("auto", None):
                return jax.devices(backend)
            return jax.devices()

        if timeout <= 0:
            return _discover()

        result: dict = {}

        def _worker():
            try:
                result["devices"] = _discover()
            except BaseException as e:  # re-raised on the caller thread
                result["error"] = e

        t = threading.Thread(target=_worker, name="bigdl-engine-init", daemon=True)
        t.start()
        t.join(timeout)
        if t.is_alive():
            raise RuntimeError(
                f"Engine.init: backend discovery for {backend!r} did not complete "
                f"within {timeout:.0f}s (BIGDL_INIT_TIMEOUT). The accelerator "
                f"runtime is likely hung or unreachable. Raise BIGDL_INIT_TIMEOUT "
                f"if the backend is just slow to attach, or set JAX_PLATFORMS=cpu "
                f"/ BIGDL_BACKEND=cpu to run on CPU.")
        if "error" in result:
            raise result["error"]
        return result["devices"]

    @classmethod
    def _build_mesh(cls, devices, mesh_shape, mesh_axes):
        import numpy as np
        from jax.sharding import Mesh

        if mesh_shape is None:
            return Mesh(np.asarray(devices), (cls.DATA_AXIS,))
        axes = tuple(mesh_axes) if mesh_axes is not None else tuple(
            [cls.DATA_AXIS, cls.MODEL_AXIS, cls.SEQ_AXIS, cls.PIPE_AXIS][: len(mesh_shape)])
        n = int(np.prod(mesh_shape))
        if n != len(devices):
            raise ValueError(
                f"mesh_shape {tuple(mesh_shape)} needs {n} devices but "
                f"{len(devices)} are available: {devices}")
        arr = np.asarray(devices).reshape(tuple(mesh_shape))
        return Mesh(arr, axes)

    # ---------------------------------------------------------------- access
    @classmethod
    def is_initialized(cls) -> bool:
        return _STATE.initialized

    @classmethod
    def _require_init(cls) -> None:
        if not _STATE.initialized:
            # Auto-init with defaults for ergonomic local use; the reference hard-fails,
            # but on TPU there is no cluster conf that could be mis-detected. A later
            # explicit Engine.init always overrides an auto-init.
            cls.init()
            _STATE.auto_initialized = True

    @classmethod
    def config(cls) -> EngineConfig:
        cls._require_init()
        return _STATE.config

    @classmethod
    def mesh(cls):
        """The default device mesh (1-D ``('data',)`` unless overridden)."""
        cls._require_init()
        return _STATE.mesh

    @classmethod
    def set_mesh(cls, mesh) -> None:
        cls._require_init()
        _STATE.mesh = mesh

    @classmethod
    def devices(cls):
        cls._require_init()
        return _STATE.devices

    @classmethod
    def device_count(cls) -> int:
        """Total devices in the active mesh (the reference's nodeNumber×coreNumber analog)."""
        cls._require_init()
        return int(_STATE.mesh.devices.size)

    @classmethod
    def local_device_count(cls) -> int:
        cls._require_init()
        return _STATE.config.core_number

    @classmethod
    def node_number(cls) -> int:
        cls._require_init()
        return _STATE.config.node_number

    @classmethod
    def compute_dtype(cls):
        cls._require_init()
        return _STATE.config.compute_dtype

    @classmethod
    def set_compute_dtype(cls, dtype) -> None:
        cls._require_init()
        _STATE.config.compute_dtype = dtype

    @classmethod
    def shutdown_distributed(cls, timeout: float | None = None) -> None:
        """Tear down the ``jax.distributed`` client, bounded by ``timeout``
        seconds (default ``BIGDL_INIT_TIMEOUT``) — the shutdown barrier can
        wedge forever when a peer died, which is exactly when survivors need
        to move on. On a clean (or already-dead) shutdown both distributed
        flags clear and a later ``Engine.init(coordinator_address=...)`` may
        bring up a fresh client; on a TIMEOUT the client is considered still
        live and re-init keeps raising — re-exec the process instead."""
        if not (_STATE.distributed_initialized
                or _STATE.distributed_client_live):
            return
        import jax

        if timeout is None:
            timeout = float(_env("BIGDL_INIT_TIMEOUT", "120"))
        result: dict = {}

        def _worker():
            try:
                jax.distributed.shutdown()
            except Exception as e:
                result["error"] = e

        t = threading.Thread(target=_worker, name="bigdl-dist-shutdown",
                             daemon=True)
        t.start()
        t.join(timeout)
        _STATE.distributed_initialized = False
        if t.is_alive():
            logger.error(
                "Engine.shutdown_distributed: jax.distributed.shutdown did "
                "not complete within %.0fs (dead peer wedging the barrier?) — "
                "the client is abandoned but still live; re-init in this "
                "process will refuse. Re-exec to recover cleanly.", timeout)
            return
        if "error" in result:
            # "not running" / mid-teardown errors all mean the same thing for
            # our bookkeeping: no usable client remains
            logger.warning("Engine.shutdown_distributed: %r", result["error"])
        _STATE.distributed_client_live = False
        logger.info("jax.distributed client shut down")

    @classmethod
    def reset(cls) -> None:
        """Tear down for tests. Clears the distributed-init latch so a
        re-``init`` with a coordinator does not silently skip bring-up — but
        the CLIENT liveness flag survives (reset cannot destroy the client);
        re-init while it is live raises, see :meth:`shutdown_distributed`."""
        _STATE.initialized = False
        _STATE.mesh = None
        _STATE.devices = None
        _STATE.distributed_initialized = False
        _STATE.config = EngineConfig()
