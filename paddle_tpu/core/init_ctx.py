"""Process-level initialization + global flags.

Replaces the reference's gflags surface (paddle/utils/Flags.h:19-43: use_gpu,
trainer_count, trainer_id, num_gradient_servers, ...) and ``paddle.init``
(python/paddle/v2/__init__.py:65 → initPaddle). Here ``trainer_count`` maps to the
data axis of a `jax.sharding.Mesh`; multi-host topology comes from
``jax.distributed.initialize`` (see paddle_tpu/parallel/distributed.py).
"""

from __future__ import annotations

import dataclasses
import logging
import os
from typing import Any, Dict

log = logging.getLogger("paddle_tpu")


@dataclasses.dataclass
class GlobalFlags:
    # Device topology (reference: --use_gpu, --trainer_count; Flags.h:19-43).
    use_tpu: bool = True
    trainer_count: int = 1
    trainer_id: int = 0
    num_hosts: int = 1
    # Logging / stats (reference: --log_period, --show_param_stats_period).
    log_period: int = 100
    show_param_stats_period: int = 0
    # Random seed (reference: --seed).
    seed: int = 0
    # Dtype policy name ("float32" | "bfloat16").
    dtype_policy: str = "float32"
    extras: Dict[str, Any] = dataclasses.field(default_factory=dict)


_flags = GlobalFlags()
_initialized = False


def flags() -> GlobalFlags:
    return _flags


def is_initialized() -> bool:
    return _initialized


# One fixed, git-ignored directory inside the checkout: the path is part of
# jax's cache key, so a cache that moves between runs never hits.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compilation_cache() -> str:
    """Turn on jax's persistent compilation cache and return its directory.

    The directory is placed from OUTSIDE the program: where
    JAX_COMPILATION_CACHE_DIR is set jax reads it itself and nothing here
    touches it; unset, the cache lives at DEFAULT_CACHE_DIR. Repeat runs then
    skip XLA compilation for unchanged programs — tracing still happens, but
    the compile (the dominant cost) is served from disk.

    The min-size/min-compile-time thresholds are zeroed so even the small CPU
    oracle programs cache; cache entries are keyed on serialized HLO + backend
    so a stale entry cannot be served for changed code."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        if jax.config.jax_compilation_cache_dir != DEFAULT_CACHE_DIR:
            jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
            # jax latches its cache object (even a None one, if a compile
            # ran before any dir was configured): a dir change needs an
            # explicit reset or the setting is a no-op
            from jax.experimental.compilation_cache import compilation_cache

            compilation_cache.reset_cache()
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from paddle_tpu.core import stats

    cache_dir = jax.config.jax_compilation_cache_dir
    if stats.install_cache_listener():  # once per process, not per caller
        log.info("persistent compilation cache at %s", cache_dir)
    return cache_dir


def require_tpu() -> None:
    """Raise unless jax's default backend is a TPU or the caller asked for
    the CPU by naming it in JAX_PLATFORMS (the test suite, rehearsals).
    use_tpu means the TPU: a run that asked for it must not silently train
    on whatever backend jax fell back to."""
    import jax

    if "cpu" in (jax.config.jax_platforms or "").split(","):
        return
    platform = jax.default_backend()
    if platform != "tpu":
        raise RuntimeError(
            f"use_tpu is set but jax's default backend is {platform!r}, not "
            "a TPU; to run on the CPU on purpose set JAX_PLATFORMS=cpu or "
            "pass --use_tpu=0"
        )


def init(**kwargs: Any) -> GlobalFlags:
    """paddle.init analog. Accepts the v1 flag names; unknown flags are kept in
    ``extras`` rather than rejected (the reference forwards argv to gflags)."""
    global _initialized
    from paddle_tpu.core import dtypes

    for key, value in kwargs.items():
        if key == "use_gpu":  # v1 compat: GPU flag means "use the accelerator"
            _flags.use_tpu = bool(value)
        elif hasattr(_flags, key) and key != "extras":
            setattr(_flags, key, type(getattr(_flags, key))(value))
        else:
            _flags.extras[key] = value
    if _flags.use_tpu:
        require_tpu()
    dtypes.set_policy(dtypes.get(_flags.dtype_policy))
    enable_compilation_cache()
    if not logging.getLogger().handlers:
        logging.basicConfig(
            level=logging.INFO,
            format="%(asctime)s %(name)s %(levelname)s %(message)s",
        )
    _initialized = True
    return _flags
