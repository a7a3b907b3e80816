"""Timers + profiler hooks (SURVEY §5 tracing/profiling).

Parity: utils/Stat.h:63 StatSet / :114 Stat / :189 TimerOnce and the
REGISTER_TIMER* macros (:215-224) that the hot loop stamps
(TrainerInternal.cpp:94-152, per-layer timers NeuralNetwork.cpp:258/298);
hl_profiler_start/end (hl_cuda.h:338) maps to jax.profiler traces.

Gating: the reference compiles timers out unless WITH_TIMER=ON; here the
equivalent is the PADDLE_TPU_TIMER env var / enable_timers() — disabled
timers cost one dict lookup and a truth test."""

from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Dict, Iterator, Optional


class Stat:
    """Accumulates wall time + call count for one named timer (Stat.h:114)."""

    __slots__ = ("name", "total", "count", "max")

    def __init__(self, name: str):
        self.name = name
        self.total = 0.0
        self.count = 0
        self.max = 0.0

    def add(self, seconds: float) -> None:
        self.total += seconds
        self.count += 1
        if seconds > self.max:
            self.max = seconds

    def __repr__(self):
        avg = self.total / max(self.count, 1)
        return (
            f"{self.name}: total={self.total * 1e3:.2f}ms count={self.count} "
            f"avg={avg * 1e3:.3f}ms max={self.max * 1e3:.3f}ms"
        )


class StatSet:
    """Global registry of Stats (Stat.h:63 StatSet + BarrierStatSet)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._stats: Dict[str, Stat] = {}
        self.enabled = os.environ.get("PADDLE_TPU_TIMER", "").lower() not in (
            "", "0", "false", "off",
        )

    def get(self, name: str) -> Stat:
        with self._lock:
            s = self._stats.get(name)
            if s is None:
                s = self._stats[name] = Stat(name)
            return s

    def reset(self) -> None:
        with self._lock:
            self._stats.clear()

    def report(self) -> str:
        # deterministic order (total desc, then name) and a percent-of-total
        # column, so timer splits are diffable across bench runs — equal
        # totals no longer land in dict-insertion order
        with self._lock:
            stats = sorted(self._stats.values(), key=lambda s: (-s.total, s.name))
        grand = sum(s.total for s in stats)
        lines = ["======= StatSet: [GlobalStatInfo] status ======"]
        lines += [
            f"  {s!r} ({100.0 * s.total / grand if grand else 0.0:5.1f}%)"
            for s in stats
        ]
        return "\n".join(lines)

    def as_dict(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {
                n: {"total_ms": s.total * 1e3, "count": s.count, "max_ms": s.max * 1e3}
                for n, s in self._stats.items()
            }


GLOBAL_STATS = StatSet()


def enable_timers(on: bool = True) -> None:
    GLOBAL_STATS.enabled = on


# every NAMED EventCounter registers here so the observability plane
# (paddle_tpu/obs/metrics.py) can absorb them behind one read interface
# without touching their hot-path increment cost
EVENT_COUNTERS: Dict[str, "EventCounter"] = {}


class EventCounter:
    """Thread-safe named counters for rare-but-load-bearing runtime events
    (divergence guard trips, feeder retries, pipeline stalls, master
    reconnects). Unlike Stat these are unconditional — failure telemetry must
    not hide behind PADDLE_TPU_TIMER.

    A `name` registers the counter group in EVENT_COUNTERS for the metrics
    exporter; anonymous counters stay private."""

    def __init__(self, name: Optional[str] = None):
        self._lock = threading.Lock()
        self._counts: Dict[str, int] = {}
        self.name = name
        if name:
            EVENT_COUNTERS[name] = self

    def incr(self, name: str, n: int = 1) -> int:
        with self._lock:
            self._counts[name] = self._counts.get(name, 0) + n
            return self._counts[name]

    def get(self, name: str) -> int:
        with self._lock:
            return self._counts.get(name, 0)

    def as_dict(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counts)

    def reset(self) -> None:
        with self._lock:
            self._counts.clear()


# fault-tolerance event counters (trainer divergence guard — incremented at
# guard POLLS by the device counter's delta, so one entry may cover a whole
# guard_check_every window — pipeline retries/stalls, master client
# reconnects/failovers, trainer-lease evictions, lost task acks, preemption
# drains, standby takeovers)
FT_EVENTS = EventCounter("ft")

# data-path events that are normal but worth counting: `padded_batches`
# (trailing batches padded to the mesh data-axis multiple instead of
# dropped — trainer + DevicePrefetcher increment it per padded batch)
DATA_EVENTS = EventCounter("data")


# -- memory / collective byte accounting (ISSUE 5 observability) -------------
#
# The sharded-update claims ("opt state 1/N per chip", "collective bytes cut
# 2-4x") are backed by numbers, not vibes: per-chip resident bytes come from
# sharding metadata (no device sync, usable at pass end inside the hot-loop
# discipline), HBM peaks from the backend's memory_stats() where the platform
# exposes it (TPU; CPU returns None and callers fall back to tree sizes).


def per_chip_tree_bytes(tree) -> int:
    """Bytes one chip holds for `tree`: per-leaf shard size from sharding
    metadata (replicated leaves count fully, P('data')-sharded leaves count
    1/N). Pure metadata — never fetches or syncs device buffers."""
    import jax
    import numpy as np

    total = 0
    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.Array):
            try:
                shard = leaf.sharding.shard_shape(leaf.shape)
            except Exception:  # uncommitted/fully-replicated fallback
                shard = leaf.shape
            total += int(np.prod(shard, dtype=np.int64)) * leaf.dtype.itemsize
        else:
            total += np.asarray(leaf).nbytes
    return total


def device_memory_stats() -> Dict[str, int]:
    """`jax.local_devices()[0].memory_stats()` where the backend implements
    it (TPU: bytes_in_use / peak_bytes_in_use / ...), else {} — callers use
    per_chip_tree_bytes as the portable fallback."""
    import jax

    try:
        stats = jax.local_devices()[0].memory_stats()
    except Exception:
        return {}
    if not stats:
        return {}
    return {k: int(v) for k, v in stats.items() if isinstance(v, (int, float))}

# Timer names stamped by the async execution runtime (PADDLE_TPU_TIMER):
#   hostFeed / h2d        input-pipeline legs (trainer or prefetcher worker)
#   forwardBackward       the device-step segment (syncs only when timing on)
#   ckptFetch             non-blocking device→host snapshot copy (train thread)
#   ckptWrite             npz/CRC/v1/retention on the async writer thread


# -- recompile / input-pipeline telemetry ------------------------------------
#
# Every distinct batch-shape signature traces and compiles the jitted step
# again (SURVEY §7 hard-part (2): XLA recompiles per shape). The trainer
# records one signature per batch; the counter exposes per-pass and all-time
# distinct counts and warns once when shape churn crosses a threshold —
# the usual culprit is a missing/too-fine `seq_bucket` on a sequence slot.


def batch_signature(batch) -> tuple:
    """Hashable shape/dtype signature of a feed-ready batch dict — the same
    information XLA keys its compiled-executable cache on."""
    import numpy as np

    return tuple(
        sorted(
            (k, tuple(np.shape(v)), str(getattr(v, "dtype", type(v).__name__)))
            for k, v in batch.items()
        )
    )


class RecompileStats:
    """Counts distinct batch-shape signatures (== step recompiles) plus
    persistent-compilation-cache hits/misses reported by jax.monitoring."""

    def __init__(self, warn_threshold: int = 0):
        self._lock = threading.Lock()
        self._all: set = set()
        self._pass: set = set()
        self._warned = False
        self.cache_hits = 0
        self.cache_misses = 0
        self.warn_threshold = warn_threshold or int(
            os.environ.get("PADDLE_TPU_SHAPE_WARN", "8")
        )

    def record(self, signature: tuple) -> bool:
        """Record one batch signature; True when it is new this pass (i.e.
        the compiled step for it was not yet built this pass)."""
        with self._lock:
            new = signature not in self._pass
            self._pass.add(signature)
            self._all.add(signature)
            n = len(self._pass)
            should_warn = (
                new and not self._warned and n == self.warn_threshold
            )
            if should_warn:
                self._warned = True
        if should_warn:
            import logging

            logging.getLogger("paddle_tpu.stats").warning(
                "input pipeline produced %d distinct batch shapes this pass; "
                "each one recompiles the train step — check seq_bucket / "
                "batch-size settings for shape churn", n,
            )
        return new

    def start_pass(self) -> None:
        with self._lock:
            self._pass = set()

    def pass_signatures(self) -> int:
        with self._lock:
            return len(self._pass)

    def total_signatures(self) -> int:
        with self._lock:
            return len(self._all)

    def reset(self) -> None:
        with self._lock:
            self._all = set()
            self._pass = set()
            self._warned = False
            self.cache_hits = 0
            self.cache_misses = 0

    def report(self) -> str:
        return (
            f"shape signatures: pass={self.pass_signatures()} "
            f"total={self.total_signatures()} "
            f"persistent-cache hits={self.cache_hits} "
            f"misses={self.cache_misses}"
        )


RECOMPILES = RecompileStats()

_cache_listener_installed = False


def install_cache_listener() -> bool:
    """Count persistent-compilation-cache hits/misses into RECOMPILES via
    jax.monitoring (events /jax/compilation_cache/cache_hits|cache_misses).
    Idempotent — True only for the call that installed it; importing jax
    here is fine — callers already run under it."""
    global _cache_listener_installed
    if _cache_listener_installed:
        return False
    import jax

    def _on_event(event: str, **_kw) -> None:
        if event.endswith("/cache_hits"):
            RECOMPILES.cache_hits += 1
        elif event.endswith("/cache_misses"):
            RECOMPILES.cache_misses += 1

    jax.monitoring.register_event_listener(_on_event)
    _cache_listener_installed = True
    return True


@contextlib.contextmanager
def timer(name: str) -> Iterator[None]:
    """REGISTER_TIMER_INFO analog: `with timer("forwardBackward"): ...`."""
    if not GLOBAL_STATS.enabled:
        yield
        return
    t0 = time.perf_counter()
    try:
        yield
    finally:
        GLOBAL_STATS.get(name).add(time.perf_counter() - t0)


class TimerOnce:
    """Stat.h:189 TimerOnce: manual start/stop object form."""

    def __init__(self, name: str):
        self.name = name
        self._t0: Optional[float] = None

    def start(self) -> "TimerOnce":
        self._t0 = time.perf_counter()
        return self

    def stop(self) -> None:
        if self._t0 is not None and GLOBAL_STATS.enabled:
            GLOBAL_STATS.get(self.name).add(time.perf_counter() - self._t0)
        self._t0 = None


# -- device profiler (hl_profiler_start/end → jax.profiler) -----------------
#
# Idempotent on purpose: jax.profiler raises RuntimeError on a second
# start_trace and on stop without start; a double-wrapped event handler or a
# crashed profiled pass must degrade to a warning, not kill training.

_profiler_active = False


def profiler_start(logdir: str = "/tmp/paddle_tpu_profile") -> None:
    """Start a jax.profiler trace. A second start while one is active warns
    and no-ops instead of propagating jax's "already started" RuntimeError."""
    global _profiler_active
    import logging

    import jax

    if _profiler_active:
        logging.getLogger("paddle_tpu.stats").warning(
            "profiler_start: a trace is already active — ignoring the "
            "second start (stop the first with profiler_stop())"
        )
        return
    try:
        jax.profiler.start_trace(logdir)
    except RuntimeError as e:
        # started outside our bookkeeping (e.g. by user code calling jax
        # directly); adopt it so profiler_stop() still works
        logging.getLogger("paddle_tpu.stats").warning(
            "profiler_start: jax reports a trace already running (%s); "
            "adopting it", e,
        )
    _profiler_active = True


def profiler_stop() -> None:
    """Stop the active trace; a stop without a start is a silent no-op."""
    global _profiler_active
    import jax

    if not _profiler_active:
        return
    try:
        jax.profiler.stop_trace()
    finally:
        _profiler_active = False


@contextlib.contextmanager
def profile_region(name: str) -> Iterator[None]:
    """Named trace annotation inside a profiler capture."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield
