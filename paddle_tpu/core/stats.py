"""Event counters, recompile/compile telemetry, memory accounting and the
device-profiler hooks (SURVEY §5 tracing/profiling).

Parity: hl_profiler_start/end (hl_cuda.h:338) maps to jax.profiler traces.
The reference's REGISTER_TIMER* macros (utils/Stat.h) have no analog here:
the intervals they stamped are spans in obs/trace.py's ring (`train.*`,
`pipeline.*`, `compile.*`), recorded without a switch and without a device
sync, and counters at the same boundaries live in obs/metrics.py."""

from __future__ import annotations

import contextlib
import os
import threading
from typing import Dict, Iterator, Optional


# every NAMED EventCounter registers here so the observability plane
# (paddle_tpu/obs/metrics.py) can absorb them behind one read interface
# without touching their hot-path increment cost
EVENT_COUNTERS: Dict[str, "EventCounter"] = {}


class EventCounter:
    """Thread-safe named counters for rare-but-load-bearing runtime events
    (divergence guard trips, feeder retries, pipeline stalls, master
    reconnects). Unconditional — failure telemetry must not hide behind a
    switch.

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

# jax.monitoring's events for the three phases of one compile: a scalar event
# when a phase begins, a time-span event with wall-clock start and end when
# it ends, each with the function's name
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_COMPILE_PHASES = {
    _TRACE_EVENT: "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}


def install_cache_listener() -> bool:
    """jax.monitoring listeners: persistent-compilation-cache hits and
    misses counted into RECOMPILES (events /jax/compilation_cache/
    cache_hits|cache_misses), and every compile's phases recorded as
    `compile.trace` / `compile.lower` / `compile.backend` flight-recorder
    spans (attrs: `fun_name`) with `paddle_tpu_compile_seconds_total{phase}`
    beside them: WHICH function was traced, lowered and compiled (or loaded
    from the persistent cache: `backend` covers both) when, and for how
    long. Only a thread's OUTERMOST compile event is kept: whatever jax
    reports while a trace is open on the same thread — a jit traced inside
    another's trace (every jnp.multiply in a model is one: 4,185 trace
    events for one ResNet-50 step, 3,350 of them under 10 us), or an op run
    eagerly on a concrete value there, with its own lower and backend —
    leaves no span and no seconds of its own, because the outer
    `compile.trace` span covers it. So one thread's spans never overlap and
    the three phases' seconds add up to at most that thread's wall time.
    Idempotent — True only for the call that installed them; importing jax
    here is fine — callers already run under it."""
    global _cache_listener_installed
    if _cache_listener_installed:
        return False
    import jax

    # core reaches UP into obs here, and obs.metrics imports this module: the
    # compile listener lives beside the cache listener (ISSUE 26) and writes
    # to the one span ring and the one registry, so the imports stay local
    # to the install call
    from paddle_tpu.obs import metrics as obs_metrics
    from paddle_tpu.obs import trace

    tracing = threading.local()  # .depth: traces open on this thread

    def _on_event(event: str, **_kw) -> None:
        if event.endswith("/cache_hits"):
            RECOMPILES.cache_hits += 1
        elif event.endswith("/cache_misses"):
            RECOMPILES.cache_misses += 1

    def _on_begin(event: str, _value, **_kw) -> None:
        if event == _TRACE_EVENT:
            tracing.depth = getattr(tracing, "depth", 0) + 1

    def _on_time_span(event: str, start_time: float, end_time: float, **kw) -> None:
        phase = _COMPILE_PHASES.get(event)
        if phase is None:
            return
        if event == _TRACE_EVENT:
            tracing.depth = max(0, getattr(tracing, "depth", 0) - 1)
        if getattr(tracing, "depth", 0):
            return  # inside an outer trace on this thread, which covers it
        t0, t1 = int(start_time * 1e9), int(end_time * 1e9)
        trace.record_flight(
            "compile." + phase, t0, t1, attrs={"fun_name": kw.get("fun_name")}
        )
        obs_metrics.observe_compile(phase, (t1 - t0) * 1e-9)

    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_scalar_listener(_on_begin)
    jax.monitoring.register_event_time_span_listener(_on_time_span)
    _cache_listener_installed = True
    return True


# -- device profiler (hl_profiler_start/end → jax.profiler) -----------------
#
# Idempotent on purpose: jax.profiler raises RuntimeError on a second
# start_trace and on stop without start; a double-wrapped event handler or a
# crashed profiled pass must degrade to a warning, not kill training.

_profiler_active = False


def profiler_options():
    """The device planes and user annotations are what a trace of a pass is
    read for. With jax's defaults (Python tracer on, host tracer at every
    level, each program's HLO copied into the trace) starting the profiler
    stalled the host for 2.3-4.1 s under ResNet-50's step and the traced
    pass read 27-65% device idle where the program runs at 0.006% (PERF.md
    section 6, PR 25; perfbench/harness.py::trace_options has the same
    finding). So: Python tracer off, host tracer at level 1 (user
    TraceAnnotations only, so profile_region() still shows), no HLO copies."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    options.enable_hlo_proto = False
    return options


def profiler_start(logdir: str = "/tmp/paddle_tpu_profile") -> None:
    """Start a jax.profiler trace with profiler_options(). A second start
    while one is active warns and no-ops instead of propagating jax's
    "already started" RuntimeError."""
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
        jax.profiler.start_trace(logdir, profiler_options=profiler_options())
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
