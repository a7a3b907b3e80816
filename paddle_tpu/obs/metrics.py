"""Fleet metrics: one registry over every counter the runtime already keeps.

The repo grew its telemetry organically — the
`FT_EVENTS`/`DATA_EVENTS`/`SERVING_EVENTS` EventCounters, `RecompileStats`,
ad-hoc `stats()` dicts on the master/allocator/serving server. This module
puts ONE read path over all of them:

  * `MetricsRegistry` — counter / gauge / histogram primitives for new
    instrumentation, plus `register_collector()` hooks that absorb the
    existing stats objects without moving them (they self-register via
    `stats.EVENT_COUNTERS`; their hot-path increment cost is unchanged).
  * `snapshot()` — a flat {dotted.name: value} dict, small enough to
    piggyback on a master heartbeat; `FleetMetrics` aggregates the
    per-trainer snapshots server-side so `MasterServer.stats()` answers for
    the whole fleet, not one process.
  * `to_prometheus_text()` — the standard exposition format, served by the
    `metrics` RPC on the master and serving servers and by
    `python -m paddle_tpu.obs export`.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, Iterable, List, Mapping, NamedTuple, Optional, Tuple

__all__ = [
    "Counter",
    "FleetMetrics",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "REGISTRY",
    "Sample",
    "aggregate_snapshots",
    "observe_compile",
    "observe_deadline_miss",
    "observe_engine_restart",
    "observe_input_wait",
    "observe_pages_recycled",
    "observe_pipeline_batch",
    "observe_prefix_cow",
    "observe_prefix_evictions",
    "observe_prefix_hit",
    "observe_shed",
    "observe_train_dispatch",
    "snapshot",
    "to_prometheus_text",
]


class Sample(NamedTuple):
    name: str
    mtype: str  # counter | gauge | histogram-derived
    value: float
    labels: Tuple[Tuple[str, str], ...] = ()


def _labels(kw: Mapping[str, Any]) -> Tuple[Tuple[str, str], ...]:
    return tuple(sorted((str(k), str(v)) for k, v in kw.items()))


class Counter:
    """Monotonic counter; one value per label set."""

    mtype = "counter"

    def __init__(self, name: str, help: str = ""):
        self.name, self.help = name, help
        self._lock = threading.Lock()
        self._vals: Dict[tuple, float] = {}

    def inc(self, n: float = 1.0, **labels: Any) -> None:
        key = _labels(labels)
        with self._lock:
            self._vals[key] = self._vals.get(key, 0.0) + n

    def value(self, **labels: Any) -> float:
        with self._lock:
            return self._vals.get(_labels(labels), 0.0)

    def samples(self) -> Iterable[Sample]:
        with self._lock:
            items = list(self._vals.items())
        for key, v in items or [((), 0.0)]:
            yield Sample(self.name, self.mtype, v, key)


class Gauge(Counter):
    """Last-write-wins value; `set()` replaces, `inc()` still adjusts."""

    mtype = "gauge"

    def set(self, v: float, **labels: Any) -> None:
        with self._lock:
            self._vals[_labels(labels)] = float(v)


class Histogram:
    """Fixed-bucket histogram (cumulative counts, Prometheus convention)."""

    mtype = "histogram"
    DEFAULT_BUCKETS = (
        0.001, 0.005, 0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
    )

    def __init__(self, name: str, help: str = "", buckets: Optional[Iterable[float]] = None):
        self.name, self.help = name, help
        self.buckets = tuple(sorted(buckets or self.DEFAULT_BUCKETS))
        self._lock = threading.Lock()
        self._counts = [0] * (len(self.buckets) + 1)  # +inf tail
        self._sum = 0.0
        self._n = 0

    def observe(self, v: float) -> None:
        with self._lock:
            self._sum += v
            self._n += 1
            for i, b in enumerate(self.buckets):
                if v <= b:
                    self._counts[i] += 1
                    return
            self._counts[-1] += 1

    def samples(self) -> Iterable[Sample]:
        with self._lock:
            counts, total, n = list(self._counts), self._sum, self._n
        cum = 0
        for b, c in zip(self.buckets, counts):
            cum += c
            yield Sample(
                f"{self.name}_bucket", "counter", float(cum), (("le", repr(b)),)
            )
        yield Sample(f"{self.name}_bucket", "counter", float(n), (("le", "+Inf"),))
        yield Sample(f"{self.name}_sum", "counter", total)
        yield Sample(f"{self.name}_count", "counter", float(n))


class MetricsRegistry:
    """Named metrics + pluggable collectors over pre-existing stats objects."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: Dict[str, Any] = {}
        self._collectors: List[Callable[[], Iterable[Sample]]] = []

    def _get(self, cls, name: str, help: str, **kw):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = cls(name, help, **kw)
            elif not isinstance(m, cls):
                raise TypeError(
                    f"metric {name!r} already registered as {type(m).__name__}"
                )
            return m

    def counter(self, name: str, help: str = "") -> Counter:
        return self._get(Counter, name, help)

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._get(Gauge, name, help)

    def histogram(self, name: str, help: str = "", buckets=None) -> Histogram:
        return self._get(Histogram, name, help, buckets=buckets)

    def register_collector(self, fn: Callable[[], Iterable[Sample]]) -> None:
        with self._lock:
            if fn not in self._collectors:
                self._collectors.append(fn)

    def collect(self) -> List[Sample]:
        with self._lock:
            metrics = list(self._metrics.values())
            collectors = list(self._collectors)
        out: List[Sample] = []
        for m in metrics:
            out.extend(m.samples())
        for fn in collectors:
            out.extend(fn())
        return out

    def reset(self) -> None:
        with self._lock:
            self._metrics.clear()


def _stats_collector() -> Iterable[Sample]:
    """Absorb core/stats.py state: every registered EventCounter group and
    the recompile/compile-cache telemetry."""
    from paddle_tpu.core import stats

    for group, ec in stats.EVENT_COUNTERS.items():
        for event, n in sorted(ec.as_dict().items()):
            yield Sample(
                "paddle_tpu_events_total", "counter", float(n),
                (("event", event), ("group", group)),
            )
    rc = stats.RECOMPILES
    yield Sample(
        "paddle_tpu_shape_signatures", "gauge", float(rc.total_signatures())
    )
    yield Sample(
        "paddle_tpu_compile_cache_hits_total", "counter", float(rc.cache_hits)
    )
    yield Sample(
        "paddle_tpu_compile_cache_misses_total", "counter",
        float(rc.cache_misses),
    )


def _trace_collector() -> Iterable[Sample]:
    from paddle_tpu.obs import trace

    yield Sample(
        "paddle_tpu_trace_spans_recorded_total", "counter",
        float(trace.TRACER.recorded),
    )
    yield Sample(
        "paddle_tpu_trace_spans_dropped_total", "counter",
        float(trace.TRACER.dropped),
    )


REGISTRY = MetricsRegistry()
REGISTRY.register_collector(_stats_collector)
REGISTRY.register_collector(_trace_collector)


def observe_resize(phase_seconds: Mapping[str, float]) -> None:
    """Record one completed elastic-resize epoch on this process's registry:
    bumps `paddle_tpu_resize_epochs_total` and adds each phase's seconds to
    `paddle_tpu_resize_latency_seconds_total{phase=drain|reshard|resume}`.
    Counters (not gauges) on purpose: trainer heartbeats piggyback
    `snapshot()` and the master sums snapshots key-by-key, so the fleet
    aggregate reads as total epochs and total seconds per phase (mean =
    seconds/epochs) instead of a meaningless summed last-value."""
    REGISTRY.counter(
        "paddle_tpu_resize_epochs_total",
        "completed elastic resize epochs",
    ).inc()
    lat = REGISTRY.counter(
        "paddle_tpu_resize_latency_seconds_total",
        "elastic resize wall-clock by phase",
    )
    for phase, s in phase_seconds.items():
        lat.inc(float(s), phase=phase)


# -- the train loop's and set-up's own time (ISSUE 26) ------------------------
#
# Counters at the boundaries where obs/trace.py's flight-recorder spans are
# stamped (train.input_wait, train.dispatch, pipeline.hostFeed, compile.*),
# fed the spans' own durations: over any interval, input-wait share is a ratio
# of two deltas on a dashboard, without a trace. Counters, so that heartbeat
# snapshots sum across a fleet.


def observe_input_wait(seconds: float) -> None:
    """The train thread waited this long for its next item (one
    `train.input_wait` span)."""
    REGISTRY.counter(
        "paddle_tpu_train_input_wait_seconds_total",
        "seconds the train loop waited for its reader's next item",
    ).inc(seconds)


def observe_train_dispatch() -> None:
    """One device dispatch enqueued (one `train.dispatch` span)."""
    REGISTRY.counter(
        "paddle_tpu_train_dispatches_total",
        "device dispatches enqueued by the train loop",
    ).inc()


def observe_pipeline_batch() -> None:
    """The prefetch worker fed one batch (one `pipeline.hostFeed` span)."""
    REGISTRY.counter(
        "paddle_tpu_pipeline_batches_total",
        "batches fed by the prefetch worker",
    ).inc()


def observe_compile(phase: str, seconds: float) -> None:
    """One phase of one compile finished (one `compile.<phase>` span);
    phase is 'trace', 'lower' or 'backend' (XLA, or the persistent cache
    handing the executable back)."""
    REGISTRY.counter(
        "paddle_tpu_compile_seconds_total",
        "seconds spent tracing, lowering and backend-compiling, by phase",
    ).inc(seconds, phase=phase)


def observe_fused_projection_xent(path: str) -> None:
    """A classification cost from logits was TRACED in a forward other than
    init's (once per cost per trace, never per step, never on an eager
    call; nn/costs._count_xent_path): path is 'fused' where the Network gave
    it its linear projection (ops/xent.linear_softmax_xent), 'unfused' where
    the projection's value is wanted elsewhere and the logits exist."""
    REGISTRY.counter(
        "paddle_tpu_fused_projection_xent_total",
        "classification costs traced, by whether they fused their projection",
    ).inc(path=path)


def observe_attention_decoder_scan() -> None:
    """An attention decoder's teacher-forced scan was TRACED in a forward
    other than init's (once per decoder per trace, never per step;
    nn/attention_layers.AttentionDecoder.forward): the scan whose backward
    forms the encoder's gradient in one contraction after the loop."""
    REGISTRY.counter(
        "paddle_tpu_attention_decoder_scan_total",
        "attention decoder training scans traced",
    ).inc()


def observe_ssm_decode(path: str) -> None:
    """A Mamba-2 layer's one-token recurrence was TRACED into a decode step
    (once per call each time the step is traced, never per step;
    serving/hybrid_moe_lm.HybridMoELM._ssm_decode): path is 'kernel' where
    the Pallas kernel runs it (ops/pallas/ssm_decode.py), 'oracle' where
    `mamba2.ssm_step` does."""
    REGISTRY.counter(
        "paddle_tpu_ssm_decode_total",
        "Mamba-2 one-token recurrences traced, by path (kernel|oracle)",
    ).inc(path=path)


def observe_grouped_matmul(path: str) -> None:
    """One of the routed expert block's two grouped products was TRACED
    (once per product each time a step is traced, never per step;
    serving/moe.expert_block): path is 'kernel' where the Pallas kernel runs
    it (ops/pallas/grouped_matmul.py), 'ragged_dot' where XLA's ragged-dot
    does."""
    REGISTRY.counter(
        "paddle_tpu_grouped_matmul_total",
        "grouped expert products traced, by path (kernel|ragged_dot)",
    ).inc(path=path)


def observe_paged_attention_decode(path: str, window: int) -> None:
    """A layer's decode attention over the page pool was TRACED into a step
    (once per call each time the step is traced, never per step;
    serving/model.PagedLM._paged_attention_local): path is 'kernel' where
    the Pallas kernel runs it (ops/pallas/paged_attention.py), 'oracle'
    where the jnp gather does; window is the layer's (0: the whole
    context)."""
    REGISTRY.counter(
        "paddle_tpu_paged_attention_decode_total",
        "decode attention calls traced, by path (kernel|oracle) and window",
    ).inc(path=path, window=str(int(window)))


# -- serving resilience (ISSUE 10) -------------------------------------------
#
# One naming authority for the serving failure-path counters, so the
# scheduler/session/server increment the same metrics chaos_bench and the
# `metrics` RPC read back. All counters (never gauges): they ride heartbeat
# snapshots and fleet aggregation sums them key-by-key.


def observe_deadline_miss(kind: str) -> None:
    """One request missed a deadline; kind is 'ttft' (first token landed
    late — the client-hedging signal) or 'total' (request cancelled)."""
    REGISTRY.counter(
        "paddle_tpu_serving_deadline_misses_total",
        "serving requests past a deadline, by kind (ttft|total)",
    ).inc(kind=kind)


def observe_shed(reason: str) -> None:
    """One request rejected by load shedding (queue bound, already-expired
    deadline, or load-aware overload check) — the named reason matches the
    QuotaExceeded the caller saw."""
    REGISTRY.counter(
        "paddle_tpu_serving_shed_total",
        "serving requests shed at admission, by named reason",
    ).inc(reason=reason)


def observe_engine_restart(cause: str) -> None:
    """The serving supervisor restarted the decode engine; cause is 'fault'
    (engine thread raised) or 'stall' (no step progress past the watchdog)."""
    REGISTRY.counter(
        "paddle_tpu_serving_engine_restarts_total",
        "serving engine restarts by the session supervisor, by cause",
    ).inc(cause=cause)


def observe_pages_recycled(n: int) -> None:
    """KV pages returned to the free list by a cancellation (deadline expiry
    or client abandonment), as opposed to normal retirement — the leak-watch
    counter the serving chaos drill gates on."""
    REGISTRY.counter(
        "paddle_tpu_serving_pages_recycled_on_cancel_total",
        "KV pages recycled from cancelled (not normally retired) requests",
    ).inc(n)


def observe_preemption() -> None:
    """A dry page pool took a slot back: the request admitted last returned
    to the front of the queue with its tokens (ISSUE 34)."""
    REGISTRY.counter(
        "paddle_tpu_serving_preemptions_total",
        "requests preempted because a running request needed a KV page and none was free",
    ).inc()


def observe_replayed_tokens(n: int) -> None:
    """`n` decode-lane token-steps rebuilt the K/V of preempted requests'
    known tokens: device work that produced no new token."""
    REGISTRY.counter(
        "paddle_tpu_serving_replayed_tokens_total",
        "token-steps spent rebuilding the K/V of preempted requests",
    ).inc(n)


def observe_decode_overlapped() -> None:
    """A decode step was dispatched while the step before it was still
    unfetched (ISSUE 36): beside `serving_decode_steps` the share of steps
    the device ran under the host's work and not beside it."""
    REGISTRY.counter(
        "paddle_tpu_serving_decode_overlapped_steps_total",
        "decode steps dispatched before the previous step's tokens were fetched",
    ).inc()


def observe_wasted_lanes(n: int) -> None:
    """`n` decode lanes' tokens were dropped at the fetch: their requests had
    left their slots (an EOS a step earlier, a cancel, an expiry) after the
    step was dispatched. One lane-step each, never a token."""
    REGISTRY.counter(
        "paddle_tpu_serving_wasted_lane_steps_total",
        "decode lanes dispatched for a request that had already finished or left",
    ).inc(n)


def set_kv_pages_in_use(n: int) -> None:
    """Pages of the KV pool some slot or the prefix index holds right now,
    as of the last engine step."""
    REGISTRY.gauge(
        "paddle_tpu_serving_kv_pages_in_use",
        "KV pool pages held by slots or the prefix index at the last engine step",
    ).set(n)


def observe_layer_passes(phase: str, n: int) -> None:
    """`n` token-layer applications ran (tokens x the layers each passed, a
    looped stack's passes counted each); phase is 'prefill' or 'decode'."""
    REGISTRY.counter(
        "paddle_tpu_serving_layer_passes_total",
        "token-layer applications of the served model, by phase",
    ).inc(n, phase=phase)


def observe_decode_step(slots: int, layer_passes: int) -> None:
    """One decode step advanced `slots` requests by a token each (one
    `serve.decode` span), each through `layer_passes` layer applications."""
    REGISTRY.counter(
        "paddle_tpu_serving_decode_slot_steps_total",
        "slots advanced by decode steps: the batch a step's weights are shared over, summed",
    ).inc(slots)
    observe_layer_passes("decode", slots * layer_passes)


def set_kv_bytes_per_token(n: int) -> None:
    """What one token holds of the page pool, K and V over every cache layer."""
    REGISTRY.gauge(
        "paddle_tpu_serving_kv_bytes_per_token",
        "bytes of the KV page pool one token occupies",
    ).set(n)


def set_recurrent_state_bytes_per_slot(n: int) -> None:
    """What one slot holds beside its pages: the recurrent state a model
    declares (0 for a model whose requests live in pages alone)."""
    REGISTRY.gauge(
        "paddle_tpu_serving_recurrent_state_bytes_per_slot",
        "bytes of per-request state outside the page pool that one slot holds",
    ).set(n)


def observe_moe_counters(name: str, delta) -> None:
    """What the device counted since the last read (ServingSession.
    read_counters; never a decode step's fetch). `moe_expert_tokens`
    [layers, held experts]: tokens assigned, by MoE layer and by the
    expert's place among those this chip holds; `moe_assignments`
    [layers, 2]: assignments that landed on a held expert and that went to
    an absent one."""
    if name == "moe_expert_tokens":
        counter = REGISTRY.counter(
            "paddle_tpu_serving_moe_expert_tokens_total",
            "tokens assigned to each expert this chip holds, by MoE layer",
        )
        for layer, row in enumerate(delta):
            for expert, n in enumerate(row):
                if n:
                    counter.inc(int(n), layer=layer, expert=expert)
    elif name == "moe_assignments":
        counter = REGISTRY.counter(
            "paddle_tpu_serving_moe_assignments_total",
            "router assignments, by whether the expert is held here or absent",
        )
        for where, n in zip(("here", "absent"), delta.sum(0)):
            if n:
                counter.inc(int(n), where=where)


def observe_prefix_hit(pages: int) -> None:
    """An admission aliased `pages` cached prefix pages into a new slot's
    block table (ISSUE 19) — each page is prefill work the request skipped."""
    REGISTRY.counter(
        "paddle_tpu_serving_prefix_pages_shared_total",
        "KV pages aliased from the shared-prefix cache into new slots",
    ).inc(pages)


def observe_prefix_cow(n: int) -> None:
    """Prefix lookups that stopped at a genuine divergence (the chain had
    cached continuations, just not this prompt's) — the copy-on-write
    boundary where the request switches to a private page."""
    REGISTRY.counter(
        "paddle_tpu_serving_prefix_cow_total",
        "prefix-cache lookups ending at a copy-on-write divergence",
    ).inc(n)


def observe_prefix_evictions(n: int) -> None:
    """Unreferenced cached prefix pages LRU-evicted — under pool pressure at
    reserve time, or by the --prefix_cache_pages cap at registration."""
    REGISTRY.counter(
        "paddle_tpu_serving_prefix_evictions_total",
        "prefix-cache pages evicted (pool pressure or cache-size cap)",
    ).inc(n)


# -- router tier (ISSUE 15 multi-replica serving) -----------------------------


def observe_takeover(plane: str) -> None:
    """A warm standby took over a dead control plane (runtime/election.py);
    plane is 'master', 'router' or 'autoscaler'. Paired with the
    `<plane>_takeover` FT_EVENTS key — this is the labeled cross-plane
    counter the HA chaos drill gates on."""
    REGISTRY.counter(
        "paddle_tpu_takeovers_total",
        "control-plane standby takeovers, by plane",
    ).inc(plane=plane)


def observe_replica_evicted(cause: str) -> None:
    """The router evicted a replica lease; cause is 'lease' (heartbeats
    stopped — death or a self-fenced wedge), 'conn' (dispatch/pump
    connections dead), 'deregister' or 'drain_timeout'."""
    REGISTRY.counter(
        "paddle_tpu_router_replica_evictions_total",
        "serving replicas evicted from the router fleet, by cause",
    ).inc(cause=cause)


def observe_replica_failover(cause: str) -> None:
    """One in-flight request re-submitted to a survivor after its replica
    was lost — re-execution is token-identical (pinned per-request seed)."""
    REGISTRY.counter(
        "paddle_tpu_router_failovers_total",
        "in-flight requests failed over to a surviving replica, by cause",
    ).inc(cause=cause)


def observe_router_hedge() -> None:
    """A token-less request past its TTFT hedge was duplicated onto a second
    replica (first token wins, loser cancelled server-side)."""
    REGISTRY.counter(
        "paddle_tpu_router_hedges_total",
        "cross-replica TTFT hedges launched by the router",
    ).inc()


def observe_late_result_dropped() -> None:
    """A partitioned-then-healed replica answered a request the router had
    already failed over: the late winner was dropped by the fleet dedup map
    — the exactly-once counter the router chaos drill gates on."""
    REGISTRY.counter(
        "paddle_tpu_router_late_results_dropped_total",
        "late replica results dropped by the fleet (tenant, request) dedup",
    ).inc()


def observe_router_shed(reason: str) -> None:
    """The router shed a submit fleet-wide ('no_replicas', or 'overload'
    when every live replica shed/was saturated) — always with the tightest
    retry_after_ms any replica offered, never a hang."""
    REGISTRY.counter(
        "paddle_tpu_router_shed_total",
        "submits shed by the router fleet-wide, by named reason",
    ).inc(reason=reason)


def observe_scale_decision(lever: str, direction: str) -> None:
    """The autoscaler admitted one scale action past its hysteresis /
    cooldown / flap gates; lever is 'serving' (spawn/drain) or 'train'
    (resize epoch), direction 'grow' or 'shrink'. Counters (not gauges) on
    purpose: decisions accumulate, and the controller's own process is
    expendable — rates come from deltas, not last-values."""
    REGISTRY.counter(
        "paddle_tpu_autoscaler_decisions_total",
        "autoscaler scale actions admitted, by lever and direction",
    ).inc(lever=lever, direction=direction)


def observe_scale_suppressed(reason: str) -> None:
    """The decision engine wanted an action but a rate-limit gate held it:
    reason is 'startup' (post-restart quiet period), 'cooldown',
    'flap' (direction reversal inside the flap window) or 'backoff'
    (after a rejected/timed-out resize)."""
    REGISTRY.counter(
        "paddle_tpu_autoscaler_suppressed_total",
        "autoscaler actions suppressed by rate-limit gates, by reason",
    ).inc(reason=reason)


def observe_scale_rejected(lever: str) -> None:
    """A pulled lever refused the order (resize rejected by the master's
    one-epoch-at-a-time rule, or timed out) — the backoff trigger."""
    REGISTRY.counter(
        "paddle_tpu_autoscaler_rejected_total",
        "autoscaler lever pulls rejected or timed out, by lever",
    ).inc(lever=lever)


# -- heartbeat snapshots + fleet aggregation ---------------------------------


def _flat_key(s: Sample) -> str:
    if not s.labels:
        return s.name
    return s.name + "{" + ",".join(f"{k}={v}" for k, v in s.labels) + "}"


def snapshot(registry: Optional[MetricsRegistry] = None) -> Dict[str, float]:
    """Flat {key: value} view of every sample — the payload a trainer
    piggybacks on its master heartbeat (a few hundred bytes of line-JSON)."""
    return {
        _flat_key(s): s.value for s in (registry or REGISTRY).collect()
    }


def aggregate_snapshots(snaps: Iterable[Mapping[str, float]]) -> Dict[str, float]:
    """Sum per-trainer snapshots key-by-key. Counters sum exactly; summed
    gauges read as fleet totals (per-trainer values stay visible in the raw
    snapshots a caller can keep)."""
    out: Dict[str, float] = {}
    for snap in snaps:
        for k, v in snap.items():
            try:
                out[k] = out.get(k, 0.0) + float(v)
            except (TypeError, ValueError):
                continue  # a garbled value must not poison the aggregate
    return out


class FleetMetrics:
    """Server-side store of per-trainer heartbeat snapshots (master plane).

    Entries expire after `ttl_s` without a fresh heartbeat (a dead trainer's
    last numbers must not inflate the fleet forever) and are dropped eagerly
    on deregister/eviction alongside the membership lease."""

    def __init__(self, ttl_s: float = 60.0):
        self.ttl_s = float(ttl_s)
        self._lock = threading.Lock()
        self._by_id: Dict[str, Tuple[float, Dict[str, float]]] = {}

    def update(self, trainer_id: str, snap: Mapping[str, Any]) -> None:
        if not trainer_id or not isinstance(snap, Mapping):
            return
        clean = {
            str(k): float(v)
            for k, v in snap.items()
            if isinstance(v, (int, float))
        }
        with self._lock:
            self._by_id[trainer_id] = (time.monotonic(), clean)

    def drop(self, trainer_id: Optional[str]) -> None:
        if not trainer_id:
            return
        with self._lock:
            self._by_id.pop(trainer_id, None)

    def aggregate(self) -> Dict[str, Any]:
        cutoff = time.monotonic() - self.ttl_s
        with self._lock:
            live = {
                tid: snap
                for tid, (seen, snap) in self._by_id.items()
                if seen >= cutoff
            }
        return {
            "reporting_trainers": len(live),
            "counters": aggregate_snapshots(live.values()),
        }


# -- Prometheus exposition ---------------------------------------------------


def _escape(v: str) -> str:
    return v.replace("\\", r"\\").replace('"', r"\"").replace("\n", r"\n")


def _fmt(v: float) -> str:
    """Exposition-format a sample value losslessly: %g truncates to 6
    significant digits, which corrupts large counters (1234567 → 1.23457e+06
    = 1234570) and breaks rate() over long-running servers. Integral values
    print as integers, the rest with full float precision."""
    f = float(v)
    if f.is_integer() and abs(f) < 2**53:
        return str(int(f))
    return repr(f)


def to_prometheus_text(
    registry: Optional[MetricsRegistry] = None,
    fleet: Optional[Mapping[str, Any]] = None,
    extra: Optional[Mapping[str, float]] = None,
) -> str:
    """Render the registry (plus an optional fleet aggregate and flat extra
    gauges) in the Prometheus text exposition format."""
    samples = (registry or REGISTRY).collect()
    by_name: Dict[str, List[Sample]] = {}
    types: Dict[str, str] = {}
    for s in samples:
        by_name.setdefault(s.name, []).append(s)
        types.setdefault(s.name, "counter" if s.mtype == "counter" else s.mtype)
    lines: List[str] = []
    for name in sorted(by_name):
        lines.append(f"# TYPE {name} {types[name]}")
        for s in by_name[name]:
            if s.labels:
                lab = ",".join(f'{k}="{_escape(v)}"' for k, v in s.labels)
                lines.append(f"{name}{{{lab}}} {_fmt(s.value)}")
            else:
                lines.append(f"{name} {_fmt(s.value)}")
    if extra:
        for k, v in sorted(extra.items()):
            lines.append(f"# TYPE {k} gauge")
            lines.append(f"{k} {_fmt(v)}")
    if fleet:
        n = int(fleet.get("reporting_trainers", 0) or 0)
        lines.append("# TYPE paddle_tpu_fleet_reporting_trainers gauge")
        lines.append(f"paddle_tpu_fleet_reporting_trainers {n}")
        counters = fleet.get("counters") or {}
        if counters:
            lines.append("# TYPE paddle_tpu_fleet gauge")
            for k, v in sorted(counters.items()):
                lines.append(
                    f'paddle_tpu_fleet{{key="{_escape(str(k))}"}} '
                    f"{_fmt(v)}"
                )
    return "\n".join(lines) + "\n"
