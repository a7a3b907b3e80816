"""Unified observability plane (ISSUE 7): tracing, metrics, profiling.

Three pillars over every subsystem (trainer, data pipeline, master RPC
plane, serving):

  * ``obs.trace``   — structured spans in a bounded per-process ring buffer:
                      the train loop, the prefetch worker and every compile
                      always record (a flight recorder); RPC, serving and
                      router spans are near-zero cost unless PADDLE_TPU_TRACE
                      is set; trace context piggybacked on the RPC frames,
                      exported as Perfetto-loadable Chrome trace-event JSON.
  * ``obs.metrics`` — counter/gauge/histogram registry absorbing the
                      existing EventCounter telemetry; trainer
                      snapshots ride on master heartbeats into a fleet-wide
                      aggregate; Prometheus text via the `metrics` RPC and
                      ``python -m paddle_tpu.obs export``.
  * ``obs.profile`` — ``--profile pass:N`` jax.profiler capture of one pass
                      plus per-executable HLO cost buckets (the ROADMAP
                      item-2 target list) in the bench JSON.

README "Observability" has the operator-facing walkthrough."""

from paddle_tpu.obs import metrics, trace  # noqa: F401
from paddle_tpu.obs.metrics import REGISTRY  # noqa: F401
from paddle_tpu.obs.trace import (  # noqa: F401
    TRACER,
    enable_tracing,
    export_chrome,
    flight,
    record_span,
    span,
)

__all__ = [
    "REGISTRY",
    "TRACER",
    "enable_tracing",
    "export_chrome",
    "flight",
    "metrics",
    "record_span",
    "span",
    "trace",
]
