"""Continuous-batching inference serving runtime (ISSUE 6 / ROADMAP item 1).

The long-lived serving layer over the generation stack: a `ServingSession`
owns device state across requests (params loaded once, one compiled decode
program shared by every mixed-length request via a paged KV cache), a
scheduler forms dynamic batches at decode-step boundaries, admission control
and per-tenant quotas guard the front door, and a TCP front-end reuses the
master's line-JSON request-routing plane.

    from paddle_tpu.serving import make_demo_session
    s = make_demo_session(max_slots=8)
    h = s.submit([1, 5, 9], max_new_tokens=16)
    s.run_until_idle()
    print(h.result())

CLI: `python -m paddle_tpu serve` (README "Serving")."""

from paddle_tpu.serving.hybrid_moe_lm import HybridMoEConfig, HybridMoELM
from paddle_tpu.serving.kv_cache import PagedKVCache
from paddle_tpu.serving.looped_lm import LoopedLM, LoopedLMConfig, load_checkpoint
from paddle_tpu.serving.model import LMConfig, PagedLM, ServableLM
from paddle_tpu.serving.quota import QuotaExceeded, TenantQuotas
from paddle_tpu.serving.scheduler import (
    FinishReason,
    RequestHandle,
    Scheduler,
)
from paddle_tpu.serving.session import (
    SERVING_EVENTS,
    ServingSession,
    make_demo_session,
)
from paddle_tpu.serving.fleet import FleetView, Replica, ReplicaAgent
from paddle_tpu.serving.router import Router, RouterHandle, RouterServer

__all__ = [
    "PagedKVCache",
    "HybridMoEConfig",
    "HybridMoELM",
    "LMConfig",
    "LoopedLM",
    "LoopedLMConfig",
    "PagedLM",
    "ServableLM",
    "load_checkpoint",
    "QuotaExceeded",
    "TenantQuotas",
    "FinishReason",
    "RequestHandle",
    "Scheduler",
    "SERVING_EVENTS",
    "ServingSession",
    "make_demo_session",
    "FleetView",
    "Replica",
    "ReplicaAgent",
    "Router",
    "RouterHandle",
    "RouterServer",
]
