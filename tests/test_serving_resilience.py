"""Serving resilience (ISSUE 10).

The load-bearing claims, each tested directly:

  * deadlines — a request past its total-latency deadline is cancelled with
    the NAMED reason 'deadline' whether it is still queued or mid-decode,
    and its KV pages return to the free list the same step; TTFT-deadline
    misses are counted (the client-hedging signal) but never fatal;
  * overload shedding — admission rejects a request whose estimated queue
    wait exceeds its deadline budget ('overload', with a `retry_after_ms`
    hint) and a full queue ('queue') instead of queueing doomed work;
  * client abandonment — `result(timeout=)` expiring CANCELS the request
    server-side (reason 'client_timeout'), closing the classic leak where
    the client raises but the request keeps decoding and holding pages;
  * engine crash recovery — for every seeded fault site (decode_raise,
    engine_stall, page_exhaust) the supervisor restarts the engine,
    re-initializes the page pool and replays in-flight prompts so the run is
    RESULT-TRANSPARENT (same tokens as unfaulted) with zero page leak; past
    the restart budget every outstanding request fails 'engine_error';
  * hedged retry — `ServingClient.generate(hedge_ttft_s=)` re-submits under
    the same idempotency key after a TTFT miss and the server dedup
    guarantees exactly ONE engine execution per request id;
  * incremental poll — tokens generated so far ride every poll reply (the
    first step toward streaming delivery).

Deadline/cancellation unit tests drive the engine inline and pass explicit
`now` timestamps to step() — no sleeps, fully deterministic; the supervisor
tests run the real engine thread under seeded faults."""

import threading
import time

import pytest

from paddle_tpu.core import faults

pytestmark = [pytest.mark.serving, pytest.mark.chaos]

VOCAB = 96

NAMED_REASONS = {
    "eos", "length", "deadline", "cancelled", "client_timeout",
    "engine_error",
}


@pytest.fixture(scope="module")
def model_and_params():
    import jax

    from paddle_tpu.serving.model import LMConfig, ServableLM

    model = ServableLM(
        LMConfig(vocab=VOCAB, n_layers=2, d_model=32, n_heads=2, max_len=96)
    )
    return model, model.init_params(jax.random.PRNGKey(0))


def make_session(model_and_params, **kw):
    from paddle_tpu.serving.session import ServingSession

    model, params = model_and_params
    kw.setdefault("max_slots", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_buckets", (8, 16, 32))
    kw.setdefault("max_new_limit", 16)
    return ServingSession(model, params, **kw)


PROMPTS = [
    [1, 5, 9, 11],
    [1, 7],
    [1, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14],
    [1, 40, 41, 42, 43, 44, 45, 46],
]


# -- deadlines ----------------------------------------------------------------


def test_deadline_expires_in_queue(model_and_params):
    """A queued request past its deadline is reaped at the next step
    boundary with the named reason — before it ever costs a prefill."""
    s = make_session(model_and_params)
    total_free = s.cache.free_pages
    h = s.submit(PROMPTS[0], 8, deadline_s=5.0)
    misses0 = s.scheduler.deadline_misses
    s.step(h.t_deadline + 0.001)  # simulated clock: past the deadline
    assert h.done and h.status == h.CANCELLED
    assert h.finish_reason == "deadline"
    assert s.scheduler.deadline_misses == misses0 + 1
    assert s.cache.free_pages == total_free, "nothing was ever reserved"
    with pytest.raises(RuntimeError, match="deadline"):
        h.result()


def test_deadline_expires_mid_decode_recycles_pages(model_and_params):
    """A RUNNING request whose deadline passes is retired at the step
    boundary and its reserved KV pages return to the free list THAT step."""
    s = make_session(model_and_params)
    total_free = s.cache.free_pages
    h = s.submit(PROMPTS[2], 16, deadline_s=30.0)
    s.step()  # admit + prefill: pages now reserved
    assert h.status == h.RUNNING and s.cache.free_pages < total_free
    recycled0 = s.scheduler.pages_recycled_on_cancel
    s.step(h.t_deadline + 0.001)
    assert h.done and h.finish_reason == "deadline"
    assert len(h.tokens) < 16, "cancelled mid-decode, not run to budget"
    assert s.cache.free_pages == total_free, "pages must recycle on expiry"
    assert s.scheduler.pages_recycled_on_cancel > recycled0


def test_ttft_deadline_miss_counted_not_fatal(model_and_params):
    """TTFT is a *hedging signal*: a late first token increments the miss
    counter but the request still runs to a normal completion."""
    from paddle_tpu.serving.session import SERVING_EVENTS

    s = make_session(model_and_params)
    before = SERVING_EVENTS.get("serving_ttft_deadline_missed")
    # a freshly-jitted prefill takes far longer than 1ms, so the first
    # token is guaranteed late
    h = s.submit(PROMPTS[0], 4, ttft_deadline_s=1e-3)
    s.run_until_idle()
    assert h.done and h.status == h.DONE
    assert h.finish_reason in ("length", "eos")
    assert SERVING_EVENTS.get("serving_ttft_deadline_missed") == before + 1


def test_deadline_defaults_resolve_tenant_then_session(model_and_params):
    """Resolution order: explicit per-request value > tenant quota default >
    session-wide default; None all the way down = no deadline."""
    from paddle_tpu.serving.quota import TenantQuotas

    quotas = TenantQuotas(max_concurrent=8, default_deadline_s=7.0)
    quotas.set_quota("gold", deadline_s=3.0, ttft_deadline_s=0.5)
    s = make_session(model_and_params, quotas=quotas)
    gold = s.submit(PROMPTS[0], 2, tenant="gold")
    assert abs((gold.t_deadline - gold.t_submit) - 3.0) < 0.25
    assert abs((gold.t_ttft_deadline - gold.t_submit) - 0.5) < 0.25
    other = s.submit(PROMPTS[1], 2, tenant="other")
    assert abs((other.t_deadline - other.t_submit) - 7.0) < 0.25
    explicit = s.submit(PROMPTS[1], 2, tenant="gold", deadline_s=1.0)
    assert abs((explicit.t_deadline - explicit.t_submit) - 1.0) < 0.25

    s2 = make_session(model_and_params, default_deadline_s=2.0)
    sess_default = s2.submit(PROMPTS[0], 2)
    assert abs((sess_default.t_deadline - sess_default.t_submit) - 2.0) < 0.25
    none = make_session(model_and_params).submit(PROMPTS[0], 2)
    assert none.t_deadline is None and none.t_ttft_deadline is None


# -- overload shedding --------------------------------------------------------


def test_admission_sheds_doomed_request_with_retry_hint(model_and_params):
    """Load-aware admission: when the wait estimate says the deadline budget
    cannot be met, the request is shed at the front door with the named
    reason 'overload' and a retry_after_ms hint — not queued to die."""
    from paddle_tpu.serving.quota import QuotaExceeded

    s = make_session(model_and_params)
    s.scheduler._ewma_service_s = 1.0  # observed: one request takes ~1s
    shed0 = s.scheduler.shed
    with pytest.raises(QuotaExceeded) as ei:
        s.submit(PROMPTS[0], 8, deadline_s=0.5)
    assert ei.value.reason == "overload"
    assert ei.value.retry_after_ms >= 500
    assert s.scheduler.shed == shed0 + 1
    # an already-expired deadline is its own named reason
    with pytest.raises(QuotaExceeded) as ei:
        s.submit(PROMPTS[0], 8, deadline_s=0.0)
    assert ei.value.reason == "deadline"
    # no deadline -> no load gate: the same request is admitted
    h = s.submit(PROMPTS[0], 8)
    assert h.status == h.QUEUED
    h.cancel()


def test_ttft_budget_compared_to_queue_wait_not_completion(model_and_params):
    """A TTFT deadline shorter than one service time must NOT shed on an
    idle server (TTFT ≈ queue wait, which is 0 there — the 'counted, never
    fatal' contract); it DOES shed once a queue actually stands between the
    request and its first token."""
    from paddle_tpu.serving.quota import QuotaExceeded

    s = make_session(model_and_params)
    s.scheduler._ewma_service_s = 1.0
    h = s.submit(PROMPTS[0], 8, ttft_deadline_s=0.5)  # idle: admitted
    assert h.status == h.QUEUED
    # an already-expired TTFT budget still admits (it only counts a miss)
    h2 = s.submit(PROMPTS[0], 8, ttft_deadline_s=0.0)
    assert h2.status == h2.QUEUED
    # ~3 waves of queue now stand ahead -> est queue wait > 0.5s -> shed
    for _ in range(3 * s.cache.max_slots):
        s.submit(PROMPTS[1], 2)
    with pytest.raises(QuotaExceeded) as ei:
        s.submit(PROMPTS[0], 8, ttft_deadline_s=0.5)
    assert ei.value.reason == "overload"


def test_queue_bound_shed_carries_retry_hint(model_and_params):
    from paddle_tpu.serving.quota import QuotaExceeded

    s = make_session(model_and_params, max_queue=2)
    s.scheduler.submit([1, 2], 2, "x")
    s.scheduler.submit([1, 2], 2, "x")
    with pytest.raises(QuotaExceeded) as ei:
        s.scheduler.submit([1, 2], 2, "x")
    assert ei.value.reason == "queue"
    assert ei.value.retry_after_ms is not None and ei.value.retry_after_ms >= 1


# -- client abandonment (the satellite fix) -----------------------------------


def test_result_timeout_cancels_server_side(model_and_params):
    """The pre-ISSUE-10 leak: result(timeout=) raised client-side while the
    request kept decoding and holding KV pages. Now the expiry cancels the
    request — queued ones immediately, running ones at the next step
    boundary with their pages recycled."""
    s = make_session(model_and_params)
    total_free = s.cache.free_pages

    # queued: cancelled inline, nothing was reserved
    q = s.submit(PROMPTS[0], 8)
    with pytest.raises(TimeoutError, match="cancelled server-side"):
        q.result(timeout=0.01)
    assert q.done and q.status == q.CANCELLED
    assert q.finish_reason == "client_timeout"

    # running: pages reserved at admission must come back at the boundary
    r = s.submit(PROMPTS[2], 16)
    s.step()
    assert r.status == r.RUNNING and s.cache.free_pages < total_free
    recycled0 = s.scheduler.pages_recycled_on_cancel
    with pytest.raises(TimeoutError):
        r.result(timeout=0.01)
    assert not r.done, "a running request retires at the boundary, not mid-step"
    s.step()
    assert r.done and r.finish_reason == "client_timeout"
    assert s.cache.free_pages == total_free
    assert s.scheduler.pages_recycled_on_cancel > recycled0

    # opt-out keeps the old semantics for callers that poll later
    keep = s.submit(PROMPTS[1], 8)
    with pytest.raises(TimeoutError):
        keep.result(timeout=0.01, cancel_on_timeout=False)
    assert not keep.done and keep.status == keep.QUEUED
    s.run_until_idle()
    assert keep.done and keep.status == keep.DONE


# -- incremental poll ---------------------------------------------------------


def test_poll_returns_tokens_so_far(model_and_params):
    """Every poll of an unfinished request delivers the tokens generated so
    far — prefix-consistent across polls (streaming's first step)."""
    from paddle_tpu.serving.server import ServingServer

    s = make_session(model_and_params)
    srv = ServingServer(session=s)
    try:
        rid = srv.dispatch(
            "submit", {"prompt": PROMPTS[0], "max_new_tokens": 6}, None
        )["request_id"]
        s.step()  # prefill -> first token
        p1 = srv.dispatch("poll", {"request_id": rid}, None)
        assert not p1["done"]
        assert p1["tokens"] and len(p1["tokens"]) == p1["tokens_so_far"]
        s.step()
        p2 = srv.dispatch("poll", {"request_id": rid}, None)
        assert len(p2["tokens"]) > len(p1["tokens"])
        assert p2["tokens"][: len(p1["tokens"])] == p1["tokens"]
        s.run_until_idle()
        done = srv.dispatch("poll", {"request_id": rid}, None)
        assert done["done"] and done["finish_reason"] in ("length", "eos")
        assert done["tokens"][: len(p2["tokens"])] == p2["tokens"]
    finally:
        srv.stop()


def test_cancel_rpc(model_and_params):
    from paddle_tpu.serving.server import ServingServer

    s = make_session(model_and_params)
    srv = ServingServer(session=s)
    try:
        rid = srv.dispatch(
            "submit", {"prompt": PROMPTS[0], "max_new_tokens": 6}, None
        )["request_id"]
        r = srv.dispatch("cancel", {"request_id": rid}, None)
        assert r["cancelled"] is True
        p = srv.dispatch("poll", {"request_id": rid}, None)
        assert p["done"] and p["cancelled"] and p["finish_reason"] == "cancelled"
        # idempotent once finished
        again = srv.dispatch("cancel", {"request_id": rid}, None)
        assert again["cancelled"] is False and again["done"] is True
    finally:
        srv.stop()


# -- engine crash recovery ----------------------------------------------------


@pytest.mark.timeout(120)
@pytest.mark.parametrize(
    "site,spec",
    [
        ("decode_raise", "decode_raise:step=3"),
        ("engine_stall", "engine_stall:step=2"),
        ("page_exhaust", "page_exhaust:step=0"),
    ],
)
def test_engine_recovery_result_transparent_zero_leak(
    model_and_params, site, spec, monkeypatch
):
    """The acceptance bits, per seeded fault site: the supervisor restarts
    the engine, every accepted request finishes with a NAMED reason and the
    SAME tokens as an unfaulted run (replay is result-transparent), and the
    page free list is whole afterwards."""
    monkeypatch.setenv("PADDLE_TPU_SERVING_STALL_S", "1")

    clean = make_session(model_and_params)
    ref_handles = [clean.submit(p, 8) for p in PROMPTS]
    clean.run_until_idle()
    ref = [h.tokens for h in ref_handles]

    s = make_session(
        model_and_params, engine_stall_timeout_s=0.3, engine_restart_max=5
    )
    total_free = s.cache.free_pages
    with faults.inject(spec, seed=0) as inj:
        s.serve_forever()
        handles = [s.submit(p, 8, deadline_s=60.0) for p in PROMPTS]
        deadline = time.monotonic() + 90
        for h in handles:
            assert h._event.wait(max(0.1, deadline - time.monotonic())), (
                f"request {h.request_id} never completed after {site}"
            )
        fired = dict(inj.fired)
    s.stop()
    assert fired.get(site, 0) >= 1, "the seeded fault must actually fire"
    assert s.engine_restarts >= 1, "the supervisor must have recovered"
    assert all(h.finish_reason in NAMED_REASONS for h in handles)
    assert [h.tokens for h in handles] == ref, (
        "replayed greedy decode must be result-transparent"
    )
    assert s.cache.free_pages == total_free, "zero page leak after recovery"


def test_a_fault_with_a_step_in_flight_recovers_token_exact(model_and_params):
    """ISSUE 36: the engine faults with a decode step dispatched and its
    tokens unfetched. The recovery ends that step first (a request it
    finished is complete and is not replayed), replays the others from
    their prompts, sampled ones through the same draws, and every handle
    ends token-exact with nothing left in flight and no page leaked."""
    import logging

    plan = [
        (PROMPTS[0], 8, {}),
        (PROMPTS[1], 3, {}),  # prefill + two decode steps: done by the step in flight
        (PROMPTS[2], 8, dict(temperature=0.8, top_k=20, seed=7)),
        (PROMPTS[3], 8, dict(temperature=0.8, top_k=20, seed=11)),
    ]
    clean = make_session(model_and_params)
    ref_handles = [clean.submit(p, n, **kw) for p, n, kw in plan]
    clean.run_until_idle()
    ref = [h.tokens for h in ref_handles]

    s = make_session(model_and_params)
    total_free = s.cache.free_pages
    handles = [s.submit(p, n, **kw) for p, n, kw in plan]
    s.step()
    s.step()  # the second decode step is dispatched, its tokens unfetched
    assert s._in_flight is not None and len(handles[1].tokens) == 2
    with faults.inject("decode_raise:1.0", seed=0):
        with pytest.raises(Exception) as fault:
            s.step()
    assert s._in_flight is not None, "the fault left the step in flight"
    s._recover("fault", fault.value, logging.getLogger("paddle_tpu.serving"))
    assert s._in_flight is None
    assert handles[1].done and handles[1].tokens == ref[1], (
        "what the step in flight finished is complete, not replayed"
    )
    assert all(h.tokens == [] for h in handles if not h.done)  # replayed whole
    s.run_until_idle()
    assert [h.tokens for h in handles] == ref
    assert s.engine_restarts == 1 and s._in_flight is None
    assert s.cache.free_pages == total_free, "zero page leak after recovery"


@pytest.mark.timeout(60)
def test_restart_budget_exhausted_fails_engine_error(model_and_params):
    """Past engine_restart_max the supervisor gives up LOUDLY: outstanding
    requests fail with the named reason 'engine_error' and new submits are
    refused — a dead engine must never look healthy-but-slow."""
    s = make_session(model_and_params, engine_restart_max=1)
    total_free = s.cache.free_pages
    with faults.inject("decode_raise:1.0", seed=0):  # every decode attempt
        s.serve_forever()
        h = s.submit(PROMPTS[0], 8)
        assert h._event.wait(30)
    assert h.status == h.CANCELLED and h.finish_reason == "engine_error"
    assert s.engine_restarts == 1
    assert s.cache.free_pages == total_free
    with pytest.raises(RuntimeError, match="died"):
        s.submit(PROMPTS[1], 4)
    s.stop()


# -- hedged retry / dedup -----------------------------------------------------


@pytest.mark.timeout(120)
def test_hedged_generate_exactly_one_execution(model_and_params):
    """The hedge re-submits under the SAME idempotency key after a TTFT
    miss; the server's (tenant, client_req_id) dedup reattaches it to the
    original request — exactly one engine execution, one set of tokens."""
    from paddle_tpu.serving.server import ServingClient, ServingServer

    ref_sess = make_session(model_and_params)
    ref_h = ref_sess.submit(PROMPTS[0], 6)
    ref_sess.run_until_idle()

    s = make_session(model_and_params)
    # hold the engine: a placeholder thread makes ServingServer.start (and
    # serve_forever's idempotence guard) treat it as already running, so
    # nothing decodes until it starts for real below — the hedge is then
    # guaranteed to fire on a genuinely token-less request, and the dedup
    # path (not timing luck) is what collapses the two submits
    s._thread = threading.Thread(target=lambda: None)
    srv = ServingServer(session=s).start()
    try:
        c = ServingClient(srv.address)
        out = {}

        def run():
            out["resp"] = c.generate(
                PROMPTS[0], 6, hedge_ttft_s=0.1, timeout_s=60.0
            )

        t = threading.Thread(target=run, daemon=True)
        t.start()
        deadline = time.monotonic() + 10
        while c.hedges == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert c.hedges == 1, "TTFT miss must have triggered the hedge"
        s._thread = None
        s.serve_forever()
        t.join(60)
        assert not t.is_alive() and out["resp"]["done"]
        assert out["resp"]["tokens"] == ref_h.tokens
        # exactly one engine execution for the hedged pair
        assert s.scheduler.completed == 1
        with srv._handles_lock:
            assert len(srv._handles) == 1
        c.close()
    finally:
        srv.stop()
