"""KV pages are handed out as tokens are written (ISSUE 34): a slot holds
the pages its written tokens need plus the page of its next write; a dry
pool preempts the request admitted last, which is rebuilt when it is
admitted again. Tiny float32 models on the CPU:

(a) under preemption every request's tokens, greedy and seeded-sampled,
    equal the same session's over a pool with room, and never shrink;
(b) the pool never leaks: under preemption, cancel mid-flight, deadline
    expiry and cancel_tenant every page comes back and every refcount is 0;
(c) liveness: a pool of exactly one maximal request's pages serves a queue;
(d) the rule composes with the prefix cache, chunked prefill and
    speculation (a verify round grows, a rejection trims);
(e) the scheduler's rules one by one: the admission predicate and its
    headroom, the victim, the queue's order, what the handle keeps, what the
    service-time estimate and the quota see;
(f) the counters, the gauge and the `serve.preempt` span."""

import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from paddle_tpu.obs import metrics as obs_metrics
from paddle_tpu.obs import trace
from paddle_tpu.serving.kv_cache import PagedKVCache
from paddle_tpu.serving.quota import TenantQuotas
from paddle_tpu.serving.scheduler import FinishReason, Scheduler
from paddle_tpu.serving.session import ServingSession

pytestmark = pytest.mark.serving

VOCAB = 96
PS = 4
MAX_NEW = 24
BUCKETS = (8, 16)


@pytest.fixture(scope="module")
def servable():
    from paddle_tpu.serving.model import LMConfig, ServableLM

    model = ServableLM(LMConfig(vocab=VOCAB, n_layers=2, d_model=32, n_heads=2, max_len=96))
    return model, model.init_params(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def looped():
    from paddle_tpu.serving.looped_lm import LoopedLM, LoopedLMConfig

    model = LoopedLM(LoopedLMConfig(vocab=VOCAB + 1, n_layers=2, d_model=32, n_heads=2,
                                    head_dim=16, d_ff=48, ut_steps=2, max_len=96,
                                    dtype="float32"))
    return model, model.init_params(jax.random.PRNGKey(0))


def make_session(model_and_params, **kw):
    model, params = model_and_params
    kw = dict(dict(max_slots=4, page_size=PS, prefill_buckets=BUCKETS,
                   max_new_limit=MAX_NEW), **kw)
    return ServingSession(model, params, **kw)


def plan(n=10, seed=0, longest=15):
    """(prompt, max_new, sampling) of n requests: every other one sampled."""
    rs = np.random.default_rng(seed)
    out = []
    for i in range(n):
        prompt = [1] + [int(t) for t in rs.integers(3, VOCAB, int(rs.integers(3, longest)))]
        kw = {} if i % 2 == 0 else dict(temperature=0.8, top_k=20, seed=100 + i)
        out.append((prompt, int(rs.integers(4, MAX_NEW + 1)), kw))
    return out


def drive(s, requests, each_step=None):
    """Submit all, step to the end; the handles' tokens may only grow."""
    hs = [s.submit(p, n, **kw) for p, n, kw in requests]
    seen = [0] * len(hs)
    while s.scheduler.has_work():
        s.step()
        for i, h in enumerate(hs):
            assert len(h.tokens) >= seen[i], "handle.tokens shrank"
            seen[i] = len(h.tokens)
        if each_step is not None:
            each_step(s)
    return hs


def assert_nothing_held(s):
    if s.cache.prefix is not None:
        s.cache.flush_prefix()
    assert s.cache.pages_in_use == 0 and s.stats()["pages_in_use"] == 0
    assert not any(s.cache._refcount), "a page kept a reference"
    assert not any(s.cache._slot_pages) and not s.cache.block_table().any()


def holds_no_more_than_written(s):
    """The rule itself, at every step boundary: written tokens' pages, plus
    at most the page of the next write (a verify round's surplus is trimmed
    before the step ends)."""
    for slot, act in s.scheduler.active_slots():
        held = len(s.cache.slot_pages(slot))
        assert s.cache.pages_needed(act.written) <= held, (slot, act.written, held)
        assert held <= max(s.cache.pages_needed(len(act.prompt)),
                           s.cache.pages_needed(act.written + 1))


# -- (a) token for token ---------------------------------------------------------------

@pytest.mark.parametrize("kind", ["servable", "looped"])
def test_preempted_requests_answer_token_for_token(kind, request):
    model_and_params = request.getfixturevalue(kind)
    requests = plan()
    roomy = make_session(model_and_params)
    want = [list(h.tokens) for h in drive(roomy, requests)]
    assert roomy.stats()["preemptions"] == 0 == roomy.stats()["replayed_tokens"]
    for num_pages in (11, 17):
        s = make_session(model_and_params, num_pages=num_pages)
        hs = drive(s, requests, each_step=holds_no_more_than_written)
        st = s.stats()
        assert st["preemptions"] > 0, "the pool was meant to run dry"
        assert st["replayed_tokens"] > 0
        assert [list(h.tokens) for h in hs] == want
        assert all(h.done and h.finish_reason in ("length", "eos") for h in hs)
        assert st["decode_shape_signatures"] == 1
        # what the window counts: every token once, the replayed ones apart
        assert st["tokens_generated"] == roomy.stats()["tokens_generated"]
        assert_nothing_held(s)


# -- (b) no leak -----------------------------------------------------------------------

def _until_someone_is_preempted(s):
    """Step until a preempted request waits at the head of the queue."""
    for _ in range(400):
        s.step()
        head = s.scheduler.waiting[0] if s.scheduler.waiting else None
        if head is not None and head.t_started is not None:
            return head
    raise AssertionError("nothing was preempted")


@pytest.mark.parametrize("how", ["preemption", "cancel", "deadline", "cancel_tenant"])
def test_the_pool_never_leaks(servable, how):
    quotas = TenantQuotas(token_capacity=10_000)
    s = make_session(servable, num_pages=13, quotas=quotas)
    requests = plan(12, seed=1)
    if how == "preemption":
        drive(s, requests)
        assert s.stats()["preemptions"] > 0
    else:
        kw = dict(deadline_s=1000.0) if how == "deadline" else {}
        hs = [s.submit(p, n, tenant="ab"[i % 2], **dict(skw, **kw))
              for i, (p, n, skw) in enumerate(requests)]
        head = _until_someone_is_preempted(s)
        victim, had = head.handle, list(head.handle.tokens)
        assert s.scheduler.active_slots() and victim.status == victim.RUNNING
        if how == "cancel":
            # one in a slot, the preempted one in the queue, one never admitted
            running = s.scheduler.active_slots()[0][1].handle
            queued = s.scheduler.waiting[-1].handle
            assert queued.status == queued.QUEUED
            assert running.cancel() and victim.cancel() and queued.cancel()
            assert victim.done and victim.finish_reason == FinishReason.CANCELLED
            assert victim.tokens == had, "a cancelled request keeps what it streamed"
            s.run_until_idle()
            assert running.done and running.finish_reason == FinishReason.CANCELLED
        elif how == "deadline":
            import time

            s.step(now=time.monotonic() + 2000.0)  # everyone is past it
            assert all(h.done for h in hs)
            assert victim.finish_reason == FinishReason.DEADLINE and victim.tokens == had
            assert not s.scheduler.has_work()
        else:
            assert s.cancel_tenant(victim.tenant) >= 1
            assert victim.done and victim.tokens == had
            s.run_until_idle()
            assert all(h.done for h in hs)
    assert_nothing_held(s)
    # the quota saw ONE request each: no hold left, and every token a
    # finished or cancelled request did not use is back in the bucket
    for tenant, row in quotas.stats().items():
        assert row["in_flight"] == 0, (tenant, row)


# -- (c) liveness ----------------------------------------------------------------------

def test_a_pool_of_one_maximal_request_serves_a_queue(servable):
    prompt_len, n = 16, MAX_NEW
    rs = np.random.default_rng(5)
    requests = [([1] + [int(t) for t in rs.integers(3, VOCAB, prompt_len - 1)], n, {})
                for _ in range(5)]
    whole = -(-(prompt_len + n) // PS)
    s = make_session(servable, num_pages=whole + 1,  # and the dump page
                     prefill_buckets=(8, 24))
    with pytest.raises(ValueError, match="KV pages"):
        s.submit(requests[0][0] + [5], n)  # one token more could never finish
    hs = drive(s, requests, each_step=holds_no_more_than_written)
    assert all(h.done and h.finish_reason in ("length", "eos") for h in hs)
    assert sum(len(h.tokens) == n for h in hs) >= 3, "the case needs maximal requests"
    assert s.stats()["preemptions"] > 0
    want = [list(h.tokens) for h in drive(make_session(servable), requests)]
    assert [list(h.tokens) for h in hs] == want
    assert_nothing_held(s)


# -- (d) composition -------------------------------------------------------------------

SYS = [1] + list(range(3, 18))  # 16 shared tokens: 4 full pages


def _shared_prefix_plan():
    rs = np.random.default_rng(2)
    out = []
    for i in range(8):
        suffix = [int(t) for t in rs.integers(20, VOCAB, int(rs.integers(2, 9)))]
        kw = {} if i % 2 == 0 else dict(temperature=0.7, top_k=10, seed=7 + i)
        out.append((SYS + suffix, int(rs.integers(8, MAX_NEW + 1)), kw))
    return out


@pytest.mark.parametrize("with_cache", [False, True], ids=["chunked", "chunked+prefix"])
def test_preemption_composes_with_chunked_prefill_and_the_prefix_cache(servable, with_cache):
    kw = dict(prefill_chunk=8, prefix_cache=with_cache)
    requests = _shared_prefix_plan()
    want = [list(h.tokens) for h in drive(make_session(servable, **kw), requests)]
    s = make_session(servable, num_pages=15 if with_cache else 19, **kw)
    hs = drive(s, requests)
    st = s.stats()
    assert st["preemptions"] > 0 and st["prefill_chunks_committed"] > 0
    assert [list(h.tokens) for h in hs] == want
    if with_cache:
        # a preempted request's registered prompt pages stay in the index:
        # what is held at the end is the cache, and nothing else
        assert st["prefix_pages_shared"] > 0
        assert st["pages_in_use"] == st["prefix_pages_cached"] == st["prefix_pages_unreferenced"]
    assert_nothing_held(s)


def test_preemption_composes_with_speculation(servable):
    cycle = [5, 9, 11]
    rs = np.random.default_rng(3)
    requests = [([1] + cycle * int(rs.integers(2, 5)), int(rs.integers(10, MAX_NEW + 1)),
                 {} if i % 2 == 0 else dict(temperature=0.8, top_k=20, seed=40 + i))
                for i in range(8)]
    plain = [list(h.tokens) for h in drive(make_session(servable), requests)]
    roomy = make_session(servable, speculate_k=4)
    assert [list(h.tokens) for h in drive(roomy, requests)] == plain
    assert roomy.stats()["spec_rounds"] > 0 and roomy.stats()["preemptions"] == 0
    s = make_session(servable, speculate_k=4, num_pages=15)
    hs = drive(s, requests, each_step=holds_no_more_than_written)
    st = s.stats()
    assert [list(h.tokens) for h in hs] == plain
    assert st["preemptions"] > 0 and st["spec_rounds"] > 0
    # a verify round grew to its K+1 positions, and what the rejections
    # left over went back while the requests were in flight
    assert st["spec_pages_trimmed"] > 0
    assert st["verify_shape_signatures"] == 1 == st["decode_shape_signatures"]
    assert_nothing_held(s)


# -- (e) the scheduler's rules ---------------------------------------------------------

def _scheduler(num_pages=9, max_slots=3, **kw):
    cache = PagedKVCache(n_layers=1, kv_dim=4, num_pages=num_pages, page_size=PS,
                         max_slots=max_slots, max_pages_per_seq=8)
    return Scheduler(cache, **kw), cache


def _finish_prefill(act, first_token=7):
    act.append(first_token)


def test_admission_gives_the_prompts_pages_and_keeps_a_page_a_live_slot():
    sch, cache = _scheduler(num_pages=9)            # 8 pages to hand out
    a = sch.submit([1] * 9, 16, "t")                 # 3 pages of prompt, 7 in all
    b = sch.submit([1] * 9, 16, "t")
    c = sch.submit([1] * 5, 16, "t")
    admitted = sch.pop_admissions(now=1.0)
    # a: 3+1 <= 8. b: 3+1 and one kept for a = 5 <= 8-3. c: 2+1 and one each
    # for a and b = 5 > 2 free: the queue's head waits, and nothing passes it
    assert [act.handle for _, act in admitted] == [a, b]
    assert [len(cache.slot_pages(slot)) for slot, _ in admitted] == [3, 3]
    assert cache.free_pages == 2 and sch.queue_depth() == 1
    assert c.status == c.QUEUED
    # the load estimate prices by the same predicate: a prompt of 1 page
    # would fit beside them (1+1+2 <= ... no: 4 > 2), an empty pool takes c
    with sch.lock:
        assert not sch._fits_now(5, 21) and not sch._fits_now(1, 17)
    sch.retire(admitted[0][0], FinishReason.LENGTH)
    with sch.lock:
        assert sch._fits_now(5, 21)                  # 2+1, one for b: 4 <= 5
    assert [act.handle for _, act in sch.pop_admissions(now=2.0)] == [c]


def test_a_request_that_fits_alone_is_admitted_into_an_empty_pool():
    sch, cache = _scheduler(num_pages=3)             # 2 pages: 8 tokens
    h = sch.submit([1] * 5, 3, "t")                  # 2 pages now, 2 in all
    assert [a.handle for _, a in sch.pop_admissions(now=0.0)] == [h]
    assert len(cache.slot_pages(0)) == 2 and cache.free_pages == 0


def test_a_dry_pool_preempts_the_request_admitted_last():
    sch, cache = _scheduler(num_pages=8, max_slots=3)   # 7 pages
    old = sch.submit([1] * 4, 16, "t")
    mid = sch.submit([1] * 4, 16, "t")
    (s_old, a_old), (s_mid, a_mid) = sch.pop_admissions(now=1.0)
    young = sch.submit([1] * 4, 16, "t")
    (s_young, a_young), = sch.pop_admissions(now=2.0)
    never = sch.submit([1] * 4, 16, "t")
    for act in (a_old, a_mid, a_young):
        _finish_prefill(act)
    assert a_old.admit_seq < a_mid.admit_seq < a_young.admit_seq
    assert cache.free_pages == 4
    # every slot's next write crosses into a second page: 3 of the 4 go
    assert sch.grow([(s, 5) for s in (s_young, s_old, s_mid)], now=3.0) == []
    assert cache.free_pages == 1
    for act in (a_old, a_mid, a_young):
        for t in range(4):
            act.append(9)
    assert a_old.next_pos == 8 and a_young.handle.tokens == [7, 9, 9, 9, 9]
    # a third page each: one is free, so the oldest and the next get theirs
    # (the second from the youngest, who is preempted) in whatever order
    # they are asked for
    out = sch.grow([(s_young, 9), (s_mid, 9), (s_old, 9)], now=4.0)
    assert [(slot, act.handle, freed) for slot, act, freed in out] == [(s_young, young, 2)]
    assert sch.slots[s_young] is None and sch.preemptions == 1
    assert len(cache.slot_pages(s_old)) == len(cache.slot_pages(s_mid)) == 3
    assert cache.free_pages == 1
    # it waits at the FRONT, ahead of a request that never ran, with its
    # tokens, running as far as a client can tell
    assert [w.handle for w in sch.waiting] == [young, never]
    assert young.tokens == [7, 9, 9, 9, 9] and young.status == young.RUNNING
    assert not young.done
    # it needs the pages of prompt + tokens (3) plus one, and one each for
    # the two live slots: 6 > 1 free, so it waits, and so does all behind it
    assert sch.pop_admissions(now=5.0) == []
    sch.retire(s_old, FinishReason.LENGTH)
    assert sch.pop_admissions(now=6.0) == [], "3 + 1 and one for the live slot > 4 free"
    sch.retire(s_mid, FinishReason.LENGTH)
    (slot, again), (_, last) = sch.pop_admissions(now=9.0)
    assert last.handle is never
    assert again.handle is young and again.replaying and again.generated == 0
    assert len(cache.slot_pages(slot)) == 1, "the prompt's pages, the rest as it rebuilds"
    # the first admission's stamp stays; the 5 s it waited are not service
    assert again.t_started == 2.0 and again.preempted_s == 5.0
    assert last.t_started == 9.0 and last.preempted_s == 0.0
    assert again.admit_seq > a_mid.admit_seq
    # the replay takes the known tokens, whatever the step hands it
    assert again.append(7) is False and again.last_token == 7 and again.next_pos == 4
    assert again.append(0) is False and again.last_token == 9 and again.next_pos == 5
    for _ in range(3):
        assert again.append(0) is False
    assert not again.replaying and again.next_pos == 8
    assert again.append(3) is True and young.tokens == [7, 9, 9, 9, 9, 3]


def test_the_oldest_request_is_never_the_victim_and_the_asker_may_be():
    sch, cache = _scheduler(num_pages=4, max_slots=2)   # 3 pages
    old = sch.submit([1] * 4, 8, "t")
    young = sch.submit([1] * 4, 8, "t")
    (s_old, a_old), = sch.pop_admissions(now=0.0)
    # the second waits for its prompt's page + 1 beside a page kept for the
    # first: 3 > 2 free; the first grows, then there is room
    assert sch.queue_depth() == 1
    _finish_prefill(a_old)
    assert sch.grow([(s_old, 5)], now=0.0) == [] and cache.free_pages == 1
    # admitted with force (a page freed and taken again in between, say)
    cache.reserve(1, 4)
    from paddle_tpu.serving.scheduler import ActiveSeq

    w = sch.waiting.popleft()
    a_young = ActiveSeq(w.handle, w.prompt)
    a_young.admit_seq, a_young.t_started = 99, 1.0
    sch.slots[1] = a_young
    _finish_prefill(a_young)
    assert cache.free_pages == 0
    # the youngest asks and none is free: it is its own victim
    out = sch.grow([(1, 5)], now=2.0)
    assert [(slot, act.handle) for slot, act, _ in out] == [(1, young)]
    assert sch.slots[s_old] is a_old and cache.free_pages == 1
    # the oldest asks: it gets the page; alone it can always finish
    for _ in range(4):
        a_old.append(9)
    assert sch.grow([(s_old, 9)], now=3.0) == []
    assert len(cache.slot_pages(s_old)) == 3 and old.tokens == [7, 9, 9, 9, 9]


def test_service_time_leaves_out_the_time_spent_preempted():
    import time

    sch, cache = _scheduler(num_pages=4, max_slots=2)
    h = sch.submit([1] * 4, 2, "t")
    t0 = time.monotonic() - 103.0                      # admitted 103 s ago
    (slot, act), = sch.pop_admissions(now=t0)
    _finish_prefill(act)
    cache.reserve(1, 8)                                # someone holds the rest
    (_, lost, _), = sch.grow([(slot, 5)], now=t0 + 1.0)
    assert lost.handle is h and not h.done
    cache.release(1)
    (slot, act), = sch.pop_admissions(now=t0 + 101.0)  # 100 s out of a slot
    assert act.t_started == t0 and act.preempted_s == 100.0
    act.append(7), act.append(8)
    sch.retire(slot, FinishReason.LENGTH)
    assert h.done and sch._ewma_service_s == pytest.approx(3.0, abs=0.5)


def test_the_quota_refund_of_a_preempted_request_is_a_running_requests():
    quotas = TenantQuotas(token_capacity=100)
    sch, cache = _scheduler(num_pages=4, max_slots=2, quotas=quotas)
    h = sch.submit([1] * 4, 10, "t")                  # 14 charged
    (slot, act), = sch.pop_admissions(now=0.0)
    _finish_prefill(act)
    cache.reserve(1, 8)
    assert sch.grow([(slot, 5)], now=1.0)
    assert quotas.stats()["t"]["in_flight"] == 1
    assert h.cancel() and h.done and h.tokens == [7]
    row = quotas.stats()["t"]
    # the prompt and the one token it made are consumed, 9 come back
    assert row["in_flight"] == 0 and row["level"] == pytest.approx(100 - 14 + 9)


# -- (f) counters, gauge, span ---------------------------------------------------------

def test_preemptions_are_counted_and_each_leaves_a_span(servable):
    pre = obs_metrics.REGISTRY.counter("paddle_tpu_serving_preemptions_total")
    rep = obs_metrics.REGISTRY.counter("paddle_tpu_serving_replayed_tokens_total")
    gauge = obs_metrics.REGISTRY.gauge("paddle_tpu_serving_kv_pages_in_use")

    def spans():
        return [r for r in trace.TRACER.snapshot() if r[0] == "serve.preempt"]

    requests = plan()
    p0, r0, n0 = pre.value(), rep.value(), len(spans())
    roomy = make_session(servable)
    drive(roomy, requests)
    # a pool with room never engages it: zero, in stats and exposition alike
    assert (pre.value(), rep.value(), len(spans())) == (p0, r0, n0)
    assert roomy.stats()["preemptions"] == 0 == roomy.stats()["replayed_tokens"]
    s = make_session(servable, num_pages=13)
    seen = []

    def watch(sess):
        seen.append((pre.value(), rep.value()))
        assert gauge.value() == sess.cache.pages_in_use

    drive(s, requests, each_step=watch)
    st = s.stats()
    assert st["preemptions"] > 0 and st["replayed_tokens"] > 0
    assert pre.value() - p0 == st["preemptions"]
    assert rep.value() - r0 == st["replayed_tokens"]
    assert seen == sorted(seen), "the counters only rise"
    assert gauge.value() == 0
    rows = spans()[n0:]
    assert len(rows) == st["preemptions"]
    for r in rows:
        assert r[6]["pages"] >= 1 and r[6]["written"] >= 1
        assert r[6]["pages"] == s.cache.pages_needed(r[6]["written"]) or \
            r[6]["pages"] == s.cache.pages_needed(r[6]["written"] + 1)
    text = obs_metrics.to_prometheus_text()
    for name in ("paddle_tpu_serving_preemptions_total",
                 "paddle_tpu_serving_replayed_tokens_total",
                 "paddle_tpu_serving_kv_pages_in_use"):
        assert name in text
    # stats() and the registry's counters (asserted above) are the record:
    # no third copy of the same events beside them (ISSUE 37)
    from paddle_tpu.serving.session import SERVING_EVENTS

    assert not {"serving_preemptions", "serving_replayed_tokens"} & set(
        SERVING_EVENTS.as_dict())
