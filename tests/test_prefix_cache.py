"""Shared-prefix KV cache (ISSUE 19).

The load-bearing claims, each pinned directly:

  * ALIASING — a prompt whose leading pages are cached aliases them into
    its slot read-only (refcounted) and prefills only its own suffix; the
    match never covers the whole prompt (the final chunk must still emit
    the sampled first token), and only COMMITTED pages ever register.
  * TOKEN IDENTITY — cache-on tokens are bitwise cache-off tokens: greedy
    AND seeded-sampled, chunked AND whole-prompt-routed prompts, with ONE
    decode signature (the cache is host-side block-table state; no
    executable ever learns it exists).
  * ACCOUNTING — a page frees exactly once, at refcount zero: releasing a
    slot that shares pages decrefs without freeing (cancel-mid-decode
    regression), LRU eviction only ever takes unreferenced cached pages,
    and after churn + flush the free list is whole (zero leak).
  * TENANCY — chains are rooted per tenant: identical prompts from two
    tenants never alias each other's pages, and hit counters are
    per-tenant in stats().
  * COMPOSITION — crash recovery invalidates the index (no stale aliases
    into the dead pool) and replays token-bitwise while the cache
    re-populates; speculation's +K headroom and aliased pages coexist
    without leak or double-free; adaptive draft-K stays a pure rule.
"""

import time

import pytest

from paddle_tpu.core import faults
from paddle_tpu.serving.kv_cache import PagedKVCache
from paddle_tpu.serving.prefix_cache import PrefixIndex
from paddle_tpu.serving.speculation import next_draft_k

pytestmark = [pytest.mark.serving, pytest.mark.prefix]

VOCAB = 96


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
    kw.setdefault("page_size", 4)
    kw.setdefault("prefill_buckets", (8, 16, 32))
    kw.setdefault("max_new_limit", 16)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("prefix_cache", True)
    return ServingSession(model, params, **kw)


def make_cache(**kw):
    kw.setdefault("n_layers", 2)
    kw.setdefault("kv_dim", 8)
    kw.setdefault("num_pages", 16)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_pages_per_seq", 8)
    kw.setdefault("prefix_cache", True)
    return PagedKVCache(**kw)


# a 24-token shared "system prompt" plus per-user 3-token suffixes
SYS = list(range(3, 27))


def user_prompts(n, base=40):
    return [SYS + [base + i, base + i + 1, base + i + 2] for i in range(n)]


# -- index + allocator units (no jax) -----------------------------------------


def test_match_caps_below_whole_prompt():
    """A fully-cached prompt still recomputes its final token: the match
    limit is (len-1)//page_size pages, so >= 1 suffix token always remains
    for the chunk that samples the request's first output."""
    assert PrefixIndex.max_match_pages(12, 4) == 2
    assert PrefixIndex.max_match_pages(13, 4) == 3
    assert PrefixIndex.max_match_pages(4, 4) == 0
    assert PrefixIndex.max_match_pages(3, 4) == 0
    c = make_cache()
    prompt = list(range(1, 13))  # 12 tokens = 3 exact pages
    c.reserve(0, 16, tenant="a", prompt=prompt)
    c.commit_prefix(0, "a", prompt, len(prompt))  # registers all 3
    assert len(c.prefix) == 3
    # ...but an identical prompt may only alias 2 of them
    assert c.peek_hit_tokens("a", prompt) == 8


def test_alias_refcount_and_physical_free_exactly_once():
    """Reserve→commit→alias: shared pages carry one ref per slot plus the
    index's; release() reports only PHYSICAL frees, so a page never
    double-frees and never leaks."""
    c = make_cache()
    total = c.free_pages
    prompt = list(range(1, 13))
    p0 = c.reserve(0, 16, tenant="a", prompt=prompt)  # 4 fresh pages
    assert c.hit_tokens(0) == 0
    c.commit_prefix(0, "a", prompt, len(prompt))
    p1 = c.reserve(1, 16, tenant="a", prompt=prompt)
    assert c.hit_tokens(1) == 8
    assert p1[:2] == p0[:2] and p1[2] not in p0, "2 aliased + private CoW"
    assert c.page_refcount(p0[0]) == 3  # slot0 + slot1 + index
    # slot0 out: pages 0-2 still referenced -> only its private page 3 frees
    assert c.release(0) == 1
    # slot1 out: its 2 fresh pages free; aliased pages stay cached (rc 1)
    assert c.release(1) == 2
    assert c.prefix_stats()["prefix_pages_unreferenced"] == 3
    # flush drops the index's refs -> everything home, counted exactly once
    assert c.flush_prefix() == 3
    assert c.free_pages == total


def test_uncommitted_pages_never_register():
    """Registration follows COMMITTED tokens only: a slot mid-prefill
    exposes exactly its committed full pages, never pages whose KV is still
    being written."""
    c = make_cache()
    prompt = list(range(1, 13))
    c.reserve(0, 16, tenant="a", prompt=prompt)
    assert c.commit_prefix(0, "a", prompt, 3) == 0   # no full page yet
    assert c.commit_prefix(0, "a", prompt, 6) == 1   # page 0 committed
    assert c.peek_hit_tokens("a", prompt) == 4
    assert c.commit_prefix(0, "a", prompt, 6) == 0   # idempotent
    assert c.commit_prefix(0, "a", prompt, 12) == 2  # the rest
    assert c.peek_hit_tokens("a", prompt) == 8


def test_peek_is_pure():
    """The admission-pricing peek mutates nothing: no recency bump, no
    counters, no root creation — pricing must not perturb eviction order."""
    c = make_cache()
    prompt = list(range(1, 13))
    c.peek_hit_tokens("ghost", prompt)
    idx = c.prefix
    assert idx.lookups == 0 and idx._roots == {} and idx._tick == 0
    c.reserve(0, 16, tenant="a", prompt=prompt)
    c.commit_prefix(0, "a", prompt, 12)
    tick0 = idx._tick
    c.peek_hit_tokens("a", prompt)
    assert idx._tick == tick0 and idx.hits == 0


def test_lru_eviction_under_pool_pressure():
    """Unreferenced cached pages are capacity, not occupancy: can_admit
    counts them, reserve LRU-evicts them when the free list runs short, and
    a just-matched prefix can never evict itself (its refs go up first)."""
    c = make_cache(num_pages=12)
    prompt = list(range(1, 13))
    c.reserve(0, 16, tenant="a", prompt=prompt)
    c.commit_prefix(0, "a", prompt, 12)
    c.release(0)
    assert c.free_pages == 8 and c.prefix_stats()["prefix_pages_cached"] == 3
    c.reserve(1, 24, tenant="b", prompt=list(range(50, 56)))  # 6 fresh
    # the admission predicate (ISSUE 34): the pages of what is to be
    # written plus one, never more than the whole life's, and a free page
    # for every live slot besides
    assert c.can_admit(20, 20, 0), "2 free + 3 evictable must admit 5 pages"
    assert c.can_admit(13, 32, 0), "4 pages to write + 1, of a life of 8"
    assert not c.can_admit(13, 32, 1), "a live slot's page must stay free"
    assert not c.can_admit(20, 20, 1)
    c.reserve(2, 20, tenant="b", prompt=list(range(60, 66)))
    s = c.prefix_stats()
    assert s["prefix_evictions"] == 3 and s["prefix_pages_cached"] == 0
    c.release(1), c.release(2)
    assert c.free_pages == 11


def test_matched_prefix_survives_same_reserve_eviction():
    """The eviction loop inside reserve must not free the pages the SAME
    reservation just matched: they are increffed before eviction runs."""
    c = make_cache(num_pages=10, max_pages_per_seq=9)
    prompt = list(range(1, 13))
    c.reserve(0, 16, tenant="a", prompt=prompt)
    c.commit_prefix(0, "a", prompt, 12)
    c.release(0)  # 3 cached (1 unreachable for the next match), 5 free
    # 2 aliased + 7 fresh needed, 5 free -> evicts the non-matched cached
    # page(s); the 2 matched pages must survive
    pages = c.reserve(1, 36, tenant="a", prompt=prompt)
    assert c.hit_tokens(1) == 8
    assert c.page_refcount(pages[0]) >= 2
    c.release(1)
    c.flush_prefix()
    assert c.free_pages == 9


def test_cache_size_cap_evicts_lru():
    """--prefix_cache_pages bounds the index: registration past the cap
    LRU-evicts unreferenced entries (best-effort — live aliases pin)."""
    c = make_cache(num_pages=32, prefix_cache_pages=2)
    p1, p2 = list(range(1, 13)), list(range(20, 32))
    c.reserve(0, 16, tenant="a", prompt=p1)
    c.commit_prefix(0, "a", p1, 12)
    c.release(0)
    assert c.prefix_stats()["prefix_pages_cached"] == 2  # capped already
    c.reserve(1, 16, tenant="a", prompt=p2)
    c.commit_prefix(1, "a", p2, 12)
    c.release(1)
    s = c.prefix_stats()
    assert s["prefix_pages_cached"] == 2 and s["prefix_evictions"] >= 3
    c.flush_prefix()
    assert c.free_pages == 31


def test_reset_invalidates_index_no_stale_aliases():
    """Crash recovery: reset() rebuilds the allocator AND drops the index —
    every cached page id pointed into the dead pool, so a replayed request
    must miss, re-prefill, and re-populate."""
    c = make_cache()
    total = c.free_pages
    prompt = list(range(1, 13))
    c.reserve(0, 16, tenant="a", prompt=prompt)
    c.commit_prefix(0, "a", prompt, 12)
    hits0 = c.prefix.hits
    c.reset()
    assert c.free_pages == total and len(c.prefix) == 0
    c.reserve(0, 16, tenant="a", prompt=prompt)
    assert c.hit_tokens(0) == 0, "no stale aliases into the re-init pool"
    c.commit_prefix(0, "a", prompt, 12)
    c.reserve(1, 16, tenant="a", prompt=prompt)
    assert c.hit_tokens(1) == 8, "the cache re-populates after recovery"
    assert c.prefix.hits > hits0, "telemetry is cumulative across resets"


def test_tenant_isolation_unit():
    """Identical token streams under different tenants walk disjoint
    chains: tenant b's reserve matches nothing and registers its own
    pages."""
    c = make_cache()
    prompt = list(range(1, 13))
    pa = c.reserve(0, 16, tenant="a", prompt=prompt)
    c.commit_prefix(0, "a", prompt, 12)
    pb = c.reserve(1, 16, tenant="b", prompt=prompt)
    assert c.hit_tokens(1) == 0, "cross-tenant aliasing is forbidden"
    assert not set(pa) & set(pb)
    c.commit_prefix(1, "b", prompt, 12)
    # now each tenant hits its OWN chain
    c.reserve(2, 16, tenant="a", prompt=prompt)
    c.reserve(3, 16, tenant="b", prompt=prompt)
    assert c.slot_pages(2)[:2] == pa[:2]
    assert c.slot_pages(3)[:2] == pb[:2]
    by_tenant = c.prefix_stats()["prefix_hit_rate_by_tenant"]
    assert by_tenant["a"] > 0 and by_tenant["b"] > 0


def test_adaptive_k_rule_pure():
    """next_draft_k (ROADMAP 1a): additive-increase on full acceptance,
    fall-to-observed on divergence, clamped to [1, k_max] — and a pure
    function (same inputs, same K, forever: the bitwise-replay contract)."""
    assert next_draft_k(3, 8, drafted=3, accepted=3) == 4   # grow
    assert next_draft_k(8, 8, drafted=8, accepted=8) == 8   # capped
    assert next_draft_k(6, 8, drafted=6, accepted=2) == 3   # fall to obs+1
    assert next_draft_k(6, 8, drafted=6, accepted=0) == 1   # floor
    assert next_draft_k(4, 8, drafted=0, accepted=0) == 4   # no evidence
    assert next_draft_k(0, 8, drafted=2, accepted=2) == 2   # clamp then grow
    for args in [(3, 8, 3, 3), (6, 8, 6, 2)]:
        assert next_draft_k(*args) == next_draft_k(*args)


# -- end-to-end token identity ------------------------------------------------


def run_prompts(model_and_params, prompts, prefix, temp=0.0, max_new=6, **kw):
    s = make_session(model_and_params, prefix_cache=prefix, **kw)
    handles = []
    for i, p in enumerate(prompts):
        handles.append(
            s.submit(p, max_new_tokens=max_new, tenant="t0",
                     temperature=temp, seed=1000 + i)
        )
        # drain between submits so later prompts actually see a warm cache
        s.run_until_idle()
    toks = [h.result(timeout=30) for h in handles]
    return toks, s


@pytest.mark.parametrize("temp", [0.0, 0.8])
def test_tokens_bitwise_cache_on_off(model_and_params, temp):
    """The acceptance bit: greedy AND seeded-sampled tokens are bitwise
    identical cache-on vs cache-off, across chunk-routed (long) and
    whole-prompt-routed (short) prompts — with ONE decode signature and a
    real hit rate (the cache demonstrably engaged)."""
    prompts = user_prompts(4) + [[7, 8, 9], [7, 8, 9]]  # long×4 + short×2
    ref, _ = run_prompts(model_and_params, prompts, prefix=False, temp=temp)
    out, s = run_prompts(model_and_params, prompts, prefix=True, temp=temp)
    assert out == ref, "the cache must be result-invisible"
    st = s.stats()
    assert st["prefix_hit_rate"] > 0.3 and st["prefix_pages_shared"] >= 18
    assert st["decode_shape_signatures"] == 1
    assert st["prefix_cache_enabled"] is True


def test_short_prompt_whole_path_registers_then_hits(model_and_params):
    """A short prompt prefills whole (one padded forward) yet still
    registers its full pages; an identical later prompt hits and routes
    through the chunked path for its suffix only."""
    prompts = [[7, 8, 9, 10, 11], [7, 8, 9, 10, 11]]
    out, s = run_prompts(model_and_params, prompts, prefix=True)
    assert out[0] == out[1]
    st = s.stats()
    assert st["prefix_hits"] == 1 and st["prefix_hit_tokens"] == 4
    assert st["prefill_chunks_committed"] == 1, (
        "the second prompt prefills only its 1-token suffix"
    )


def test_zero_page_leak_after_churn(model_and_params):
    """Alias/evict/retire churn across tenants ends with every page home
    after a flush — the leak gate."""
    s = make_session(model_and_params)
    total = s.cache.free_pages
    for tenant in ("a", "b"):
        for p in user_prompts(3):
            s.submit(p, max_new_tokens=4, tenant=tenant)
        s.run_until_idle()
    for h_p in user_prompts(2, base=60):
        s.submit(h_p, max_new_tokens=4, tenant="a")
    s.run_until_idle()
    assert s.scheduler.completed == 8
    s.cache.flush_prefix()
    assert s.cache.free_pages == total, "zero page leak after churn"


# -- satellite 2: cancel-mid-decode with a shared prefix ----------------------


def test_cancel_mid_decode_shared_prefix_counts_physical_frees(
    model_and_params
):
    """Two slots share a prefix; one is cancelled mid-decode. The recycle
    counter must count the cancelled slot's PHYSICAL frees exactly once —
    shared pages only decref — and nothing the survivor or the cache still
    references may hit the free list."""
    s = make_session(model_and_params)
    total = s.cache.free_pages
    warm = s.submit(SYS + [40, 41, 42], max_new_tokens=2, tenant="t0")
    s.run_until_idle()
    assert warm.done
    a = s.submit(SYS + [50, 51, 52], max_new_tokens=12, tenant="t0")
    b = s.submit(SYS + [60, 61, 62], max_new_tokens=12, tenant="t0")
    # admit + prefill both, decode a few steps, then cancel `a` mid-decode
    for _ in range(8):
        s.step()
    assert a.status == a.RUNNING and b.status == b.RUNNING
    slot_a = next(
        slot for slot, act in s.scheduler.active_slots()
        if act.handle.request_id == a.request_id
    )
    pages_a = s.cache.slot_pages(slot_a)
    shared_a = [p for p in pages_a if s.cache.page_refcount(p) > 1]
    private_a = [p for p in pages_a if s.cache.page_refcount(p) == 1]
    assert shared_a and private_a, "the slot must genuinely share pages"
    recycled0 = s.scheduler.pages_recycled_on_cancel
    free0 = s.cache.free_pages
    assert a.cancel()
    s.step()
    assert a.done and a.finish_reason == "cancelled"
    freed = s.scheduler.pages_recycled_on_cancel - recycled0
    assert freed == len(private_a), (
        "recycle counter = physical frees only: shared pages just decref"
    )
    assert s.cache.free_pages == free0 + freed
    for p in shared_a:
        assert s.cache.page_refcount(p) >= 1, "no double-free of shared pages"
    s.run_until_idle()
    assert b.done and b.status == b.DONE, "the survivor decodes to the end"
    s.cache.flush_prefix()
    assert s.cache.free_pages == total


# -- satellite 3: crash recovery with a warm cache ----------------------------


@pytest.mark.chaos
@pytest.mark.timeout(120)
@pytest.mark.parametrize(
    "site,spec",
    [
        ("decode_raise", "decode_raise:step=3"),
        ("page_exhaust", "page_exhaust:step=0"),
    ],
)
def test_crash_recovery_with_warm_cache_bitwise(
    model_and_params, site, spec, monkeypatch
):
    """Seeded faults against a WARM cache: the supervisor restarts the
    engine, reset() invalidates the index (no stale aliases into the dead
    pool), replayed requests are token-bitwise vs unfaulted, the free list
    is whole, and the cache re-populates for post-restart traffic."""
    monkeypatch.setenv("PADDLE_TPU_SERVING_STALL_S", "1")
    prompts = user_prompts(4)

    clean = make_session(model_and_params, prefix_cache=True)
    ref_handles = [clean.submit(p, 8, tenant="t0") for p in prompts]
    clean.run_until_idle()
    ref = [h.tokens for h in ref_handles]

    s = make_session(
        model_and_params, prefix_cache=True,
        engine_stall_timeout_s=0.3, engine_restart_max=5,
    )
    total_free = s.cache.free_pages
    # warm the cache BEFORE the faults arm: the shared prefix is cached and
    # later admissions genuinely alias it when the fault fires
    w = s.submit(SYS + [80, 81, 82], 2, tenant="t0")
    s.run_until_idle()
    assert w.done and s.stats()["prefix_pages_cached"] > 0
    with faults.inject(spec, seed=0) as inj:
        s.serve_forever()
        handles = [s.submit(p, 8, tenant="t0", deadline_s=60.0)
                   for p in prompts]
        deadline = time.monotonic() + 90
        for h in handles:
            assert h._event.wait(max(0.1, deadline - time.monotonic())), (
                f"request {h.request_id} never completed after {site}"
            )
        fired = dict(inj.fired)
    s.stop()
    assert fired.get(site, 0) >= 1, "the seeded fault must actually fire"
    assert s.engine_restarts >= 1, "the supervisor must have recovered"
    assert [h.tokens for h in handles] == ref, (
        "warm-cache replay must be result-transparent"
    )
    st = s.stats()
    assert st["prefix_pages_cached"] > 0, "the cache re-populated"
    s.cache.flush_prefix()
    assert s.cache.free_pages == total_free, "zero page leak after recovery"


# -- satellite 4: tenant isolation end-to-end ---------------------------------


def test_tenant_isolation_end_to_end(model_and_params):
    """Identical prompts across tenants never alias: tenant b's first
    submission is a cold miss even though tenant a just cached the same
    bytes, and stats() reports per-tenant hit rates."""
    s = make_session(model_and_params)
    p = SYS + [40, 41, 42]
    ha1 = s.submit(p, 4, tenant="a")
    s.run_until_idle()
    hb1 = s.submit(p, 4, tenant="b")
    s.run_until_idle()
    ha2 = s.submit(p, 4, tenant="a")
    hb2 = s.submit(p, 4, tenant="b")
    s.run_until_idle()
    assert ha1.tokens == hb1.tokens == ha2.tokens == hb2.tokens
    st = s.stats()
    by_tenant = st["prefix_hit_rate_by_tenant"]
    tokens_by_tenant = st["prefix_hit_tokens_by_tenant"]
    # each tenant hit only its OWN earlier registration: one cold miss each,
    # one full hit each -> identical per-tenant counters, no cross-leak
    assert tokens_by_tenant["a"] == tokens_by_tenant["b"] == 24
    assert 0 < by_tenant["a"] == by_tenant["b"] < 1


# -- speculation composition --------------------------------------------------


def test_speculation_composes_with_prefix_cache(model_and_params, monkeypatch):
    """Speculation's +K headroom and aliased prefix pages coexist: repeated
    repetitive prompts hit the cache AND speculate, tokens stay bitwise vs
    cache-off, trims only ever free private tail pages (no double-free),
    and the pool is whole after flush."""
    from paddle_tpu.serving.speculation import PromptLookupDrafter

    prompt = SYS + [5, 9, 11] * 5  # shared prefix + a cyclic tail
    (want,), _ = run_prompts(
        model_and_params, [prompt], prefix=False, speculate_k=0, max_new=12,
    )
    assert len(want) == 12
    # What these weights generate after the tail repeats no bigram inside 12
    # tokens, so the prompt-lookup drafter rightly drafts nothing; the draft
    # this test needs is built: the true continuation but for its last
    # token, so every round both accepts and rejects.
    seen, drafts = [], []

    def draft(self, k):
        seen.append(self._ctx[: len(prompt)] == prompt)
        n = len(self) - len(prompt)
        d = want[n:n + k]
        d[-1] = (d[-1] + 1) % VOCAB
        drafts.append(d)
        return d

    monkeypatch.setattr(PromptLookupDrafter, "draft", draft)
    ref, _ = run_prompts(
        model_and_params, [prompt, prompt], prefix=False, speculate_k=4,
        max_new=12,
    )
    n_off = len(drafts)
    out, s = run_prompts(
        model_and_params, [prompt, prompt], prefix=True, speculate_k=4,
        max_new=12,
    )
    assert out == ref == [want, want]
    # precondition: with its prompt's pages aliased the drafter was still
    # shown the whole prompt and asked for (and gave) a draft every round
    assert n_off > 0 and len(drafts) == 2 * n_off and all(seen)
    st = s.stats()
    assert st["spec_rounds"] > 0 and st["prefix_hits"] >= 1
    assert st["spec_tokens_accepted"] > 0
    assert st["spec_tokens_accepted"] < st["spec_tokens_drafted"]
    assert 1.0 <= st["spec_effective_k"] <= 4.0
    assert st["verify_shape_signatures"] <= 1
    total = s.cache.num_pages - 1
    s.cache.flush_prefix()
    assert s.cache.free_pages == total, "no leak from headroom + aliasing"


def test_adaptive_k_converges_on_acceptance(model_and_params):
    """On a perfectly cyclic stream (acceptance ~1) the effective K grows
    past its floor: spec_effective_k ends ABOVE the all-miss floor of 1 and
    the draft budget is actually being used."""
    prompt = [5, 9, 11, 17] * 4
    out, s = run_prompts(model_and_params, [prompt], prefix=False,
                         speculate_k=6, max_new_limit=24, max_new=20)
    st = s.stats()
    assert st["spec_rounds"] >= 2
    assert st["spec_effective_k"] > 1.5, (
        f"adaptive K never grew: {st['spec_effective_k']}"
    )
    assert st["spec_acceptance_rate"] > 0.3
