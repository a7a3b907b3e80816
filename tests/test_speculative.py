"""Prompt-lookup speculative decoding (ISSUE 16).

The load-bearing claims, each tested directly:

  * result transparency — tokens are IDENTICAL with speculation on vs off
    vs the naive full-context greedy reference, on repetitive prompts (where
    drafts land), random prompts (where they mostly don't), and a mixed
    batch of both; `speculate_k=0` bitwise-recovers the non-speculative
    engine;
  * replay-stable sampling — at temperature > 0 a drafted-and-accepted
    token is sampled through the same fold_in(key, emitted_token_index) as
    the token the plain decode loop would have emitted, so seeded sampling
    is ALSO identical with speculation on vs off;
  * one verify program — every speculative round, whatever the draft
    length or request mix, records exactly ONE [1, K+1] verify_chunk shape
    signature, and the decode loop stays at its one signature;
  * paging — a verify round grows the slot to its K+1 positions and what
    a rejection leaves over is trimmed back to the pool, and retirement
    returns everything;
  * the drafter — pure function of the committed tokens: indexes n-grams
    incrementally, drafts the continuation after the PREVIOUS occurrence
    (never self-matching the live suffix), slides its window so cyclic
    tails draft whole cycles, and returns [] rather than guessing."""

import numpy as np
import pytest

pytestmark = pytest.mark.serving

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
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_buckets", (8, 16, 32))
    kw.setdefault("max_new_limit", 24)
    return ServingSession(model, params, **kw)


def greedy_reference(model, params, prompt, max_new):
    import jax.numpy as jnp

    toks, out = list(prompt), []
    for _ in range(max_new):
        logits = model.forward_logits(params, jnp.asarray([toks], jnp.int32))
        nxt = int(jnp.argmax(logits[0, len(toks) - 1]))
        out.append(nxt)
        toks.append(nxt)
        if nxt == model.cfg.eos_id:
            break
    return out


# repetitive prompts (drafts land), random-ish prompts (drafts mostly miss),
# and a short prompt below the n-gram threshold (never drafts at round 1)
REPETITIVE = [
    [1] + [5, 9, 11] * 5,
    [1] + [7, 8] * 7,
    [1] + [40, 41, 42, 43] * 4,
]
RANDOM = [
    [1, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15, 16, 17, 18],
    [1, 90, 2, 90],
    [1, 7],
]


def _run_all(session, prompts, max_new, **submit_kw):
    handles = [session.submit(p, max_new, **submit_kw) for p in prompts]
    session.run_until_idle()
    return [h.tokens for h in handles]


def test_speculative_greedy_equals_nonspec_and_reference(model_and_params):
    """The acceptance bit: speculation changes STEP COUNT, never tokens —
    on prompts where drafting works, where it doesn't, and mixed."""
    model, params = model_and_params
    prompts = REPETITIVE + RANDOM

    spec = make_session(model_and_params, speculate_k=4)
    got_spec = _run_all(spec, prompts, 12)
    assert spec.spec_rounds >= 1, "workload never exercised speculation"

    base = make_session(model_and_params, speculate_k=0)
    got_base = _run_all(base, prompts, 12)
    assert got_spec == got_base
    assert base.spec_rounds == 0 and base.verify_shape_signatures() == 0

    ref = [greedy_reference(model, params, p, 12) for p in prompts]
    assert got_spec == ref


def test_speculative_sampling_replay_stable(model_and_params):
    """Seeded sampling at temperature > 0: an accepted draft position uses
    the SAME fold_in(seed-key, emitted_token_index) sample the plain decode
    loop would draw, so tokens are identical spec vs non-spec — the replay
    contract that keeps crash recovery and router failover bitwise."""
    kw = dict(temperature=0.8, top_k=20, seed=1234)
    spec = make_session(model_and_params, speculate_k=4)
    got_spec = _run_all(spec, REPETITIVE, 12, **kw)
    base = make_session(model_and_params, speculate_k=0)
    got_base = _run_all(base, REPETITIVE, 12, **kw)
    assert got_spec == got_base
    # sampled continuations of repetitive prompts still draft (the sampled
    # tail re-walks its own n-grams often enough) — otherwise this test
    # silently proves nothing
    assert spec.spec_rounds >= 1


def test_one_verify_signature_and_decode_stays_compiled(model_and_params):
    """Every verify round shares ONE compiled [1, K+1] program regardless
    of draft length or batch mix, and speculation adds NOTHING to the
    decode program's signature count."""
    s = make_session(model_and_params, speculate_k=4)
    _run_all(s, REPETITIVE + RANDOM, 12)
    assert s.spec_rounds >= 2
    assert s.verify_shape_signatures() == 1
    sigs = s.decode_shape_signatures()
    _run_all(s, REPETITIVE, 10)
    assert s.decode_shape_signatures() == sigs
    assert s.verify_shape_signatures() == 1


def test_speculate_k0_is_todays_engine(model_and_params):
    """`speculate_k=0` must recover the pre-ISSUE-16 engine exactly: no
    drafter state, no verify executable, no page grown for K+1 positions."""
    s = make_session(model_and_params, speculate_k=0)
    got = _run_all(s, RANDOM, 8)
    assert all(len(t) > 0 for t in got)
    st = s.stats()
    assert st["speculate_k"] == 0
    assert st["spec_rounds"] == 0 and st["spec_tokens_drafted"] == 0
    assert st["verify_shape_signatures"] == 0
    assert st["spec_pages_trimmed"] == 0


def test_spec_pages_reserved_trimmed_and_recycled(model_and_params, monkeypatch):
    """A verify round GROWS the slot to its K+1 positions first and trims
    back what a rejection leaves past the accepted frontier (ISSUE 34: no
    +K headroom is reserved at admission); everything returns at
    retirement — later requests reuse the same pool with nothing leaked."""
    from paddle_tpu.serving.speculation import PromptLookupDrafter

    s = make_session(model_and_params, speculate_k=8, page_size=8)
    free0 = s.cache.free_pages
    want = _run_all(s, REPETITIVE, 16)[0]
    assert s.cache.free_pages == free0, "pages leaked across retirement"
    # Whether the three above trimmed anything is their drafts' luck: an
    # accepted draft keeps the pages its round grew to. Build the case: a
    # request whose every draft provably misses (one token that is NOT the
    # model's next: `want` is its greedy continuation, speculation being
    # result-transparent) commits exactly one token a round, so every round
    # grows to K+1 positions and gives back all but the next write's page.
    prompt = REPETITIVE[0]
    assert len(prompt) == 16 and len(want) == 16, "case needs 4 full pages"
    monkeypatch.setattr(
        PromptLookupDrafter, "draft",
        lambda self, k: [(want[len(self) - len(prompt)] + 1) % VOCAB],
    )
    trims = []  # (pages held, total_len asked, pages freed, still active)
    real_trim = s.cache.trim

    def spy(slot, total_len):
        held = len(s.cache.slot_pages(slot))
        freed = real_trim(slot, total_len)
        active = slot in dict(s.scheduler.active_slots())
        trims.append((held, total_len, freed, active))
        return freed

    monkeypatch.setattr(s.cache, "trim", spy)
    rounds0, accepted0 = s.spec_rounds, s.spec_tokens_accepted
    h = s.submit(prompt, 16)
    s.run_until_idle()
    assert h.tokens == want
    # precondition: tokens 2..15 each came from a verify round that rejected
    # its draft, so the slot entered the next round with exactly one left
    assert s.spec_rounds - rounds0 == 14
    assert s.spec_tokens_accepted == accepted0
    # ... the first with its last token at position 16: the round grew the
    # slot to positions 16..24 (25 tokens, 4 pages), one token was accepted,
    # and the trim kept the pages of 18 tokens (17 written + the next
    # write) and gave the 4th back while the request was in flight
    assert len(trims) == 14 and trims[0] == (4, 18, 1, True), trims
    for i, (held, asked, freed, active) in enumerate(trims):
        assert active and asked == 18 + i
        assert held == s.cache.pages_needed(16 + i + 8 + 1)
        assert freed == held - s.cache.pages_needed(asked)
    assert s.cache.free_pages == free0
    # 13 of the 14 rounds reached into a page the accepted token did not
    trimmed0 = sum(t[2] for t in trims)
    assert trimmed0 == 13 and s.spec_pages_trimmed >= trimmed0
    # pool still serves follow-up work after trim/release churn
    h = s.submit(REPETITIVE[0], 8)
    s.run_until_idle()
    assert len(h.tokens) == 8
    assert s.cache.free_pages == free0


def test_drafter_drafts_previous_occurrence_not_self():
    """The live suffix's own (latest) index entry is the suffix itself; a
    draft must come from the occurrence BEFORE it — the period-1 case that
    breaks a naive latest-only index."""
    from paddle_tpu.serving.speculation import PromptLookupDrafter

    d = PromptLookupDrafter(ngram=2)
    d.feed([7, 7, 7, 7])
    # suffix (7,7) latest occurrence IS the tail; previous predicts 7s
    assert d.draft(3) == [7, 7, 7]


def test_drafter_cycles_and_misses():
    from paddle_tpu.serving.speculation import PromptLookupDrafter

    d = PromptLookupDrafter(ngram=2)
    d.feed([1, 5, 9, 11, 5, 9, 11, 5, 9])
    # sliding window drafts the WHOLE cycle forward, past the match end
    assert d.draft(6) == [11, 5, 9, 11, 5, 9]
    # an unseen suffix refuses to guess
    miss = PromptLookupDrafter(ngram=2)
    miss.feed([1, 2, 3, 4, 5])
    assert miss.draft(4) == []
    # below the n-gram threshold there is nothing to look up
    tiny = PromptLookupDrafter(ngram=3)
    tiny.feed([1, 2])
    assert tiny.draft(4) == []


def test_drafter_sync_is_incremental_and_deterministic():
    """sync() feeds only the unseen tail, and the draft is a pure function
    of the committed sequence — two drafters shown the same history in
    different increments agree exactly (the replay contract)."""
    from paddle_tpu.serving.speculation import PromptLookupDrafter

    prompt = [1, 5, 9, 11, 5, 9, 11]
    gen = [5, 9, 11, 5]
    a = PromptLookupDrafter(ngram=2)
    for i in range(len(gen) + 1):
        a.sync(prompt, gen[:i])
    b = PromptLookupDrafter(ngram=2)
    b.sync(prompt, gen)
    assert len(a) == len(b) == len(prompt) + len(gen)
    assert a.draft(5) == b.draft(5)


def test_eos_truncates_committed_draft(model_and_params):
    """A drafted continuation that crosses EOS commits only up to the stop
    token — spec and non-spec agree on the finish reason and length."""
    spec = make_session(model_and_params, speculate_k=6)
    base = make_session(model_and_params, speculate_k=0)
    # long budgets so any EOS the model emits lands mid-budget
    for p in REPETITIVE + RANDOM:
        hs = spec.submit(p, 20)
        spec.run_until_idle()
        hb = base.submit(p, 20)
        base.run_until_idle()
        assert hs.tokens == hb.tokens
        assert hs.finish_reason == hb.finish_reason
        eos = spec.cfg.eos_id
        if eos in hs.tokens:
            assert hs.tokens.index(eos) == len(hs.tokens) - 1
