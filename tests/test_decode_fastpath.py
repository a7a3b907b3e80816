"""Decode fast path (ISSUE 11): ragged paged-attention kernel, chunked
prefill, on-device sampling.

The load-bearing claims, each tested directly:

  * kernel oracle — the Pallas ragged paged-attention decode kernel
    (interpret mode on CPU) matches the jnp dense-gather path to float
    tolerance across mixed lengths, ages and block-table layouts, and a
    serving session running through the kernel produces IDENTICAL tokens to
    the oracle session end to end;
  * chunked prefill — committing a prompt C tokens per engine step
    reproduces the whole-prompt prefill exactly (tokens equal), serves
    prompts beyond the largest bucket, and never skips a decode step: an
    already-decoding stream gains one token at EVERY engine step while a
    long prompt's chunks commit;
  * sampling — per-request seeded keys: same seed ⇒ same tokens, explicit
    temperature 0 ⇒ bitwise the greedy path, top_k=1 ⇒ greedy; an engine
    crash replay regenerates bitwise-identical SAMPLED tokens (the PR 10
    result-transparency contract extended beyond greedy);
  * admission guards — prompt+budget past LMConfig.max_len is rejected at
    the front door with a named error (silent XLA index-clamp regression);
  * shape discipline — chunked prefill + mixed greedy/sampled requests
    still record exactly ONE decode signature (zero recompiles)."""

import numpy as np
import pytest

from paddle_tpu.core import faults

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
    kw.setdefault("max_new_limit", 16)
    return ServingSession(model, params, **kw)


PROMPTS = [
    [1, 5, 9, 11],
    [1, 7],
    [1, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15, 16, 17, 18],
    [1, 40, 41, 42, 43, 44, 45, 46],
]


# -- ragged paged-attention kernel vs the jnp gather oracle -------------------


def _oracle_paged_attention(q, k_pages, v_pages, block_table, positions,
                            scale, n_heads):
    """The jnp dense-gather path, verbatim from ServableLM._paged_attention's
    CPU branch — duplicated here so the test fails if either side drifts."""
    import jax
    import jax.numpy as jnp

    s, kd = q.shape
    ps = k_pages.shape[1]
    hd = kd // n_heads
    qh = q.reshape(s, n_heads, hd)
    k_seq = k_pages[block_table].reshape(s, -1, n_heads, hd)
    v_seq = v_pages[block_table].reshape(s, -1, n_heads, hd)
    ctx_idx = jnp.arange(block_table.shape[1] * ps)
    mask = ctx_idx[None, :] <= positions[:, None]
    sc = jnp.einsum("shd,sthd->sht", qh, k_seq) * scale
    sc = jnp.where(mask[:, None, :], sc, -1e9)
    w = jax.nn.softmax(sc.astype(jnp.float32), -1)
    return jnp.einsum("sht,sthd->shd", w, v_seq).reshape(s, -1)


def _assert_kernel_matches_oracle(rng, bt, positions, ps, H=2, HD=8):
    """The kernel in interpret mode against the jnp gather oracle over one
    block table and one set of positions, at atol 1e-5. The kernel takes the
    whole [L, NP, PS, KD] pool and a layer index: the layer under test is
    buried behind a decoy so a wrong index shows."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.paged_attention import paged_attention_decode

    S, NP, KD = bt.shape[0], int(bt.max()) + 2, H * HD
    q = jnp.asarray(rng.randn(S, KD), jnp.float32)
    kp = jnp.asarray(rng.randn(NP, ps, KD), jnp.float32)
    vp = jnp.asarray(rng.randn(NP, ps, KD), jnp.float32)
    got = paged_attention_decode(
        q, jnp.stack([vp, kp]), jnp.stack([kp, vp]),
        jnp.asarray(bt), jnp.asarray(positions),
        layer=1, scale=1.0 / np.sqrt(HD), n_heads=H,
    )
    want = _oracle_paged_attention(
        q, kp, vp, jnp.asarray(bt), jnp.asarray(positions),
        1.0 / np.sqrt(HD), H,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("seed,ps,pmax", [(0, 8, 4), (1, 4, 7), (2, 16, 3)])
def test_kernel_matches_oracle_mixed_lengths(seed, ps, pmax):
    """Interpret-mode equality across mixed lengths, pages and block-table
    layouts — including empty slots (position 0, all-dump tables), partially
    filled pages, and out-of-order physical page assignments."""
    rng = np.random.RandomState(seed)
    S = 5
    NP = 1 + pmax * S
    # ragged: each slot owns a random number of shuffled physical pages
    bt = np.zeros((S, pmax), np.int32)
    free = list(rng.permutation(np.arange(1, NP)))
    positions = np.zeros(S, np.int32)
    for s_ in range(S - 1):  # last slot stays empty (dump table, position 0)
        n = rng.randint(1, pmax + 1)
        pages = [free.pop() for _ in range(n)]
        bt[s_, :n] = pages
        positions[s_] = rng.randint(0, n * ps)
    _assert_kernel_matches_oracle(rng, bt, positions, ps)


@pytest.fixture
def three_page_blocks(monkeypatch):
    """B = 3 pages of 8 tokens: the token cap the kernel states, lowered for
    the test so that a table of 7 pages is walked in blocks of 3, 3 and 1
    (B itself stays a function of the shapes and that cap)."""
    from paddle_tpu.ops.pallas import paged_attention

    monkeypatch.setattr(paged_attention, "BLOCK_TOKENS", 24)
    paged_attention._pages_per_block.cache_clear()
    assert paged_attention._pages_per_block(8, 128, 7) == 3
    yield
    paged_attention._pages_per_block.cache_clear()


def _owned(pmax, held):
    """A block table of len(held) slots: slot i owns held[i] distinct pages
    in a shuffled physical order, the rest of its row the dump page."""
    bt = np.zeros((len(held), pmax), np.int32)
    pages = iter(np.random.RandomState(11).permutation(
        np.arange(1, 1 + sum(held))
    ))
    for i, n in enumerate(held):
        bt[i, :n] = [next(pages) for _ in range(n)]
    return bt


# name -> (pages each slot holds, each slot's position); PS 8, PMAX 7, B 3:
# block 0 is tokens 0-23, block 1 tokens 24-47, block 2 the one page 48-55
BLOCK_WALK = {
    # the table's width is no multiple of B: the last block is one page wide
    "pmax_not_multiple_of_b": ([7, 6, 4, 1, 2], [50, 41, 30, 3, 12]),
    # contexts that end in the first and in the last token of a block
    "ends_on_block_edges": ([4, 6, 3, 3, 7], [24, 47, 23, 0, 48]),
    # full context: position PMAX * PS - 1
    "full_context": ([7, 7, 1], [55, 55, 7]),
    "every_slot_empty": ([0, 0, 0, 0], [0, 0, 0, 0]),
}


@pytest.mark.parametrize("case", sorted(BLOCK_WALK))
def test_kernel_matches_oracle_block_walk(case, three_page_blocks):
    """What walking a slot in blocks of B pages makes new: a ragged last
    block, contexts ending on a block's edges, full and empty tables."""
    held, positions = BLOCK_WALK[case]
    _assert_kernel_matches_oracle(
        np.random.RandomState(5), _owned(7, held),
        np.asarray(positions, np.int32), ps=8,
    )


def test_kernel_matches_oracle_aliased_pages(three_page_blocks):
    """Two slots name the SAME physical pages (the prefix cache's aliasing,
    read-only) at different positions, a third shares only their first
    block: each slot's walk fetches the pages for itself."""
    bt = _owned(7, [5, 5, 6])
    bt[1] = bt[0]
    bt[2, :3] = bt[0, :3]
    _assert_kernel_matches_oracle(
        np.random.RandomState(6), bt, np.asarray([39, 33, 44], np.int32), ps=8,
    )


def test_kernel_block_wider_than_table():
    """At the stated budget and cap B would be 64 pages of 8 tokens; a table
    of 7 pages holds it to 7, one block a slot, its tail past the slot's
    position never fetched."""
    from paddle_tpu.ops.pallas import paged_attention

    assert paged_attention._pages_per_block(8, 128, 7) == 7
    _assert_kernel_matches_oracle(
        np.random.RandomState(7), _owned(7, [7, 2, 0, 5]),
        np.asarray([55, 9, 0, 32], np.int32), ps=8,
    )


def test_pages_per_block_comes_from_the_shapes(caplog):
    """B at the geometries the chip sees, each logged once: the benchmark's
    cell (16 heads of 128), a --tp=4 shard of it (4 heads), chip_smoke's
    demo (2 heads of 16, padded to one lane tile) and its aligned one."""
    import logging

    from paddle_tpu.ops.pallas import paged_attention

    paged_attention._pages_per_block.cache_clear()
    with caplog.at_level(logging.INFO, logger="paddle_tpu"):
        assert paged_attention._pages_per_block(16, 2048, 128) == 8
        assert paged_attention._pages_per_block(16, 2048, 128) == 8
        assert paged_attention._pages_per_block(16, 512, 128) == 32
        assert paged_attention._pages_per_block(16, 128, 8) == 8
        assert paged_attention._pages_per_block(16, 2048, 8) == 8
    # a bfloat16 pool's page is half the bytes: ouro_2_6b's 80-page table
    # walks 16 pages a block where a float32 pool's walks 8
    assert paged_attention._pages_per_block(16, 2048, 80, 2) == 16
    assert paged_attention._pages_per_block(16, 2048, 80, 4) == 8
    lines = [
        r.getMessage() for r in caplog.records
        if "pages a block" in r.getMessage()
    ]
    assert len(lines) == 4
    assert lines[0].startswith(
        "paged_attention_decode: 8 pages a block (128 tokens"
    )
    assert "at most 16 blocks a slot" in lines[0]


def test_kernel_session_tokens_equal_oracle_session(
    model_and_params, monkeypatch
):
    """End to end: a serving session dispatching the Pallas kernel (interpret
    mode) generates IDENTICAL tokens to the jnp-oracle session over a mixed
    stream with joins and retires — greedy-decode argmax equality, the
    acceptance bar for the TPU fast path being CPU-verifiable."""
    oracle = make_session(model_and_params)
    ref = [oracle.submit(p, 8) for p in PROMPTS]
    oracle.run_until_idle()

    monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
    kernel = make_session(model_and_params)
    got = [kernel.submit(p, 8) for p in PROMPTS]
    kernel.run_until_idle()
    assert [h.tokens for h in got] == [h.tokens for h in ref]
    assert kernel.decode_shape_signatures() == 1


# -- chunked prefill ----------------------------------------------------------


def test_chunked_prefill_tokens_equal_whole_prompt(model_and_params):
    """chunk-by-chunk KV commit reproduces the whole-prompt prefill exactly:
    same tokens for every prompt, chunk size not dividing the prompt included."""
    ref = make_session(model_and_params)
    want = [ref.submit(p, 8) for p in PROMPTS]
    ref.run_until_idle()

    for chunk in (3, 8):
        s = make_session(model_and_params, prefill_chunk=chunk)
        got = [s.submit(p, 8) for p in PROMPTS]
        s.run_until_idle()
        assert [h.tokens for h in got] == [h.tokens for h in want], (
            f"chunked prefill (C={chunk}) must be result-transparent"
        )
        assert s.prefill_chunks_committed > 0


def test_chunked_prefill_serves_prompts_beyond_buckets(model_and_params):
    """Chunking lifts the bucket cap: a prompt longer than the largest
    bucket decodes correctly (vs the full-context greedy reference) where
    the unchunked session rejects it."""
    import jax.numpy as jnp

    model, params = model_and_params
    long_prompt = [1] + list(range(3, 60))  # 58 tokens > largest bucket 32

    plain = make_session(model_and_params)
    with pytest.raises(ValueError, match="bucket"):
        plain.submit(long_prompt, 4)

    s = make_session(model_and_params, prefill_chunk=8)
    h = s.submit(long_prompt, 8)
    s.run_until_idle()

    toks, out = list(long_prompt), []
    for _ in range(8):
        logits = model.forward_logits(params, jnp.asarray([toks], jnp.int32))
        nxt = int(jnp.argmax(logits[0, len(toks) - 1]))
        out.append(nxt)
        toks.append(nxt)
        if nxt == model.cfg.eos_id:
            break
    assert h.tokens == out


def test_bucket_gap_prompt_served_via_chunks(model_and_params):
    """A prompt in the gap between the largest bucket and a LARGER chunk
    size must be admitted (chunked), not rejected — with chunking on, no
    prompt up to max_len is unservable, and a longer prompt must never
    succeed where a shorter one fails."""
    import jax.numpy as jnp

    model, params = model_and_params
    s = make_session(
        model_and_params, prefill_buckets=(8, 16), prefill_chunk=64,
    )
    gap_prompt = [1] + list(range(3, 40))  # 38 tokens: > bucket 16, < chunk 64
    h = s.submit(gap_prompt, 6)
    s.run_until_idle()
    toks, out = list(gap_prompt), []
    for _ in range(6):
        logits = model.forward_logits(params, jnp.asarray([toks], jnp.int32))
        nxt = int(jnp.argmax(logits[0, len(toks) - 1]))
        out.append(nxt)
        toks.append(nxt)
        if nxt == model.cfg.eos_id:
            break
    assert h.tokens == out


def test_load_estimator_prices_in_flight_prefill(model_and_params):
    """The wait estimate also prices chunks STILL TO COMMIT for prompts
    already mid-prefill in slots — a tight-deadline request arriving behind
    a half-committed long prompt must see those engine steps in its
    estimate (the PR 10 overload-shed contract)."""
    s = make_session(model_and_params, prefill_chunk=8)
    long_prompt = [1] + list(range(3, 60))  # 58 tokens -> 8 chunks
    s.submit(long_prompt, 4)
    s.step()  # admit + first chunk: 7 chunks remain in flight
    sch = s.scheduler
    with sch.lock:
        sch._ewma_service_s = 1.0
        sch._ewma_step_s = 0.1
    base = 1.0  # empty queue, fits now: one service wave
    est = sch.estimate_wait_s(8, prompt_len=4)
    assert est == pytest.approx(base + 7 * 0.1), (
        "remaining in-flight chunks must be priced into the estimate"
    )


def test_no_decode_step_skipped_during_chunked_prefill(model_and_params):
    """The no-stall contract: while a long prompt's chunks commit, an
    already-decoding stream gains exactly one token at EVERY engine step —
    the decode stream never waits for the prefill."""
    s = make_session(model_and_params, prefill_chunk=8)
    short = s.submit(PROMPTS[0], 16)
    s.step()  # admit + prefill (first token) + decode dispatched
    s.step()  # the second token fetched behind the next dispatch (ISSUE 36)
    assert len(short.tokens) == 2
    long_prompt = [1] + list(range(3, 60))
    long = s.submit(long_prompt, 4)
    while long.tokens == [] and not short.done:
        n_before = len(short.tokens)
        s.step()
        assert len(short.tokens) == n_before + 1, (
            "a decode step was skipped while a chunk committed"
        )
    assert s.prefill_chunks_committed >= 7  # 58 tokens / C=8

    # the long prompt itself finishes correctly alongside
    s.run_until_idle()
    alone = make_session(model_and_params, prefill_chunk=8)
    h = alone.submit(long_prompt, 4)
    alone.run_until_idle()
    assert long.tokens == h.tokens


def test_load_estimator_prices_chunks(model_and_params):
    """The PR 10 wait estimate accounts for chunk count: with a long prompt
    queued, the estimated wait grows by its extra chunks' engine steps."""
    s = make_session(model_and_params, prefill_chunk=8)
    sch = s.scheduler
    assert sch._chunk_steps(4) == 0   # fits a bucket and one chunk
    assert sch._chunk_steps(8) == 0
    assert sch._chunk_steps(9) == 2   # chunked: ceil(9/8) chunk steps
    assert sch._chunk_steps(58) == 8
    # a prompt beyond every bucket chunks even when it fits ONE chunk
    gap = make_session(
        model_and_params, prefill_buckets=(8, 16), prefill_chunk=64,
    ).scheduler
    assert gap._chunk_steps(40) == 1
    with sch.lock:
        sch._ewma_service_s = 1.0
        sch._ewma_step_s = 0.1
    flat = sch.estimate_wait_s(16, prompt_len=8)
    chunky = sch.estimate_wait_s(66, prompt_len=58)
    assert chunky == pytest.approx(flat + 8 * 0.1)
    # TTFT estimate includes the request's own chunks too
    with sch.lock:
        t_flat = sch._estimate_ttft_wait_s(16, 8)
        t_chunky = sch._estimate_ttft_wait_s(66, 58)
    assert t_chunky == pytest.approx(t_flat + 8 * 0.1)


# -- on-device sampling -------------------------------------------------------


def test_sampling_deterministic_same_seed(model_and_params):
    """Same (seed, temperature, top_k) ⇒ same tokens, across sessions; a
    different seed diverges; explicit temperature 0 and top_k=1 are bitwise
    the greedy path."""
    def run(**kw):
        s = make_session(model_and_params)
        h = s.submit(PROMPTS[0], 12, **kw)
        s.run_until_idle()
        return h.tokens

    a = run(temperature=0.8, top_k=10, seed=42)
    b = run(temperature=0.8, top_k=10, seed=42)
    c = run(temperature=0.8, top_k=10, seed=7)
    greedy = run()
    assert a == b, "same seed must reproduce bitwise"
    assert a != c, "different seeds must diverge (fixed seeds chosen so)"
    assert run(temperature=0.0, seed=3) == greedy
    assert run(temperature=0.9, top_k=1, seed=3) == greedy, (
        "top_k=1 keeps only the argmax token"
    )


def test_sampling_batched_equals_alone(model_and_params):
    """Batching transparency extends to sampling: a sampled request's tokens
    are identical whether it runs alone or in a full mixed batch (explicit
    seeds — slot assignment must not leak into the draw)."""
    alone_tokens = []
    for i, p in enumerate(PROMPTS):
        s = make_session(model_and_params)
        h = s.submit(p, 8, temperature=0.7, top_k=8, seed=100 + i)
        s.run_until_idle()
        alone_tokens.append(h.tokens)

    batched = make_session(model_and_params)
    hs = [
        batched.submit(p, 8, temperature=0.7, top_k=8, seed=100 + i)
        for i, p in enumerate(PROMPTS)
    ]
    batched.run_until_idle()
    assert [h.tokens for h in hs] == alone_tokens


@pytest.mark.chaos
@pytest.mark.timeout(120)
def test_sampled_replay_bitwise_across_engine_restart(model_and_params):
    """The PR 10 crash-replay contract extended beyond greedy: a decode_raise
    mid-run restarts the engine, and the replayed SAMPLED requests reuse
    their seeds + token step indices — tokens bitwise-equal to unfaulted."""
    import time

    kw = dict(temperature=0.8, top_k=16)
    clean = make_session(model_and_params)
    ref = [clean.submit(p, 8, seed=50 + i, **kw) for i, p in enumerate(PROMPTS)]
    clean.run_until_idle()

    s = make_session(
        model_and_params, engine_stall_timeout_s=0.3, engine_restart_max=5
    )
    with faults.inject("decode_raise:step=3", seed=0) as inj:
        s.serve_forever()
        handles = [
            s.submit(p, 8, seed=50 + i, deadline_s=60.0, **kw)
            for i, p in enumerate(PROMPTS)
        ]
        deadline = time.monotonic() + 90
        for h in handles:
            assert h._event.wait(max(0.1, deadline - time.monotonic()))
        fired = dict(inj.fired)
    s.stop()
    assert fired.get("decode_raise", 0) >= 1
    assert s.engine_restarts >= 1
    assert [h.tokens for h in handles] == [h.tokens for h in ref], (
        "sampled replay must be bitwise result-transparent"
    )


# -- admission guards (ISSUE 11 satellite) ------------------------------------


def test_max_len_overflow_rejected_at_admission(model_and_params):
    """prompt + budget past LMConfig.max_len would index params['pos'] out
    of range inside jit — XLA clamps silently, producing wrong tokens. The
    session must reject at admission with a named error instead."""
    # chunking admits prompts beyond the buckets, so max_len is the only
    # guard left on that path — 90 + 16 > max_len 96
    s = make_session(model_and_params, prefill_chunk=8)
    with pytest.raises(ValueError, match="max_len"):
        s.submit([1] + [3] * 89, 16)
    # the boundary itself (80 + 16 == max_len) is fine
    h = s.submit([1] + [3] * 79, 16)
    assert h is not None
    h.cancel()
    # the bucketed path is covered by the constructor invariant: a session
    # whose buckets + budget could overflow max_len refuses to build at all
    from paddle_tpu.serving.session import ServingSession

    model, params = model_and_params
    with pytest.raises(ValueError, match="max_len"):
        ServingSession(
            model, params, max_slots=4, page_size=8,
            prefill_buckets=(8, 16, 64), max_new_limit=64,
        )


# -- shape discipline ---------------------------------------------------------


def test_one_decode_signature_with_chunks_and_sampling(model_and_params):
    """The zero-recompile gate survives the fast path: chunked prefill,
    greedy and sampled requests mixed — ONE decode signature."""
    s = make_session(model_and_params, prefill_chunk=8)
    for ln in s.buckets:
        s.submit([1] + [3] * (ln - 1), 4)
    s.run_until_idle()
    assert s.decode_shape_signatures() == 1

    hs = [
        s.submit(PROMPTS[0], 8),
        s.submit([1] + list(range(3, 60)), 8),  # chunked long prompt
        s.submit(PROMPTS[1], 8, temperature=0.9, top_k=4, seed=1),
        s.submit(PROMPTS[3], 8, temperature=0.5),
    ]
    s.run_until_idle()
    assert all(h.done for h in hs)
    assert s.decode_shape_signatures() == 1
