"""Continuous-batching serving runtime (ISSUE 6).

The load-bearing claims, each tested directly:

  * batching transparency — a request's generated tokens are IDENTICAL
    whether it ran alone, in a full batch, or joined/retired mid-stream
    (per-slot computation never crosses the slot dimension), and they match
    a naive full-context greedy reference;
  * one decode program — a mixed-length request stream records exactly one
    decode-step shape signature (the PR-1 RecompileStats zero-recompile
    assertion);
  * KV paging — pages are handed out as tokens are written, recycled at retirement,
    and reused by later requests;
  * admission control — queue bounds, per-tenant token quotas and
    concurrency caps reject at the front door;
  * the front-end — register/heartbeat tenant leases over the master's
    line-JSON plane, blocking generate, submit/poll, eviction cancelling
    queued work;
  * GenerationSession — build/load once, generate many (run_generation's
    rebuild-per-call hoisted out)."""

import threading
import time

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
    kw.setdefault("max_new_limit", 16)
    return ServingSession(model, params, **kw)


def greedy_reference(model, params, prompt, max_new):
    """Naive sequential decode: full-context forward per token — the
    semantics `run_generation`-style serving gives one request at a time.
    The context is zero-padded to one fixed length so the forward compiles
    once, not once per op per token (causal masking: padding cannot leak
    into a valid position — see ServableLM.forward_logits)."""
    import jax
    import jax.numpy as jnp

    width = -(-(len(prompt) + max_new) // 16) * 16
    forward = jax.jit(model.forward_logits)
    toks, out = list(prompt), []
    for _ in range(max_new):
        padded = toks + [0] * (width - len(toks))
        logits = forward(params, jnp.asarray([padded], jnp.int32))
        nxt = int(jnp.argmax(logits[0, len(toks) - 1]))
        out.append(nxt)
        toks.append(nxt)
        if nxt == model.cfg.eos_id:
            break
    return out


PROMPTS = [
    [1, 5, 9, 11],
    [1, 7],
    [1, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15, 16, 17, 18],
    [1, 40, 41, 42, 43, 44, 45, 46],
    [1, 90, 2, 90],  # early EOS-ish content; exercises retire-before-others
    [1] + list(range(3, 30)),
]


def test_batched_equals_sequential_and_reference(model_and_params):
    """The acceptance bit: dynamic batching changes THROUGHPUT, never
    tokens. All-at-once == one-at-a-time == full-context reference."""
    model, params = model_and_params

    batched = make_session(model_and_params)
    handles = [batched.submit(p, 10) for p in PROMPTS]
    batched.run_until_idle()
    got_batched = [h.tokens for h in handles]

    sequential = make_session(model_and_params)
    got_sequential = []
    for p in PROMPTS:
        h = sequential.submit(p, 10)
        sequential.run_until_idle()
        got_sequential.append(h.tokens)

    assert got_batched == got_sequential
    ref = [greedy_reference(model, params, p, 10) for p in PROMPTS]
    assert got_batched == ref


def test_midstream_join_and_retire(model_and_params):
    """A request joining at a step boundary neither perturbs the running
    request (bitwise) nor waits for it (retires first when shorter)."""
    s = make_session(model_and_params)
    long = s.submit(PROMPTS[2], 16)
    # advance a few decode steps before the join
    for _ in range(4):
        s.step()
    assert not long.done
    short = s.submit(PROMPTS[1], 3)
    order = []

    while s.scheduler.has_work():
        s.step()
        for name, h in (("short", short), ("long", long)):
            if h.done and name not in order:
                order.append(name)
    assert order == ["short", "long"], "shorter joiner must retire first"

    # bitwise unperturbed vs running each alone
    alone = make_session(model_and_params)
    h_long = alone.submit(PROMPTS[2], 16)
    alone.run_until_idle()
    h_short = alone.submit(PROMPTS[1], 3)
    alone.run_until_idle()
    assert long.tokens == h_long.tokens
    assert short.tokens == h_short.tokens


def test_kv_page_recycling(model_and_params):
    """An admission holds its PROMPT's pages and no more (ISSUE 34); the
    slot grows by a page whenever a write crosses into one, never past the
    pages of what is written plus the next write; retirement returns all."""
    s = make_session(model_and_params)
    cache, total_free = s.cache, s.cache.free_pages
    h = s.submit(PROMPTS[0], 8)
    s._admit()
    used_first = cache.slot_pages(0)
    assert len(used_first) == cache.pages_needed(len(PROMPTS[0]))
    assert cache.free_pages == total_free - len(used_first)
    held = set(used_first)
    while s.scheduler.has_work():
        s.step()
        act = s.scheduler.slots[0]
        if act is not None:
            pages = cache.slot_pages(0)
            held |= set(pages)
            # written tokens' pages, plus at most the page of the next write
            assert cache.pages_needed(act.next_pos) <= len(pages) \
                <= cache.pages_needed(act.next_pos + 1)
            assert cache.free_pages == total_free - len(pages)
    assert h.done
    assert len(held) == cache.pages_needed(len(PROMPTS[0]) + 8 - 1)
    assert cache.free_pages == total_free, "retirement must return pages"

    # a later request must REUSE the recycled physical pages
    s.submit(PROMPTS[1], 8)
    s._admit()
    reused = cache.slot_pages(0)
    assert set(reused) <= held
    s.run_until_idle()
    assert cache.free_pages == total_free


def test_zero_decode_recompiles_on_mixed_stream(model_and_params):
    """Variable lengths, variable ages, joins and retires — ONE decode
    signature for the whole lifetime (the compiled-program-sharing claim)."""
    s = make_session(model_and_params)
    # warmup: one request per bucket
    for ln in s.buckets:
        s.submit([1] + [3] * (ln - 1), 4)
    s.run_until_idle()
    assert s.decode_shape_signatures() == 1
    sigs0 = s.decode_shape_signatures()

    handles = [s.submit(p, 12) for p in PROMPTS * 2]
    s.run_until_idle()
    assert all(h.done for h in handles)
    assert s.decode_shape_signatures() - sigs0 == 0
    assert s.decode_shape_signatures() == 1


def test_prefill_compiles_bounded_by_buckets(model_and_params):
    """Prompt lengths 2..18 land in 3 buckets -> at most 3 prefill shapes
    (the 'few padded lengths' contract; jit's cache is keyed on shape)."""
    s = make_session(model_and_params)
    for ln in (2, 3, 5, 8, 9, 12, 16, 17, 18):
        s.submit([1] + [3] * (ln - 1), 2)
    s.run_until_idle()
    try:
        n = s._prefill._cache_size()
    except AttributeError:
        pytest.skip("jit cache introspection unavailable on this jax")
    assert n <= len(s.buckets)


def test_quota_and_queue_rejection(model_and_params):
    from paddle_tpu.serving.quota import QuotaExceeded, TenantQuotas

    quotas = TenantQuotas(token_capacity=40, tokens_per_s=0.0, max_concurrent=2)
    s = make_session(model_and_params, quotas=quotas, max_queue=3)

    # token quota: prompt 4 + max_new 16 = 20 per request; third exceeds 40
    a = s.submit(PROMPTS[0], 16, tenant="t1")
    b = s.submit(PROMPTS[0], 16, tenant="t1")  # noqa: F841 — holds quota
    with pytest.raises(QuotaExceeded) as ei:
        s.submit(PROMPTS[0], 16, tenant="t1")
    assert ei.value.reason in ("tokens", "concurrency")
    # another tenant is unaffected (per-tenant bucket)
    c = s.submit(PROMPTS[1], 4, tenant="t2")
    s.run_until_idle()
    assert a.done and c.done
    assert s.scheduler.rejected == 1

    # refund accounting: releasing returns UNUSED tokens (early EOS) and
    # frees the concurrency hold — after a manual refund t1 can submit again
    quotas.release("t1", unused_tokens=20)
    quotas.admit("t1", 20)
    quotas.release("t1", 20)

    # queue bound: an unserved flood rejects at max_queue
    s2 = make_session(model_and_params, max_queue=2)
    s2.scheduler.submit([1, 2], 2, "x")
    s2.scheduler.submit([1, 2], 2, "x")
    with pytest.raises(QuotaExceeded) as ei:
        s2.scheduler.submit([1, 2], 2, "x")
    assert ei.value.reason == "queue"


def test_oversize_requests_rejected_up_front(model_and_params):
    s = make_session(model_and_params)
    with pytest.raises(ValueError):
        s.submit([1] * 33, 4)  # beyond the largest bucket
    with pytest.raises(ValueError):
        s.submit([], 4)


@pytest.mark.timeout(120)
def test_server_roundtrip_and_eviction(model_and_params):
    """The line-JSON front-end: register/lease, blocking generate,
    submit/poll, stats, and lease-expiry cancelling queued requests."""
    from paddle_tpu.serving.quota import TenantQuotas
    from paddle_tpu.serving.server import ServingClient, ServingServer

    s = make_session(
        model_and_params,
        quotas=TenantQuotas(max_concurrent=8),
    )
    srv = ServingServer(session=s, lease_s=1.0, require_register=True).start()
    try:
        c = ServingClient(srv.address)
        with pytest.raises(RuntimeError):
            c.generate(PROMPTS[0], 4)  # unregistered
        # a fabricated tenant_id must NOT pass for registered (it would mint
        # itself a fresh quota bucket per request)
        c.tenant_id = "tr-forged-999"
        with pytest.raises(RuntimeError):
            c.generate(PROMPTS[0], 4)
        c.tenant_id = None
        tid = c.register()
        assert tid
        r = c.generate(PROMPTS[0], 6)
        assert r["done"] and len(r["tokens"]) <= 6
        # async submit/poll
        rid = c.submit(PROMPTS[1], 4)
        for _ in range(200):
            p = c.poll(rid)
            if p.get("done"):
                break
            time.sleep(0.02)
        assert p["done"] and p["finish_reason"] in ("length", "eos")
        st = c.stats()
        assert st["live_tenants"] >= 1 and st["completed"] >= 2
        # retry-exactness: a resent submit with the same idempotency key
        # reattaches to the SAME request (no duplicate queueing/charging)
        r1 = srv.dispatch(
            "submit",
            {"prompt": PROMPTS[1], "max_new_tokens": 2, "client_req_id": "k1"},
            tid,
        )
        r2 = srv.dispatch(
            "submit",
            {"prompt": PROMPTS[1], "max_new_tokens": 2, "client_req_id": "k1"},
            tid,
        )
        assert r1["request_id"] == r2["request_id"]
        # identical tokens through the wire as in-process
        direct = make_session(model_and_params)
        h = direct.submit(PROMPTS[0], 6)
        direct.run_until_idle()
        assert r["tokens"] == h.tokens
        c.close()

        # eviction: stop the ENGINE so a queued request cannot start, let the
        # lease lapse, and verify the reaper cancels the tenant's queued work
        s.stop()
        c2 = ServingClient(srv.address)
        t2 = c2.register()
        rid2 = c2.submit(PROMPTS[0], 4)
        c2.close()  # silent from here on — the lease must lapse
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            with srv._handles_lock:
                h2 = srv._handles.get(rid2)
            if h2 is not None and h2.done:
                break
            time.sleep(0.05)
        assert h2 is not None and h2.status == h2.CANCELLED
        assert srv.membership.evicted >= 1
        assert t2 != tid
    finally:
        srv.stop()


@pytest.mark.timeout(180)
def test_cli_serve_subprocess(tmp_path):
    """`python -m paddle_tpu serve --demo` as a real OS process: prints its
    address, serves a generate RPC, drains cleanly on SIGTERM."""
    import json
    import os
    import signal
    import subprocess
    import sys

    from paddle_tpu.serving.server import ServingClient

    proc = subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu", "serve", "--demo",
         "--max_slots=2", "--page_size=8", "--prefill_buckets=8,16",
         "--max_new_limit=8"],
        stdout=subprocess.PIPE, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    try:
        line = proc.stdout.readline()
        addr = json.loads(line)["address"]
        c = ServingClient((addr[0], int(addr[1])))
        r = c.generate([1, 5, 9], max_new_tokens=6, timeout_s=60.0)
        assert r["done"] and 0 < len(r["tokens"]) <= 6
        c.close()
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            raise
    assert proc.returncode == 0


def test_generation_session_builds_once(monkeypatch):
    """GenerationSession: the Network is initialized and the checkpoint
    loaded ONCE; repeat generates reuse the same parameter buffers and
    reproduce run_generation exactly."""
    import jax.numpy as jnp

    from paddle_tpu.nn import layers as L
    from paddle_tpu.nn.graph import reset_name_scope
    from paddle_tpu.trainer import generation as G

    reset_name_scope()
    x = L.Data("x", shape=(4,))
    out = L.Fc(x, 3, act=None, name="gen_out")

    class _Ctx:
        evaluators = []

    class _PC:
        outputs = [out]
        context = _Ctx()

    sess = G.GenerationSession(_PC())
    batch = {"x": np.ones((2, 4), np.float32)}
    assert not sess.built
    assert sess.generate(batch) == {}  # no printers declared -> nothing written
    assert sess.built
    params_first = sess._params
    sess.generate(batch)
    assert sess._params is params_first, "repeat generate must not re-init"

    # the wrapper path is the same code
    assert G.run_generation(_PC(), batch) == {}

    # init counted: a second generate must not call Network.init again
    calls = {"n": 0}
    real_init = sess.net.init

    def counting_init(*a, **k):
        calls["n"] += 1
        return real_init(*a, **k)

    sess2 = G.GenerationSession(_PC())
    monkeypatch.setattr(sess2.net, "init", counting_init)
    sess2.generate(batch)
    sess2.generate(batch)
    assert calls["n"] <= 1


# -- one decode step in flight (ISSUE 36) --------------------------------------------
#
# The engine dispatches decode step N before it fetches step N-1's tokens.
# What that may never change is a token: over the four served models, a
# session driven by step() serves what the full-context reference gives
# across everything that is learned one step late (an EOS, a cancel, an
# expiry), a dry pool that preempts, the shortest budgets, and both ways an
# engine runs to idleness. One parametrised test over the models and the runs.

FLIGHT_PROMPTS = [
    [1, 17, 61, 5, 88, 40, 9, 33, 50, 61, 7],
    [1, 5, 9, 11],
    [1, 7, 70, 23, 8, 3],
    [1, 40, 41, 42, 43, 44, 45, 46, 47],
    [1, 90, 12, 90, 31],
    [1, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14],
]
FLIGHT_NEW = 12


def _tiny_model(kind):
    """(model, params) at the tiny configuration its own test file serves."""
    import jax

    if kind == "servable":
        from paddle_tpu.serving.model import LMConfig, ServableLM

        model = ServableLM(LMConfig(vocab=VOCAB, n_layers=2, d_model=32,
                                    n_heads=2, max_len=96))
        return model, model.init_params(jax.random.PRNGKey(0))
    if kind == "looped":
        from test_looped_lm import tiny
    elif kind == "hybrid":
        from test_hybrid_moe_lm import tiny
    else:
        from test_window_moe_lm import tiny
    return tiny()


@pytest.fixture(scope="module", params=["servable", "looped", "hybrid", "window"])
def flight_model(request):
    """The model with its EOS set to a token that ONE prompt's greedy answer
    reaches at its third draw or later (and, where the tiny weights allow,
    no other answer at all), so that a request samples `eos_id` in the
    middle of a run while others go on: (model, params, the prompts with
    that one first, what the full-context reference gives for each)."""
    import dataclasses

    model, params = _tiny_model(request.param)
    endless = type(model)(dataclasses.replace(model.cfg, eos_id=-1))
    full = [greedy_reference(endless, params, p, FLIGHT_NEW) for p in FLIGHT_PROMPTS]
    found = [(sum(w[k] in other for other in full), a, k)
             for a, w in enumerate(full) for k in range(2, 7)
             if w[k] not in w[:k]]
    _, a, k = min(found)
    eos = full[a][k]
    model = type(model)(dataclasses.replace(model.cfg, eos_id=eos))
    prompts = [FLIGHT_PROMPTS[a]] + FLIGHT_PROMPTS[:a] + FLIGHT_PROMPTS[a + 1:]
    want = [greedy_reference(model, params, p, FLIGHT_NEW) for p in prompts]
    assert want[0] == full[a][:k + 1]
    return model, params, prompts, want


def _flight_session(flight_model, **kw):
    from paddle_tpu.serving.session import ServingSession

    model, params, _, _ = flight_model
    kw = dict(dict(max_slots=4, page_size=4, prefill_buckets=(8, 16),
                   max_new_limit=24), **kw)
    return ServingSession(model, params, **kw)


def _wasted_by_eos(want, budgets, eos):
    """Requests whose EOS is found with their next lane already dispatched:
    every EOS but a first token's (retired at the admission) and a budget's
    last (its lane was never dispatched again)."""
    return sum(1 for w, n in zip(want, budgets)
               if w[-1] == eos and 1 < len(w) < n)


def _nothing_in_flight(s):
    assert s._in_flight is None
    assert not s.scheduler.has_work()
    assert s.stats()["pages_in_use"] == 0


def _run_eos(fm):
    """(a) a request samples `eos_id` while others continue: its handle ends
    AT the EOS, the lane dispatched behind it is dropped and counted, and
    the next tenant of its slot (and of its slot's state) is exact."""
    model, _, prompts, want = fm
    s = _flight_session(fm)
    hs = [s.submit(p, FLIGHT_NEW) for p in prompts]  # 6 requests, 4 slots
    slot_of = {}
    while s.scheduler.has_work():
        s.step()
        for slot, act in s.scheduler.active_slots():
            slot_of.setdefault(act.handle.request_id, slot)
    assert [h.tokens for h in hs] == want
    eos = model.cfg.eos_id
    assert hs[0].tokens[-1] == eos and hs[0].finish_reason == "eos"
    assert eos not in hs[0].tokens[:-1]
    # the first to finish: a queued request took its slot, and is exact
    assert slot_of[hs[0].request_id] in (slot_of[hs[4].request_id],
                                         slot_of[hs[5].request_id])
    st = s.stats()
    assert st["wasted_lanes"] == _wasted_by_eos(want, [FLIGHT_NEW] * 6, eos) >= 1
    assert st["decode_shape_signatures"] == 1
    _nothing_in_flight(s)


def _run_gone(fm):
    """(b) a cancel() and a deadline expiry, each with a lane in flight: the
    lanes are dropped, no token is wrong or extra, the slots' next tenants
    are exact."""
    _, _, prompts, want = fm
    s = _flight_session(fm)
    cancelled = s.submit(prompts[1], FLIGHT_NEW)
    expired = s.submit(prompts[2], FLIGHT_NEW, deadline_s=1000.0)
    kept = s.submit(prompts[3], FLIGHT_NEW)
    for _ in range(3):
        s.step()
    flying = {act.handle.request_id for _, act in s._in_flight[1]}
    assert {cancelled.request_id, expired.request_id} <= flying
    before = s.stats()["wasted_lanes"]
    assert cancelled.cancel()
    s.step(now=time.monotonic() + 2000.0)  # past the deadline: both are reaped
    assert cancelled.done and cancelled.status == "cancelled"
    assert expired.done and expired.finish_reason == "deadline"
    later = [s.submit(p, FLIGHT_NEW) for p in prompts[4:]]
    s.run_until_idle()
    assert s.stats()["wasted_lanes"] - before >= 2
    for h, w in ((cancelled, want[1]), (expired, want[2])):
        assert 0 < len(h.tokens) < len(w) and h.tokens == w[:len(h.tokens)]
    assert [kept.tokens] + [h.tokens for h in later] == want[3:]
    _nothing_in_flight(s)


def _run_dry_pool(fm):
    """(c) a dry pool preempts and replays (tests/test_kv_paging.py's set-up):
    the victim's last token is on its handle before it replays."""
    _, _, prompts, want = fm
    s = _flight_session(fm, num_pages=9)
    hs = [s.submit(p, FLIGHT_NEW) for p in prompts]
    seen = [0] * len(hs)
    while s.scheduler.has_work():
        s.step()
        for i, h in enumerate(hs):
            assert len(h.tokens) >= seen[i], "handle.tokens shrank"
            seen[i] = len(h.tokens)
    st = s.stats()
    assert st["preemptions"] > 0 and st["replayed_tokens"] > 0
    assert [h.tokens for h in hs] == want
    assert st["decode_shape_signatures"] == 1
    _nothing_in_flight(s)


def _run_short_budgets(fm):
    """(d) max_new_tokens 1 and 2: no decode step, one decode step."""
    _, _, prompts, want = fm
    s = _flight_session(fm)
    one = s.submit(prompts[1], 1)
    s.run_until_idle()
    assert one.tokens == want[1][:1] and s.stats()["decode_steps"] == 0
    two = s.submit(prompts[2], 2)
    s.run_until_idle()
    assert two.tokens == want[2][:2] and s.stats()["decode_steps"] == 1
    # beside a request that goes on
    hs = [s.submit(prompts[3], FLIGHT_NEW), s.submit(prompts[1], 1),
          s.submit(prompts[2], 2)]
    s.run_until_idle()
    assert [h.tokens for h in hs] == [want[3], want[1][:1], want[2][:2]]
    assert all(h.finish_reason in ("length", "eos") for h in hs)
    assert s.stats()["wasted_lanes"] == 0
    _nothing_in_flight(s)


def _run_to_idleness(fm):
    """(e) run_until_idle and the supervised serve_forever both deliver the
    last token and leave nothing in flight."""
    _, _, prompts, want = fm
    s = _flight_session(fm)
    hs = [s.submit(p, FLIGHT_NEW) for p in prompts[:3]]
    s.run_until_idle()
    assert [h.tokens for h in hs] == want[:3]
    _nothing_in_flight(s)
    s.serve_forever()
    try:
        hs = [s.submit(p, FLIGHT_NEW) for p in prompts[3:]]
        assert [h.result(timeout=120.0) for h in hs] == want[3:]
        deadline = time.monotonic() + 30.0
        while s._in_flight is not None and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        s.stop()
    _nothing_in_flight(s)


@pytest.mark.parametrize("run", [_run_eos, _run_gone, _run_dry_pool,
                                 _run_short_budgets, _run_to_idleness],
                         ids=lambda f: f.__name__[5:])
def test_one_step_in_flight_serves_the_references_tokens(flight_model, run):
    run(flight_model)


def test_overlapped_steps_and_fetches_are_counted(model_and_params, monkeypatch):
    """Over K consecutive decode-only steps `overlapped_steps` rises by K-1
    and the host fetches ONE token array a step; with speculation on every
    step drains, so it stays 0; one decode signature and one executable."""
    from paddle_tpu.serving import session as session_mod

    fetched = []

    class CountingNumpy:
        def __getattr__(self, name):
            return getattr(np, name)

        @staticmethod
        def asarray(a, *args, **kw):
            fetched.append(a)
            return np.asarray(a, *args, **kw)

    s = make_session(model_and_params)
    hs = [s.submit(p, 12) for p in PROMPTS[:3]]
    s.step()  # the admissions and the first decode dispatch: nothing to fetch yet
    assert s.stats()["decode_steps"] == 1 and s.stats()["overlapped_steps"] == 0
    monkeypatch.setattr(session_mod, "np", CountingNumpy())
    k = 6
    for _ in range(k):
        s.step()
    st = s.stats()
    assert st["decode_steps"] == 1 + k and st["overlapped_steps"] == k
    assert len(fetched) == k and all(a.shape == (4,) for a in fetched)
    monkeypatch.undo()
    s.run_until_idle()
    st = s.stats()
    assert st["overlapped_steps"] == st["decode_steps"] - 1
    assert st["decode_shape_signatures"] == 1 and s._decode._cache_size() == 1
    assert all(len(h.tokens) == 12 or h.finish_reason == "eos" for h in hs)

    spec = make_session(model_and_params, speculate_k=4)
    for p in PROMPTS[:3]:
        spec.submit(p, 12)
    spec.run_until_idle()
    st = spec.stats()
    assert st["decode_steps"] > 0 and st["overlapped_steps"] == 0
    assert st["wasted_lanes"] == 0 and spec._in_flight is None
