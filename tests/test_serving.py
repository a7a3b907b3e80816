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
