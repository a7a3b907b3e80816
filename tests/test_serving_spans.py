"""The engine step accounts for its own time (ISSUE 37): a session driven on
the CPU through whole-prompt admissions, chunked prefills, preemptions and
the last drain, one `step()` at a time, with what each call did read off the
session's own counters beside the spans the call left on the always-on ring.

(a) every working `step()` leaves one `serve.step` whose attrs equal what
    the step did; an idle one leaves the ring as it was;
(b) `serve.admit`, `serve.chunk`, `serve.preempt` and `serve.decode` are its
    children, lie inside it, and carry their parts of its `wait_ns`: the sum
    is the step's, save where the step drained outside a child;
(c) `replaying` over a drive is `stats()["replayed_tokens"]`;
(d) `handle.t_admitted` is the scheduler's stamp, None until then;
(e) a request that brought a wire context has its admission under ITS
    trace, the others under the step's."""

import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from paddle_tpu.obs import trace
from paddle_tpu.serving.session import ServingSession

pytestmark = pytest.mark.serving

NAME, START, DUR, TRACE, SPAN, PARENT, ATTRS, THREAD = range(8)
VOCAB = 96
CHUNK = 8
CHILDREN = ("serve.admit", "serve.chunk", "serve.preempt", "serve.decode")


@pytest.fixture(scope="module")
def servable():
    from paddle_tpu.serving.model import LMConfig, ServableLM

    model = ServableLM(LMConfig(vocab=VOCAB, n_layers=2, d_model=32, n_heads=2, max_len=96))
    return model, model.init_params(jax.random.PRNGKey(0))


def make_session(servable, **kw):
    model, params = servable
    kw = dict(dict(max_slots=4, page_size=4, prefill_buckets=(8, 16), max_new_limit=24,
                   num_pages=15, prefill_chunk=CHUNK), **kw)
    return ServingSession(model, params, **kw)


def plan(n=12, seed=3):
    """Prompts of 3-7 tokens (a bucket's, whole) and of 9-30 (chunked)."""
    rs = np.random.default_rng(seed)
    out = []
    for i in range(n):
        length = int(rs.integers(9, 31)) if i % 3 == 1 else int(rs.integers(3, 8))
        prompt = [1] + [int(t) for t in rs.integers(3, VOCAB, length - 1)]
        out.append((prompt, int(rs.integers(6, 25))))
    return out


def new_rows(recorded_before):
    """The engine's rows since (a first step also compiles: `compile.*`)."""
    n = trace.TRACER.recorded - recorded_before
    rows = trace.TRACER.snapshot()[-n:] if n else []
    return [r for r in rows if r[NAME].startswith("serve.")]


@pytest.fixture(scope="module")
def drive(servable):
    """[(what the call did by the session's counters, the rows it left)],
    one a step() call, over a plan that admits, chunks, preempts and drains;
    and the session and its handles afterwards."""
    s = make_session(servable)
    handles = [s.submit(p, n) for p, n in plan()]
    assert all(h.t_admitted is None for h in handles)
    calls = []
    while s.scheduler.has_work():
        st0, n0 = s.stats(), trace.TRACER.recorded
        flying = s._in_flight is not None
        s.step()
        st = s.stats()
        did = {k: st[k] - st0[k] for k in
               ("decode_steps", "prefill_chunks_committed", "preemptions", "overlapped_steps")}
        # a step that ended the one in flight without dispatching behind it
        did["drained"] = flying and not did["overlapped_steps"]
        for _, act in s.scheduler.active_slots():
            assert act.handle.t_admitted == act.t_started is not None
        calls.append((did, new_rows(n0)))
    return s, handles, calls


def test_the_drive_admits_chunks_preempts_and_drains(drive):
    s, handles, calls = drive
    st = s.stats()
    assert all(h.done and h.tokens for h in handles)
    assert st["preemptions"] > 0 and st["prefill_chunks_committed"] > 0
    assert any(did["drained"] for did, _ in calls)
    names = {r[NAME] for _, rows in calls for r in rows}
    assert names == {"serve.step", *CHILDREN}, names


def test_one_serve_step_a_working_call_whose_attrs_say_what_it_did(drive):
    _, _, calls = drive
    for did, rows in calls:
        steps = [r for r in rows if r[NAME] == "serve.step"]
        assert len(steps) == 1, (did, [r[NAME] for r in rows])
        a = steps[0][ATTRS]
        by_name = {n: [r for r in rows if r[NAME] == n] for n in CHILDREN}
        assert set(a) == {"admitted", "chunks", "decoded", "slots", "preempted", "wait_ns"}
        assert all(isinstance(v, int) for v in a.values()), a
        assert a["admitted"] == len(by_name["serve.admit"])
        assert a["chunks"] == did["prefill_chunks_committed"] == len(by_name["serve.chunk"])
        assert a["decoded"] == did["decode_steps"] == len(by_name["serve.decode"])
        assert a["preempted"] == did["preemptions"] == len(by_name["serve.preempt"])
        assert a["slots"] == sum(r[ATTRS]["slots"] for r in by_name["serve.decode"])
        assert (a["slots"] > 0) == bool(a["decoded"])
        # the budget: two ring writes a decode step, one more an admission,
        # a chunk or a preemption
        assert len(rows) == 1 + a["decoded"] + a["admitted"] + a["chunks"] + a["preempted"]


def test_children_lie_inside_their_step_and_share_its_wait(drive):
    _, _, calls = drive
    for did, rows in calls:
        step = next(r for r in rows if r[NAME] == "serve.step")
        kids = [r for r in rows if r is not step]
        assert 0 <= step[ATTRS]["wait_ns"] <= step[DUR]
        for r in kids:
            assert r[NAME] in CHILDREN
            assert (r[TRACE], r[PARENT]) == (step[TRACE], step[SPAN])
            assert step[START] <= r[START] and r[START] + r[DUR] <= step[START] + step[DUR]
            assert 0 <= r[ATTRS].get("wait_ns", 0) <= r[DUR]
        own = step[ATTRS]["wait_ns"] - sum(r[ATTRS].get("wait_ns", 0) for r in kids)
        assert own >= 0
        if not did["drained"]:
            assert own == 0, (did, step[ATTRS])
    # a drain outside any child did happen, and is the step's own wait
    assert any(
        did["drained"] and next(r for r in rows if r[NAME] == "serve.step")[ATTRS]["wait_ns"]
        > sum(r[ATTRS].get("wait_ns", 0) for r in rows if r[NAME] in CHILDREN)
        for did, rows in calls)


def test_admissions_and_chunks_say_what_was_prefilled(drive):
    s, handles, calls = drive
    rows = [r for _, rs in calls for r in rs]
    by_request = {h.request_id: h for h in handles}
    admits = [r[ATTRS] for r in rows if r[NAME] == "serve.admit"]
    chunks = [r[ATTRS] for r in rows if r[NAME] == "serve.chunk"]
    assert admits and chunks
    for a in admits:
        h = by_request[a["request_id"]]
        assert a["prompt"] == h.prompt_len <= a["bucket"] and a["bucket"] in s.buckets
        assert a["queued_ms"] == int(1e3 * (h.t_admitted - h.t_submit)) >= 0
        assert a["replay"] in (0, 1)
    # every preempted whole-prompt request comes back once as a replay
    assert sum(a["replay"] for a in admits) > 0
    for c in chunks:
        h = by_request[c["request_id"]]
        assert 0 < c["tokens"] <= CHUNK and c["start"] + c["tokens"] <= h.prompt_len
        # only a prompt's last chunk fetches (the first token)
        assert (c["wait_ns"] > 0) == (c["start"] + c["tokens"] == h.prompt_len)
    # a prompt's chunks cover it, once each time it is (re)built
    for h in handles:
        mine = [c for c in chunks if c["request_id"] == h.request_id]
        if mine:
            assert sum(c["tokens"] for c in mine) % h.prompt_len == 0


def test_replaying_lanes_are_the_replayed_tokens(drive):
    s, _, calls = drive
    decodes = [r[ATTRS] for _, rows in calls for r in rows if r[NAME] == "serve.decode"]
    assert all(0 <= d["replaying"] <= d["slots"] for d in decodes)
    assert all(d["layer_passes"] == s.layer_passes for d in decodes)
    assert sum(d["replaying"] for d in decodes) == s.stats()["replayed_tokens"] > 0


def test_an_idle_step_leaves_the_ring_as_it_was(drive):
    s, _, _ = drive
    assert not s.scheduler.has_work() and s._in_flight is None
    before = trace.TRACER.recorded
    for _ in range(3):
        assert s.step() is False
    assert trace.TRACER.recorded == before


def test_t_admitted_is_the_schedulers_stamp_and_survives_a_preemption(drive, servable):
    _, handles, _ = drive
    for h in handles:
        assert h.t_submit <= h.t_admitted <= h.t_first_token <= h.t_done
    # a preempted request keeps its FIRST admission's stamp
    s = make_session(servable, prefill_chunk=None)
    hs = [s.submit([1] + [5] * 6, 24) for _ in range(6)]
    first = {}
    while s.scheduler.has_work():
        s.step()
        for h in hs:
            if h.t_admitted is not None:
                assert first.setdefault(h.request_id, h.t_admitted) == h.t_admitted
    assert s.stats()["preemptions"] > 0 and len(first) == len(hs)


def test_a_wire_context_takes_the_admission_into_the_requests_trace(servable):
    s = make_session(servable, num_pages=None)
    was = trace.TRACER.enabled
    trace.enable_tracing(True)
    try:
        with trace.span("client.call") as client:
            traced = s.submit([1, 7, 9], 4)
        plain = s.submit([1, 8, 9, 11], 4)
        n0 = trace.TRACER.recorded
        s.run_until_idle()
    finally:
        trace.enable_tracing(was)
    rows = new_rows(n0)
    admits = {r[ATTRS]["request_id"]: r for r in rows if r[NAME] == "serve.admit"}
    step = next(r for r in rows if r[NAME] == "serve.step")
    assert (admits[traced.request_id][TRACE], admits[traced.request_id][PARENT]) == (
        client.trace_id, client.span_id)
    assert (admits[plain.request_id][TRACE], admits[plain.request_id][PARENT]) == (
        step[TRACE], step[SPAN])
    # the step's own attrs count both
    assert step[ATTRS]["admitted"] == 2
