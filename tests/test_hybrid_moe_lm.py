"""HybridMoELM (serving/hybrid_moe_lm.py) against its plain reference
(perfbench/reference/hybrid_moe_lm.py) at a tiny size on the CPU, float32:
d 64, layers m m a m, 4 Mamba heads of 16 with state 16, 4 query heads over
2 K/V heads, 8 experts top-3 of which 4 are held.

(a) prefill then decoding through the paged cache and the carried state gives
    the reference's logits; (b) a bucket's padding leaves no trace: the same
    prompt in two buckets leaves bitwise the same state and first token;
    (c) the chunked scan is the token-by-token recurrence, and a prompt
    prefilled in chunks is the prompt prefilled whole; (d) the shares add up;
    (e) no token is dropped; (f) a request's tokens do not depend on its
    batch nor on its slot's last tenant; (g) the grouped-head kernel equals
    its oracle; (h) what a recurrence cannot carry is refused; (i) an engine
    restart replays to the same tokens; (j) the counters and the span;
    (k) a checkpoint loads as its architecture."""

import dataclasses
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from paddle_tpu.core import faults
from paddle_tpu.ops import mamba2
from paddle_tpu.serving.hybrid_moe_lm import HybridMoEConfig, HybridMoELM
from paddle_tpu.serving.looped_lm import load_checkpoint
from paddle_tpu.serving.session import ServingSession
from perfbench.reference import hybrid_moe_lm as ref
from perfbench.reference import lowprec

# embedding_multiplier 1: at 12 a tiny tied model repeats its last token
TINY = dict(vocab=257, layer_types=("mamba", "mamba", "attention", "mamba"), d_model=64,
            n_heads=4, n_kv_heads=2, head_dim=16, mamba_heads=4, mamba_head_dim=16,
            mamba_state=16, mamba_chunk=8, num_experts_routed=8, experts_held=(0, 1, 2, 3),
            top_k=3, expert_width=32, shared_width=48, embedding_multiplier=1.0,
            max_len=96, dtype="float32")
TOL = 2e-4   # of the logits' std: the order of the sums alone separates the two
PS = 8
PROMPT = [1, 17, 201, 5, 88, 140, 9, 33, 250, 61, 7]


def tiny(**over):
    model = HybridMoELM(HybridMoEConfig(**dict(TINY, **over)))
    return model, model.init_params(jax.random.PRNGKey(0))


def numbers(cfg: HybridMoEConfig) -> dict:
    """The model's numbers under the configuration file's keys (the reference's)."""
    return {
        "layer_types": list(cfg.layer_types), "rms_norm_eps": cfg.rms_eps,
        "residual_multiplier": cfg.residual_multiplier, "embedding_multiplier": cfg.embedding_multiplier,
        "attention_multiplier": cfg.attention_multiplier, "logits_scaling": cfg.logits_scaling,
        "mamba_n_heads": cfg.mamba_heads, "mamba_d_head": cfg.mamba_head_dim,
        "mamba_d_state": cfg.mamba_state, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "num_experts_per_tok": cfg.top_k,
        "experts_held": list(cfg.experts_held),
    }


def session(model, params, **kw):
    kw = dict(dict(max_slots=4, page_size=PS, prefill_buckets=(16, 32), max_new_limit=24), **kw)
    return ServingSession(model, params, **kw)


def reference_logits(model, params, tokens, positions):
    with jax.default_matmul_precision("highest"):
        return ref.logits_at(params, jnp.asarray(tokens), jnp.asarray(positions),
                             numbers(model.cfg), lowprec.identity)


def served(model, params, prompt, steps, bucket=16, chunk=None, slot=0, state=None):
    """Prefill `prompt` (whole in `bucket`, or in `chunk`s), then `steps`
    greedy decode steps, through the model's own programs over a paged cache
    and a 2-slot state: (tokens, the logits each was chosen from, the state)."""
    grabbed = []
    model._sample = lambda logits, *a: (grabbed.append(logits), jnp.argmax(logits, -1).astype(jnp.int32))[1]
    pages = -(-(len(prompt) + steps + 1) // PS)
    kp = jnp.zeros((model.cache_layers, pages + 1, PS, model.cache_width), model.cache_dtype)
    vp = jnp.zeros_like(kp)
    if state is None:
        state = {k: jnp.zeros((2,) + s, d) for k, (s, d) in model.state_spec().items()}
        state.update({k: jnp.zeros(s, d) for k, (s, d) in model.counter_spec().items()})
    row = jnp.arange(1, pages + 1, dtype=jnp.int32)[None]
    slots = jnp.asarray([slot], jnp.int32)
    zeros = (jnp.zeros(1, jnp.uint32), jnp.zeros(1, jnp.float32), jnp.zeros(1, jnp.int32))
    n = len(prompt)
    try:
        with jax.default_matmul_precision("highest"):
            if chunk is None:
                toks = jnp.zeros((1, bucket), jnp.int32).at[0, :n].set(jnp.asarray(prompt))
                tok, kc, vc, new = model.prefill(params, toks, jnp.asarray([n]), *zeros)
                kp, vp, state = model.commit_prefill_state(
                    kp, vp, state, kc, vc, new, jnp.asarray([n]), row, jnp.zeros(1, jnp.int32), slots)
            else:
                for start in range(0, n, chunk):
                    piece = prompt[start:start + chunk]
                    toks = jnp.zeros((1, chunk), jnp.int32).at[0, :len(piece)].set(jnp.asarray(piece))
                    kp, vp, state, tok = model.prefill_chunk(
                        params, kp, vp, state, toks, jnp.asarray([start]), jnp.asarray([n]),
                        row, slots, *zeros)
            after_prompt = jax.tree.map(lambda a: a, state)
            seq = list(prompt) + [int(tok[0])]
            lane = np.zeros(2, np.int32)
            live = np.zeros(2, bool)
            live[slot] = True
            table = jnp.zeros((2, row.shape[1]), jnp.int32).at[slot].set(row[0])
            for _ in range(steps):
                kp, vp, state, tok = model.decode_step(
                    params, kp, vp, state, jnp.asarray(lane).at[slot].set(seq[-1]),
                    jnp.asarray(lane).at[slot].set(len(seq) - 1), jnp.asarray(live), table,
                    jnp.zeros(2, jnp.uint32), jnp.zeros(2, jnp.int32), jnp.zeros(2, jnp.float32),
                    jnp.zeros(2, jnp.int32))
                seq.append(int(tok[slot]))
    finally:
        del model._sample
    logits = jnp.concatenate([g[slot:slot + 1] if g.shape[0] == 2 else g for g in grabbed])
    return seq, logits, after_prompt, state


def worst_gap(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.std(want))


# -- (a) -----------------------------------------------------------------------

def test_prefill_then_paged_decode_gives_the_references_logits():
    model, params = tiny()
    seq, logits, _, _ = served(model, params, PROMPT, steps=9)
    assert len(set(seq[len(PROMPT):])) > 2, "a degenerate model compares nothing"
    positions = np.arange(len(PROMPT) - 1, len(seq) - 1)
    want = reference_logits(model, params, seq[:-1], positions)
    assert logits.shape == want.shape
    assert worst_gap(logits, want) < TOL
    # and the whole-context forward, position by position
    with jax.default_matmul_precision("highest"):
        full = model.forward_logits(params, jnp.asarray(seq[:-1])[None])[0]
    assert worst_gap(full[positions], want) < TOL


# -- (b) -----------------------------------------------------------------------

def test_the_same_prompt_in_two_buckets_leaves_bitwise_the_same_state_and_first_token():
    model, params = tiny()
    a = served(model, params, PROMPT, steps=0, bucket=16)
    b = served(model, params, PROMPT, steps=0, bucket=32)
    assert a[0] == b[0]
    for name in ("ssm", "conv"):
        assert np.array_equal(np.asarray(a[2][name][0]), np.asarray(b[2][name][0])), name
    assert float(jnp.max(jnp.abs(a[2]["ssm"][0]))) > 0
    # the tail is the last three INPUTS of the prompt, not of the bucket:
    # one token shorter, another tail
    c = served(model, params, PROMPT[:-1], steps=0, bucket=16)
    assert not np.array_equal(np.asarray(a[2]["conv"][0]), np.asarray(c[2]["conv"][0]))


# -- (c) -----------------------------------------------------------------------

@pytest.mark.parametrize("t,chunk", [(11, 4), (8, 8), (29, 8), (5, 256)])
def test_the_chunked_scan_is_the_token_by_token_recurrence(t, chunk):
    h, p, n = 3, 4, 5
    ks = jax.random.split(jax.random.PRNGKey(t), 6)
    x = jax.random.normal(ks[0], (2, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, t, h)))
    dt = dt.at[1, t - 2:].set(0.0)                 # a row two tokens shorter
    a_neg = -jnp.exp(jax.random.normal(ks[2], (h,)))
    b, c = jax.random.normal(ks[3], (2, t, n)), jax.random.normal(ks[4], (2, t, n))
    s0 = jax.random.normal(ks[5], (2, h, p, n))
    y, final = mamba2.ssd_chunked(x, dt, a_neg, b, c, s0, chunk)
    s, ys = s0, []
    for i in range(t):
        yi, s = mamba2.ssm_step(s, x[:, i], dt[:, i], a_neg, b[:, i], c[:, i])
        ys.append(yi)
    assert np.allclose(y, jnp.stack(ys, 1), atol=2e-5)
    assert np.allclose(final, s, atol=2e-5)


def test_a_prompt_prefilled_in_chunks_is_the_prompt_prefilled_whole():
    model, params = tiny()
    prompt = PROMPT + [40, 41, 42, 77, 3, 19, 100, 101]   # 19: chunks of 8, 8 and 3
    whole = served(model, params, prompt, steps=4, bucket=32)
    parts = served(model, params, prompt, steps=4, chunk=8)
    assert whole[0] == parts[0]
    assert worst_gap(parts[1][2:], whole[1]) < TOL   # a chunk samples each time: the last one's
    for name in ("ssm", "conv"):
        assert np.allclose(whole[2][name][0], parts[2][name][0], atol=1e-5), name
    # through the session, beside a request decoding
    s = session(model, params, prefill_chunk=8, max_new_limit=8)
    hs = [s.submit(prompt, 5), s.submit(PROMPT, 5)]
    s.run_until_idle()
    assert [int(t) for t in hs[0].tokens] == whole[0][len(prompt):len(prompt) + 5]
    assert s.prefill_chunks_committed == 3 + 2      # 19 and 11 tokens in chunks of 8


# -- (d), (e) ------------------------------------------------------------------

def moe_alone(model, params, layer, h):
    w = {k: params[k][layer] for k in ("router", "sh_wi", "sh_wo")}
    w.update(moe_wi=params["moe_wi"], moe_wo=params["moe_wo"], layer=layer)
    with jax.default_matmul_precision("highest"):
        out, by_expert, where = model._moe(w, h, jnp.ones(h.shape[0], bool))
        return out, by_expert, where, model._gated(h, w["sh_wi"], w["sh_wo"])


def test_the_shares_add_up():
    """Both halves' expert parts plus the shared MLP ONCE are the uncut layer."""
    whole, params = tiny(experts_held=tuple(range(8)))
    h = jax.random.normal(jax.random.PRNGKey(5), (13, 64))
    parts, landed = [], 0
    for held in ((0, 1, 2, 3), (4, 5, 6, 7)):
        half = HybridMoELM(dataclasses.replace(whole.cfg, experts_held=held))
        mine = dict(params, moe_wi=params["moe_wi"][:, list(held)], moe_wo=params["moe_wo"][:, list(held)])
        out, by_expert, where, shared = moe_alone(half, mine, 1, h)
        parts.append(out)
        landed += int(where[0])
        assert int(where[0]) + int(where[1]) == 13 * 3 and int(by_expert.sum()) == int(where[0])
    assert landed == 13 * 3
    w = {k: params[k][1] for k in ("router", "moe_wi", "moe_wo", "sh_wi", "sh_wo")}
    with jax.default_matmul_precision("highest"):
        uncut = ref.expert_block(w, h, 3, None, lambda a, m: jnp.matmul(a, m, precision="highest"),
                                 lowprec.identity)
        half = ref.expert_block({**w, "moe_wi": w["moe_wi"][:4], "moe_wo": w["moe_wo"][:4]}, h, 3,
                                [0, 1, 2, 3], lambda a, m: jnp.matmul(a, m, precision="highest"),
                                lowprec.identity)
    assert np.allclose(parts[0] + parts[1] + shared, uncut, atol=1e-5)
    assert np.allclose(parts[0] + shared, half, atol=1e-5)
    assert not np.allclose(parts[0] + shared, uncut, atol=1e-3)


def test_a_batch_routed_wholly_to_one_held_expert_drops_no_token():
    model, params = tiny()
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(6), (32, 64))) + 0.1
    router = jnp.zeros((64, 8)).at[:, 2].set(1.0).at[:, 5].set(0.5).at[:, 7].set(0.25)
    params = dict(params, router=params["router"].at[1].set(router))
    out, by_expert, where, shared = moe_alone(model, params, 1, h)
    assert [int(n) for n in by_expert] == [0, 0, 32, 0] and [int(n) for n in where] == [32, 64]
    w = {k: params[k][1] for k in ("router", "moe_wi", "moe_wo", "sh_wi", "sh_wo")}
    with jax.default_matmul_precision("highest"):
        want = ref.expert_block(w, h, 3, [0, 1, 2, 3], lambda a, m: jnp.matmul(a, m, precision="highest"),
                                lowprec.identity)
    assert np.allclose(out + shared, want, atol=1e-5)
    assert float(jnp.min(jnp.max(jnp.abs(out), -1))) > 0, "every token got its expert's output"


# -- (f) -----------------------------------------------------------------------

def test_a_requests_tokens_are_bitwise_the_same_alone_in_a_full_batch_and_in_a_used_slot():
    model, params = tiny()
    rs = np.random.default_rng(0)
    others = [[1] + [int(t) for t in rs.integers(3, 257, n)] for n in (5, 14, 9, 20, 7)]
    alone = session(model, params)
    h = alone.submit(PROMPT, 12)
    alone.run_until_idle()
    want = [int(t) for t in h.tokens]
    assert len(set(want)) > 2
    full = session(model, params)
    hs = [full.submit(p, 6 + i) for i, p in enumerate(others[:3])] + [full.submit(PROMPT, 12)]
    hs += [full.submit(p, 9) for p in others[3:]] + [full.submit(PROMPT, 12)]
    full.run_until_idle()
    # the second copy was admitted into a slot another request had just left
    assert [int(t) for t in hs[3].tokens] == want == [int(t) for t in hs[-1].tokens]
    assert full.decode_shape_signatures() == 1


# -- (g) -----------------------------------------------------------------------

@pytest.mark.parametrize("n_kv,group,hd,dtype", [(2, 2, 16, "float32"), (2, 4, 128, "bfloat16"),
                                                  (1, 4, 64, "float32")])
def test_the_grouped_head_kernel_in_interpret_mode_equals_the_oracle(monkeypatch, n_kv, group, hd, dtype):
    from paddle_tpu.ops.pallas.paged_attention import paged_attention_decode

    model, _ = tiny(n_heads=n_kv * group, n_kv_heads=n_kv, head_dim=hd, dtype=dtype)
    kd, slots, pages = n_kv * hd, 5, 9
    ks = jax.random.split(jax.random.PRNGKey(n_kv * group), 3)
    q = jax.random.normal(ks[0], (slots, kd * group), jnp.float32).astype(dtype)
    kp = jax.random.normal(ks[1], (2, pages * slots + 1, PS, kd), jnp.float32).astype(dtype)
    vp = jax.random.normal(ks[2], (2, pages * slots + 1, PS, kd), jnp.float32).astype(dtype)
    table = jnp.asarray(np.random.default_rng(1).permutation(pages * slots).reshape(slots, pages) + 1,
                        jnp.int32)
    positions = jnp.asarray([0, 7, 8, 40, pages * PS - 1], jnp.int32)
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "0")
    want = model._paged_attention(q, kp, vp, table, positions, layer=1)
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
    got = model._paged_attention(q, kp, vp, table, positions, layer=jnp.asarray(1, jnp.int32))
    tol = 2e-5 if dtype == "float32" else 2e-2
    assert got.shape == want.shape == (slots, kd * group)
    assert np.allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol), (
        float(jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)))))
    # a query head reads ITS K/V head: with the other head's pages zeroed the
    # first group's context does not move and the second's does
    half = kp.at[..., kd // n_kv:].set(0) if n_kv > 1 else None
    if half is not None:
        moved = paged_attention_decode(q, half, vp, table, positions, layer=1, scale=model.scale,
                                       n_heads=n_kv * group, group=group)
        assert np.allclose(np.asarray(moved[:, :group * hd], np.float32),
                           np.asarray(got[:, :group * hd], np.float32), atol=tol)
        assert not np.allclose(np.asarray(moved[:, group * hd:], np.float32),
                               np.asarray(got[:, group * hd:], np.float32), atol=tol)


def test_a_session_serves_through_the_grouped_kernel_as_through_the_oracle(monkeypatch):
    model, params = tiny()
    tokens = []
    for flag in ("0", "interpret"):
        monkeypatch.setenv("PADDLE_TPU_PALLAS", flag)
        s = session(model, params)
        h = s.submit(PROMPT, 6)
        s.run_until_idle()
        tokens.append([int(t) for t in h.tokens])
    assert tokens[0] == tokens[1]


# -- (h) -----------------------------------------------------------------------

@pytest.mark.parametrize("kw", [dict(prefix_cache=True, prefill_chunk=8), dict(speculate_k=2)])
def test_what_a_recurrence_cannot_carry_is_refused_at_construction(kw):
    model, params = tiny()
    with pytest.raises(ValueError, match="recurrence"):
        session(model, params, **kw)


def test_a_mesh_is_refused():
    from paddle_tpu.parallel.rules import make_tp_mesh

    with pytest.raises(ValueError, match="expert"):
        HybridMoELM(HybridMoEConfig(**TINY), mesh=make_tp_mesh(2))


# -- (i) -----------------------------------------------------------------------

def test_an_engine_restart_replays_to_the_same_tokens():
    model, params = tiny()
    prompts = [PROMPT, PROMPT[:6], [1, 9, 9, 200, 13, 55, 21]]
    clean = session(model, params)
    want = [clean.submit(p, 8) for p in prompts]
    clean.run_until_idle()
    s = session(model, params, engine_stall_timeout_s=0.3, engine_restart_max=5)
    with faults.inject("decode_raise:step=3", seed=0) as inj:
        s.serve_forever()
        handles = [s.submit(p, 8, deadline_s=60.0) for p in prompts]
        deadline = time.monotonic() + 90
        for h in handles:
            assert h._event.wait(max(0.1, deadline - time.monotonic()))
        fired = dict(inj.fired)
    s.stop()
    assert fired.get("decode_raise", 0) >= 1 and s.engine_restarts >= 1
    assert [h.tokens for h in handles] == [h.tokens for h in want]
    assert s.state["ssm"].shape[0] == 4 and s.stats()["state_bytes_per_chip"] > 0


# -- (j) -----------------------------------------------------------------------

def test_the_counters_and_the_span_record_the_experts_the_state_and_every_layer():
    from paddle_tpu.obs import metrics, trace

    model, params = tiny()
    before = len([r for r in trace.TRACER.snapshot() if r[0] == "serve.decode"])
    by_expert = metrics.REGISTRY.counter("paddle_tpu_serving_moe_expert_tokens_total")
    where = metrics.REGISTRY.counter("paddle_tpu_serving_moe_assignments_total")
    passes = metrics.REGISTRY.counter("paddle_tpu_serving_layer_passes_total")
    w0 = {k: where.value(where=k) for k in ("here", "absent")}
    e0 = sum(s.value for s in by_expert.samples())
    d0 = passes.value(phase="decode")
    s = session(model, params)
    hs = [s.submit(PROMPT, 5), s.submit(PROMPT[:6], 3)]
    s.run_until_idle()
    rows = [r for r in trace.TRACER.snapshot() if r[0] == "serve.decode"][before:]
    assert [r[6]["slots"] for r in rows] == [2, 2, 1, 1] and {r[6]["layer_passes"] for r in rows} == {4}
    assert s.layer_passes == 4 and model.cache_layers == 1
    assert passes.value(phase="decode") - d0 == 6 * 4
    # every token of both prompts and every decoded token, top-3, at 4 layers
    tokens = len(PROMPT) + 6 + 6
    read = s.read_counters()
    assert read["moe_assignments"].shape == (4, 2) and read["moe_expert_tokens"].shape == (4, 4)
    assert (read["moe_assignments"].sum(1) == 3 * tokens).all()
    assert (read["moe_expert_tokens"].sum(1) == read["moe_assignments"][:, 0]).all()
    here = where.value(where="here") - w0["here"]
    assert here == read["moe_assignments"][:, 0].sum() > 0
    assert where.value(where="absent") - w0["absent"] == read["moe_assignments"][:, 1].sum()
    assert sum(x.value for x in by_expert.samples()) - e0 == here
    # a second read counts nothing twice
    assert (s.read_counters()["moe_assignments"] == read["moe_assignments"]).all()
    assert where.value(where="here") - w0["here"] == here
    gauge = metrics.REGISTRY.gauge("paddle_tpu_serving_kv_bytes_per_token")
    assert gauge.value() == 2 * 1 * 32 * 4          # ONE cache layer of 2 K/V heads of 16
    state = metrics.REGISTRY.gauge("paddle_tpu_serving_recurrent_state_bytes_per_slot")
    assert state.value() == 3 * (4 * 16 * 16 + 3 * (64 + 32)) * 4


# -- (k) -----------------------------------------------------------------------

def test_a_checkpoint_records_its_architecture_and_loads_as_it(tmp_path):
    model, params = tiny(dtype="bfloat16")
    path = str(tmp_path / "hybrid.npz")
    model.save(path, params)
    again, loaded = load_checkpoint(path)
    assert isinstance(again, HybridMoELM) and again.cfg == model.cfg
    assert all(loaded[k].dtype == params[k].dtype and bool(jnp.all(loaded[k] == params[k])) for k in params)
    assert loaded["m_a_log"].dtype == jnp.float32 and loaded["m_in"].dtype == jnp.bfloat16


def test_serve_load_dispatches_on_the_checkpoints_architecture(tmp_path):
    import argparse

    from paddle_tpu import cli

    model, params = tiny()
    path = str(tmp_path / "hybrid.npz")
    model.save(path, params)
    parser = argparse.ArgumentParser()
    cli._serve_args(parser)
    served_ = cli.build_serve_session(parser.parse_args(
        ["--load", path, "--prefill_buckets=16,32", "--max_new_limit=16", "--page_size=8"]))
    assert isinstance(served_.model, HybridMoELM) and served_.k_pages.shape[0] == 1
    direct = session(model, params, max_new_limit=16)
    handles = [s.submit(PROMPT, 6) for s in (served_, direct)]
    served_.run_until_idle()
    direct.run_until_idle()
    assert [int(t) for t in handles[0].tokens] == [int(t) for t in handles[1].tokens]
