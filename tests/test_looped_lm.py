"""LoopedLM (serving/looped_lm.py) against its plain reference
(perfbench/reference/looped_lm.py) at a tiny size on the CPU: d 64, 2 heads
of 32, f 96, 3 layers run 4 times, vocabulary 257.

(a) prefill then decoding through the paged cache gives the reference's
    logits at every served position; (b) chunked prefill and verify_chunk
    commit the pages the whole-prompt prefill commits; (c) a request's
    tokens do not depend on its batch; (d) the loop is real; (e) the Pallas
    kernel over a bfloat16 pool with a traced layer equals the gather
    oracle; (f) the bfloat16 policy stays inside the tiny cell's limits and
    the fp8 control does not."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from paddle_tpu.serving.looped_lm import LoopedLM, LoopedLMConfig, load_checkpoint
from paddle_tpu.serving.model import LMConfig, ServableLM
from paddle_tpu.serving.session import ServingSession
from perfbench.reference import looped_lm as ref
from perfbench.reference import lowprec

TINY = dict(vocab=257, n_layers=3, d_model=64, n_heads=2, head_dim=32, d_ff=96,
            ut_steps=4, max_len=96)
# (a)'s tolerance, of the logits' std: what separates the program from the
# reference in float32 is the order of its sums alone (the paged softmax is
# reassociated, a scan's products are another order than a loop's)
TOL = 2e-4
PS = 8


def tiny(dtype="float32", **over):
    model = LoopedLM(LoopedLMConfig(**dict(TINY, dtype=dtype, **over)))
    return model, model.init_params(jax.random.PRNGKey(0))


def session(model, params, **kw):
    kw = dict(dict(max_slots=4, page_size=PS, prefill_buckets=(16, 32), max_new_limit=24), **kw)
    return ServingSession(model, params, **kw)


def reference_logits(params, tokens, positions, ut_steps=TINY["ut_steps"],
                     cast=lowprec.identity, **kw):
    with jax.default_matmul_precision("highest"):
        return ref.logits_at(params, jnp.asarray(tokens)[None], jnp.asarray(positions)[None],
                             TINY["n_heads"], ut_steps, cast, **kw)[0]


def served_logits(model, params, prompt, steps, chunk=None):
    """Prefill `prompt`, then `steps` decode steps of the reference's greedy
    tokens, through the model's own programs over a paged cache: the logits
    each token was chosen from (sampling stubbed to hand the logits back)."""
    grabbed = []
    model._sample = lambda logits, *a: (grabbed.append(logits), jnp.argmax(logits, -1).astype(jnp.int32))[1]
    pages = -(-(len(prompt) + steps + 1) // PS)
    kp = jnp.zeros((model.cache_layers, pages + 1, PS, model.cache_width), model.cache_dtype)
    vp = jnp.zeros_like(kp)
    row = jnp.arange(1, pages + 1, dtype=jnp.int32)[None]
    zeros = (jnp.zeros(1, jnp.uint32), jnp.zeros(1, jnp.float32), jnp.zeros(1, jnp.int32))
    n = len(prompt)
    with jax.default_matmul_precision("highest"):
        if chunk is None:
            toks = jnp.zeros((1, 16), jnp.int32).at[0, :n].set(jnp.asarray(prompt))
            tok, kc, vc = model.prefill(params, toks, jnp.asarray([n]), *zeros)
            kp, vp = model.commit_prefill(kp, vp, kc, vc, jnp.asarray([n]), row, jnp.zeros(1, jnp.int32))
        else:
            for start in range(0, n, chunk):
                piece = prompt[start:start + chunk]
                toks = jnp.zeros((1, chunk), jnp.int32).at[0, :len(piece)].set(jnp.asarray(piece))
                kp, vp, tok = model.prefill_chunk(
                    params, kp, vp, toks, jnp.asarray([start]), jnp.asarray([n]), row, *zeros)
        seq = list(prompt) + [int(tok[0])]
        for _ in range(steps):
            kp, vp, tok = model.decode_step(
                params, kp, vp, jnp.asarray(seq[-1:]), jnp.asarray([len(seq) - 1]),
                jnp.ones(1, bool), row, zeros[0], jnp.zeros(1, jnp.int32), zeros[1], zeros[2])
            seq.append(int(tok[0]))
    del model._sample
    return seq, jnp.concatenate(grabbed), (kp, vp)


PROMPT = [1, 17, 201, 5, 88, 140, 9, 33, 250, 61, 7]


def worst_gap(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.std(want))


# -- (a) -----------------------------------------------------------------------

def test_prefill_then_paged_decode_gives_the_references_logits():
    model, params = tiny()
    seq, got, _ = served_logits(model, params, PROMPT, steps=12)
    positions = np.arange(len(PROMPT) - 1, len(seq) - 1)
    want = reference_logits(params, seq[:-1], positions)
    assert got.shape == want.shape == (13, TINY["vocab"])
    assert worst_gap(got, want) < TOL


# -- (b) -----------------------------------------------------------------------

def test_chunked_prefill_and_verify_commit_the_whole_prompts_pages():
    model, params = tiny()
    seq, whole_logits, (kw, vw) = served_logits(model, params, PROMPT, steps=0)
    _, chunk_logits, (kc, vc) = served_logits(model, params, PROMPT, steps=0, chunk=4)
    n = len(PROMPT)
    used = np.zeros(kw.shape[1:3], bool).reshape(-1)
    used[PS: PS + n] = True                      # page 0 is the dump page
    used = used.reshape(kw.shape[1:3])
    for a, b in ((kw, kc), (vw, vc)):
        np.testing.assert_allclose(np.asarray(a)[:, used], np.asarray(b)[:, used], atol=2e-5)
    assert worst_gap(chunk_logits[-1], whole_logits[-1]) < TOL
    # verify_chunk over [last prompt token, the served token]: the K/V it
    # commits at the prompt's last position is what the prefill put there,
    # and its first sampled token is the prefill's
    row = jnp.arange(1, kw.shape[1], dtype=jnp.int32)[None]
    with jax.default_matmul_precision("highest"):
        kv2, vv2, sampled = model.verify_chunk(
            params, kw, vw, jnp.asarray([[PROMPT[-1], seq[-1]]]), jnp.asarray([n - 1]), row,
            jnp.zeros(1, jnp.uint32), jnp.zeros(1, jnp.int32), jnp.zeros(1, jnp.float32),
            jnp.zeros(1, jnp.int32))
    assert int(sampled[0]) == seq[-1]
    np.testing.assert_allclose(np.asarray(kv2)[:, used], np.asarray(kw)[:, used], atol=2e-5)
    np.testing.assert_allclose(np.asarray(vv2)[:, used], np.asarray(vw)[:, used], atol=2e-5)


# -- (c) -----------------------------------------------------------------------

def test_a_requests_tokens_are_bitwise_the_same_alone_and_in_a_full_batch():
    model, params = tiny()
    rs = np.random.default_rng(5)
    prompts = [[1] + [int(t) for t in rs.integers(3, 257, n)] for n in (10, 4, 25, 14, 7, 30)]
    alone = session(model, params)
    h = alone.submit(prompts[0], 20)
    alone.run_until_idle()
    full = session(model, params)
    hs = [full.submit(p, 20) for p in prompts]
    full.run_until_idle()
    assert [int(t) for t in hs[0].tokens] == [int(t) for t in h.tokens] and len(h.tokens) == 20
    assert full.decode_shape_signatures() == 1
    assert full.k_pages.shape[0] == 12 == model.cache_layers and full.layer_passes == 12
    assert full.stats()["pages_in_use"] == 0


# -- (d) -----------------------------------------------------------------------

def test_the_loop_is_real():
    model1, params = tiny(ut_steps=1)
    seq, got1, _ = served_logits(model1, params, PROMPT, steps=4)
    positions = np.arange(len(PROMPT) - 1, len(seq) - 1)
    one_pass = reference_logits(params, seq[:-1], positions, ut_steps=1)
    assert worst_gap(got1, one_pass) < TOL
    model4, _ = tiny()
    seq4, got4, _ = served_logits(model4, params, PROMPT, steps=4)
    positions = np.arange(len(PROMPT) - 1, len(seq4) - 1)
    assert worst_gap(got4, reference_logits(params, seq4[:-1], positions, ut_steps=1)) > 100 * TOL
    # a reference whose pass t attends over pass t-1's keys and values (a
    # cache one pass deep) is another model: the program is far from it
    shared = reference_logits(params, seq4[:-1], positions, share_cache=True)
    assert worst_gap(got4, shared) > 100 * TOL
    assert worst_gap(got4, reference_logits(params, seq4[:-1], positions)) < TOL


# -- (e) -----------------------------------------------------------------------

@pytest.mark.parametrize("kd,heads", [(256, 2), (64, 2)])
def test_the_kernel_over_a_bfloat16_pool_with_a_traced_layer_equals_the_oracle(monkeypatch, kd, heads):
    """Interpret mode. Over bfloat16 the oracle rounds the normalised
    weights and the kernel's recurrence the unnormalised, and both round q:
    agreement is to bfloat16's 2**-8 of the values' range, not to float32's."""
    from paddle_tpu.ops.pallas import paged_attention

    monkeypatch.setattr(paged_attention, "BLOCK_TOKENS", 24)  # three pages a block
    model = ServableLM(LMConfig(vocab=11, n_layers=1, d_model=kd, n_heads=heads))
    layers, pages, slots, pmax = 5, 29, 4, 7
    rs = np.random.default_rng(0)
    kp = jnp.asarray(rs.normal(size=(layers, pages, PS, kd)), jnp.bfloat16)
    vp = jnp.asarray(rs.normal(size=(layers, pages, PS, kd)), jnp.bfloat16)
    q = jnp.asarray(rs.normal(size=(slots, kd)), jnp.bfloat16)
    table = jnp.asarray(rs.permutation(np.arange(1, pages))[: slots * pmax].reshape(slots, pmax), jnp.int32)
    positions = jnp.asarray([0, 9, 30, 55], jnp.int32)

    def over_layers(kp, vp):
        def body(_, layer):
            return None, model._paged_attention(q, kp, vp, table, positions, layer=layer)
        return jax.lax.scan(body, None, jnp.asarray([3, 0, 4], jnp.int32))[1]

    monkeypatch.setenv("PADDLE_TPU_PALLAS", "0")
    want = jax.jit(over_layers)(kp, vp)
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
    got = jax.jit(over_layers)(kp, vp)
    assert got.dtype == want.dtype == jnp.bfloat16 and got.shape == (3, slots, kd)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               atol=3e-2, rtol=2e-2)
    # and it is the layer asked for: layer 3's context is not layer 0's
    assert float(jnp.max(jnp.abs(want[0].astype(jnp.float32) - want[1].astype(jnp.float32)))) > 0.1


def test_a_bfloat16_session_serves_through_the_kernel_as_through_the_oracle(monkeypatch):
    model, params = tiny("bfloat16")
    prompts = [[1, 5, 9, 11, 7, 200, 31], [1] + list(range(40, 60))]

    def run():
        s = session(model, params)
        assert s.k_pages.dtype == jnp.bfloat16
        hs = [s.submit(p, 6) for p in prompts]
        s.run_until_idle()
        return [[int(t) for t in h.tokens] for h in hs]

    monkeypatch.setenv("PADDLE_TPU_PALLAS", "0")
    oracle = run()
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
    kernel = run()
    assert all(len(t) == 6 for t in kernel)
    # the first tokens are the prefill's (no kernel): equal; behind them a
    # bfloat16 rounding may part the two greedy paths
    assert [t[0] for t in kernel] == [t[0] for t in oracle]


# -- (f) -----------------------------------------------------------------------

def token_gaps(params32, seq, n_prompt, cast=None):
    """As the benchmark's comparison: by how much the logit of each served
    token lies below the float32 reference's best (of the token a control's
    lower precision puts first, where `cast` is given)."""
    positions = np.arange(n_prompt - 1, len(seq) - 1)
    want = reference_logits(params32, seq[:-1], positions)
    if cast is None:
        chosen = jnp.asarray(seq[n_prompt:])
    else:
        chosen = jnp.argmax(reference_logits(params32, seq[:-1], positions, cast=cast), -1)
    gap = jnp.max(want, -1) - jnp.take_along_axis(want, chosen[:, None], -1)[:, 0]
    return float(gap.max()), float(gap.mean()), float(jnp.std(want))


def test_the_bfloat16_policy_is_inside_the_tiny_cells_limits_and_fp8_is_not():
    import json

    with open(os.path.join(ROOT, "tests", "perfbench_cpu", "data", "looped",
                           "workloads", "ouro_tiny.worked_answers_saturated.json")) as f:
        limits = json.load(f)["check"]["limits"]
    model, params = tiny("bfloat16")
    params32 = {k: v.astype(jnp.float32) for k, v in params.items()}
    worst = [0.0, 0.0]
    control = [0.0, 0.0]
    for seed in range(3):
        rs = np.random.default_rng(seed)
        prompt = [1] + [int(t) for t in rs.integers(3, 257, 12)]
        s = session(model, params)
        h = s.submit(prompt, 24)
        s.run_until_idle()
        seq = prompt + [int(t) for t in h.tokens]
        w, m, std = token_gaps(params32, seq, len(prompt))
        worst = [max(worst[0], w), max(worst[1], m)]
        cw, cm, _ = token_gaps(params32, seq, len(prompt), cast=lowprec.CASTS["fp8"])
        control = [max(control[0], cw), max(control[1], cm)]
    assert worst[0] <= limits["token_logit_gap"] and worst[1] <= limits["token_logit_gap_mean"], worst
    assert control[0] > limits["token_logit_gap"] or control[1] > limits["token_logit_gap_mean"], control


# -- the checkpoint, the CLI's dispatch, TP ---------------------------------------

def test_a_checkpoint_records_its_architecture_and_loads_as_it(tmp_path):
    model, params = tiny("bfloat16")
    path = str(tmp_path / "looped.npz")
    model.save(path, params)
    again, loaded = load_checkpoint(path)
    assert isinstance(again, LoopedLM) and again.cfg == model.cfg
    assert all(loaded[k].dtype == jnp.bfloat16 and bool(jnp.all(loaded[k] == params[k])) for k in params)
    old = ServableLM(LMConfig(vocab=31, n_layers=1, d_model=16, n_heads=2, max_len=32))
    old_path = str(tmp_path / "servable.npz")
    old.save(old_path, old.init_params(jax.random.PRNGKey(1)))
    assert type(load_checkpoint(old_path)[0]) is ServableLM


def test_serve_load_dispatches_on_the_checkpoints_architecture(tmp_path):
    """`paddle_tpu serve --load` (cli.build_serve_session) over a LoopedLM's
    checkpoint: the same session class, the model's own cache."""
    import argparse

    from paddle_tpu import cli

    model, params = tiny("bfloat16")
    path = str(tmp_path / "looped.npz")
    model.save(path, params)
    parser = argparse.ArgumentParser()
    cli._serve_args(parser)
    served = cli.build_serve_session(parser.parse_args(
        ["--load", path, "--prefill_buckets=16,32", "--max_new_limit=16", "--page_size=8"]))
    assert type(served) is ServingSession and isinstance(served.model, LoopedLM)
    assert served.k_pages.shape[0] == 12 and served.k_pages.dtype == jnp.bfloat16
    direct = session(model, params, max_new_limit=16)
    handles = [s.submit(PROMPT, 6) for s in (served, direct)]
    served.run_until_idle()
    direct.run_until_idle()
    assert [int(t) for t in handles[0].tokens] == [int(t) for t in handles[1].tokens]


def test_tensor_parallel_serves_the_single_chips_tokens():
    from paddle_tpu.parallel.rules import make_tp_mesh

    cfg = LoopedLMConfig(**dict(TINY, vocab=256, dtype="float32"))
    one = LoopedLM(cfg)
    params = one.init_params(jax.random.PRNGKey(0))
    two = LoopedLM(cfg, mesh=make_tp_mesh(2))
    assert set(two.param_logical_axes()) == set(params)
    prompt = [1, 5, 9, 11, 7, 200, 31]
    tokens = []
    for model in (one, two):
        s = session(model, params)
        h = s.submit(prompt, 8)
        s.run_until_idle()
        tokens.append([int(t) for t in h.tokens])
        if model is two:
            st = s.stats()
            assert st["tp"] == 2 and st["pool_bytes_per_chip"] * 2 == s.k_pages.nbytes * 2
    assert tokens[0] == tokens[1]
    with pytest.raises(ValueError, match="d_ff"):
        LoopedLM(LoopedLMConfig(**dict(TINY, vocab=256, d_ff=97)), mesh=make_tp_mesh(2))


# -- spans and counters ---------------------------------------------------------------

def test_the_decode_span_and_the_counters_record_the_loop():
    from paddle_tpu.obs import metrics, trace

    model, params = tiny()
    before = len([r for r in trace.TRACER.snapshot() if r[0] == "serve.decode"])
    passes = metrics.REGISTRY.counter("paddle_tpu_serving_layer_passes_total")
    slot_steps = metrics.REGISTRY.counter("paddle_tpu_serving_decode_slot_steps_total")
    p0, d0, s0 = passes.value(phase="prefill"), passes.value(phase="decode"), slot_steps.value()
    s = session(model, params)
    hs = [s.submit(PROMPT, 5), s.submit(PROMPT[:6], 3)]
    s.run_until_idle()
    rows = [r for r in trace.TRACER.snapshot() if r[0] == "serve.decode"][before:]
    assert len(rows) == s.decode_steps == 4
    assert [r[6]["slots"] for r in rows] == [2, 2, 1, 1] and {r[6]["layer_passes"] for r in rows} == {12}
    assert slot_steps.value() - s0 == 6 == sum(len(h.tokens) - 1 for h in hs)
    assert passes.value(phase="decode") - d0 == 6 * 12
    assert passes.value(phase="prefill") - p0 == (len(PROMPT) + 6) * 12
    gauge = metrics.REGISTRY.gauge("paddle_tpu_serving_kv_bytes_per_token")
    assert gauge.value() == 2 * 12 * 64 * 4
    old = ServableLM(LMConfig(vocab=31, n_layers=2, d_model=16, n_heads=2, max_len=64))
    served = ServingSession(old, old.init_params(jax.random.PRNGKey(0)), max_slots=2,
                            page_size=8, prefill_buckets=(16,), max_new_limit=8)
    assert served.layer_passes == 2 and served.k_pages.dtype == jnp.float32
    assert gauge.value() == 2 * 2 * 16 * 4
