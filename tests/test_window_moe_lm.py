"""WindowMoELM (serving/window_moe_lm.py) against its plain reference
(perfbench/reference/window_moe_lm.py) at a tiny size on the CPU, float32:
d 64, layers w w w f w w (a window of 16 positions), 2 dense layers then 4 of
16 experts top-4 with a shared one, 4 query heads over 2 K/V heads, pages of
4 positions.

(a) prefill then decoding through both kinds of cache to 4 windows' length
    gives the reference's logits; (b) a prompt prefilled in chunks that
    straddle the window's edge gives them too, and so does the same prompt
    prefilled whole past the ring's length; (c) a preempted request replays
    bitwise; (d) the sigmoid routing with its bias and scale is the hand
    computation; (e) the prefix cache and speculation are refused; (f) the
    kernel's window in interpret mode is its oracle's, and with no window it
    is the kernel it was; (g) a program that ignores the window is not the
    reference; (h) the counters and the spans."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from paddle_tpu.obs import trace
from paddle_tpu.obs.metrics import REGISTRY
from paddle_tpu.serving import moe
from paddle_tpu.serving.kv_cache import ring_pages
from paddle_tpu.serving.session import ServingSession
from paddle_tpu.serving.window_moe_lm import FULL, WINDOW, WindowMoEConfig, WindowMoELM
from perfbench.reference import lowprec
from perfbench.reference import window_moe_lm as ref

TINY = dict(vocab=257, layer_types=(WINDOW, WINDOW, WINDOW, FULL, WINDOW, WINDOW), d_model=64,
            n_heads=4, n_kv_heads=2, head_dim=16, window=16, n_dense=2, dense_width=96,
            num_experts=16, top_k=4, expert_width=32, shared_width=32, route_scale=2.826,
            embedding_scale=8.0, max_len=128, dtype="float32")
TOL = 2e-4   # of the logits' std: the order of the sums alone separates the two
PS = 4
RING = ring_pages(16, PS)
PROMPT = [1, 17, 201, 5, 88, 140, 9, 33, 250, 61, 7]


def tiny(**over):
    model = WindowMoELM(WindowMoEConfig(**dict(TINY, **over)))
    params = model.init_params(jax.random.PRNGKey(0))
    # a bias that moves the choice, as the configuration's weights draw it
    params["expert_bias"] = 0.02 * jax.random.normal(jax.random.PRNGKey(7), params["expert_bias"].shape)
    return model, params


def numbers(cfg: WindowMoEConfig) -> dict:
    """The model's numbers under the configuration file's keys (the reference's)."""
    return {
        "layer_types": list(cfg.layer_types), "sliding_window": cfg.window,
        "rms_norm_eps": cfg.rms_eps, "head_dim": cfg.head_dim, "hidden_size": cfg.d_model,
        "num_key_value_heads": cfg.n_kv_heads, "num_dense_layers": cfg.n_dense,
        "num_experts_per_tok": cfg.top_k, "route_scale": cfg.route_scale,
        "rope_theta": cfg.rope_theta, "mup_enabled": cfg.embedding_scale != 1.0,
    }


def reference_logits(model, params, tokens, positions, **over):
    t = -(-len(tokens) // 8) * 8
    padded = jnp.zeros(t, jnp.int32).at[: len(tokens)].set(jnp.asarray(tokens))
    with jax.default_matmul_precision("highest"):
        return ref.logits_at(params, padded, len(tokens), jnp.asarray(positions),
                             dict(numbers(model.cfg), **over), lowprec.identity, rows=8)


def served(model, params, prompt, steps, bucket=16, chunk=None, slot=0):
    """Prefill `prompt` (whole in `bucket`, or in `chunk`s), then `steps`
    greedy decode steps, through the model's own programs over a 2-slot
    cache of both kinds: (tokens, the logits each was chosen from)."""
    grabbed = []
    model._sample = lambda logits, *a: (grabbed.append(logits), jnp.argmax(logits, -1).astype(jnp.int32))[1]
    pages = -(-(len(prompt) + steps + 1) // PS)
    full = (1, 2 * pages + 1, PS, model.cache_width)
    rings = (5, 2 * RING + 1, PS, model.cache_width)
    kp = (jnp.zeros(full), jnp.zeros(rings))
    vp = (jnp.zeros(full), jnp.zeros(rings))
    state = {k: jnp.zeros(s, d) for k, (s, d) in model.counter_spec().items()}
    table = np.concatenate([1 + np.arange(2 * pages).reshape(2, pages),
                            1 + np.arange(2 * RING).reshape(2, RING)], 1).astype(np.int32)
    row = jnp.asarray(table[slot: slot + 1])
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
            seq = list(prompt) + [int(tok[0])]
            lane = jnp.zeros(2, jnp.int32)
            live = jnp.zeros(2, bool).at[slot].set(True)
            for _ in range(steps):
                kp, vp, state, tok = model.decode_step(
                    params, kp, vp, state, lane.at[slot].set(seq[-1]),
                    lane.at[slot].set(len(seq) - 1), live, jnp.asarray(table),
                    jnp.zeros(2, jnp.uint32), lane, jnp.zeros(2, jnp.float32), lane)
                seq.append(int(tok[slot]))
    finally:
        del model._sample
    logits = jnp.concatenate([g[slot:slot + 1] if g.shape[0] == 2 else g[-1:] for g in grabbed])
    return seq, logits


def worst_gap(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.std(want))


def session(model, params, **kw):
    kw = dict(dict(max_slots=4, page_size=PS, prefill_buckets=(16,), max_new_limit=48,
                   prefill_chunk=8), **kw)
    return ServingSession(model, params, **kw)


# -- (a) -----------------------------------------------------------------------

def test_prefill_then_decode_through_both_caches_gives_the_references_logits():
    model, params = tiny()
    seq, logits = served(model, params, PROMPT, steps=64 - len(PROMPT))
    assert len(seq) == 65 and len(set(seq[len(PROMPT):])) > 4, "a degenerate model compares nothing"
    positions = np.arange(len(PROMPT) - 1, len(seq) - 1)
    want = reference_logits(model, params, seq[:-1], positions)
    assert logits.shape == want.shape
    assert worst_gap(logits, want) < TOL
    # and the whole-context forward, position by position
    with jax.default_matmul_precision("highest"):
        full = model.forward_logits(params, jnp.asarray(seq[:-1])[None])[0]
    assert worst_gap(full[positions], want) < TOL


# -- (b) -----------------------------------------------------------------------

@pytest.mark.parametrize("chunk,bucket", [(8, None), (12, None), (None, 48)])
def test_a_long_prompt_in_chunks_across_the_window_or_whole_past_the_ring(chunk, bucket):
    model, params = tiny()
    rs = np.random.default_rng(3)
    prompt = [1] + [int(t) for t in rs.integers(3, 257, 40)]     # 41: 2.5 windows, 2 rings
    seq, logits = served(model, params, prompt, steps=6, chunk=chunk, bucket=bucket or 16)
    positions = np.arange(len(prompt) - 1, len(seq) - 1)
    want = reference_logits(model, params, seq[:-1], positions)
    # a chunk samples after each: the prompt's last chunk's is the first token's
    assert worst_gap(logits[-len(positions):], want) < TOL


# -- (c) -----------------------------------------------------------------------

def test_a_preempted_request_replays_bitwise():
    model, params = tiny()
    rs = np.random.default_rng(0)
    requests = [([1] + [int(t) for t in rs.integers(3, 257, n)], m)
                for n, m in ((30, 40), (9, 44), (20, 40), (12, 36), (25, 30), (6, 44))]

    def run(**kw):
        s = session(model, params, **kw)
        hs = [s.submit(p, m) for p, m in requests]
        s.run_until_idle()
        assert s.cache.pages_in_use == 0
        return [[int(t) for t in h.tokens] for h in hs], s.stats()

    roomy, calm = run()
    tight, stormy = run(num_pages=40)
    assert calm["preemptions"] == 0 and stormy["preemptions"] > 0 and stormy["replayed_tokens"] > 0
    assert tight == roomy
    assert max(len(p) + len(t) for (p, _), t in zip(requests, roomy)) > 2 * 16


# -- (d) -----------------------------------------------------------------------

def test_the_sigmoid_routing_is_the_hand_computation():
    logits = jnp.asarray([[2.0, -1.0, 0.5, 0.0, 1.5], [0.1, 0.2, 0.3, 0.4, -3.0]], jnp.float32)
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.8, -0.5], jnp.float32)
    gate, idx = moe.choose(logits, 2, bias, route_scale=2.826)
    s = 1.0 / (1.0 + np.exp(-np.asarray(logits)))
    # row 0: s + b = .881 .269 .622 1.3 .318 -> experts 3 and 0 (the bias
    # chose 3; its weight is its score alone)
    # row 1: s + b = .525 .550 .574 1.399 -.547 -> experts 3 and 2
    assert [sorted(r) for r in np.asarray(idx).tolist()] == [[0, 3], [2, 3]]
    for row, picked in enumerate(np.asarray(idx)):
        want = 2.826 * s[row, picked] / s[row, picked].sum()
        assert np.allclose(np.asarray(gate[row]), want, atol=1e-6)
    # granite's routing is unchanged: softmax over the chosen logits
    g2, i2 = moe.choose(logits, 2)
    assert np.asarray(i2).tolist() == [[0, 4], [3, 2]]
    assert np.allclose(np.asarray(g2[0]), np.exp([2.0, 1.5]) / np.exp([2.0, 1.5]).sum(), atol=1e-6)


# -- (e) -----------------------------------------------------------------------

@pytest.mark.parametrize("kw", [dict(prefix_cache=True), dict(speculate_k=2)])
def test_the_prefix_cache_and_speculation_are_refused(kw):
    model, params = tiny()
    with pytest.raises(ValueError, match="window layers"):
        session(model, params, **kw)


def test_a_mesh_is_refused():
    from paddle_tpu.parallel.rules import make_tp_mesh

    with pytest.raises(ValueError, match="one chip"):
        WindowMoELM(WindowMoEConfig(**TINY), mesh=make_tp_mesh(2))


# -- (f) -----------------------------------------------------------------------

def _ring_inputs(dtype, window, slots=6, group=2, n_kv=2, hd=16, seed=0):
    ring = ring_pages(window, PS)
    kd = n_kv * hd
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (slots, kd * group), jnp.float32).astype(dtype)
    kp = jax.random.normal(ks[1], (2, ring * slots + 1, PS, kd), jnp.float32).astype(dtype)
    vp = jax.random.normal(ks[2], (2, ring * slots + 1, PS, kd), jnp.float32).astype(dtype)
    table = jnp.asarray(1 + np.arange(ring * slots).reshape(slots, ring), jnp.int32)
    return q, kp, vp, table


@pytest.mark.parametrize("window,dtype", [(16, "float32"), (13, "float32"), (16, "bfloat16"),
                                          (6, "float32")])
def test_the_kernels_window_in_interpret_mode_is_the_oracles(monkeypatch, window, dtype):
    model, _ = tiny(window=window, dtype=dtype)
    q, kp, vp, table = _ring_inputs(dtype, window)
    # an empty slot (position 0), a context shorter than the window, one
    # whose window starts mid-page, one page-aligned, two long past it
    positions = jnp.asarray([0, 7, 22, 31, 61, 100], jnp.int32)
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "0")
    want = model._paged_attention(q, kp, vp, table, positions, layer=1, window=window)
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
    got = model._paged_attention(q, kp, vp, table, positions, layer=jnp.asarray(1, jnp.int32),
                                 window=window)
    tol = 2e-5 if dtype == "float32" else 2e-2
    assert got.shape == want.shape
    assert np.allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol), (
        float(jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)))))
    # the window is what it reads: its oldest key moves the output, the key
    # just before it does not (where the ring still holds that key's page)
    ring, slot = table.shape[1], 4
    pos = int(positions[slot])
    for at, moves in ((pos - window, False), (pos - window + 1, True), (pos, True)):
        page, top = at // PS, pos // PS
        if top - (top - page) % ring != page:
            continue                                  # that page's entry holds a newer one
        hit = kp.at[1, table[slot, page % ring], at % PS].add(5.0)
        again = model._paged_attention(q, hit, vp, table, positions, layer=jnp.asarray(1, jnp.int32),
                                       window=window)
        same = np.allclose(np.asarray(again[slot], np.float32), np.asarray(got[slot], np.float32),
                           atol=tol)
        assert same != moves, (at, moves)


# the Mosaic program of the no-window grouped call below (5 slots, 2 layers
# of 26 pages of 4 positions, 4 query heads over 2 K/V heads of 16, float32),
# printed without locations: the kernel's before windows existed. A change
# to the kernel that is meant records the new one here.
PARENT_KERNEL_SHA256 = "a3c28a885bf131166092a5e4935f49f1e09daa293c8ad9551c21aa0d2f60a546"


def _mosaic_program(text):
    import base64
    import hashlib
    import re

    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    body = base64.b64decode(re.search(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', text).group(1))
    ctx = ir.Context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True
    with ctx:
        asm = ir.Module.parse(body).operation.get_asm(enable_debug_info=False)
    return hashlib.sha256(asm.encode()).hexdigest()


def test_with_no_window_the_kernel_is_the_parents(monkeypatch):
    """The three served cells' kernel: with no window it is, op for op, the
    Mosaic program it was before windows existed, and the windowed walk with
    a window past every position reads the same pages in the same blocks
    and gives its outputs bit for bit."""
    from paddle_tpu.ops.pallas import paged_attention
    from paddle_tpu.ops.pallas.paged_attention import paged_attention_decode

    monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
    q, kp, vp, table = _ring_inputs("float32", 16, slots=5, group=2, seed=3)
    pages = table.shape[1]
    positions = jnp.asarray([0, 3, 9, 17, pages * PS - 1], jnp.int32)
    kw = dict(layer=1, scale=0.25, n_heads=4, group=2)
    plain = paged_attention_decode(q, kp, vp, table, positions, **kw)
    wide = paged_attention_decode(q, kp, vp, table, positions, window=10 ** 6, **kw)
    assert np.array_equal(np.asarray(plain), np.asarray(wide))
    monkeypatch.setattr(paged_attention, "interpret_mode", lambda: False)

    def lowered(**w):
        return jax.jit(lambda *a: paged_attention_decode(*a, **kw, **w)).trace(
            q, kp, vp, table, positions).lower(lowering_platforms=("tpu",)).as_text()

    assert _mosaic_program(lowered()) == PARENT_KERNEL_SHA256
    assert _mosaic_program(lowered(window=8)) != PARENT_KERNEL_SHA256


# -- (g) -----------------------------------------------------------------------

def test_a_program_that_ignores_the_window_is_not_the_reference():
    model, params = tiny()
    seq, logits = served(model, params, PROMPT, steps=40)
    positions = np.arange(len(PROMPT) - 1, len(seq) - 1)
    want = reference_logits(model, params, seq[:-1], positions)
    unwindowed = reference_logits(model, params, seq[:-1], positions, sliding_window=10 ** 6)
    past = positions >= 16           # where the window cuts anything
    assert worst_gap(logits[past], want[past]) < TOL
    assert worst_gap(unwindowed[past], want[past]) > 100 * TOL
    assert worst_gap(unwindowed[~past], want[~past]) < TOL


# -- (h) -----------------------------------------------------------------------

def test_the_counters_and_the_spans_record_both_kinds_of_page():
    model, params = tiny()
    calls = REGISTRY.counter("paddle_tpu_paged_attention_decode_total",
                             "decode attention calls traced, by path (kernel|oracle) and window")
    before = {k: calls.value(path="oracle", window=k) for k in ("0", "16")}
    s = session(model, params)
    rs = np.random.default_rng(5)
    long_prompt = [1] + [int(t) for t in rs.integers(3, 257, 29)]    # 30: four chunks of 8
    hs = [s.submit(long_prompt, 20), s.submit(PROMPT, 30)]
    recorded = trace.TRACER.recorded
    s.run_until_idle()
    assert all(len(h.tokens) == n for h, n in zip(hs, (20, 30)))
    assert calls.value(path="oracle", window="16") - before["16"] == 5
    assert calls.value(path="oracle", window="0") - before["0"] == 1
    counted = s.read_counters()
    assert counted["moe_expert_tokens"].shape == (4, 16)
    # every token of every MoE layer went to four experts, all held here
    assert (counted["moe_assignments"][:, 1] == 0).all()
    assert (counted["moe_expert_tokens"].sum(1) == counted["moe_assignments"][:, 0]).all()
    assert (counted["moe_assignments"][:, 0] % 4 == 0).all()
    rows = trace.TRACER.snapshot()[-(trace.TRACER.recorded - recorded):]
    decodes = [r[6] for r in rows if r[0] == "serve.decode"]
    chunks = [r[6] for r in rows if r[0] == "serve.chunk"]
    assert decodes and all(a["pages_window"] <= a["pages_full"] for a in decodes)
    assert all(a["pages_window"] <= RING * a["slots"] for a in decodes)
    assert max(a["pages_full"] for a in decodes) > RING * 2 - 1 or max(
        a["context_tokens"] for a in decodes) > 16
    # the long prompt's four chunks of 8 and the short one's two
    assert sorted(a["window_from"] for a in chunks) == [0, 0, 0, 0, 1, 9]
