"""Every pallas_call in ops/pallas/ lowered FOR THE TPU from the CPU sandbox.

The kernel tests elsewhere run in interpret mode, which accepts programs
Mosaic refuses (block shapes, tilings, unsupported primitives). Lowering with
`lowering_platforms=("tpu",)` and interpret off runs the real Pallas→Mosaic
lowering rules without a chip, at the shapes chip_smoke.py sends: a refusal
shows here in seconds instead of costing chip time. What lowering cannot see
— Mosaic's own passes and the VMEM budget — the slow-tier test below compiles
ahead of time against a described v5e topology (libtpu, no device needed).

The same described v5e compiles the serving programs that write the page
pool (no Pallas in them; they are here because one file may describe the
topology: a second file can land on another xdist worker, which cannot load
libtpu too): what XLA:TPU does to the pool, a relayout or an in-place
update, is in the compiled text and costs no chip time to read.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import paged_attention, rnn_kernels, ssm_decode
from paddle_tpu.serving.session import decode_step_in_flight


@pytest.fixture(autouse=True)
def _mosaic_not_interpret(monkeypatch):
    monkeypatch.setattr(rnn_kernels, "interpret_mode", lambda: False)
    monkeypatch.setattr(paged_attention, "interpret_mode", lambda: False)
    monkeypatch.setattr(ssm_decode, "interpret_mode", lambda: False)


def _lstm_fwd(proj, mask, w, b, h0, c0):
    return rnn_kernels.lstm_seq_fused(proj, mask, w, b, h0, c0)


def _lstm_bwd(proj, mask, w, b, h0, c0):
    def loss(proj, w, b, h0, c0):
        hs, hl, cl = rnn_kernels.lstm_seq_fused(proj, mask, w, b, h0, c0)
        return jnp.sum(hs) + jnp.sum(hl) + jnp.sum(cl)

    return jax.grad(loss, (0, 1, 2, 3, 4))(proj, w, b, h0, c0)


def _gru_fwd(proj, mask, wzr, wc, b, h0):
    return rnn_kernels.gru_seq_fused(proj, mask, wzr, wc, b, h0)


def _gru_bwd(proj, mask, wzr, wc, b, h0):
    def loss(proj, wzr, wc, b, h0):
        hs, hl = rnn_kernels.gru_seq_fused(proj, mask, wzr, wc, b, h0)
        return jnp.sum(hs) + jnp.sum(hl)

    return jax.grad(loss, (0, 1, 2, 3, 4))(proj, wzr, wc, b, h0)


def _attention(q, k, v, mask):
    return rnn_kernels.attention_seq_fused(q, k, v, mask, 0.125)


def _paged(n_heads):
    def f(q, k_pages, v_pages, table, positions):
        return paged_attention.paged_attention_decode(
            q, k_pages, v_pages, table, positions,
            layer=1, scale=0.25, n_heads=n_heads,
        )

    return f


def _paged_window(n_heads, group, window):
    def f(q, k_pages, v_pages, table, positions):
        return paged_attention.paged_attention_decode(
            q, k_pages, v_pages, table, positions,
            layer=1, scale=0.088, n_heads=n_heads, group=group, window=window,
        )

    return f


def _ssm_shapes(s, m, h, p, n):
    bf16 = jnp.bfloat16
    return [(s, m, h, p, n), ((), jnp.int32), ((s, h, p), bf16), (s, h), (h,),
            ((s, n), bf16), ((s, n), bf16), ((s,), jnp.bool_)]


def _lstm_shapes(t, b, h):
    return [(t, b, 4 * h), (t, b, 1), (h, 4 * h), (4 * h,), (b, h), (b, h)]


def _gru_shapes(t, b, h):
    return [(t, b, 3 * h), (t, b, 1), (h, 2 * h), (h, h), (3 * h,), (b, h)]


def _paged_shapes(s, heads, hd, ps, pmax, layers=2, n_pages=None):
    kd = heads * hd
    pool = (layers, n_pages or 1 + s * pmax, ps, kd)
    return [(s, kd), pool, pool, ((s, pmax), jnp.int32), ((s,), jnp.int32)]


# (name, fn, argument shapes, pallas_calls expected) at chip_smoke.py's FULL
# shapes: seq2seq's GRU, LSTMs of hidden 256 and 1280, the demo and the
# lane-aligned serving geometries, and the shapes the benchmark and --tp=4
# send the paged-attention kernel. The backward programs re-run the forward kernel.
CASES = [
    ("lstm_fwd_h256", _lstm_fwd, _lstm_shapes(100, 64, 256), 1),
    ("lstm_bwd_h256", _lstm_bwd, _lstm_shapes(100, 64, 256), 2),
    ("lstm_fwd_h1280", _lstm_fwd, _lstm_shapes(100, 64, 1280), 1),
    ("lstm_bwd_h1280", _lstm_bwd, _lstm_shapes(100, 64, 1280), 2),
    ("gru_fwd", _gru_fwd, _gru_shapes(50, 128, 512), 1),
    ("gru_bwd", _gru_bwd, _gru_shapes(50, 128, 512), 2),
    ("attention", _attention,
     [(16, 128, 128), (16, 128, 128), (16, 128, 128), (16, 1, 128)], 1),
    ("paged_demo", _paged(2), _paged_shapes(8, 2, 16, 16, 8), 1),
    ("paged_aligned", _paged(16), _paged_shapes(16, 16, 128, 16, 8), 1),
    # the benchmark's served cell (servable_lm_2048: 32 slots, 16 heads of
    # 128, 128 pages of 16 a slot, a pool of 24 layers x 833 pages) and one
    # shard of it under --tp=4 (4 of the 16 heads)
    ("paged_cell", _paged(16),
     _paged_shapes(32, 16, 128, 16, 128, layers=24, n_pages=833), 1),
    ("paged_tp_shard", _paged(4),
     _paged_shapes(32, 4, 128, 16, 128, layers=24, n_pages=833), 1),
    # trinity_mini's cell: 64 slots, 32 query heads over 4 K/V heads of 128,
    # bfloat16; a window layer's ring of 129 pages a slot over 5 layers, and
    # the full layer's 1120 pages a slot
    ("paged_window_cell", _paged_window(32, 8, 2048),
     [((64, 4096), jnp.bfloat16), ((5, 1 + 64 * 129, 16, 512), jnp.bfloat16),
      ((5, 1 + 64 * 129, 16, 512), jnp.bfloat16), ((64, 129), jnp.int32), ((64,), jnp.int32)], 1),
    ("paged_full_trinity", _paged_window(32, 8, 0),
     [((64, 4096), jnp.bfloat16), ((2, 71681, 16, 512), jnp.bfloat16),
      ((2, 71681, 16, 512), jnp.bfloat16), ((64, 1120), jnp.int32), ((64,), jnp.int32)], 1),
    # the Mamba-2 decode step at granite_4_0_h_small's cell (64 slots, a stack
    # of nine layers of 128 heads of 64 x 128, the layer traced) and at
    # chip_smoke's small hybrid model
    ("ssm_decode_cell", ssm_decode.ssm_decode, _ssm_shapes(64, 9, 128, 64, 128), 1),
    ("ssm_decode_small", ssm_decode.ssm_decode, _ssm_shapes(2, 3, 8, 64, 128), 1),
]


def _avals(shapes, sharding=None):
    out = []
    for s in shapes:
        shape, dtype = s if isinstance(s[0], tuple) else (s, jnp.float32)
        out.append(jax.ShapeDtypeStruct(shape, dtype, sharding=sharding))
    return out


@pytest.mark.parametrize("name,fn,shapes,n_calls", CASES, ids=[c[0] for c in CASES])
def test_lowers_to_mosaic_custom_call(name, fn, shapes, n_calls):
    text = (
        jax.jit(fn).trace(*_avals(shapes))
        .lower(lowering_platforms=("tpu",)).as_text()
    )
    assert text.count("tpu_custom_call") == n_calls


def test_rnn_dispatch_decides_from_shapes(monkeypatch, caplog):
    """A carry whose blocks cannot fit VMEM takes the scan path by a rule on
    the shapes, with a log line — not by catching the compiler."""
    import logging

    from paddle_tpu.ops import rnn

    monkeypatch.setenv("PADDLE_TPU_PALLAS", "1")
    assert rnn._use_fused(True, "lstm", 64, 1280)   # hidden 1280: resident
    assert rnn._use_fused(True, "gru", 128, 512)    # seq2seq
    with caplog.at_level(logging.INFO, logger="paddle_tpu"):
        assert not rnn._use_fused(True, "lstm", 256, 1280)
        assert not rnn._use_fused(True, "gru", 64, 2048)
    assert "lax.scan path" in caplog.text
    assert not rnn._use_fused(False, "lstm", 8, 8)  # exotic activations


@pytest.fixture(scope="module")
def on_chip():
    """One chip of a described v5e as a sharding for avals. The persistent
    compile cache is off meanwhile: it cannot read such an entry back
    without a chip and warns at every later compile."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cached)
    compilation_cache.reset_cache()


@pytest.mark.slow
def test_mosaic_compiles_for_v5e(on_chip):
    """Ahead-of-time compile against a described v5e: Mosaic's own passes
    and the scoped-VMEM allocation, including the largest carries the
    dispatch rule lets through."""
    edge = [
        ("lstm_bwd_edge", _lstm_bwd, _lstm_shapes(8, 128, 1280), 2),
        ("gru_bwd_edge", _gru_bwd, _gru_shapes(8, 64, 1536), 2),
    ]
    for name, fn, shapes, n_calls in CASES + edge:
        compiled = jax.jit(fn).lower(*_avals(shapes, on_chip)).compile()
        assert compiled.as_text().count("tpu_custom_call") == n_calls, name


# -- the page pool is written in place ---------------------------------------
# The served cell's pool (servable_lm_2048: 24 layers x 833 pages of 16
# positions x 2048 lanes, 2.62 GB each for K and V) under the commit program,
# and the same pool at two layers (218 MB each) under the two programs that
# run a forward before they commit: those keep 112 MB of relaid weights
# whatever the commit does, so their temporaries get one pool's bytes where
# the commit alone gets 64 MB.
CELL_POOL = (24, 833, 16, 2048)
MAX_PAGES = 128

# program: (method, positions, layers, the most its temporaries may take)
POOL_WRITERS = {
    "commit_bucket_64": ("commit_prefill", 64, 24, 64e6),
    "commit_bucket_1024": ("commit_prefill", 1024, 24, 64e6),
    "prefill_chunk_64": ("prefill_chunk", 64, 2, 218e6),
    "verify_chunk_5": ("verify_chunk", 5, 2, 218e6),
}


@pytest.mark.parametrize("program", sorted(POOL_WRITERS))
def test_page_pool_is_written_in_place(program, on_chip):
    """No instruction of the pool's shape is a `copy`, both donated pools
    are the outputs' buffers, and the temporaries hold no pool: a scatter
    whose window spans the layer dim costs four whole-pool copies and a pool
    of temporaries here (PERF.md, PR 32)."""
    from paddle_tpu.serving.model import LMConfig, ServableLM

    method, positions, n_layers, most_temp = POOL_WRITERS[program]
    pool = (n_layers,) + CELL_POOL[1:]
    kd = pool[3]
    model = ServableLM(LMConfig(vocab=512, n_layers=n_layers, d_model=kd,
                                n_heads=16, max_len=2048))
    one, rows = ((1,), jnp.int32), ((1, MAX_PAGES), jnp.int32)
    seeds, temps = ((1,), jnp.uint32), ((1,), jnp.float32)
    kv, tokens = (n_layers, 1, positions, kd), ((1, positions), jnp.int32)
    shapes = [pool, pool] + {
        # kc, vc, lengths, block_rows, starts
        "commit_prefill": [kv, kv, one, rows, one],
        # tokens, starts, lengths, block_rows, seeds, temps, top_ks
        "prefill_chunk": [tokens, one, one, rows, seeds, temps, one],
        # tokens, starts, block_rows, seeds, steps0, temps, top_ks
        "verify_chunk": [tokens, one, rows, seeds, one, temps, one],
    }[method]
    args = _avals(shapes, on_chip)
    if method != "commit_prefill":  # the two that take the parameters first
        args.insert(0, jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=on_chip),
            jax.eval_shape(model.init_params, jax.random.PRNGKey(0)),
        ))
    pools = (len(args) - len(shapes), len(args) - len(shapes) + 1)
    compiled = (
        jax.jit(getattr(model, method), donate_argnums=pools)
        .lower(*args).compile()
    )
    shape = re.escape("f32[" + ",".join(map(str, pool)) + "]")
    copies = [
        line.strip()[:120] for line in compiled.as_text().splitlines()
        if re.search(r"= " + shape + r"\S* copy\(", line)
    ]
    assert not copies, copies
    memory = compiled.memory_analysis()
    pool_bytes = 4 * n_layers * pool[1] * pool[2] * kd
    assert memory.alias_size_in_bytes == 2 * pool_bytes
    assert memory.temp_size_in_bytes < most_temp


# -- the looped decoder's decode step, at Ouro-2.6B's size -------------------

def test_the_looped_decode_step_is_one_layer_body_scanned_in_place(on_chip, monkeypatch):
    """LoopedLM.decode_step at ouro_2_6b's shapes (48 stacked layers run 4
    times, bfloat16, a pool 192 layers deep, 16 slots) compiled for the
    described v5e: ONE Mosaic call serves the 192 cache layers (the layer a
    traced scalar over a bfloat16 pool), no instruction of the pool's or of
    a stacked weight's shape is a `copy` (the scan reads each layer's slice
    where it lies: no second pass over 5 GB of weights a step), both pools
    are the outputs' buffers and the temporaries are megabytes."""
    from paddle_tpu.serving.looped_lm import LoopedLM, LoopedLMConfig

    monkeypatch.setenv("PADDLE_TPU_PALLAS", "1")
    model = LoopedLM(LoopedLMConfig(
        vocab=49152, n_layers=48, d_model=2048, n_heads=16, head_dim=128,
        d_ff=5632, ut_steps=4, max_len=1280,
    ))
    slots, pages = 16, 300
    pool = (model.cache_layers, pages, 16, model.cache_width)

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=on_chip)

    params = jax.tree.map(
        lambda a: aval(a.shape, a.dtype),
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0)),
    )
    s = (slots,)
    # the SESSION's decode step (ISSUE 36): the model's, with each lane's
    # token taken from the host's or from the step before's, on the device
    compiled = jax.jit(decode_step_in_flight(model), donate_argnums=(1, 2)).lower(
        params, aval(pool, jnp.bfloat16), aval(pool, jnp.bfloat16),
        aval(s, jnp.int32), aval(s, jnp.int32), aval(s, jnp.bool_),
        aval(s, jnp.int32), aval(s, jnp.bool_),
        aval((slots, 80), jnp.int32), aval(s, jnp.uint32), aval(s, jnp.int32),
        aval(s, jnp.float32), aval(s, jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    big = [pool] + [tuple(a.shape) for a in params.values() if len(a.shape) == 3]
    copies = [
        line.strip()[:120] for line in text.splitlines()
        if any(re.search(r"= bf16\[" + ",".join(map(str, shape)) + r"\]\S* copy\(", line)
               for shape in big)
    ]
    assert not copies, copies
    memory = compiled.memory_analysis()
    pool_bytes = 2 * pool[0] * pool[1] * pool[2] * pool[3]
    assert memory.alias_size_in_bytes == 2 * pool_bytes
    assert memory.temp_size_in_bytes < 64e6


# -- the hybrid decoder's decode step, at granite-4.0-h-small's cut ------------

def test_the_hybrid_decode_step_reads_experts_and_state_where_they_lie(on_chip, monkeypatch):
    """HybridMoELM.decode_step at granite_4_0_h_small's shapes (nine Mamba-2
    layers in two scanned runs round one attention layer, 36 of 72 experts a
    layer, bfloat16, 64 slots of float32 state) compiled for the described
    v5e: the grouped-head paged-attention kernel passes Mosaic, each scanned
    Mamba run's recurrence is the `ssm_decode` kernel and no fusion reads
    the stacked state a second time for y, the grouped
    expert products take the WHOLE expert stacks (no instruction of a
    layer's experts' shape or of the state's is a copy or a slice cut for
    them: either is a third of the step), pools and state are the outputs'
    buffers, and the temporaries are megabytes."""
    from paddle_tpu.serving.hybrid_moe_lm import HybridMoEConfig, HybridMoELM

    monkeypatch.setenv("PADDLE_TPU_PALLAS", "1")
    model = HybridMoELM(HybridMoEConfig(
        vocab=50176, layer_types=("mamba",) * 5 + ("attention",) + ("mamba",) * 4,
        d_model=4096, n_heads=32, n_kv_heads=8, head_dim=128, mamba_heads=128,
        mamba_head_dim=64, mamba_state=128, num_experts_routed=72,
        experts_held=tuple(range(36)), top_k=10, expert_width=768, shared_width=1536,
        attention_multiplier=1.0 / 128, max_len=2048,
    ))
    slots = 64
    pool = (model.cache_layers, 8193, 16, model.cache_width)

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=on_chip)

    params = jax.tree.map(
        lambda a: aval(a.shape, a.dtype),
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0)),
    )
    state = {k: aval((slots,) + s, d) for k, (s, d) in model.state_spec().items()}
    state.update({k: aval(s, d) for k, (s, d) in model.counter_spec().items()})
    s = (slots,)
    # the SESSION's decode step (ISSUE 36), as the looped test above
    compiled = jax.jit(decode_step_in_flight(model), donate_argnums=(1, 2, 3)).lower(
        params, aval(pool, jnp.bfloat16), aval(pool, jnp.bfloat16), state,
        aval(s, jnp.int32), aval(s, jnp.int32), aval(s, jnp.bool_),
        aval(s, jnp.int32), aval(s, jnp.bool_),
        aval((slots, 128), jnp.int32), aval(s, jnp.uint32), aval(s, jnp.int32),
        aval(s, jnp.float32), aval(s, jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert pool == (1, 8193, 16, 1024) and 'paged_attention_decode' in text
    # the Mamba-2 recurrence is the kernel's, once a scanned run, and no
    # fusion reads the stacked state to reduce it to y: the second read of
    # 268 MB a layer and step that the kernel removes (PERF.md, PR 38)
    assert len(re.findall(r"= \(f32\[64,4,1,2048\]\S*, f32\[64,9,128,64,128\]\S*\) custom-call\(",
                          text)) == 2, "ssm_decode kernel expected once a Mamba run"
    shapes = dict(re.findall(r"%(\S+) = (\S+?)\{", text))
    readouts = [
        line.strip()[:140] for line in text.splitlines()
        if re.search(r"= f32\[64,128,64\]\S* fusion\(", line)
        and any(shapes.get(op) == "f32[64,9,128,64,128]"
                for op in re.findall(r"%([\w.-]+)", line.split("fusion(", 1)[1]))
    ]
    assert not readouts, readouts
    cut = [
        line.strip()[:140] for line in text.splitlines()
        if re.search(r"= (bf16\[36,(4096,1536|768,4096)\]|f32\[64,(9,)?128,64,128\])\S* "
                     r"(copy|fusion|dynamic-slice|slice)\(", line)
        and "dynamic-update-slice" not in line.split("=")[0]
    ]
    assert not cut, cut
    memory = compiled.memory_analysis()
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in state.values())
    # (the tail's three rows are laid out padded: at least, and within a tenth)
    expected = 2 * 2 * int(np.prod(pool)) + held
    assert expected <= memory.alias_size_in_bytes < 1.1 * expected
    assert memory.argument_size_in_bytes < 12.6e9 and memory.temp_size_in_bytes < 64e6


# -- the NMT decoder's backward, at seq2seq_nmt.train's shapes -----------------

def _while_bodies(text):
    """{body name: its instruction lines} of every while loop in the text."""
    names = set(re.findall(r" while\(.*?body=%?([\w.-]+)", text))
    bodies, cur = {}, None
    for line in text.splitlines():
        if cur is None:
            m = re.match(r"%?([\w.-]+) \(", line)
            if m and m.group(1) in names:
                cur = bodies.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        else:
            cur.append(line)
    return bodies


def test_the_nmt_decoders_backward_loop_carries_no_encoder_gradient(on_chip):
    """jax.grad of the attention decoder's teacher-forced scan at the cell's
    shapes (batch 512, 50 source and 50 target steps, encoder states 1024
    wide, hidden and attention 512, bfloat16) compiled for the described
    v5e: no loop body has a fusion of the encoder's [512, 50, 1024] shape
    (the float32 gradient carried through the reverse loop, 210 MB read and
    written a step, is formed once after it), and no array anywhere has the
    source steps minor: [512, 1024, 50] is the encoder's bfloat16 copy laid
    out with 50 steps padded to 128 lanes, which slows both context
    contractions 2.3 times, and [512, 512, 50] the scores' tanh recomputed
    transposed for v's gradient alone (PERF.md, PR 42)."""
    from paddle_tpu.core import dtypes
    from paddle_tpu.nn import layers as L
    from paddle_tpu.nn.attention_layers import AttentionDecoder, DecoderParams
    from paddle_tpu.ops import linalg, rnn

    b, t, d_enc, d_emb, h = 512, 50, 1024, 512, 512

    def aval(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=on_chip)

    params = DecoderParams(
        w_enc=aval(d_enc, h), w_dec=aval(h, h), v=aval(h), w_in=aval(d_emb + d_enc, 3 * h),
        gru=rnn.GruParams(w_hzr=aval(h, 2 * h), w_hc=aval(h, h), bias=aval(3 * h)),
        w_init=aval(d_enc, h),
    )
    dec = AttentionDecoder(L.Data("enc", shape=(d_enc,), is_seq=True),
                           L.Data("emb", shape=(d_emb,), is_seq=True), h)

    def loss(p, enc, emb, lengths):
        with dtypes.policy_scope(dtypes.bf16_policy()):
            proj_emb = linalg.matmul(emb, p.w_in[:d_emb])
            hs = dec.teacher_forced(p, enc, lengths, proj_emb, lengths)
        return jnp.sum(hs.astype(jnp.float32))

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        params, aval(b, t, d_enc), aval(b, t, d_emb), aval(b, dtype=jnp.int32)
    ).compile().as_text()
    bodies = _while_bodies(text)
    assert len(bodies) == 2, sorted(bodies)  # the forward scan and the reverse one
    carried = [
        line.strip()[:140] for lines in bodies.values() for line in lines
        if re.search(rf"= \(?\w+\[{b},{t},{d_enc}\]\S* fusion\(", line)
    ]
    assert not carried, carried
    steps_minor = sorted(set(re.findall(rf"\w+\[{b},(?:{d_enc}|{h}),{t}\]", text)))
    assert not steps_minor, steps_minor
