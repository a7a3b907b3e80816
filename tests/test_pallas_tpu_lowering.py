"""Every pallas_call in ops/pallas/ lowered FOR THE TPU from the CPU sandbox.

The kernel tests elsewhere run in interpret mode, which accepts programs
Mosaic refuses (block shapes, tilings, unsupported primitives). Lowering with
`lowering_platforms=("tpu",)` and interpret off runs the real Pallas→Mosaic
lowering rules without a chip, at the shapes chip_smoke.py sends: a refusal
shows here in seconds instead of costing chip time. What lowering cannot see
— Mosaic's own passes and the VMEM budget — the slow-tier test below compiles
ahead of time against a described v5e topology (libtpu, no device needed).
"""

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.pallas import paged_attention, rnn_kernels


@pytest.fixture(autouse=True)
def _mosaic_not_interpret(monkeypatch):
    monkeypatch.setattr(rnn_kernels, "interpret_mode", lambda: False)
    monkeypatch.setattr(paged_attention, "interpret_mode", lambda: False)


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


@pytest.mark.slow
def test_mosaic_compiles_for_v5e():
    """Ahead-of-time compile against a described v5e: Mosaic's own passes
    and the scoped-VMEM allocation, including the largest carries the
    dispatch rule lets through."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    on_chip = SingleDeviceSharding(topo.devices[0])
    edge = [
        ("lstm_bwd_edge", _lstm_bwd, _lstm_shapes(8, 128, 1280), 2),
        ("gru_bwd_edge", _gru_bwd, _gru_shapes(8, 64, 1536), 2),
    ]
    for name, fn, shapes, n_calls in CASES + edge:
        compiled = jax.jit(fn).lower(*_avals(shapes, on_chip)).compile()
        assert compiled.as_text().count("tpu_custom_call") == n_calls, name
