"""Op-level tests: conv/pool vs numpy reference, sequence ops vs per-example loops.

This is the analog of the reference's CPU-vs-GPU compare idiom
(paddle/math/tests/test_matrixCompare.cpp; function/*OpTest.cpp) — here numpy
loops are the oracle for the XLA lowering."""

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import conv as conv_ops
from paddle_tpu.ops import sequence as seq_ops


def _np_conv2d(x, w, stride, pad):
    b, h, wid, cin = x.shape
    kh, kw, _, cout = w.shape
    xp = np.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    oh = (h + 2 * pad - kh) // stride + 1
    ow = (wid + 2 * pad - kw) // stride + 1
    out = np.zeros((b, oh, ow, cout), np.float32)
    for i in range(oh):
        for j in range(ow):
            patch = xp[:, i * stride : i * stride + kh, j * stride : j * stride + kw, :]
            out[:, i, j, :] = np.tensordot(patch, w, axes=([1, 2, 3], [0, 1, 2]))
    return out


def test_conv2d_matches_numpy(np_rng):
    x = np_rng.randn(2, 8, 8, 3).astype(np.float32)
    w = np_rng.randn(3, 3, 3, 5).astype(np.float32)
    got = np.asarray(conv_ops.conv2d(x, w, stride=2, padding=1))
    want = _np_conv2d(x, w, 2, 1)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_max_pool(np_rng):
    x = np_rng.randn(2, 6, 6, 4).astype(np.float32)
    got = np.asarray(conv_ops.max_pool2d(x, 2, 2))
    want = x.reshape(2, 3, 2, 3, 2, 4).max(axis=(2, 4))
    np.testing.assert_allclose(got, want)


def test_avg_pool_exclusive_padding(np_rng):
    x = np.ones((1, 4, 4, 1), np.float32)
    got = np.asarray(conv_ops.avg_pool2d(x, 3, 2, padding=1, exclusive=True))
    # with exclusive counting every window averages ones → 1.0 everywhere
    np.testing.assert_allclose(got, np.ones_like(got))


def test_conv_transpose_shape(np_rng):
    x = np_rng.randn(2, 4, 4, 8).astype(np.float32)
    w = np_rng.randn(4, 4, 16, 8).astype(np.float32)
    out = conv_ops.conv2d_transpose(x, w, stride=2, padding=1)
    assert out.shape == (2, 8, 8, 16)


def test_seq_pooling_vs_loop(np_rng):
    x = np_rng.randn(3, 7, 4).astype(np.float32)
    lengths = np.array([3, 7, 1], np.int32)
    for fn, red in [
        (seq_ops.seq_sum, lambda v: v.sum(0)),
        (seq_ops.seq_mean, lambda v: v.mean(0)),
        (seq_ops.seq_max, lambda v: v.max(0)),
    ]:
        got = np.asarray(fn(jnp.asarray(x), jnp.asarray(lengths)))
        want = np.stack([red(x[i, : lengths[i]]) for i in range(3)])
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_seq_last_first(np_rng):
    x = np_rng.randn(3, 5, 2).astype(np.float32)
    lengths = np.array([2, 5, 1], np.int32)
    got = np.asarray(seq_ops.seq_last(jnp.asarray(x), jnp.asarray(lengths)))
    want = np.stack([x[i, lengths[i] - 1] for i in range(3)])
    np.testing.assert_allclose(got, want)
    np.testing.assert_allclose(
        np.asarray(seq_ops.seq_first(jnp.asarray(x))), x[:, 0]
    )


def test_seq_softmax(np_rng):
    x = np_rng.randn(2, 6).astype(np.float32)
    lengths = np.array([4, 6], np.int32)
    got = np.asarray(seq_ops.seq_softmax(jnp.asarray(x), jnp.asarray(lengths)))
    assert got[0, 4:].sum() == 0
    np.testing.assert_allclose(got.sum(-1), [1.0, 1.0], rtol=1e-5)


def test_context_projection(np_rng):
    x = np_rng.randn(2, 5, 3).astype(np.float32)
    lengths = np.array([3, 5], np.int32)
    got = np.asarray(
        seq_ops.context_projection(jnp.asarray(x), jnp.asarray(lengths), -1, 3)
    )
    assert got.shape == (2, 5, 9)
    # middle block is x itself (masked beyond length)
    np.testing.assert_allclose(got[1, :, 3:6], x[1])
    # first block at t=0 is zeros (no left context)
    np.testing.assert_allclose(got[:, 0, 0:3], 0)
    # right context beyond sequence end is zero for the short sequence
    np.testing.assert_allclose(got[0, 2, 6:9], 0)


def test_bilinear_resize():
    x = jnp.arange(16, dtype=jnp.float32).reshape(1, 4, 4, 1)
    out = conv_ops.bilinear_resize(x, 8, 8)
    assert out.shape == (1, 8, 8, 1)


def test_fused_batch_norm_matches_autodiff_oracle():
    """ops/normalization.py custom VJP vs plain-jnp autodiff in f32."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import normalization as N

    rs = np.random.RandomState(7)
    x = rs.randn(8, 5, 5, 6).astype(np.float32) * 2 + 1.5
    gamma = rs.randn(6).astype(np.float32) * 0.5 + 1.0
    beta = rs.randn(6).astype(np.float32)
    eps = 1e-5

    def oracle(x, g, b):
        axes = (0, 1, 2)
        m = jnp.mean(x, axis=axes)
        v = jnp.var(x, axis=axes)
        y = (x - m) * jax.lax.rsqrt(v + eps) * g + b
        return y

    def loss_fused(args):
        y, _, _ = N.batch_norm_train(*args, eps)
        return jnp.sum(jnp.sin(y))

    def loss_oracle(args):
        return jnp.sum(jnp.sin(oracle(*args)))

    y_f, m_f, v_f = N.batch_norm_train(x, gamma, beta, eps)
    np.testing.assert_allclose(np.asarray(y_f), np.asarray(oracle(x, gamma, beta)),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(m_f), x.mean((0, 1, 2)), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(v_f), x.var((0, 1, 2)), rtol=1e-3, atol=1e-4)

    g1 = jax.grad(loss_fused)((x, gamma, beta))
    g2 = jax.grad(loss_oracle)((x, gamma, beta))
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=2e-3, atol=2e-3)

    # inference path
    y_i = N.batch_norm_inference(x, gamma, beta, m_f, v_f, eps)
    np.testing.assert_allclose(np.asarray(y_i), np.asarray(y_f), rtol=2e-3, atol=2e-3)


def test_softmax_xent_matches_log_softmax_oracle():
    """Fused big-vocab CE (ops/xent.py) vs the naive f32 log_softmax path:
    value and gradient, in f32 exactly and in bf16 at bf16 tolerance."""
    import jax
    from paddle_tpu.ops import xent as xent_ops

    rng = np.random.RandomState(7)
    n, v = 32, 97
    logits = rng.randn(n, v).astype(np.float32) * 3.0
    labels = rng.randint(0, v, n).astype(np.int32)

    def oracle(x, y):
        logp = jax.nn.log_softmax(x.astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]

    got = xent_ops.softmax_xent_with_logits(jnp.asarray(logits), jnp.asarray(labels))
    want = oracle(jnp.asarray(logits), jnp.asarray(labels))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)

    g_got = jax.grad(lambda x: xent_ops.softmax_xent_with_logits(x, jnp.asarray(labels)).sum())(
        jnp.asarray(logits)
    )
    g_want = jax.grad(lambda x: oracle(x, jnp.asarray(labels)).sum())(jnp.asarray(logits))
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_want), rtol=1e-5, atol=1e-6)

    # bf16 logits: big tensors stay bf16 end-to-end, loss still finite/close
    lb = jnp.asarray(logits, jnp.bfloat16)
    got16 = xent_ops.softmax_xent_with_logits(lb, jnp.asarray(labels))
    assert got16.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got16), np.asarray(want), rtol=5e-2, atol=5e-2)
    g16 = jax.grad(lambda x: xent_ops.softmax_xent_with_logits(x, jnp.asarray(labels)).sum())(lb)
    assert g16.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(g16, np.float32), np.asarray(g_want), rtol=5e-2, atol=5e-2
    )


# -- ops/xent.linear_softmax_xent: the projection and its cross-entropy --------


def _proj_case(n=300, d=24, v=97, seed=11):
    rng = np.random.RandomState(seed)
    return dict(
        x=jnp.asarray(rng.randn(n, d).astype(np.float32)),
        w=jnp.asarray(rng.randn(d, v).astype(np.float32) * 0.3),
        b=jnp.asarray(rng.randn(v).astype(np.float32)),
        y=jnp.asarray(rng.randint(0, v, n).astype(np.int32)),
        g=jnp.asarray(rng.rand(n).astype(np.float32)),
    )


# (rows, vocabulary, bias)
_PROJ_CASES = {
    "tall": (300, 97, True),
    "whole_tiles": (384, 128, True),
    "wide_vocab": (37, 1000, True),
    "no_bias": (300, 97, False),
    "small_no_bias": (64, 97, False),
}


@pytest.mark.parametrize("precision", ["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(_PROJ_CASES))
def test_linear_softmax_xent_matches_the_unfused_pair(case, precision):
    """linear_softmax_xent(x, w, b, y) against softmax_xent_with_logits(
    linalg.linear(x, w, b), y): values and the gradients of x, w and b, to
    1e-6 of each array's size under the f32 policy and at the oracle test's
    bf16 tolerance under bf16; rows and vocabularies that fill whole tiles
    and that do not, with and without a bias."""
    import jax
    from paddle_tpu.core import dtypes
    from paddle_tpu.ops import linalg
    from paddle_tpu.ops import xent as xent_ops

    n, v, bias = _PROJ_CASES[case]
    c = _proj_case(n=n, v=v)
    policy = dtypes.get(precision)
    b = c["b"] if bias else None
    argnums = (0, 1, 2) if bias else (0, 1)

    def fused(x, w, b_):
        return (xent_ops.linear_softmax_xent(x, w, b_, c["y"], policy) * c["g"]).sum()

    def pair(x, w, b_):
        logits = linalg.linear(x, w, b_, policy)
        return (xent_ops.softmax_xent_with_logits(logits, c["y"]) * c["g"]).sum()

    per_row = xent_ops.linear_softmax_xent(c["x"], c["w"], b, c["y"], policy)
    want_rows = xent_ops.softmax_xent_with_logits(
        linalg.linear(c["x"], c["w"], b, policy), c["y"]
    )
    assert per_row.dtype == jnp.float32 and per_row.shape == (n,)
    got = jax.value_and_grad(fused, argnums)(c["x"], c["w"], b)
    want = jax.value_and_grad(pair, argnums)(c["x"], c["w"], b)
    tol = 1e-6 if precision == "f32" else 5e-2
    np.testing.assert_allclose(np.asarray(per_row), np.asarray(want_rows), rtol=tol, atol=tol)
    for name, a, e in zip("xwb", got[1], want[1]):
        assert a.dtype == e.dtype == jnp.float32, name  # masters' cotangents
        scale = max(1.0, float(jnp.max(jnp.abs(e))))
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(e), rtol=tol, atol=tol * scale, err_msg=name
        )


@pytest.mark.parametrize("precision", ["f32", "bf16"])
def test_projected_cost_of_a_masked_weighted_sequence(precision):
    """A [B, T] sequence with a length mask and a per-token weight through
    CostLayer.forward: the cost that took its Fc's work against the same
    layers with the logits kept as an output, which leaves the Fc in place."""
    import jax
    from paddle_tpu.core import dtypes
    from paddle_tpu.nn import costs as C
    from paddle_tpu.nn import layers as L
    from paddle_tpu.nn.graph import Network, reset_name_scope

    reset_name_scope()
    bsz, t, d, v = 20, 15, 12, 33
    policy = dtypes.get(precision)
    h = L.Data("h", shape=(d,), is_seq=True)
    lbl = L.Data("lbl", shape=(), is_seq=True)
    wt = L.Data("wt", shape=(), is_seq=True)
    logits = L.Fc(h, v, act=None, name="proj")
    cost = C.ClassificationCost(logits, lbl, weight=wt, name="cost", coeff=0.5)
    fused, plain = Network([cost]), Network([cost, logits])
    assert list(fused.fused_projections) == ["cost"] and not plain.fused_projections
    rng = np.random.RandomState(2)
    lens = rng.randint(1, t + 1, bsz).astype(np.int32)
    batch = {
        "h": rng.randn(bsz, t, d).astype(np.float32), "h.lengths": lens,
        "lbl": rng.randint(0, v, (bsz, t)).astype(np.int32), "lbl.lengths": lens,
        "wt": rng.rand(bsz, t).astype(np.float32), "wt.lengths": lens,
    }
    params, states = fused.init(jax.random.PRNGKey(0), batch, policy=policy)
    params["proj.b"] = jnp.asarray(rng.randn(v).astype(np.float32))

    def loss(net):
        def f(p, hv):
            outs, _ = net.apply(p, states, dict(batch, h=hv), train=True, policy=policy)
            return outs["cost"].value
        return jax.value_and_grad(f, (0, 1))(params, jnp.asarray(batch["h"]))

    (lf, (gf, gxf)), (lp, (gp, gxp)) = loss(fused), loss(plain)
    tol = 1e-6 if precision == "f32" else 5e-2
    np.testing.assert_allclose(float(lf), float(lp), rtol=tol, atol=tol)
    for k in gp:
        np.testing.assert_allclose(np.asarray(gf[k]), np.asarray(gp[k]), rtol=tol, atol=tol, err_msg=k)
    np.testing.assert_allclose(np.asarray(gxf), np.asarray(gxp), rtol=tol, atol=tol)
    # rows past a sequence's length carry no gradient either way
    dead = np.arange(t)[None, :] >= lens[:, None]
    assert not np.asarray(gxf)[dead].any()
