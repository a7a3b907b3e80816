"""The attention decoder's training scan against the scan it replaced.

The oracle below is the teacher-forced scan as it was written before the
decoder got its own backward: `jax.checkpoint` around the step, and autodiff
carrying the encoder's gradient through the reverse loop. The decoder's scan
must run the same forward and give the same gradients, while its backward
forms the encoder's gradient after the loop, in one contraction."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from paddle_tpu.core import dtypes
from paddle_tpu.nn import layers as L
from paddle_tpu.nn.attention_layers import AttentionDecoder, DecoderParams
from paddle_tpu.nn.graph import Network, reset_name_scope
from paddle_tpu.ops import attention as attn_ops
from paddle_tpu.ops import linalg
from paddle_tpu.ops import rnn as rnn_ops

# distinct sizes, so that the encoder's [B, Ts, De] is no other array's shape
B, TS, TT, DE, DEMB, H, A = 3, 7, 6, 10, 4, 5, 9
LENGTHS = {
    "full": ([TS] * B, [TT] * B),
    "ragged": ([TS, 4, 2], [TT, 3, 5]),
}
POLICIES = {"f32": dtypes.f32_policy(), "bf16": dtypes.bf16_policy()}


@pytest.fixture(autouse=True)
def _fresh_names():
    reset_name_scope()


def _decoder():
    enc = L.Data("enc", shape=(DE,), is_seq=True)
    emb = L.Data("emb", shape=(DEMB,), is_seq=True)
    return AttentionDecoder(enc, emb, H, attention_size=A)


def _inputs(seed=0):
    rs = np.random.RandomState(seed)

    def n(*shape, scale=0.5):
        return jnp.asarray(rs.randn(*shape).astype(np.float32) * scale)

    p = DecoderParams(
        w_enc=n(DE, A), w_dec=n(H, A), v=n(A), w_in=n(DEMB + DE, 3 * H),
        gru=rnn_ops.GruParams(w_hzr=n(H, 2 * H), w_hc=n(H, H), bias=n(3 * H, scale=0.1)),
        w_init=n(DE, H),
    )
    return p, n(B, TS, DE, scale=1.0), n(B, TT, 3 * H, scale=1.0), n(B, TT, H, scale=1.0)


def _oracle(dec, p, enc, enc_lengths, proj_emb, trg_lengths):
    """The scan as it was: the checkpointed step closes over the encoder."""
    d_emb = p.w_in.shape[0] - enc.shape[-1]
    enc_proj = linalg.matmul(enc, p.w_enc)
    h0 = dec.initial_state(p, enc, enc_lengths)
    mask = (jnp.arange(proj_emb.shape[1])[None, :] < trg_lengths[:, None]).astype(h0.dtype)

    def scan_step(h, xs):
        pe_t, m_t = xs
        context, _ = attn_ops.additive_attention(enc, enc_proj, h, p.w_dec, p.v, enc_lengths)
        proj = pe_t + linalg.matmul(context, p.w_in[d_emb:])
        h_new = rnn_ops.gru_step(proj, h, p.gru)
        m = m_t[:, None]
        h = m * h_new + (1 - m) * h
        return h, h

    xs = (jnp.swapaxes(proj_emb, 0, 1), jnp.swapaxes(mask, 0, 1))
    _, hs = lax.scan(jax.checkpoint(scan_step), h0, xs)
    return jnp.swapaxes(hs, 0, 1)


def _new(dec, p, enc, enc_lengths, proj_emb, trg_lengths):
    return dec.teacher_forced(p, enc, enc_lengths, proj_emb, trg_lengths)


def _loss_fn(run, lengths, policy):
    """A scalar of the hidden states; the forward runs under `policy`, the
    gradient is taken wherever the caller takes it."""
    dec = _decoder()
    enc_len, trg_len = (jnp.asarray(x, jnp.int32) for x in LENGTHS[lengths])

    def loss(p, enc, proj_emb, probe):
        with dtypes.policy_scope(policy):
            # the model's proj_emb comes out of a matmul in the compute dtype
            hs = run(dec, p, enc, enc_len, policy.cast(proj_emb), trg_len)
        return jnp.sum(hs.astype(jnp.float32) * probe)

    return loss


def _grads(run, lengths, policy):
    p, enc, proj_emb, probe = _inputs()
    loss = _loss_fn(run, lengths, policy)
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(p, enc, proj_emb, probe)


def _leaves(grads):
    names = ["p." + f for f in DecoderParams._fields[:4]] + [
        "p.gru." + f for f in rnn_ops.GruParams._fields] + ["p.w_init", "enc", "proj_emb"]
    leaves = jax.tree.leaves(grads)
    assert len(leaves) == len(names)
    return dict(zip(names, (np.asarray(x, np.float64) for x in leaves)))


def _worst_gap(got, want):
    """max |got - want| over max |want|, per leaf → the worst leaf."""
    gaps = {k: np.max(np.abs(got[k] - want[k])) / np.max(np.abs(want[k])) for k in want}
    return max(gaps.values())


@pytest.mark.parametrize("lengths", sorted(LENGTHS))
@pytest.mark.parametrize("policy", sorted(POLICIES))
def test_forward_and_gradients_match_the_checkpointed_scan(policy, lengths):
    """The forward is the oracle's bit for bit under both policies. f32:
    every gradient within rtol 1e-5 (the encoder's gradient sums the target
    steps in another order). bf16: the parameters' and proj_emb's gradients
    within 1e-3 of each leaf's largest entry (the same operations, recomputed
    the same way), and the encoder's within 1e-2. That room is the one
    rounding the oracle adds: it rounds each step's w_t ⊗ dctx_t to bf16
    (2^-9 relative) before its f32 add, where the contraction sums the exact
    products of the same bf16 operands in f32 (0.08-0.11% apart here)."""
    pol = POLICIES[policy]
    p, enc, proj_emb, _ = _inputs()
    enc_len, trg_len = (jnp.asarray(x, jnp.int32) for x in LENGTHS[lengths])

    def fwd(run):
        def f(p, enc, proj_emb):
            with dtypes.policy_scope(pol):
                return run(_decoder(), p, enc, enc_len, pol.cast(proj_emb), trg_len)
        return np.asarray(jax.jit(f)(p, enc, proj_emb))

    np.testing.assert_array_equal(fwd(_new), fwd(_oracle))

    got, want = _leaves(_grads(_new, lengths, pol)), _leaves(_grads(_oracle, lengths, pol))
    if policy == "f32":
        for k in want:
            np.testing.assert_allclose(
                got[k], want[k], rtol=1e-5, atol=1e-6 * np.max(np.abs(want[k])), err_msg=k
            )
    else:
        enc_got, enc_want = got.pop("enc"), want.pop("enc")
        assert _worst_gap(got, want) < 1e-3
        assert _worst_gap({"enc": enc_got}, {"enc": enc_want}) < 1e-2


@pytest.mark.parametrize("lengths", sorted(LENGTHS))
def test_backward_runs_under_the_forwards_policy(lengths):
    """The backward is traced when the gradient is taken, here outside the
    bf16 scope the forward ran in. It must still run bf16: the gradient
    equals, bit for bit, the one taken inside that scope, and it is nearer
    the bf16 oracle than the f32 gradient is."""
    bf16 = dtypes.bf16_policy()
    p, enc, proj_emb, probe = _inputs()
    grad = jax.grad(_loss_fn(_new, lengths, bf16), argnums=(0, 1, 2))
    outside = _leaves(jax.jit(grad)(p, enc, proj_emb, probe))
    with dtypes.policy_scope(bf16):
        inside = _leaves(jax.jit(grad)(p, enc, proj_emb, probe))
    for k in inside:
        np.testing.assert_array_equal(outside[k], inside[k], err_msg=k)
    oracle = _leaves(_grads(_oracle, lengths, bf16))
    f32 = _leaves(_grads(_new, lengths, dtypes.f32_policy()))
    assert _worst_gap(outside, oracle) < _worst_gap(f32, oracle)


def _scan_output_shapes(jaxpr):
    """Shapes of every output of every scan in the jaxpr, at any depth."""
    shapes = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            shapes += [tuple(v.aval.shape) for v in eqn.outvars]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            shapes += _scan_output_shapes(sub)
    return shapes


@pytest.mark.parametrize("policy", sorted(POLICIES))
def test_no_scan_carries_the_encoders_gradient(policy):
    """No scan of the gradient's program outputs a [B, Ts, De] array: the
    encoder's gradient is formed after the loop. The oracle's does (its
    reverse loop carries it), so the walk does see such an array."""
    p, enc, proj_emb, probe = _inputs()

    def shapes(run):
        grad = jax.grad(_loss_fn(run, "ragged", POLICIES[policy]), argnums=(0, 1, 2))
        return _scan_output_shapes(jax.make_jaxpr(grad)(p, enc, proj_emb, probe).jaxpr)

    assert (B, TS, DE) in shapes(_oracle)
    new = shapes(_new)
    assert (B, TS, DE) not in new
    # the reverse loop hands out the stacked weights and context cotangents
    assert (TT, B, TS) in new and (TT, B, DE) in new


def test_the_seq2seq_step_runs_the_decoders_scan():
    """Tracing the seq2seq model's loss and gradient moves the counter by
    one; init, which runs every layer eagerly, does not move it."""
    from paddle_tpu import models
    from paddle_tpu.obs import metrics

    counter = metrics.REGISTRY.counter("paddle_tpu_attention_decoder_scan_total")
    m = models.seq2seq(40, 40, 8, 8)
    net = Network([m.cost])
    rs = np.random.RandomState(0)
    batch = {}
    for name in ("source_ids", "target_ids", "label_ids"):
        batch[name] = rs.randint(2, 40, (3, 4)).astype(np.int32)
        batch[name + ".lengths"] = np.array([4, 2, 3], np.int32)
    before = counter.value()
    params, states = net.init(jax.random.PRNGKey(0), batch)
    assert counter.value() == before

    def loss(p):
        outs, _ = net.apply(p, states, batch, train=True, policy=dtypes.bf16_policy())
        return outs[m.cost.name].value

    grads = jax.jit(jax.grad(loss))(params)
    assert counter.value() == before + 1
    assert all(np.all(np.isfinite(np.asarray(g))) for g in jax.tree.leaves(grads))
