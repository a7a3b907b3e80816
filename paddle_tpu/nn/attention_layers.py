"""Attention + attention-decoder layers.

AttentionDecoder is the TPU-native replacement for the reference's
RecurrentGradientMachine-driven NMT decoder (the recurrent_group +
simple_attention + gru_step composition of demo/seq2seq; RecurrentGradientMachine.h:32
dynamic unroll): one lax.scan over target steps with teacher forcing at train
time. Generation/beam search lives in paddle_tpu/nn/beam_search.py using the
same parameters.

The jnp attention math here (and in ops/attention.py) is the CPU oracle for
the fused Pallas attention kernel (ops/pallas/rnn_kernels.attention_seq_fused,
ISSUE 9): dot_product_attention auto-dispatches to the kernel on TPU, while
the ADDITIVE (Bahdanau) per-step attention below stays the lax.scan path —
fusing it into the decoder step is a named ROADMAP item 2 lever."""

from __future__ import annotations

import functools
from typing import List, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.core import dtypes
from paddle_tpu.core.registry import LAYERS
from paddle_tpu.nn import init as init_mod
from paddle_tpu.nn.graph import Argument, Context, Layer
from paddle_tpu.obs import metrics as obs_metrics
from paddle_tpu.ops import attention as attn_ops
from paddle_tpu.ops import linalg
from paddle_tpu.ops import rnn as rnn_ops


@LAYERS.register("simple_attention")
class SimpleAttention(Layer):
    """simple_attention (networks.py:1304): additive attention of a decoder
    state over an encoder sequence → context vector [B, D]."""

    type_name = "simple_attention"

    def __init__(self, enc: Layer, dec_state: Layer, attention_size: int = 0, name=None):
        super().__init__([enc, dec_state], name=name)
        self.attention_size = attention_size

    def forward(self, ctx: Context, ins: List[Argument]) -> Argument:
        enc, dec = ins
        assert enc.is_seq
        d_enc = enc.value.shape[-1]
        d_dec = dec.value.shape[-1]
        a = self.attention_size or d_dec
        w_enc = ctx.param(self, "w_enc", (d_enc, a), init_mod.smart_normal, None)
        w_dec = ctx.param(self, "w_dec", (d_dec, a), init_mod.smart_normal, None)
        v = ctx.param(self, "v", (a,), init_mod.smart_normal, None)
        enc_proj = linalg.matmul(enc.value, w_enc)
        context, _ = attn_ops.additive_attention(
            enc.value, enc_proj, dec.value, w_dec, v, enc.lengths
        )
        return Argument(context)


class DecoderParams(NamedTuple):
    """Everything the attention-GRU decoder step needs — shared between the
    training scan and beam-search generation."""

    w_enc: jax.Array  # [De, A] attention encoder proj
    w_dec: jax.Array  # [H, A] attention decoder proj
    v: jax.Array  # [A]
    w_in: jax.Array  # [Demb+De, 3H] input projection for the GRU
    gru: rnn_ops.GruParams
    w_init: jax.Array  # [De, H] initial-state projection (from enc last/back)


@LAYERS.register("attention_decoder")
class AttentionDecoder(Layer):
    """Teacher-forced attention decoder (training path).

    inputs: [encoder_seq [B,Ts,De], target_embedding_seq [B,Tt,Demb]]
    output: decoder hidden states [B, Tt, H] (project with Fc for logits).

    Step t attends with the *previous* hidden state, then
    GRU(input=[emb_t, context_t]) — matching the reference decoder composition
    (demo seq2seq gru_decoder_with_attention)."""

    type_name = "attention_decoder"

    def __init__(
        self,
        enc: Layer,
        target_emb: Layer,
        size: int,
        attention_size: int = 0,
        name: Optional[str] = None,
    ):
        super().__init__([enc, target_emb], name=name)
        self.size = size
        self.attention_size = attention_size

    def _params(self, ctx: Context, d_enc: int, d_emb: int) -> DecoderParams:
        h = self.size
        a = self.attention_size or h
        return DecoderParams(
            w_enc=ctx.param(self, "att.w_enc", (d_enc, a), init_mod.smart_normal, None),
            w_dec=ctx.param(self, "att.w_dec", (h, a), init_mod.smart_normal, None),
            v=ctx.param(self, "att.v", (a,), init_mod.smart_normal, None),
            w_in=ctx.param(
                self, "w_in", (d_emb + d_enc, 3 * h), init_mod.smart_normal, None
            ),
            gru=rnn_ops.GruParams(
                w_hzr=ctx.param(self, "gru.w_hzr", (h, 2 * h), init_mod.smart_normal, None),
                w_hc=ctx.param(self, "gru.w_hc", (h, h), init_mod.smart_normal, None),
                bias=ctx.param(self, "gru.b", (3 * h,), init_mod.zeros, None),
            ),
            w_init=ctx.param(self, "w_init", (d_enc, h), init_mod.smart_normal, None),
        )

    def initial_state(self, p: DecoderParams, enc_value, enc_lengths):
        """h0 = tanh(W @ first-step backward encoder state) — the reference
        seeds the decoder from the encoder's first backward state."""
        from paddle_tpu.ops import sequence as seq_ops

        first = seq_ops.seq_first(enc_value)
        return jnp.tanh(linalg.matmul(first, p.w_init))

    def step(self, p: DecoderParams, enc_value, enc_proj, enc_lengths, emb_t, h):
        d_emb = emb_t.shape[-1]
        proj_emb = linalg.matmul(emb_t, p.w_in[:d_emb])
        return self._step_proj(p, enc_value, enc_proj, enc_lengths, proj_emb, h, d_emb)

    def _step_proj(self, p: DecoderParams, enc_value, enc_proj, enc_lengths,
                   proj_emb_t, h, d_emb: int):
        """One decoder step given the *pre-projected* embedding input
        (proj_emb_t = emb_t @ w_in[:Demb] — hoisted out of the training scan
        so the only in-scan matmuls are the ones that truly depend on h)."""
        return _step(_step_params(p, d_emb), enc_value, enc_proj, enc_lengths,
                     proj_emb_t, h)

    def forward(self, ctx: Context, ins: List[Argument]) -> Argument:
        enc, emb = ins
        assert enc.is_seq and emb.is_seq
        d_emb = emb.value.shape[-1]
        p = self._params(ctx, enc.value.shape[-1], d_emb)
        # hoist the teacher-forced half of the GRU input projection: one
        # [B, T, Demb] @ [Demb, 3H] MXU matmul instead of T tiny in-scan ones
        # (r4 profile: the scan body ran at 0.4 TFLOP/s before the hoist)
        proj_emb = linalg.matmul(emb.value, p.w_in[:d_emb])
        if ctx.mode == "apply" and isinstance(enc.value, jax.core.Tracer):
            obs_metrics.observe_attention_decoder_scan()
        hs = self.teacher_forced(p, enc.value, enc.lengths, proj_emb, emb.lengths)
        return Argument(hs, emb.lengths)

    def teacher_forced(self, p: DecoderParams, enc_value, enc_lengths, proj_emb,
                       trg_lengths):
        """Hidden states [B, Tt, H] of the decoder fed the target sequence,
        given proj_emb = target_embedding @ w_in[:Demb] [B, Tt, 3H]; rows
        past their target length keep their state."""
        d_emb = p.w_in.shape[0] - enc_value.shape[-1]
        enc_proj = linalg.matmul(enc_value, p.w_enc)
        h0 = self.initial_state(p, enc_value, enc_lengths)
        t = proj_emb.shape[1]
        mask = (jnp.arange(t)[:, None] < trg_lengths[None, :]).astype(h0.dtype)
        hs = _teacher_forced_scan(
            dtypes.current(), _step_params(p, d_emb), enc_value, enc_proj,
            jnp.swapaxes(proj_emb, 0, 1), mask, enc_lengths, h0,
        )
        return jnp.swapaxes(hs, 0, 1)


class _StepParams(NamedTuple):
    """The parameters one decoder step reads: the attention's and the GRU's,
    with w_ctx = w_in[Demb:], the context's rows of the input projection."""

    w_dec: jax.Array
    v: jax.Array
    w_ctx: jax.Array
    gru: rnn_ops.GruParams


def _step_params(p: DecoderParams, d_emb: int) -> _StepParams:
    return _StepParams(p.w_dec, p.v, p.w_in[d_emb:], p.gru)


def _gru_update(w_ctx, gru, proj_emb_t, context, h):
    """The step's GRU half: input [emb_t, context_t] @ w_in, the embedding's
    share pre-projected."""
    proj = proj_emb_t + linalg.matmul(context, w_ctx)
    return rnn_ops.gru_step(proj, h, gru)


def _step(sp: _StepParams, enc, enc_proj, enc_lengths, proj_emb_t, h):
    context, _ = attn_ops.additive_attention(
        enc, enc_proj, h, sp.w_dec, sp.v, enc_lengths
    )
    return _gru_update(sp.w_ctx, sp.gru, proj_emb_t, context, h)


def _masked(m_t, h_new, h):
    """Rows past their target length keep their state."""
    m = m_t[:, None]
    return m * h_new + (1 - m) * h


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _teacher_forced_scan(policy, sp, enc, enc_proj, proj_emb, mask, lengths, h0):
    """The decoder over all target steps → hs [Tt, B, H]; proj_emb
    [Tt, B, 3H] and mask [Tt, B] are time-major. `policy` is the one the
    forward runs under: the backward is traced when the gradient is taken,
    maybe outside the forward's policy_scope, and must not read another."""
    with dtypes.policy_scope(policy):
        # the step's cast of enc, made once here: cast in the loop, XLA:TPU
        # hoists it into a [B, De, Ts] layout that pads Ts to 128 lanes
        enc_c = policy.cast(enc)

        def body(h, xs):
            pe_t, m_t = xs
            h = _masked(m_t, _step(sp, enc_c, enc_proj, lengths, pe_t, h), h)
            return h, h

        _, hs = lax.scan(body, h0, (proj_emb, mask))
    return hs


def _teacher_forced_scan_fwd(policy, sp, enc, enc_proj, proj_emb, mask, lengths, h0):
    hs = _teacher_forced_scan(policy, sp, enc, enc_proj, proj_emb, mask, lengths, h0)
    # only the [B, H] carries are saved: the backward recomputes each step's
    # [B, Ts, A] attention tensors from h_{t-1} rather than keeping 50 of them
    return hs, (sp, enc, enc_proj, proj_emb, mask, lengths, h0, hs)


def _teacher_forced_scan_bwd(policy, res, d_hs):
    """One reverse scan, each step recomputed from h_{t-1}. enc is a
    constant of the step's vjp, so the loop forms no cotangent of it:
    d_enc = Σ_t w_t ⊗ dctx_t is ONE batched contraction after the loop,
    over the stacked weights and context cotangents, accumulated in f32
    (carried in the loop it was a [B, Ts, De] f32 read and write a step)."""
    sp, enc, enc_proj, proj_emb, mask, lengths, h0, hs = res
    with dtypes.policy_scope(policy):
        enc_c = policy.cast(enc)

        def attend(w_dec, v, enc_proj, h):
            w = attn_ops.additive_weights(enc_proj, h, w_dec, v, lengths)
            return attn_ops.attention_context(enc_c, w), w

        def body(carry, xs):
            dh, d_sp, d_enc_proj = carry
            h, pe_t, m_t, dh_t = xs
            # without the barrier XLA:TPU recomputes the scores' tanh over
            # [B, Ts, A] a second time, transposed, for v's gradient alone
            enc_proj_t, h = lax.optimization_barrier((enc_proj, h))
            (context, w), attend_vjp = jax.vjp(attend, sp.w_dec, sp.v, enc_proj_t, h)
            _, update_vjp = jax.vjp(
                lambda w_ctx, gru, pe_t, h, context: _masked(
                    m_t, _gru_update(w_ctx, gru, pe_t, context, h), h
                ),
                sp.w_ctx, sp.gru, pe_t, h, context,
            )
            d_w_ctx, d_gru, d_pe, dh_u, d_context = update_vjp(dh + dh_t)
            d_dec, d_v, d_ep, dh_a = attend_vjp((d_context, jnp.zeros_like(w)))
            d_sp = jax.tree.map(
                jnp.add, d_sp, _StepParams(d_dec, d_v, d_w_ctx, d_gru)
            )
            carry = (dh_u + dh_a, d_sp, d_enc_proj + d_ep)
            return carry, (d_pe, policy.cast(w), d_context)

        h_prev = jnp.concatenate([h0[None], hs[:-1]])
        init = (jnp.zeros_like(h0), jax.tree.map(jnp.zeros_like, sp),
                jnp.zeros_like(enc_proj))
        (dh0, d_sp, d_enc_proj), (d_pe, ws, d_contexts) = lax.scan(
            body, init, (h_prev, proj_emb, mask, d_hs), reverse=True
        )
        d_enc = jnp.einsum(
            "tbs,tbd->bsd", ws, d_contexts,
            preferred_element_type=jnp.float32, precision=policy.precision,
        )
    return d_sp, d_enc.astype(enc.dtype), d_enc_proj, d_pe, None, None, dh0


_teacher_forced_scan.defvjp(_teacher_forced_scan_fwd, _teacher_forced_scan_bwd)
