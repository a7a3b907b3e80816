"""Attention primitives.

The reference composes additive attention from primitive layers
(simple_attention, trainer_config_helpers/networks.py:1304: fc + expand +
addto + tanh + fc(1) + sequence softmax + scaling + pooling). Here they are
fused ops; dot-product attention is also provided (the building block the
ring-attention sequence parallelism in paddle_tpu/parallel uses)."""

from __future__ import annotations

import math
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from paddle_tpu.core import dtypes
from paddle_tpu.ops import linalg
from paddle_tpu.ops import sequence as seq_ops

Array = jax.Array


def additive_scores(
    enc_proj: Array,  # [B, T, A] — W_e @ encoder states (precomputed)
    dec_state: Array,  # [B, H]
    w_dec: Array,  # [H, A]
    v: Array,  # [A]
) -> Array:
    """Bahdanau scores: v^T tanh(enc_proj + W_d s) → [B, T]. The score
    contraction is a dot boundary: its inputs cross to the ambient compute
    dtype (v is an f32 master param — without the cast it would promote the
    whole score path back to f32 under a bf16 policy)."""
    p = dtypes.current()
    q = linalg.matmul(dec_state, w_dec)  # [B, A]
    e = jnp.tanh(p.cast(enc_proj) + q[:, None, :])
    return jnp.einsum("bta,a->bt", e, p.cast(v))


def additive_attention(
    enc: Array,  # [B, T, D] encoder states
    enc_proj: Array,  # [B, T, A]
    dec_state: Array,  # [B, H]
    w_dec: Array,
    v: Array,
    lengths: Array,
) -> Tuple[Array, Array]:
    """→ (context [B, D], weights [B, T] f32); masked sequence softmax runs
    f32 (seq_softmax pin), the context contraction is a dot boundary in the
    ambient compute dtype."""
    weights = additive_weights(enc_proj, dec_state, w_dec, v, lengths)
    return attention_context(enc, weights), weights


def additive_weights(
    enc_proj: Array,  # [B, T, A]
    dec_state: Array,  # [B, H]
    w_dec: Array,
    v: Array,
    lengths: Array,
) -> Array:
    """The masked sequence softmax of the Bahdanau scores → weights [B, T]
    f32; padded source positions read 0."""
    return seq_ops.seq_softmax(additive_scores(enc_proj, dec_state, w_dec, v), lengths)


def attention_context(enc: Array, weights: Array) -> Array:
    """Σ_t weights[b, t] · enc[b, t] → [B, D]: a dot boundary in the ambient
    compute dtype."""
    p = dtypes.current()
    return jnp.einsum("btd,bt->bd", p.cast(enc), p.cast(weights))


def _attn_fuse_ok(q: Array, k: Array, v: Array, scale) -> bool:
    """Route to the fused pallas forward (ops/pallas/rnn_kernels.py
    attention_seq_fused) when the pallas dispatch policy is on, the scale is
    static (it folds into the kernel), and one batch row's working set —
    q/k/v blocks plus the [Tq, Tk] score tile that the fusion keeps in VMEM
    — fits the budget (default 2M f32 elements ≈ 8 MB of the ~16 MB VMEM;
    PADDLE_TPU_FUSED_ATTN_MAX overrides, 0 disables)."""
    if scale is not None and not isinstance(scale, (int, float)):
        return False  # traced scale: keep the jnp path
    limit = int(os.environ.get("PADDLE_TPU_FUSED_ATTN_MAX", "2000000"))
    if limit <= 0:
        return False
    b, tq, d = q.shape
    tk = k.shape[1]
    dv = v.shape[2]
    # score tile + mask block (worst case Mq == Tq: a full [Tq, Tk] mask
    # block is resident alongside the score tile) + q/k/v blocks + output
    row = 2 * tq * tk + tk * (d + dv) + tq * (d + dv)
    if row > limit:
        return False
    from paddle_tpu.ops import pallas as pal

    return pal.enabled()


def dot_product_attention(
    q: Array,  # [B, Tq, D]
    k: Array,  # [B, Tk, D]
    v: Array,  # [B, Tk, Dv]
    mask: Optional[Array] = None,  # [B, Tq, Tk] or [B, 1, Tk]
    scale: Optional[float] = None,
    fused: Optional[bool] = None,
) -> Array:
    """Scaled dot-product attention → [B, Tq, Dv].

    `fused=None` (auto) dispatches to the fused pallas forward on TPU (see
    _attn_fuse_ok); the jnp body below is the CPU oracle AND the exact
    source of the fused op's backward. Softmax runs f32 either way."""
    d = q.shape[-1]
    if fused is None:
        fused = _attn_fuse_ok(q, k, v, scale)
    if fused:
        from paddle_tpu.ops.pallas.rnn_kernels import attention_seq_fused

        s = float(scale) if scale is not None else 1.0 / math.sqrt(d)
        m = (
            jnp.ones((q.shape[0], 1, k.shape[1]), jnp.float32)
            if mask is None
            else mask
        )
        return attention_seq_fused(q, k, v, m, s)
    scale = scale if scale is not None else 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    logits = jnp.einsum("bqd,bkd->bqk", q, k) * scale
    if mask is not None:
        # keep-where-positive, matching the fused kernel and its oracle
        # (rnn_kernels._attn_oracle) bit for bit — the mask contract is 0/1
        # float, and the two dispatch paths must agree even off-contract
        logits = jnp.where(mask > 0, logits, seq_ops.NEG_INF)
    w = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(v.dtype)
    return jnp.einsum("bqk,bkv->bqv", w, v)
