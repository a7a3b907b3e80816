"""Softmax cross-entropy over large vocabularies, alone and fused with the
projection that makes its logits.

The reference computes softmax as an activation then gathers -log(p) in the
cost layer (paddle/cuda/src/hl_cuda_cnn.cu softmax + CostLayer.cpp
MultiClassCrossEntropy). On TPU that shape of computation is
HBM-bandwidth-bound: with a 30k vocab the [B*T, V] logits are the largest
array in the whole NMT step, and every pass over them in float32 costs twice
the bytes for no accuracy benefit in the loss.

Two ops, sharing their reductions:

`softmax_xent_with_logits(logits, labels)` keeps every [N, V] tensor in the
logits' OWN dtype and reduces in f32. That is a promise about the caller as
much as about this file: logits that arrive in float32 stay float32, residual
and gradient included. `Fc.forward` hands it exactly that under the bf16
policy: its bf16 product plus the float32 master bias is float32
(`bf16 + f32 -> f32`), so an `Fc` followed by this op moved a float32
[25600, 30000] four times a step (ledger, PR 26: 33% of seq2seq_nmt.train).

`linear_softmax_xent(x, w, b, labels, policy)` is the guard against that
promotion: the projection and its cross-entropy as ONE custom-VJP op, so the
bias never meets the logits outside a fusion.

  fwd: z = x @ w in the policy's product dtype (bf16 under the bf16 policy);
       m = max(z + b); lse = m + log(sum(exp(z + b - m))); the label's
       logit picked from z + b: the bias add and every reduction in f32
       INSIDE the fusions that read z, nothing f32 of size [N, V] written
  bwd: z = x @ w again (no [N, V] residual: x, w, b, labels and lse are);
       dz = (exp(z + b - lse) - onehot(label)) * g, computed in f32 and
       rounded to z's dtype; db = sum of the f32 values; dx = dz @ w^T and
       dw = x^T @ dz through the policy's product

The rounding points are the unfused step's: the product rounds to the
policy's dtype, the bias add and the reductions are f32, dz rounds to the
product's dtype before the two transposed products. The backward asks for
the product again and no copy is a residual: where forward and backward are
one compiled program, as in a train step, the compiler finds the forward's z
and keeps that, so at most that one [N, V] tensor exists, in the product's
dtype, and dz is never written (PERF.md section 6, PR 27: measured against a
saved copy and against row blocks, both slower at the one size a cell runs,
both deleted).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.core import dtypes

Array = jax.Array


def _f32_logits(z: Array, b: Optional[Array]) -> Array:
    """z + b in f32: elementwise, so it lives inside whatever fusion reads z."""
    x32 = z.astype(jnp.float32)
    return x32 if b is None else x32 + b.astype(jnp.float32)


def _reductions(z: Array, b: Optional[Array], labels: Array):
    """lse and the label's logit of z + b, both f32 [...]."""
    x32 = _f32_logits(z, b)
    m = jnp.max(x32, axis=-1)
    lse = m + jnp.log(jnp.sum(jnp.exp(x32 - m[..., None]), axis=-1))
    picked = jnp.take_along_axis(z, labels[..., None], axis=-1)[..., 0]
    picked = picked.astype(jnp.float32)
    if b is not None:
        picked = picked + b.astype(jnp.float32)[labels]
    return lse, picked


def _dlogits(z: Array, b: Optional[Array], labels: Array, lse: Array, g: Array):
    """(softmax(z + b) - onehot(label)) * g in f32, [..., V]."""
    p = jnp.exp(_f32_logits(z, b) - lse[..., None])
    onehot = (
        lax.broadcasted_iota(jnp.int32, z.shape, z.ndim - 1) == labels[..., None]
    )
    return (p - onehot.astype(jnp.float32)) * g[..., None].astype(jnp.float32)


@jax.custom_vjp
def softmax_xent_with_logits(logits: Array, labels: Array) -> Array:
    """Per-example -log softmax(logits)[label] → f32 [N] (labels int [N])."""
    lse, picked = _reductions(logits, None, labels)
    return lse - picked


def _fwd(logits, labels):
    lse, picked = _reductions(logits, None, labels)
    return lse - picked, (logits, labels, lse)


def _bwd(res, g):
    logits, labels, lse = res
    return _dlogits(logits, None, labels, lse, g).astype(logits.dtype), None


softmax_xent_with_logits.defvjp(_fwd, _bwd)


# -- the projection and its cross-entropy as one op ---------------------------


def _product(a, b, contract, policy):
    return lax.dot_general(
        a, b, (contract, ((), ())), precision=policy.precision,
        preferred_element_type=policy.accum_dtype,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _linear_xent(policy, x, w, b, labels):
    return _linear_xent_fwd(policy, x, w, b, labels)[0]


def _linear_xent_fwd(policy, x, w, b, labels):
    z = _product(x, w, ((1,), (0,)), policy)
    lse, picked = _reductions(z, b, labels)
    return lse - picked, (x, w, b, labels, lse)


def _linear_xent_bwd(policy, res, g):
    x, w, b, labels, lse = res
    z = _product(x, w, ((1,), (0,)), policy)  # the forward's, once compiled
    dz32 = _dlogits(z, b, labels, lse, g)
    dz = dz32.astype(z.dtype)
    dx = _product(dz, w, ((1,), (1,)), policy).astype(x.dtype)
    dw = _product(x, dz, ((0,), (0,)), policy).astype(w.dtype)
    db = None if b is None else jnp.sum(dz32, axis=0).astype(b.dtype)
    return dx, dw, db, None


_linear_xent.defvjp(_linear_xent_fwd, _linear_xent_bwd)


def linear_softmax_xent(
    x: Array,
    w: Array,
    b: Optional[Array],
    labels: Array,
    policy: Optional[dtypes.Policy] = None,
) -> Array:
    """Per-row -log softmax(x @ w + b)[label] → f32 [N], for x [N, D],
    w [D, V], b [V] or None, labels int [N]: what
    `softmax_xent_with_logits(linalg.linear(x, w, b), labels)` computes,
    without logits outside the op."""
    policy = policy or dtypes.current()
    # x and w cross the policy's cast here, outside the custom VJP, so their
    # gradients reach float32 masters through the cast's transpose
    return _linear_xent(policy, policy.cast(x), policy.cast(w), b, labels)
