"""Fused batch normalization for TPU.

Why hand-write this: the naive formulation
(`xf = x.astype(f32); mean(xf); var(xf); normalize(xf)`) lets XLA
materialize/share a float32 copy of every conv activation between the
statistics pass and the apply pass, and jax autodiff of that formulation emits
more full passes over the activation than the textbook backward needs. On a
bandwidth-bound model (ResNet-50's convolution fusions are HBM-bound on the
chip, PERF.md section 5) every extra pass over a [B,H,W,C] tensor is pure
step time.

Design (reference behavioral contract: BatchNormLayer.cpp / CudnnBatchNorm,
per-channel statistics over batch+spatial):
- statistics in ONE fused pass: sum and sum-of-squares reductions over bf16
  input with the f32 convert fused INTO the reduction (no f32 activation
  tensor exists in HBM). This is the "batch-norm statistics stay f32" leg of
  the mixed-precision contract (SGDTrainer(precision="bf16"), ISSUE 9): the
  reductions here are f32 REGARDLESS of the policy's compute dtype, by
  construction, not by Policy.cast;
- normalize in one elementwise pass (f32 math in registers, bf16 in/out);
- custom VJP with the minimal pass structure: one fused dual-reduction pass
  (sum(dy), sum(dy*xhat)) + one elementwise pass for dx.

Total traffic: fwd reads x twice + writes y once; bwd reads (x, dy) twice +
writes dx once — 9 activation-sized streams vs 13+ from autodiff.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def batch_norm_train(x, gamma, beta, eps: float):
    """Training-mode BN over all axes but the last. Returns (y, mean, var)
    with mean/var float32 [C] (biased variance, like the reference)."""
    y, mean, var = _bn_fwd_impl(x, gamma, beta, eps)
    return y, mean, var


def _bn_fwd_impl(x, gamma, beta, eps):
    axes = tuple(range(x.ndim - 1))
    n = 1
    for a in axes:
        n *= x.shape[a]
    xf = x.astype(jnp.float32)  # fused into the reductions below, never stored
    s1 = jnp.sum(xf, axis=axes)
    s2 = jnp.sum(jnp.square(xf), axis=axes)
    mean = s1 / n
    var = jnp.maximum(s2 / n - jnp.square(mean), 0.0)
    inv = jax.lax.rsqrt(var + eps)
    # scale/shift folded to per-channel a,b so the apply pass is one fma.
    # a/b stay f32 (they are [C]-sized — free) and the normalize arithmetic
    # runs f32 with ONE cast on the output: with bf16 activations and large
    # beta/mean magnitudes, doing the fma in bf16 loses mantissa;
    # XLA fuses the converts into the elementwise pass either way.
    a = gamma.astype(jnp.float32) * inv
    b = beta.astype(jnp.float32) - gamma.astype(jnp.float32) * inv * mean
    y = (xf * a + b).astype(x.dtype)
    return y, mean, var


def _bn_fwd(x, gamma, beta, eps):
    y, mean, var = _bn_fwd_impl(x, gamma, beta, eps)
    inv = jax.lax.rsqrt(var + eps)
    return (y, mean, var), (x, gamma, mean, inv)


def _bn_bwd(eps, res, cts):
    x, gamma, mean, inv = res
    dy, _dmean, _dvar = cts  # stats outputs feed moving averages: no grad path
    axes = tuple(range(x.ndim - 1))
    n = 1
    for a in axes:
        n *= x.shape[a]
    xf = x.astype(jnp.float32)
    dyf = dy.astype(jnp.float32)
    # one fused pass: both reductions read (x, dy) together
    dbeta = jnp.sum(dyf, axis=axes)
    dgx = jnp.sum(dyf * xf, axis=axes)
    # sum(dy * xhat) = inv * (sum(dy*x) - mean*sum(dy))
    dgamma = inv * (dgx - mean * dbeta)
    # dx = gamma*inv/n * (n*dy - dbeta - xhat*dgamma). Per-channel constants
    # stay f32 like the forward's a/b (same mantissa-loss argument): the fma
    # runs f32 with one cast on the output, XLA fuses the converts.
    gi = gamma.astype(jnp.float32) * inv
    c2 = gi * (dbeta + mean * inv * -dgamma) / -n  # constant term
    # xhat*dgamma = (x-mean)*inv*dgamma -> express dx as a*dy + b*x + c per channel
    bx = gi * inv * dgamma / -n
    dx = (dyf * gi + xf * bx + c2).astype(x.dtype)
    return dx, dgamma.astype(gamma.dtype), dbeta.astype(gamma.dtype)


batch_norm_train.defvjp(_bn_fwd, _bn_bwd)


def batch_norm_inference(x, gamma, beta, mean, var, eps: float):
    """Inference-mode BN with running statistics (per-channel affine only)."""
    inv = jax.lax.rsqrt(var.astype(jnp.float32) + eps)
    a = (gamma.astype(jnp.float32) * inv).astype(x.dtype)
    b = (
        beta.astype(jnp.float32)
        - gamma.astype(jnp.float32) * inv * mean.astype(jnp.float32)
    ).astype(x.dtype)
    return x * a + b
