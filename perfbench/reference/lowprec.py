"""Operand rounding for the controls: the reference computed in the nearest
precision below the configuration's. `cast` functions are applied to every
matmul/conv operand; gradients pass straight through."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def identity(x):
    return x


def _straight_through(q):
    def f(x):
        return x + jax.lax.stop_gradient(q(x) - x)
    return f


def _fp8(x):
    """e4m3 with a per-tensor scale to its largest finite value (448), the
    usual fp8 recipe; 3 mantissa bits."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _bf16(x):
    """8 bits of mantissa: what a float32 product's operands keep in one
    bfloat16 pass, and what bfloat16 storage keeps of a weight or a key.
    lax.reduce_precision and not a cast there and back: XLA:TPU allows
    excess precision and drops the pair of converts, so that control read
    every gap as exactly 0 on the chip (my chip run, PR 29, call 6)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


CASTS = {
    "float32": identity,
    "bfloat16": _straight_through(_bf16),
    "fp8": _straight_through(_fp8),
}
