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


CASTS = {
    "float32": identity,
    "fp8": _straight_through(_fp8),
}
