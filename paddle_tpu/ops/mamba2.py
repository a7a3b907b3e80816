"""The Mamba-2 mixer's three pieces ("Transformers are SSMs", Dao and Gu 2024,
arXiv:2405.21060), as a served model needs them: the short causal depthwise
convolution with a carried tail, the one-token recurrence of a decode step,
and the chunked (SSD) form of the same recurrence for a prompt.

Per head h (scalar decay, state S_h in R^{P x N}), one group of B and C:

    a_t = exp(dt_t * A_h)                    # A_h < 0, dt_t >= 0
    S_t = a_t * S_{t-1} + dt_t * x_t (outer) B_t
    y_t = S_t C_t                            # the caller adds D_h * x_t

A position with dt_t == 0 leaves the state as it was (decay 1, input 0) and
is how a caller masks what is not a token: the padded tail of a bucketed
prompt, a decode lane with no request.

The chunked form splits T into chunks of Q positions. Inside a chunk the
outputs are one masked product, (C B^T * L) (x dt) with L[l, s] the decay
from s to l; each chunk adds its inputs, decayed to its end, to the state it
was handed, and hands that on. Whatever the state is a factor of or a sum
into runs at float32 accuracy (`HIGHEST`: the state is float32, a prompt's
state must be what the token-by-token recurrence leaves); the sums' terms are
exact wherever x, B and C came in bfloat16."""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

Array = jax.Array
F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def causal_conv(
    x: Array,        # [B, T, C] this call's inputs
    tail: Array,     # [B, K-1, C] the K-1 inputs before x[:, 0]
    w: Array,        # [K, C] taps, w[K-1] on the current input
    b: Array,        # [C]
    n_valid: Array,  # [B] int32: how many of the T positions are tokens
) -> Tuple[Array, Array]:
    """y[t] = b + sum_k w[k] * x[t - (K-1) + k], float32, and the tail a
    later call continues from: the K-1 inputs that END at the last token,
    `n_valid`, not at T (a bucket's padding is no input)."""
    k, t = w.shape[0], x.shape[1]
    xp = jnp.concatenate([tail.astype(x.dtype), x], 1)       # [B, T+K-1, C]
    wf = w.astype(F32)
    y = b.astype(F32) + sum(
        wf[i] * xp[:, i: i + t].astype(F32) for i in range(k)
    )
    idx = n_valid[:, None] + jnp.arange(k - 1)[None, :]       # [B, K-1]
    new_tail = jnp.take_along_axis(xp, idx[..., None], axis=1)
    return y, new_tail


def ssm_step(
    state: Array,  # [S, H, P, N] float32
    x: Array,      # [S, H, P]
    dt: Array,     # [S, H] float32, 0 where the lane holds no token
    a_neg: Array,  # [H] float32, A = -exp(A_log)
    b: Array,      # [S, N]
    c: Array,      # [S, N]
) -> Tuple[Array, Array]:
    """One token a slot: (y [S, H, P] float32, the new state), elementwise
    float32 over the state, nothing crossing slots."""
    decay = jnp.exp(dt * a_neg)                               # [S, H]
    xdt = x.astype(F32) * dt[..., None]                       # [S, H, P]
    new = (
        state * decay[..., None, None]
        + xdt[..., None] * b.astype(F32)[:, None, None, :]
    )
    y = jnp.sum(new * c.astype(F32)[:, None, None, :], -1)
    return y, new


def ssd_chunked(
    x: Array,      # [B, T, H, P]
    dt: Array,     # [B, T, H] float32, 0 at positions that are no token
    a_neg: Array,  # [H] float32
    b: Array,      # [B, T, N]
    c: Array,      # [B, T, N]
    state: Array,  # [B, H, P, N] float32: the state before x[:, 0]
    chunk: int,
) -> Tuple[Array, Array]:
    """The recurrence over T positions, chunk by chunk: (y [B, T, H, P]
    float32, the state after the last position). T that is no multiple of
    the chunk is padded with dt = 0 positions, which change nothing."""
    bsz, t, h, p = x.shape
    n = b.shape[-1]
    q = min(int(chunk), t)
    pad = -t % q
    if pad:
        x, dt, b, c = (
            jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
            for v in (x, dt, b, c)
        )
    nc = (t + pad) // q
    xdt = (x.astype(F32) * dt[..., None]).reshape(bsz, nc, q, h, p)
    bq = b.astype(F32).reshape(bsz, nc, q, n)
    cq = c.astype(F32).reshape(bsz, nc, q, n)
    cs = jnp.cumsum((dt * a_neg).reshape(bsz, nc, q, h), 2)   # [B, nc, Q, H]
    # decay from s to l inside a chunk, 0 above the diagonal
    seg = cs[:, :, :, None, :] - cs[:, :, None, :, :]         # [B, nc, l, s, H]
    tri = jnp.tril(jnp.ones((q, q), bool))[None, None, :, :, None]
    decay = jnp.where(tri, jnp.exp(jnp.where(tri, seg, 0.0)), 0.0)
    cb = jnp.einsum("bcln,bcsn->bcls", cq, bq, precision=_HI)
    y = jnp.einsum(
        "bclsh,bcshp->bclhp", cb[..., None] * decay, xdt, precision=_HI
    )
    # what each chunk adds to the state, decayed to the chunk's end
    to_end = jnp.exp(cs[:, :, -1:, :] - cs)                   # [B, nc, Q, H]
    added = jnp.einsum(
        "bcsh,bcshp,bcsn->bchpn", to_end, xdt, bq, precision=_HI
    )
    whole = jnp.exp(cs[:, :, -1, :])                          # [B, nc, H]

    def carry(s, chunk_c):
        add_c, whole_c = chunk_c
        return s * whole_c[..., None, None] + add_c, s        # emits the state ENTERING

    final, entering = jax.lax.scan(
        carry, state, (jnp.moveaxis(added, 1, 0), jnp.moveaxis(whole, 1, 0))
    )
    entering = jnp.moveaxis(entering, 0, 1)                   # [B, nc, H, P, N]
    y = y + jnp.einsum(
        "bcln,bchpn,bclh->bclhp", cq, entering, jnp.exp(cs), precision=_HI
    )
    return y.reshape(bsz, nc * q, h, p)[:, :t], final
