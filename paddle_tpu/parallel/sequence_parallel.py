"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference (2017) scales sequences via ragged batching and dynamic RNN
unroll (SURVEY §2.5 row "Sequence parallelism": absent); a TPU-native
framework must treat long-context as first-class. Two schemes over the mesh
'seq' axis:

- `ring_attention`: Q stays put; K/V blocks rotate around the ring via
  `lax.ppermute` while a flash-style online softmax (running max / numerator /
  denominator) accumulates — memory O(T_local), compute overlapped with ICI
  transfers by XLA. (Liu et al., Ring Attention, 2023.)
- `ulysses_attention`: `lax.all_to_all` swaps the sharded axis from sequence
  to heads, runs full attention locally on H/n heads, swaps back. Cheaper at
  moderate T when heads divide the axis. (DeepSpeed-Ulysses, 2023.)

Both are exact (not approximations): tests compare against single-device
attention on the virtual CPU mesh."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

# feature-detects the check_vma/check_rep kwarg rename across jax versions
from jax import shard_map

Array = jax.Array
NEG_INF = -1e30


def _mask_scores(
    scores: Array,  # [B, H, Tq, Tk]
    q_pos: Array,  # [Tq] global positions
    k_pos: Array,  # [Tk] global positions
    lengths: Optional[Array],  # [B]
    causal: bool,
) -> Array:
    if causal:
        scores = jnp.where(
            k_pos[None, None, None, :] > q_pos[None, None, :, None],
            NEG_INF,
            scores,
        )
    if lengths is not None:
        valid = k_pos[None, :] < lengths[:, None]  # [B, Tk]
        scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    return scores


def ring_attention(
    q: Array,  # [B, T, H, D] (T sharded over `axis`)
    k: Array,
    v: Array,
    mesh: Mesh,
    axis: str = "seq",
    lengths: Optional[Array] = None,  # [B] valid key lengths (replicated)
    causal: bool = False,
    scale: Optional[float] = None,
) -> Array:
    """Exact blockwise attention with K/V rotating over the ring."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d**0.5)
    qkv_spec = P(None, axis, None, None)
    len_spec = P(None)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec)
        + ((len_spec,) if lengths is not None else ()),
        out_specs=qkv_spec,
        check_vma=False,
    )
    def ring(qb, kb, vb, *rest):
        lens = rest[0] if rest else None
        n = lax.psum(1, axis)
        my = lax.axis_index(axis)
        b, tq, h, _ = qb.shape
        tk = kb.shape[1]
        q_pos = my * tq + jnp.arange(tq)
        # [B, H, Tq, D] layout for the matmuls
        qh = jnp.swapaxes(qb, 1, 2).astype(jnp.float32) * scale

        perm = [(j, (j - 1) % n) for j in range(n)]  # block i+1 arrives next

        def step(carry, i):
            kc, vc, m, num, den = carry
            src = (my + i) % n  # which global block kc/vc hold now
            k_pos = src * tk + jnp.arange(tk)
            kh = jnp.swapaxes(kc, 1, 2).astype(jnp.float32)
            vh = jnp.swapaxes(vc, 1, 2).astype(jnp.float32)
            s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh)
            s = _mask_scores(s, q_pos, k_pos, lens, causal)
            m_new = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            num = num * alpha[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, vh)
            den = den * alpha + p.sum(axis=-1)
            kc = lax.ppermute(kc, axis, perm)
            vc = lax.ppermute(vc, axis, perm)
            return (kc, vc, m_new, num, den), None

        m0 = jnp.full((b, h, tq), NEG_INF, jnp.float32)
        num0 = jnp.zeros((b, h, tq, d), jnp.float32)
        den0 = jnp.zeros((b, h, tq), jnp.float32)
        (_, _, _, num, den), _ = lax.scan(
            step, (kb, vb, m0, num0, den0), jnp.arange(n)
        )
        out = num / jnp.maximum(den, 1e-20)[..., None]
        return jnp.swapaxes(out, 1, 2).astype(qb.dtype)

    args = (q, k, v) + ((lengths,) if lengths is not None else ())
    return ring(*args)


def ulysses_attention(
    q: Array,  # [B, T, H, D] (T sharded over `axis`; H divisible by axis size)
    k: Array,
    v: Array,
    mesh: Mesh,
    axis: str = "seq",
    lengths: Optional[Array] = None,
    causal: bool = False,
    scale: Optional[float] = None,
) -> Array:
    """All-to-all head/sequence swap: full-T attention on H/n local heads."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d**0.5)
    qkv_spec = P(None, axis, None, None)
    len_spec = P(None)
    n_seq = mesh.shape[axis]
    if q.shape[2] % n_seq != 0:
        raise ValueError(
            f"ulysses needs heads ({q.shape[2]}) divisible by mesh axis "
            f"{axis!r} ({n_seq}); use ring_attention otherwise"
        )

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec)
        + ((len_spec,) if lengths is not None else ()),
        out_specs=qkv_spec,
        check_vma=False,
    )
    def ulysses(qb, kb, vb, *rest):
        lens = rest[0] if rest else None
        # [B, T_loc, H, D] → all-to-all → [B, T_glob, H_loc, D]
        swap = lambda x: lax.all_to_all(x, axis, split_axis=2, concat_axis=1, tiled=True)
        qg, kg, vg = swap(qb), swap(kb), swap(vb)
        t = qg.shape[1]
        pos = jnp.arange(t)
        qh = jnp.swapaxes(qg, 1, 2).astype(jnp.float32) * scale
        kh = jnp.swapaxes(kg, 1, 2).astype(jnp.float32)
        vh = jnp.swapaxes(vg, 1, 2).astype(jnp.float32)
        s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh)
        s = _mask_scores(s, pos, pos, lens, causal)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhqk,bhkd->bhqd", p, vh)
        out = jnp.swapaxes(out, 1, 2).astype(qb.dtype)  # [B, T_glob, H_loc, D]
        # reverse swap: sequence back to local, heads back to full
        return lax.all_to_all(out, axis, split_axis=1, concat_axis=2, tiled=True)

    args = (q, k, v) + ((lengths,) if lengths is not None else ())
    return ulysses(*args)


def reference_attention(
    q: Array, k: Array, v: Array,
    lengths: Optional[Array] = None,
    causal: bool = False,
    scale: Optional[float] = None,
) -> Array:
    """Single-device oracle (same math, no sharding)."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d**0.5)
    t = q.shape[1]
    pos = jnp.arange(t)
    qh = jnp.swapaxes(q, 1, 2).astype(jnp.float32) * scale
    kh = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vh = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh)
    s = _mask_scores(s, pos, pos, lengths, causal)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vh)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)
