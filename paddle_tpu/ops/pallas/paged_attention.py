"""Ragged paged-attention decode kernel ("Ragged Paged Attention", PAPERS.md).

The serving decode step's inner loop is attention over a paged KV cache:
every slot owns a row of the block table mapping logical page j -> physical
page id, and attends over its own committed tokens only (per-slot length
masking — sequence length is *data*, never *shape*). The jnp path in
`serving/model.ServableLM.decode_step` materializes that as a dense gather
`k_pages[block_table]` — [S, P, PS, KD] per layer per step round-tripping
HBM — before a masked softmax. This kernel is the TPU shape of the same
computation:

  * grid = (slots, pages_per_seq); the PAGE loop is the inner grid dim;
  * the block table rides in as a SCALAR-PREFETCH operand, so each grid
    step's k/v BlockSpec index map picks the slot's PHYSICAL page straight
    out of it — the gather happens in the DMA engine, one [PS, KD] page at
    a time, and the dense [S, P, PS, KD] intermediate never exists;
  * per-slot length masking against the slot's own position (logical token
    index <= position), so ragged mixed-age batches share the executable;
  * numerically-stable ONLINE softmax in f32: running max / denominator /
    weighted-value accumulator live in VMEM scratch across the page loop
    (the flash-attention recurrence), flushed to the output on the last
    page.

Unused block-table entries point at dump page 0 and their logical indices
exceed the slot's position, so they contribute exp(-1e9 - m) == 0 exactly —
bitwise the same masking contract as the oracle.

The jnp gather path remains the CPU oracle: `paged_attention_decode` must
match it to float tolerance (argmax-equal under greedy decode) for every
mixed length / block-table layout — asserted in interpret mode on CPU by
tests/test_decode_fastpath.py, the same discipline as PR 9's fused
attention kernel. Dispatch policy lives in `ops.pallas.enabled()`:
TPU on by default, CPU oracle otherwise, PADDLE_TPU_PALLAS=interpret forces
the kernel through the Pallas interpreter for the equality tests."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import interpret_mode

Array = jax.Array

# must equal serving/model.NEG_INF: fully-masked pages then degrade to a
# zero contribution exactly as the oracle's softmax does
NEG_INF = -1e9

_LANES = 128
# f32 operands through the MXU at full f32 accuracy: the kernel must track
# the f32 oracle to float tolerance, not to one bf16 pass
_PRECISION = jax.lax.Precision.HIGHEST


def _paged_decode_kernel(
    bt_ref,    # scalar prefetch: [S * P] flattened block table (SMEM)
    pos_ref,   # scalar prefetch: [S] positions (SMEM)
    q_ref,     # [1, H, KD] — this slot's query, pre-scaled, block-diagonal:
               # row h holds head h's hd values in its own lane segment
    k_ref,     # [PS, KD] — this grid step's physical page (layer squeezed)
    v_ref,     # [PS, KD]
    out_ref,   # [1, 1, KD]
    m_scr,     # VMEM [H, LANES] running max (lane-replicated)
    l_scr,     # VMEM [H, LANES] running denominator (lane-replicated)
    acc_scr,   # VMEM [H, KD] running probs @ v, every head against ALL lanes
    *,
    page_size: int,
    head_dim: int,
):
    s = pl.program_id(0)
    p = pl.program_id(1)
    pos = pos_ref[s]

    @pl.when(p == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # pages wholly past the slot's position are fully masked: their
    # contribution is exp(NEG_INF - m) == 0 exactly, so skipping them is
    # bitwise the masked computation (page 0 always runs: index 0 <= pos)
    @pl.when(p * page_size <= pos)
    def _page():
        # all heads in one MXU call: q is block-diagonal over the lane
        # segments, so row h of q @ k^T contracts head h's lanes only
        sc = jax.lax.dot_general(
            q_ref[0], k_ref[:].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_PRECISION,
        )  # [H, PS]
        # ragged masking: logical token index within THIS slot's sequence
        idx = p * page_size + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        sc = jnp.where(idx <= pos, sc, NEG_INF)
        # online-softmax recurrence (f32 throughout)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.exp(sc - m_new)  # [H, PS]
        l_new = l_scr[:, :1] * alpha + jnp.sum(probs, axis=-1, keepdims=True)
        # row h against every lane; only head h's own segment is kept at
        # the flush (H-fold redundant MXU work on a DMA-bound kernel, in
        # exchange for no in-kernel reshape/transpose of the [PS, KD] tile)
        pv = jax.lax.dot_general(
            probs, v_ref[:].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_PRECISION,
        )  # [H, KD]
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(p == pl.num_programs(1) - 1)
    def _flush():
        # l >= exp(0 - m) > 0 always: logical index 0 is <= every position
        ctx = acc_scr[:] / l_scr[:, :1]  # [H, KD]
        head = jax.lax.broadcasted_iota(jnp.int32, ctx.shape, 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, ctx.shape, 1)
        own = (lane >= head * head_dim) & (lane < (head + 1) * head_dim)
        out_ref[0] = jnp.sum(jnp.where(own, ctx, 0.0), axis=0, keepdims=True)


def paged_attention_decode(
    q: Array,            # [S, KD] — one query token per slot
    k_pages: Array,      # [L, NP, PS, KD] — the WHOLE physical page pool
    v_pages: Array,      # [L, NP, PS, KD]
    block_table: Array,  # [S, P] int32 logical->physical page map
    positions: Array,    # [S] int32 — each slot's current token position
    *,
    layer: int,
    scale: float,
    n_heads: int,
) -> Array:
    """One decode step of ragged paged attention for all slots over layer
    `layer` of the pool: [S, KD] f32 context, numerically equivalent to the
    jnp gather oracle in `ServableLM.decode_step` (same masking, f32
    softmax; the online recurrence reassociates the sum so equality is to
    float tolerance, argmax/token-exact under greedy decode).

    The pool rides in whole and `layer` picks the page inside the DMA's
    index map: slicing `k_pages[layer]` outside would make XLA copy one
    layer of the pool per layer per step to feed the custom call."""
    s, kd = q.shape
    ps = k_pages.shape[2]
    pmax = block_table.shape[1]
    hd = kd // n_heads

    def page_map(i, j, bt, pos):
        return (layer, bt[i * pmax + j], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s, pmax),
        in_specs=[
            pl.BlockSpec((1, n_heads, kd), lambda i, j, bt, pos: (i, 0, 0)),
            # the ragged gather: the block table (prefetched to SMEM before
            # the body runs) drives which physical page the DMA fetches
            pl.BlockSpec((None, None, ps, kd), page_map),
            pl.BlockSpec((None, None, ps, kd), page_map),
        ],
        out_specs=pl.BlockSpec((1, 1, kd), lambda i, j, bt, pos: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((n_heads, _LANES), jnp.float32),
            pltpu.VMEM((n_heads, _LANES), jnp.float32),
            pltpu.VMEM((n_heads, kd), jnp.float32),
        ],
    )
    # block-diagonal queries [S, H, KD]: row h = head h's values in lanes
    # [h*hd, (h+1)*hd), zeros elsewhere
    own = (jnp.arange(kd)[None, :] // hd) == jnp.arange(n_heads)[:, None]
    qs = q.astype(jnp.float32) * scale
    q_bd = jnp.where(own[None], qs[:, None, :], 0.0)
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, page_size=ps, head_dim=hd),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, 1, kd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret_mode(),
        name="paged_attention_decode",
    )(
        block_table.astype(jnp.int32).reshape(-1), positions.astype(jnp.int32),
        q_bd, k_pages, v_pages,
    )
    return out[:, 0]
