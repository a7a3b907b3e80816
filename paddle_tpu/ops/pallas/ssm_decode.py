"""The Mamba-2 decode step's recurrence in ONE pass over the state
("Transformers are SSMs", arXiv:2405.21060; ISSUE 38).

A decode step advances every slot's state S [H, P, N] of one Mamba layer by
one token and reads it out (`ops/mamba2.ssm_step`, the oracle):

    S <- exp(dt A) S + (dt x) (outer) B;    y = S C

XLA:TPU compiles the oracle and the stack's `dynamic_update_index_in_dim`
into two fusions: the in-place update reads and writes the layer's state,
and the readout reads the OLD state a second time to recompute the new one
and reduce it (granite-4.0-h-small: 268 MB a layer, 6.6 + 2.9 ms of a 29 ms
step; PERF.md, PR 35). This kernel reads each block once, updates it,
writes it back in place and reduces it against C while it is in VMEM:

  * the WHOLE stack `[S, M, H, P, N]` is the operand and the output
    (`input_output_aliases`), and the Mamba layer m, a scanned run's traced
    index, is a scalar-prefetch operand read by the index maps, as
    `paged_attention_decode` takes its layer: no slice of the stack is cut
    for the call, and no block of another layer is touched: it keeps its
    bits because the output IS its buffer;
  * grid = (slots, blocks of hb heads); Pallas pipelines each
    `[1, 1, hb, P, N]` block in and out, double-buffered both ways;
  * inside a block the work goes a TILE of g heads at a time, g P rows of N
    lanes (g P at most 128: two heads at P = 64), TRANSPOSED on the XLU so that
    the (head, p) rows lie on the lanes: the input dt x and the decay are
    then rows that broadcast down the sublanes for free, B and C columns
    built once a block, and y = sum over N a sum down the sublanes that
    lands lane-dense, y's own [S, H, P] layout. Kept in the layout the state
    has, each head needs its dt x broadcast across the lanes and its y
    reduced across them, one cross-lane op a vector each way: that form ran
    at 550 GB/s where this one runs at the speed of a plain copy of the
    blocks (606 GB/s in a scan of 45 calls; my chip runs, PR 38);
  * the decay exp(dt A) and the input dt x are computed outside by the
    oracle's own expressions (a few MB), so the tile sees the oracle's two
    products and one sum in float32 on the VPU: the new state is the
    oracle's bit for bit; y is summed in another order than XLA's reduce,
    equal to float32 rounding;
  * a lane whose `active` is false keeps its block bit for bit (the
    oracle's select); its y, which nothing reads, is the oracle's: the state
    advanced by dt = 0, which leaves it as it was.

hb comes from the shapes (`_tiling`: the largest multiple of g
dividing H whose four state buffers fit `BLOCK_BUDGET`), never from an
argument or the environment; the choice is logged once a geometry. Blocks
of 16, 32 and 64 heads ran within 1.5% of each other at granite's shapes
(my chip runs, PR 38). Dispatch is `HybridMoELM._ssm_decode`'s, by
`ops.pallas.enabled()`: the kernel on TPU, the oracle on CPU, the kernel
through the Pallas interpreter under PADDLE_TPU_PALLAS=interpret
(tests/test_ssm_decode_kernel.py)."""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import interpret_mode

Array = jax.Array
F32 = jnp.float32

log = logging.getLogger("paddle_tpu")

# VMEM the four state buffers (in and out, two each) may take together, of
# the 16 MiB Mosaic scopes a kernel by default on a v5e
BLOCK_BUDGET = 4 << 20


@functools.lru_cache(maxsize=None)
def _tiling(heads: int, p: int, n: int):
    """(g, hb): the heads a tile transposes at once, the largest divisor of
    H whose rows fill at most one 128-lane width, and the heads a block
    holds, the largest multiple of g dividing H whose four float32 buffers
    of [hb, P, N] fit the budget (one tile at least)."""
    g = max(d for d in range(1, heads + 1) if heads % d == 0 and d * p <= max(p, 128))
    per_head = 4 * p * n * 4
    hb = max([d for d in range(g, heads + 1, g)
              if heads % d == 0 and d * per_head <= BLOCK_BUDGET] or [g])
    log.info(
        "ssm_decode: %d of %d heads a block in tiles of %d (%d KiB of VMEM "
        "state buffers)", hb, heads, g, (hb * per_head) >> 10,
    )
    return g, hb


def _ssm_decode_kernel(
    layer_ref,   # scalar prefetch: [1] the Mamba layer (SMEM)
    active_ref,  # scalar prefetch: [S] int32, 0 where the lane holds no token
    state_ref,   # [1, 1, hb, P, N] this slot's block of the layer's state
    xdt_ref,     # [1, 1, 1, hb*P] dt x, (head, p) on the lanes
    decay_ref,   # [1, 1, 1, hb*P] exp(dt A), each head's P times
    b_ref,       # [1, 1, N]
    c_ref,       # [1, 1, N]
    y_ref,       # [1, 1, 1, hb*P] y, (head, p) on the lanes
    out_ref,     # [1, 1, hb, P, N] the block written back (aliased)
    *,
    g: int,
):
    keep = active_ref[pl.program_id(0)] == 0
    hb, p, n = state_ref.shape[2:]
    rows = g * p
    # B and C as columns: row n holds b[n] on every lane
    b_col = jnp.broadcast_to(b_ref[0], (rows, n)).T              # [N, gP]
    c_col = jnp.broadcast_to(c_ref[0], (rows, n)).T
    for k in range(hb // g):
        heads, lanes = slice(k * g, (k + 1) * g), slice(k * rows, (k + 1) * rows)
        old = state_ref[0, 0, heads].reshape(rows, n)            # [gP, N]
        new = (old.T * decay_ref[0, 0, :, lanes]
               + xdt_ref[0, 0, :, lanes] * b_col)                 # [N, gP]
        y_ref[0, 0, :, lanes] = jnp.sum(new * c_col, axis=0, keepdims=True)
        out_ref[0, 0, heads] = jnp.where(keep, old, new.T).reshape(g, p, n)


def ssm_decode(
    ssm: Array,     # [S, M, H, P, N] float32: the WHOLE stacked state
    layer,          # int, or a traced int32 scalar (a scanned run's)
    x: Array,       # [S, H, P]
    dt: Array,      # [S, H] float32, 0 where the lane holds no token
    a_neg: Array,   # [H] float32, A = -exp(A_log)
    b: Array,       # [S, N]
    c: Array,       # [S, N]
    active: Array,  # [S] bool: lanes whose state advances
) -> tuple:
    """One token a slot through Mamba layer `layer` of the stack: (y
    [S, H, P] float32, the stack with that layer advanced where `active`).
    Under the decode step's trace the stack is the scan's carry, donated by
    the step, and the alias writes it in place; an eager caller keeps its
    array (XLA copies it for the alias)."""
    s, _, heads, p, n = ssm.shape
    g, hb = _tiling(heads, p, n)
    # the oracle's own expressions, outside: the kernel multiplies and adds
    decay = jnp.exp(dt * a_neg)                                   # [S, H]
    xdt = x.astype(F32) * dt[..., None]                           # [S, H, P]
    row = (s, heads // hb, 1, hb * p)

    def state_map(i, j, layer_ref, active_ref):
        return (i, layer_ref[0], j, 0, 0)

    state_spec = pl.BlockSpec((1, 1, hb, p, n), state_map)
    row_spec = pl.BlockSpec((1, 1, 1, hb * p), lambda i, j, *_: (i, j, 0, 0))
    slot_spec = pl.BlockSpec((1, 1, n), lambda i, j, *_: (i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s, heads // hb),
        in_specs=[state_spec, row_spec, row_spec, slot_spec, slot_spec],
        out_specs=[row_spec, state_spec],
    )
    y, ssm = pl.pallas_call(
        functools.partial(_ssm_decode_kernel, g=g),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct(row, F32), jax.ShapeDtypeStruct(ssm.shape, F32)),
        # operand 2 (after the two prefetched scalars) is the stack
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret_mode(),
        name="ssm_decode",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), active.astype(jnp.int32), ssm,
        xdt.reshape(row), jnp.repeat(decay, p, axis=1).reshape(row),
        b.astype(F32)[:, None], c.astype(F32)[:, None],
    )
    return y.reshape(s, heads, p), ssm
