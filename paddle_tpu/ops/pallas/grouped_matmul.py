"""The routed expert block's grouped products where each held expert gets a
handful of rows (serving/moe.py).

    out[r] = rows[r] @ w[layer, e]   for each row r of group e
    out[r] = 0                       for a row past the groups

A decode step of 64 slots routed top-8 over 128 experts gives each expert
about 4 rows and 12 MB of weights: the product is a stream of weights, and
its time is the time the weights take to arrive. XLA's ragged-dot reads
them at about a third of the HBM bandwidth there, this kernel at 80-86%
(benchmarks/grouped_matmul_bench.py on a v5e; PERF.md). It keeps
the stream going:

  * the WHOLE stack `w [L, E, K, N]` is the operand, left in HBM; the layer
    (a scanned run's traced index, or a constant), the groups' sizes and
    starts, and the expert whose weights each grid step holds are
    scalar-prefetch operands read by the weights' index map, as
    `ssm_decode` takes its layer: no slice of the stack is cut for the call;
  * grid = (blocks of tn columns, held experts), experts inner: Pallas
    fetches expert e+1's `[K, tn]` block while expert e computes; an expert
    with no rows names the block of the expert before it, so its weights
    are not fetched;
  * the rows `[M, K]` are one block, fetched once; the output block
    `[M, tn]` float32 stays in VMEM while the experts go by, zeroed at the
    first, so rows past the groups come back 0;
  * an expert's rows are taken `tm` at a time from the tm-aligned tile that
    holds its first row: each tile is one product on the MXU in float32
    accumulation, written where its row belongs to the expert and left as
    it was elsewhere (the rows of the expert before or after share the
    tile, and write their own).

tn and tm come from the shapes (`_tiling`), never from an argument or the
environment, and the choice is logged once a geometry. Which path runs is
serving/moe.py's rule on the rows a held expert gets; on CPU
`jax.lax.ragged_dot` is the oracle, and PADDLE_TPU_PALLAS=interpret runs
this kernel through the Pallas interpreter (tests/test_grouped_matmul_kernel.py)."""

from __future__ import annotations

import functools
import logging
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import interpret_mode

Array = jax.Array
F32 = jnp.float32

log = logging.getLogger("paddle_tpu")

# VMEM the rows, two weight buffers and two output buffers may take together;
# the kernel asks Mosaic for this much and a margin (a v5e core has 128 MiB)
BLOCK_BUDGET = 48 << 20
MARGIN = 16 << 20


@functools.lru_cache(maxsize=None)
def _tiling(m: int, k: int, n: int, row_bytes: int, w_bytes: int) -> Optional[Tuple[int, int]]:
    """(tm, tn), or None where even one column block of 128 does not fit.
    tm: the rows a product takes at once, one packed sublane tile of the
    rows' type (8 rows of 32 bits, 16 of 16); tn: the widest block of
    columns dividing N (N itself, or a multiple of 128) whose buffers fit
    `BLOCK_BUDGET` with the rows."""
    tm = 8 * max(1, 4 // row_bytes)
    mp = -(-m // tm) * tm
    widths = [n] + [d for d in range(n - n % 128, 127, -128) if d != n and n % d == 0]
    for tn in widths:
        used = mp * k * row_bytes + 2 * k * tn * w_bytes + 2 * mp * tn * 4
        if used <= BLOCK_BUDGET:
            log.info(
                "grouped_matmul: rows [%d, %d] in tiles of %d, weights in blocks of "
                "[%d, %d] (%d KiB of VMEM buffers)", m, k, tm, k, tn, used >> 10,
            )
            return tm, tn
    return None


def fits(m: int, k: int, n: int, row_dtype, w_dtype) -> bool:
    """Whether the kernel's blocks fit VMEM at these shapes."""
    return _tiling(m, k, n, jnp.dtype(row_dtype).itemsize, jnp.dtype(w_dtype).itemsize) is not None


def _grouped_matmul_kernel(
    layer_ref,   # scalar prefetch: [1] the layer of the stack (SMEM)
    sizes_ref,   # scalar prefetch: [E] rows of each held expert
    starts_ref,  # scalar prefetch: [E] each expert's first row
    fetch_ref,   # scalar prefetch: [E] the expert whose block step e holds
    rows_ref,    # [Mp, K] every row, sorted by expert
    w_ref,       # [K, tn] expert fetch[e]'s block of columns
    out_ref,     # [Mp, tn] float32, resident while the experts go by
    *,
    tm: int,
):
    e = pl.program_id(1)

    @pl.when(e == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    start = starts_ref[e]
    end = start + sizes_ref[e]
    first = start // tm
    tiles = jnp.where(end > start, (end + tm - 1) // tm - first, 0)

    def tile(t, carry):
        r0 = pl.multiple_of((first + t) * tm, tm)
        y = jnp.dot(rows_ref[pl.ds(r0, tm), :], w_ref[...], preferred_element_type=F32)
        r = r0 + jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
        mine = (r >= start) & (r < end)
        out_ref[pl.ds(r0, tm), :] = jnp.where(mine, y, out_ref[pl.ds(r0, tm), :])
        return carry

    jax.lax.fori_loop(0, tiles, tile, 0)


def grouped_matmul(rows: Array, w: Array, sizes: Array, layer) -> Array:
    """rows [M, K] sorted by group; w [L, E, K, N] the whole stack; sizes
    [E] int32 the rows of each of layer `layer`'s experts (an int, or a
    traced int32 scalar). Returns float32 [M, N]: each group's rows times
    its expert's weights, rows past `sum(sizes)` zero."""
    m, k = rows.shape
    tm, tn = _tiling(m, k, w.shape[3], rows.dtype.itemsize, w.dtype.itemsize)
    return _grouped_matmul(rows, w, sizes, layer, tm, tn)


def _grouped_matmul(rows: Array, w: Array, sizes: Array, layer, tm: int, tn: int) -> Array:
    """`grouped_matmul` in tiles of tm rows and blocks of tn columns
    (benchmarks/grouped_matmul_bench.py times other tilings through here)."""
    m, k = rows.shape
    _, e, _, n = w.shape
    mp = -(-m // tm) * tm
    if mp != m:
        rows = jnp.pad(rows, ((0, mp - m), (0, 0)))
    sizes = sizes.astype(jnp.int32)
    starts = jnp.cumsum(sizes) - sizes
    # an expert with no rows holds the block of the last expert before it
    # that has some (of the first that has some, before it), so the pipeline
    # sees the same block again and fetches nothing
    held = jnp.where(sizes > 0, jnp.arange(e, dtype=jnp.int32), -1)
    last = jax.lax.cummax(held)
    fetch = jnp.where(last >= 0, last, jnp.argmax(sizes > 0).astype(jnp.int32))

    def w_map(j, i, layer_ref, sizes_ref, starts_ref, fetch_ref):
        return (layer_ref[0], fetch_ref[i], 0, j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n // tn, e),
        in_specs=[
            pl.BlockSpec((mp, k), lambda j, i, *_: (0, 0), pipeline_mode=pl.Buffered(1)),
            pl.BlockSpec((pl.Squeezed(), pl.Squeezed(), k, tn), w_map),
        ],
        out_specs=pl.BlockSpec((mp, tn), lambda j, i, *_: (0, j)),
    )
    used = mp * k * rows.dtype.itemsize + 2 * k * tn * w.dtype.itemsize + 2 * mp * tn * 4
    out = pl.pallas_call(
        functools.partial(_grouped_matmul_kernel, tm=tm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mp, n), F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=used + MARGIN,
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=e * k * n * w.dtype.itemsize + mp * (k * rows.dtype.itemsize + 4 * n),
        ),
        interpret=interpret_mode(),
        name="grouped_matmul",
    )(jnp.asarray(layer, jnp.int32).reshape(1), sizes, starts, fetch, rows, w)
    return out[:m] if mp != m else out
