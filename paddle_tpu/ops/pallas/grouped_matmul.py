"""The routed expert block's grouped products (serving/moe.py).

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

Where the rows do not fit VMEM whole (Trinity's 2048-token chunk: 16,384
rows of 4 KB, 128 an expert), `grouped_matmul_streamed` keeps the weights'
stream and streams the rows and the output too:

  * the same grid and the same weights' blocks, so each held expert's
    `[K, tn]` block is still fetched once a block of columns, while the
    expert before it computes, straight from the whole stack;
  * the rows `[M, K]` and the output `[M, N]` stay in HBM, and the kernel
    walks the tm-row tiles that hold a landed row in order, each fetched
    once and written once a block of columns: tile t + 1 is on its way
    while tile t computes, and tile t - 1 leaves by DMA while it does. A
    tile that holds the boundary of two groups stays in VMEM from the one
    expert's step to the next's, each writing its own rows; tiles past the
    groups are written zero and never multiplied, so a short prompt padded
    to its bucket does not pay for its empty rows.

tn and tm come from the shapes (`_tiling`, `_stream_tiling`), never from an
argument or the environment, and the choice is logged once a geometry. Which
form runs is serving/moe.py's rule on the rows a held expert gets and what
fits; on CPU `jax.lax.ragged_dot` is the oracle, and
PADDLE_TPU_PALLAS=interpret runs both forms through the Pallas interpreter
(tests/test_grouped_matmul_kernel.py)."""

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
# the rows the MXU streams through a block of weights for about the cost of
# fewer: a row tile under it costs the MXU what one of it does
MXU_ROWS = 128


@functools.lru_cache(maxsize=None)
def _tiling(m: int, k: int, n: int, row_bytes: int, w_bytes: int) -> Optional[Tuple[int, int]]:
    """(tm, tn), or None where even one column block of 128 does not fit.
    tm: the rows a product takes at once, one packed sublane tile of the
    rows' type (8 rows of 32 bits, 16 of 16); tn: the widest block of
    columns dividing N (N itself, or a multiple of 128) whose buffers fit
    `BLOCK_BUDGET` with the rows."""
    tm = 8 * max(1, 4 // row_bytes)
    mp = -(-m // tm) * tm
    for tn in _widths(n):
        used = mp * k * row_bytes + 2 * k * tn * w_bytes + 2 * mp * tn * 4
        if used <= BLOCK_BUDGET:
            log.info(
                "grouped_matmul: rows [%d, %d] in tiles of %d, weights in blocks of "
                "[%d, %d] (%d KiB of VMEM buffers)", m, k, tm, k, tn, used >> 10,
            )
            return tm, tn
    return None


def _widths(n: int):
    """The blocks of columns that divide N, widest first: N itself, then
    each multiple of 128."""
    return [n] + [d for d in range(n - n % 128, 127, -128) if d != n and n % d == 0]


def fits(m: int, k: int, n: int, row_dtype, w_dtype) -> bool:
    """Whether the kernel's blocks fit VMEM at these shapes."""
    return _tiling(m, k, n, jnp.dtype(row_dtype).itemsize, jnp.dtype(w_dtype).itemsize) is not None


def streams(m: int, k: int, n: int, e: int, row_dtype, w_dtype) -> bool:
    """Whether the row-streaming form's buffers fit VMEM at these shapes."""
    return _stream_tiling(m, k, n, e, jnp.dtype(row_dtype).itemsize,
                          jnp.dtype(w_dtype).itemsize) is not None


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


@functools.lru_cache(maxsize=None)
def _stream_tiling(m: int, k: int, n: int, e: int, row_bytes: int,
                   w_bytes: int) -> Optional[Tuple[int, int]]:
    """(tm, tn) of the row-streaming form, or None where even a tile of one
    packed sublane tile and a column block of 128 overflow `BLOCK_BUDGET`.
    tm: the row tile that costs the MXU least, counting the tiles the groups
    touch (m / tm, and one more at each of the e - 1 group boundaries inside
    a tile) at `MXU_ROWS` rows a tile at least; tn: the widest block of
    columns dividing N whose buffers then fit."""
    sub = 8 * max(1, 4 // row_bytes)
    best = None
    for tm in (t for t in (8, 16, 32, 64, 128, 256, 512) if t >= sub):
        cost = (-(-m // tm) + e - 1) * max(tm, MXU_ROWS)
        tn = _stream_width(tm, k, n, row_bytes, w_bytes)
        if tn is not None and (best is None or cost < best[0]):
            best = (cost, tm, tn)
    if best is None:
        return None
    _, tm, tn = best
    log.info(
        "grouped_matmul: rows [%d, %d] streamed in tiles of %d over %d groups, weights in "
        "blocks of [%d, %d] (%d KiB of VMEM buffers)", m, k, tm, e, k, tn,
        _stream_bytes(tm, k, tn, row_bytes, w_bytes) >> 10,
    )
    return tm, tn


def _stream_width(tm: int, k: int, n: int, row_bytes: int, w_bytes: int) -> Optional[int]:
    """The widest block of columns dividing N (N itself, or a multiple of
    128) whose streaming buffers fit `BLOCK_BUDGET` with row tiles of tm."""
    return next((d for d in _widths(n) if _stream_bytes(tm, k, d, row_bytes, w_bytes)
                 <= BLOCK_BUDGET), None)


def _stream_bytes(tm: int, k: int, tn: int, row_bytes: int, w_bytes: int) -> int:
    """VMEM the streaming form's buffers take: two row tiles, two weight
    blocks, two float32 output tiles."""
    return 2 * tm * k * row_bytes + 2 * k * tn * w_bytes + 2 * tm * tn * 4


def _streamed_kernel(
    layer_ref,   # scalar prefetch: [1] the layer of the stack (SMEM)
    sizes_ref,   # scalar prefetch: [E] rows of each held expert
    starts_ref,  # scalar prefetch: [E] each expert's first row
    fetch_ref,   # scalar prefetch: [E] the expert whose block step e holds
    rows_hbm,    # [Mp, K] every row, sorted by expert, left in HBM
    w_ref,       # [K, tn] expert fetch[e]'s block of columns
    out_hbm,     # [Mp, N] float32, left in HBM, written a tile at a time
    rows_buf,    # [2, tm, K] the row tile in use and the next
    acc,         # [2, tm, tn] float32: the output tile in use and the last
    row_sems,    # DMA [2]
    out_sems,    # DMA [2]
    *,
    tm: int,
    tn: int,
):
    j, e = pl.program_id(0), pl.program_id(1)
    n_experts = pl.num_programs(1)
    n_tiles = rows_hbm.shape[0] // tm
    landed = starts_ref[n_experts - 1] + sizes_ref[n_experts - 1]
    filled = (landed + tm - 1) // tm   # the tiles that hold a landed row

    def rows_in(t):
        return pltpu.make_async_copy(
            rows_hbm.at[pl.ds(pl.multiple_of(t * tm, tm), tm)],
            rows_buf.at[t % 2], row_sems.at[t % 2])

    def out_of(t, slot):
        return pltpu.make_async_copy(
            acc.at[slot],
            out_hbm.at[pl.ds(pl.multiple_of(t * tm, tm), tm),
                       pl.ds(pl.multiple_of(j * tn, tn), tn)],
            out_sems.at[slot])

    @pl.when((e == 0) & (filled > 0))
    def _():
        rows_in(0).start()

    start = starts_ref[e]
    end = start + sizes_ref[e]
    first = start // tm
    tiles = jnp.where(end > start, (end - 1) // tm - first + 1, 0)

    def tile(i, carry):
        t = first + i
        slot = t % 2
        # no expert before this one has a row in tile t: the tile is new,
        # the last one is complete and leaves, the next one's rows come
        fresh = t * tm >= start

        @pl.when(fresh)
        def _():
            @pl.when(t > 0)
            def _():
                out_of(t - 1, 1 - slot).start()

            rows_in(t).wait()

            @pl.when(t + 1 < filled)
            def _():
                rows_in(t + 1).start()

            @pl.when(t > 1)
            def _():
                out_of(t - 2, slot).wait()

        y = jnp.dot(rows_buf[slot], w_ref[...], preferred_element_type=F32)
        r = t * tm + jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
        mine = (r >= start) & (r < end)
        acc[slot] = jnp.where(mine, y, jnp.where(fresh, 0.0, acc[slot]))
        return carry

    jax.lax.fori_loop(0, tiles, tile, 0)

    @pl.when(e == n_experts - 1)
    def _():
        # the last tile leaves; the tiles past the groups are written zero
        @pl.when(filled > 0)
        def _():
            out_of(filled - 1, (filled - 1) % 2).start()

            @pl.when(filled > 1)
            def _():
                out_of(filled - 2, filled % 2).wait()

            out_of(filled - 1, (filled - 1) % 2).wait()

        @pl.when(filled < n_tiles)
        def _():
            acc[0] = jnp.zeros((tm, tn), F32)

            def zero(t, carry):
                out_of(t, 0).start()
                return carry

            def wait(t, carry):
                out_of(t, 0).wait()
                return carry

            jax.lax.fori_loop(filled, n_tiles, zero, 0)
            jax.lax.fori_loop(filled, n_tiles, wait, 0)


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
    rows, prefetch = _prepare(rows, sizes, layer, tm)
    mp = rows.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n // tn, e),
        in_specs=[
            pl.BlockSpec((mp, k), lambda j, i, *_: (0, 0), pipeline_mode=pl.Buffered(1)),
            pl.BlockSpec((pl.Squeezed(), pl.Squeezed(), k, tn), _w_map),
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
    )(*prefetch, rows, w)
    return out[:m] if mp != m else out


def _prepare(rows: Array, sizes: Array, layer, tm: int):
    """(rows padded to a whole number of tm-row tiles, the four scalar
    prefetch operands: the layer, the groups' sizes and starts, and the
    expert whose weights each expert's grid step holds)."""
    m, e = rows.shape[0], sizes.shape[0]
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
    return rows, (jnp.asarray(layer, jnp.int32).reshape(1), sizes, starts, fetch)


def _w_map(j, i, layer_ref, sizes_ref, starts_ref, fetch_ref):
    return (layer_ref[0], fetch_ref[i], 0, j)


def grouped_matmul_streamed(rows: Array, w: Array, sizes: Array, layer) -> Array:
    """`grouped_matmul` where the rows do not fit VMEM whole: they come
    in tiles of tm and the output leaves in tiles of [tm, tn], each held
    expert's block of weights fetched once a block of columns."""
    m, k = rows.shape
    _, e, _, n = w.shape
    tm, tn = _stream_tiling(m, k, n, e, rows.dtype.itemsize, w.dtype.itemsize)
    return _grouped_matmul_streamed(rows, w, sizes, layer, tm, tn)


def _grouped_matmul_streamed(rows: Array, w: Array, sizes: Array, layer, tm: int,
                             tn: int) -> Array:
    """`grouped_matmul_streamed` in tiles of tm rows and blocks of tn
    columns (benchmarks/grouped_matmul_bench.py times other tilings)."""
    m, k = rows.shape
    _, e, _, n = w.shape
    rows, prefetch = _prepare(rows, sizes, layer, tm)
    mp = rows.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n // tn, e),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((pl.Squeezed(), pl.Squeezed(), k, tn), _w_map),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((2, tm, k), rows.dtype),
            pltpu.VMEM((2, tm, tn), F32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_streamed_kernel, tm=tm, tn=tn),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mp, n), F32),
        compiler_params=pltpu.CompilerParams(
            # a block of columns is one chain of row tiles from its first
            # expert to its last
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_stream_bytes(tm, k, tn, rows.dtype.itemsize, w.dtype.itemsize)
            + MARGIN,
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=e * k * n * w.dtype.itemsize
            + mp * (k * rows.dtype.itemsize * (n // tn) + 4 * n),
        ),
        interpret=interpret_mode(),
        name="grouped_matmul",
    )(*prefetch, rows, w)
    return out[:m] if mp != m else out
