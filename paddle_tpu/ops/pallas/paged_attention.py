"""Ragged paged-attention decode kernel ("Ragged Paged Attention", PAPERS.md).

The serving decode step's inner loop is attention over a paged KV cache:
every slot owns a row of the block table mapping logical page j -> physical
page id, and attends over its own committed tokens only (per-slot length
masking — sequence length is *data*, never *shape*). The jnp path in
`serving/model.ServableLM.decode_step` materializes that as a dense gather
`k_pages[block_table]` — [S, P, PS, KD] per layer per step round-tripping
HBM — before a masked softmax. This kernel is the TPU shape of the same
computation:

  * grid = (slots,): one grid step a slot, and inside it a `fori_loop` over
    the slot's BLOCKS of B pages whose trip count comes from the slot's own
    position, `ceil(pages_held / B)` with `pages_held = pos // PS + 1` — a
    slot does work for the pages it holds and for no other (an empty slot,
    position 0 over an all-dump table, walks one block of one page);
  * the pools stay in HBM (`memory_space=HBM`) and ride in whole; the block
    table and the positions are SCALAR-PREFETCH operands, and the kernel
    itself issues one async copy a held page, `pool[layer, table[slot, p]]`
    -> row p of a [B*PS, KD] VMEM tile, K and V each: the gather happens in
    the DMA engine and the dense [S, P, PS, KD] intermediate never exists.
    Pages of a block past the slot's position are not fetched at all;
  * the tiles are DOUBLE-BUFFERED along one chain that runs through the
    whole call: while block j is computed block j+1 of the slot is in
    flight, and while a slot's last block is computed the NEXT slot's first
    is (the grid is sequential, `arbitrary`, and the buffer's turn is
    carried from slot to slot in SMEM);
  * the two products and the online-softmax update run once a block over
    B*PS tokens: per-slot length masking against the slot's own position
    (logical token index <= position), running max / denominator /
    weighted-value accumulator in VMEM scratch in f32 (the flash-attention
    recurrence), flushed to the output after the slot's last block.

B comes from the shapes (`_pages_per_block`: page size, KD, the table's
width, against the VMEM budget `TILE_BUDGET` and the `BLOCK_TOKENS` cap stated
below), never from an argument or the environment; the table's width need not
be a multiple of it. The choice is logged once a geometry on the `paddle_tpu`
logger.

Masking contract, bitwise the oracle's: a token past the slot's position
scores NEG_INF, so its weight is exp(-1e9 - m) == 0 exactly, whatever the
tile holds there. The rows of a tile that no copy wrote hold what an earlier
block left, or the zeros both V tiles start a call with — finite either way,
so 0 x row is 0 (K's rows need no such care: their scores are replaced, not
multiplied).

A window (`window` W > 0, a layer that attends to its last W positions
alone) changes three things and nothing else: the table is the slot's RING
of R pages (serving/kv_cache.py), logical page p read from entry p % R; a
slot's walk starts at the first page its window touches, (pos - W + 1) //
PS, so it fetches at most R pages whatever its position; and a token before
pos - W + 1 scores NEG_INF as a token past pos does. With no window the
kernel is what it was, op for op.

The jnp gather path remains the CPU oracle: `paged_attention_decode` must
match it to float tolerance (argmax-equal under greedy decode) for every
mixed length / block-table layout — asserted in interpret mode on CPU by
tests/test_decode_fastpath.py, the same discipline as PR 9's fused
attention kernel. Dispatch policy lives in `ops.pallas.enabled()`:
TPU on by default, CPU oracle otherwise, PADDLE_TPU_PALLAS=interpret forces
the kernel through the Pallas interpreter for the equality tests."""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import interpret_mode

Array = jax.Array

log = logging.getLogger("paddle_tpu")

# must equal serving/model.NEG_INF: fully-masked pages then degrade to a
# zero contribution exactly as the oracle's softmax does
NEG_INF = -1e9

_LANES = 128
# f32 operands through the MXU at full f32 accuracy: over a float32 pool the
# kernel must track the f32 oracle to float tolerance, not to one bf16 pass.
# Over a bfloat16 pool the two products take their operands as the pool holds
# them (q and the weights rounded to bfloat16, as the oracle rounds them), in
# ONE pass with float32 accumulation: six passes over upcast pages would leave
# the MXU, not the page copies, bounding the call
_PRECISION = jax.lax.Precision.HIGHEST

# VMEM the four gathered tiles may take together (K and V, two buffers
# each), of the 16 MiB Mosaic scopes a kernel by default on a v5e: the rest
# is the products' operands split into bf16 passes, q and the accumulator
TILE_BUDGET = 4 << 20
# tokens a block at most: past a few MXU tiles of tokens a longer block
# amortises nothing more, and a short slot's one block masks most of it
BLOCK_TOKENS = 512


@functools.lru_cache(maxsize=None)
def _pages_per_block(page_size: int, kd: int, pmax: int, itemsize: int = 4,
                     ring: bool = False) -> int:
    """B, the pages one block gathers: as many as the budget, the token cap
    and the table's width allow; for a ring (a window), the most of the
    budget that divides the ring's R pages, the token cap left out: a ring
    block is ONE copy, and the fewer the copies the less each page costs.
    Logged here, once a geometry."""
    page_bytes = page_size * kd * itemsize
    if ring:
        fit = max(1, TILE_BUDGET // (4 * page_bytes))
        b = max(d for d in range(1, pmax + 1) if pmax % d == 0 and d <= fit)
    else:
        b = min(
            TILE_BUDGET // (4 * page_bytes), BLOCK_TOKENS // page_size, pmax
        )
    b = max(1, b)
    log.info(
        "paged_attention_decode: %d pages a block (%d tokens of %d lanes, "
        "%d KiB of VMEM tiles), at most %d blocks a slot",
        b, b * page_size, kd, (4 * b * page_bytes) >> 10, -(-pmax // b),
    )
    return b


def _paged_decode_kernel(
    layer_ref,  # scalar prefetch: [1] the pool's layer (SMEM)
    bt_ref,    # scalar prefetch: [S * P] flattened block table (SMEM)
    pos_ref,   # scalar prefetch: [S] positions (SMEM)
    q_ref,     # [1, 1, KD] — this slot's query
    k_hbm,     # [L, NP, PS, KD] — the whole pool, in HBM
    v_hbm,     # [L, NP, PS, KD]
    out_ref,   # [1, 1, KD]
    k_buf,     # VMEM [2, B*PS, KD] gathered K tiles, double-buffered
    v_buf,     # VMEM [2, B*PS, KD]
    sems,      # DMA semaphores [2 (K, V), 2 (buffer)]
    turn_ref,  # SMEM [1]: the buffer this slot's first block was sent to
    q_scr,     # VMEM [H, KD] the query, pre-scaled, block-diagonal: row h
               # holds head h's hd values in its own lane segment; in the
               # pool's type, as the products take it
    m_scr,     # VMEM [H, LANES] running max (lane-replicated)
    l_scr,     # VMEM [H, LANES] running denominator (lane-replicated)
    acc_scr,   # VMEM [H, KD] running probs @ v, every head against ALL lanes
    *,
    scale: float,
    page_size: int,
    head_dim: int,
    pages_per_block: int,
    pmax: int,
    group: int,
    window: int = 0,
):
    s = pl.program_id(0)
    n_slots = pl.num_programs(0)
    layer = layer_ref[0]
    ps, b = page_size, pages_per_block
    t = b * ps

    tiles = ((k_hbm, k_buf), (v_hbm, v_buf))

    if window:
        # a ring of pmax = R pages a slot, consecutive in the pool, walked a
        # block of B consecutive entries at a time (B divides R): the blocks
        # that hold a page of the window, from the one holding its first
        # page, each one copy for K and one for V
        n_ring = pmax // b

        def span(slot):
            """(the window's first page, its pages, its first ring block)."""
            pos_s = pos_ref[slot]
            lo = jnp.maximum(pos_s - window + 1, 0) // ps
            return lo, pos_s // ps - lo + 1, jax.lax.rem(lo, pmax) // b

        def ring_blocks(slot):
            lo, n, blk0 = span(slot)
            last = (jax.lax.rem(lo, pmax) + n - 1) // b
            return jnp.minimum(last - blk0 + 1, n_ring)

        def ring_copy(kv, buf, page):
            hbm, buf_ref = tiles[kv]
            return pltpu.make_async_copy(
                hbm.at[layer, pl.ds(page, b)], buf_ref.at[buf], sems.at[kv, buf]
            )

        def start(slot, blk, buf):
            c = jax.lax.rem(span(slot)[2] + blk, n_ring)
            page = bt_ref[slot * pmax + c * b]
            ring_copy(0, buf, page).start()
            ring_copy(1, buf, page).start()

        def wait(kv, blk, buf):
            ring_copy(kv, buf, 0).wait()
    else:
        def pages_held(slot):
            return jnp.minimum(pos_ref[slot] // ps + 1, pmax)

        def each_page(slot, blk, act):
            """`act(i, p)` for each page p = blk*B + i of the block the slot
            holds: the same pages when a block is sent for and when it is
            waited for, since both read the prefetched scalars."""
            held = pages_held(slot)
            for i in range(b):
                pl.when(blk * b + i < held)(
                    functools.partial(act, i, blk * b + i)
                )

        def copy(kv, buf, i, page):
            hbm, buf_ref = tiles[kv]
            return pltpu.make_async_copy(
                hbm.at[layer, page],
                buf_ref.at[buf, pl.ds(i * ps, ps)],
                sems.at[kv, buf],
            )

        def start(slot, blk, buf):
            def act(i, p):
                page = bt_ref[slot * pmax + p]
                copy(0, buf, i, page).start()
                copy(1, buf, i, page).start()

            each_page(slot, blk, act)

        def wait(kv, blk, buf):
            # a wait takes its size from the descriptor, not its source
            each_page(s, blk, lambda i, p: copy(kv, buf, i, 0).wait())

    @pl.when(s == 0)
    def _open_chain():
        v_buf[...] = jnp.zeros_like(v_buf)
        turn_ref[0] = 0
        start(0, 0, 0)

    pos = pos_ref[s]
    if window:
        n_blk = ring_blocks(s)
    else:
        n_blk = (pages_held(s) + b - 1) // b
    first = turn_ref[0]

    kd = q_scr.shape[1]
    head = jax.lax.broadcasted_iota(jnp.int32, (q_scr.shape[0], kd), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (q_scr.shape[0], kd), 1)
    own = (lane >= head * head_dim) & (lane < (head + 1) * head_dim)
    if group == 1:
        q_scr[...] = jnp.where(
            own, q_ref[0].astype(jnp.float32) * scale, 0.0
        ).astype(q_scr.dtype)
    else:
        # grouped heads: the caller laid q out block-diagonal already, a row
        # a QUERY head over the lanes of the K/V head it reads
        q_scr[...] = (q_ref[0].astype(jnp.float32) * scale).astype(q_scr.dtype)
    # the products' operands are of the pool's type (module docstring), and
    # their precision is stated either way: a dot that states none does not
    # lower under a `matmul_precision` of "high" (PERF.md section 4)
    precision = _PRECISION if k_buf.dtype == jnp.float32 else jax.lax.Precision.DEFAULT
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def block(j, carry):
        buf = (first + j) % 2
        # the chain's next link: this slot's next block, or behind its last
        # the next slot's first
        last = j + 1 == n_blk
        nxt_slot = jnp.where(last, s + 1, s)
        nxt_blk = jnp.where(last, 0, j + 1)
        pl.when(nxt_slot < n_slots)(
            lambda: start(nxt_slot, nxt_blk, 1 - buf)
        )

        wait(0, j, buf)
        # all heads in one MXU call: q is block-diagonal over the lane
        # segments, so row h of q @ k^T contracts head h's lanes only (a
        # ring block [B, PS, KD] is the same rows as a gathered [B*PS, KD])
        sc = jax.lax.dot_general(
            q_scr[...], k_buf[buf].reshape(t, -1) if window else k_buf[buf],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        )  # [H, T]
        # ragged masking: logical token index within THIS slot's sequence
        if window:
            # ring entry r holds the latest logical page j <= pos // PS
            # with j % R == r
            at = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
            entry = jax.lax.rem(span(s)[2] + j, n_ring) * b + at // ps
            top = pos // ps
            idx = (top - jax.lax.rem(top - entry + pmax, pmax)) * ps + at % ps
            sc = jnp.where((idx >= 0) & (idx <= pos) & (idx > pos - window), sc, NEG_INF)
        else:
            idx = j * t + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
            sc = jnp.where(idx <= pos, sc, NEG_INF)
        # online-softmax recurrence (f32 throughout)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.exp(sc - m_new)  # [H, T]
        l_new = l_scr[:, :1] * alpha + jnp.sum(probs, axis=-1, keepdims=True)
        wait(1, j, buf)
        # row h against every lane; only head h's own segment is kept at
        # the flush (H-fold redundant MXU work, in exchange for no
        # in-kernel reshape/transpose of the [T, KD] tile)
        pv = jax.lax.dot_general(
            probs.astype(v_buf.dtype),
            v_buf[buf].reshape(t, -1) if window else v_buf[buf],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        )  # [H, KD]
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
        return carry

    jax.lax.fori_loop(0, n_blk, block, 0)
    turn_ref[0] = (first + n_blk) % 2

    # l >= exp(0 - m) > 0 always: logical index 0 is <= every position, and
    # in a window the position itself is in it
    ctx = acc_scr[...] / l_scr[:, :1]  # [H, KD]
    if group == 1:
        out_ref[0] = jnp.sum(jnp.where(own, ctx, 0.0), axis=0, keepdims=True)
    else:
        out_ref[0] = ctx  # the caller keeps each query head's own K/V lanes


def paged_attention_decode(
    q: Array,            # [S, KD] — one query token per slot
    k_pages: Array,      # [L, NP, PS, KD] — the WHOLE physical page pool
    v_pages: Array,      # [L, NP, PS, KD]
    block_table: Array,  # [S, P] int32 logical->physical page map
    positions: Array,    # [S] int32 — each slot's current token position
    *,
    layer,               # int, or a traced int32 scalar (a scanned stack)
    scale: float,
    n_heads: int,
    group: int = 1,
    window: int = 0,
) -> Array:
    """One decode step of ragged paged attention for all slots over layer
    `layer` of the pool: [S, KD] f32 context, numerically equivalent to the
    jnp gather oracle in `PagedLM._paged_attention_local` (same masking, f32
    softmax; the online recurrence reassociates the sum so equality is to
    float tolerance, argmax/token-exact under greedy decode; over a bfloat16
    pool, to bfloat16's: the oracle rounds the normalised weights, the
    recurrence the unnormalised).

    The pool rides in whole, in HBM, and `layer` picks the page inside the
    kernel's own copies (`_decode_layer`): slicing `k_pages[layer]` outside would make XLA
    copy one layer of the pool per layer per step to feed the custom call.

    `group` query heads read each K/V head (query head j reads K/V head
    j // group): q is [S, n_heads * hd] over a pool `n_heads // group` heads
    wide. The kernel's block-diagonal query then has a row a QUERY head over
    the pool's lanes, laid out here (a few MB a call), and each row's own
    K/V lanes are picked out of the kernel's [S, n_heads, KD] here too. With
    a group of 1 the call is what it was.

    `window` W > 0: each slot attends to its last W positions, and
    `block_table` [S, R] is its ring of pages (module docstring)."""
    if group > 1:
        return _grouped_decode(
            q, k_pages, v_pages, block_table, positions,
            layer=layer, scale=scale, n_heads=n_heads, group=group,
            window=window,
        )
    s, kd_model = q.shape
    head_dim = kd_model // n_heads
    if kd_model % _LANES:
        # Mosaic (jax 0.9.0) slices no HBM ref whose minor dimension is not
        # whole lanes, so the kernel's own copies cannot name a page of such
        # a pool: a model that narrow (the demo's 2 heads of 16) pays a
        # lane-padded copy of ONE layer of its pool a call. The padded lanes
        # belong to no head: q is zero there and the flush drops them.
        def widen(x):
            lanes = [(0, 0)] * (x.ndim - 1) + [(0, -kd_model % _LANES)]
            return jnp.pad(x, lanes)

        q = widen(q)
        k_pages = widen(jax.lax.dynamic_slice_in_dim(k_pages, layer, 1))
        v_pages = widen(jax.lax.dynamic_slice_in_dim(v_pages, layer, 1))
        layer = 0
    b = _pages_per_block(
        k_pages.shape[2], q.shape[1], block_table.shape[1],
        k_pages.dtype.itemsize, ring=bool(window),
    )
    out = _decode_layer(
        jnp.asarray(layer, jnp.int32).reshape(1),
        block_table.astype(jnp.int32).reshape(-1), positions.astype(jnp.int32),
        q[:, None, :], k_pages, v_pages,
        scale=scale, n_heads=n_heads, head_dim=head_dim, pages_per_block=b,
        interpret=interpret_mode(), window=window,
    )
    return out[:, 0, :kd_model]


def _grouped_decode(q, k_pages, v_pages, block_table, positions, *, layer,
                    scale, n_heads, group, window=0):
    s = q.shape[0]
    n_kv = n_heads // group
    head_dim = q.shape[1] // n_heads
    kd = n_kv * head_dim
    if kd % _LANES:
        # as above: a pool too narrow for whole lanes pays a padded copy of
        # one layer a call
        lanes = [(0, 0)] * 3 + [(0, -kd % _LANES)]
        k_pages = jnp.pad(jax.lax.dynamic_slice_in_dim(k_pages, layer, 1), lanes)
        v_pages = jnp.pad(jax.lax.dynamic_slice_in_dim(v_pages, layer, 1), lanes)
        layer = 0
    kd_pool = k_pages.shape[3]
    # row (c, g) holds query head c * group + g in K/V head c's lanes
    eye = jnp.eye(n_kv, dtype=q.dtype)
    qd = jnp.einsum("scgd,ce->scged", q.reshape(s, n_kv, group, head_dim), eye)
    qd = jnp.pad(qd.reshape(s, n_heads, kd), [(0, 0), (0, 0), (0, kd_pool - kd)])
    b = _pages_per_block(
        k_pages.shape[2], kd_pool, block_table.shape[1], k_pages.dtype.itemsize,
        ring=bool(window),
    )
    out = _decode_layer(
        jnp.asarray(layer, jnp.int32).reshape(1),
        block_table.astype(jnp.int32).reshape(-1), positions.astype(jnp.int32),
        qd, k_pages, v_pages,
        scale=scale, n_heads=n_heads, head_dim=head_dim, pages_per_block=b,
        interpret=interpret_mode(), group=group, window=window,
    )
    own = out[..., :kd].reshape(s, n_kv, group, n_kv, head_dim)
    return jnp.einsum("scged,ce->scgd", own, jnp.eye(n_kv, dtype=out.dtype)).reshape(s, -1)


@functools.partial(
    jax.jit,
    static_argnames=(
        "scale", "n_heads", "head_dim", "pages_per_block", "interpret", "group",
        "window",
    ),
)
def _decode_layer(
    layer, table, positions, q, k_pages, v_pages,
    *, scale, n_heads, head_dim, pages_per_block, interpret, group=1, window=0,
):
    """The kernel's call. The layer is DATA (a third prefetched scalar) and
    the call is jitted, so the L calls of one decode step share one trace
    and one lowering of the kernel: traced a layer at a time its unrolled
    page copies cost the served cell 9 s of warm-up (chip run, PR 30)."""
    s, q_rows, kd = q.shape  # q_rows: 1, or a row a query head (grouped)
    ps = k_pages.shape[2]
    b = pages_per_block
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s,),
        in_specs=[
            pl.BlockSpec((1, q_rows, kd), lambda i, *_: (i, 0, 0)),
            # the ragged gather is the kernel's own: the block table
            # (prefetched to SMEM before the body runs) names the physical
            # page each of its copies fetches
            pl.BlockSpec(memory_space=pltpu.HBM),
            pl.BlockSpec(memory_space=pltpu.HBM),
        ],
        out_specs=pl.BlockSpec((1, q_rows, kd), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[
            # a ring block is one copy of B whole pages
            pltpu.VMEM((2, b, ps, kd) if window else (2, b * ps, kd), k_pages.dtype),
            pltpu.VMEM((2, b, ps, kd) if window else (2, b * ps, kd), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((n_heads, kd), k_pages.dtype),
            pltpu.VMEM((n_heads, _LANES), jnp.float32),
            pltpu.VMEM((n_heads, _LANES), jnp.float32),
            pltpu.VMEM((n_heads, kd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _paged_decode_kernel, scale=scale, page_size=ps,
            head_dim=head_dim, pages_per_block=b,
            pmax=table.shape[0] // s, group=group, window=window,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, q_rows, kd), jnp.float32),
        # sequential: the double-buffer chain runs from slot to slot
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name="paged_attention_decode",
    )(layer, table, positions, q, k_pages, v_pages)
