"""`ServableLM.commit_prefill` against a plain loop over (row, position).

The oracle below is the commit's contract written out: position `starts[r] +
j` of row r goes to offset `pos % page_size` of the page its block-table row
names for `pos // page_size` (the row's last entry for anything past it), or
to dump page 0 when it lies at or past `lengths[r]`. It imports nothing of
the model's own write, so a rewrite of that write (PR 32 made it one
scatter a layer, in place) is held to the same pools bit for bit everywhere
but in page 0, which nothing reads unmasked."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.serving.model import LMConfig, ServableLM


def _oracle(k_pages, v_pages, kc, vc, lengths, block_rows, starts):
    k, v = k_pages.copy(), v_pages.copy()
    ps = k.shape[2]
    last = block_rows.shape[1] - 1
    for row in range(kc.shape[1]):
        for j in range(kc.shape[2]):
            pos = int(starts[row]) + j
            page = 0
            if pos < lengths[row]:
                page = int(block_rows[row, min(pos // ps, last)])
            k[:, page, pos % ps] = kc[:, row, j]
            v[:, page, pos % ps] = vc[:, row, j]
    return k, v


# name: (page_size, kd, n_layers, t, starts, lengths, block rows)
CASES = {
    # 11 of a 16-token bucket: a full page, 3 of the next, the rest dumped
    "whole_prompt_shorter_than_bucket": (
        8, 256, 3, 16, [0], [11], [[3, 5, 7, 9, 0, 0]]),
    "length_a_multiple_of_page_size": (
        8, 256, 3, 16, [0], [16], [[3, 5, 7, 9, 0, 0]]),
    "chunk_at_aligned_nonzero_start": (
        8, 256, 3, 8, [16], [29], [[3, 5, 7, 9, 0, 0]]),
    # a chunk as long as a page at an odd start: it straddles two pages
    "chunk_at_unaligned_start": (
        8, 256, 3, 8, [13], [40], [[3, 5, 7, 9, 11, 0]]),
    # speculation's K+1 = 5 positions from position 6: 6, 7 | 8, 9, 10
    "verify_chunk_crossing_a_page": (
        8, 256, 3, 5, [6], [11], [[3, 5, 7, 9, 0, 0]]),
    "two_rows_at_once": (
        8, 256, 3, 16, [0, 8], [13, 20],
        [[3, 5, 7, 0, 0, 0], [2, 4, 6, 8, 0, 0]]),
    # a write that runs off the budget's end: positions 36..51 against a row
    # of 6 pages of 8, whose last entry is not held; 48 and up lie past it
    "positions_past_the_rows_last_entry": (
        8, 256, 3, 16, [36], [52], [[3, 5, 7, 9, 10, 0]]),
    # the CLI's demo model: 2 layers of 32 lanes, under one lane tile
    "pool_narrower_than_128_lanes": (
        4, 32, 2, 8, [0], [7], [[3, 5, 7, 0]]),
    "chunk_shorter_than_a_page": (
        16, 256, 3, 5, [30], [35], [[3, 5, 7, 0]]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_commit_equals_a_plain_loop(case):
    ps, kd, n_layers, t, starts, lengths, rows = CASES[case]
    n_pages = 12
    rng = np.random.default_rng(sorted(CASES).index(case))
    shape = (n_layers, n_pages, ps, kd)
    k0 = rng.standard_normal(shape).astype(np.float32)
    v0 = rng.standard_normal(shape).astype(np.float32)
    kc = rng.standard_normal((n_layers, len(starts), t, kd)).astype(np.float32)
    vc = rng.standard_normal(kc.shape).astype(np.float32)
    starts, lengths = np.array(starts, np.int32), np.array(lengths, np.int32)
    rows = np.array(rows, np.int32)

    model = ServableLM(LMConfig(vocab=16, n_layers=n_layers, d_model=kd))
    k1, v1 = jax.jit(model.commit_prefill)(
        k0, v0, kc, vc, jnp.asarray(lengths), jnp.asarray(rows),
        jnp.asarray(starts),
    )
    k1, v1 = np.asarray(k1), np.asarray(v1)
    assert k1.dtype == np.float32 and v1.dtype == np.float32

    want_k, want_v = _oracle(k0, v0, kc, vc, lengths, rows, starts)
    np.testing.assert_array_equal(k1[:, 1:], want_k[:, 1:])
    np.testing.assert_array_equal(v1[:, 1:], want_v[:, 1:])
    # the case wrote something, and only into pages the rows name
    assert not np.array_equal(want_k[:, 1:], k0[:, 1:])
    others = sorted(set(range(1, n_pages)) - set(rows.ravel().tolist()))
    np.testing.assert_array_equal(k1[:, others], k0[:, others])
    np.testing.assert_array_equal(v1[:, others], v0[:, others])
