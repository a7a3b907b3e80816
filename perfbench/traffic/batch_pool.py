"""Training feed: a pool of host batches made from the seed in set-up, which
a reader cycles for as long as the window lasts. Slots are described in the
cell's file: {"name", "kind": "normal" | "randint" | "full", "shape" (per
row), "low"/"high"/"value", "dtype"}. Every row of every batch differs."""

from __future__ import annotations

from typing import Dict, List

import ml_dtypes  # noqa: F401  (registers bfloat16 with numpy)
import numpy as np


def make_pool(params: dict, rows: int, seed: int) -> List[Dict[str, np.ndarray]]:
    rs = np.random.default_rng(seed)
    pool = []
    for _ in range(int(params["pool_batches"])):
        batch = {}
        for slot in params["slots"]:
            shape = (rows,) + tuple(slot.get("shape", ()))
            kind = slot["kind"]
            if kind == "normal":
                arr = rs.standard_normal(shape, dtype=np.float32)
            elif kind == "randint":
                arr = rs.integers(slot["low"], slot["high"], shape)
            elif kind == "full":
                arr = np.full(shape, slot["value"])
            else:
                raise ValueError(f"unknown slot kind {kind!r}")
            batch[slot["name"]] = arr.astype(np.dtype(slot["dtype"]))
        pool.append(batch)
    return pool


def order(params: dict, seed: int) -> List[int]:
    """The order in which the window cycles the pool: a permutation drawn
    from the seed, so every seed does the same work in another order."""
    n = int(params["pool_batches"])
    return [int(i) for i in np.random.default_rng(seed + 1).permutation(n)]
