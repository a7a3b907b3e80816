"""Shared step-timing harness for all benchmark entry points (bench.py,
benchmarks/*.py).

The execution barrier is a VALUE fetch (float(cost)), not
jax.block_until_ready: the host cannot hold the final cost before every
step it depends on has run, so the fetch closes the timed region on the
whole dependent chain and doubles as the finiteness check."""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Tuple

import numpy as np


def time_train_steps(
    step: Callable,
    state: Any,
    batch: Dict[str, Any],
    steps: int = 10,
    warmup: int = 2,
) -> Tuple[float, Any]:
    """Returns (seconds_per_step, final_state). `step(state, batch)` must
    return (new_state, cost_scalar, extras)."""
    for _ in range(max(warmup, 1)):
        state, cost, _ = step(state, batch)
    cost_v = float(cost)  # barrier: forces compile + warmup chain
    assert np.isfinite(cost_v), f"non-finite cost during warmup: {cost_v}"

    t0 = time.perf_counter()
    for _ in range(steps):
        state, cost, _ = step(state, batch)
    final = float(cost)  # barrier: forces the timed chain
    dt = time.perf_counter() - t0
    assert np.isfinite(final), f"non-finite cost during timing: {final}"
    return dt / steps, state


def time_multi_steps(
    multi: Callable,
    state: Any,
    batches: Dict[str, Any],
    k: int,
    dispatches: int = 4,
    warmup: int = 1,
) -> Tuple[float, Any]:
    """Times the K-step scan driver (SGDTrainer.make_multi_step): each
    dispatch runs `k` train steps in one compiled program. Returns
    (seconds_per_step, final_state); the barrier is a value fetch of the
    last scanned cost (see module docstring for why not block_until_ready)."""
    for _ in range(max(warmup, 1)):
        state, costs = multi(state, batches)
    warm = float(costs[-1])
    assert np.isfinite(warm), f"non-finite cost during warmup: {warm}"

    t0 = time.perf_counter()
    for _ in range(dispatches):
        state, costs = multi(state, batches)
    final = float(costs[-1])
    dt = time.perf_counter() - t0
    assert np.isfinite(final), f"non-finite cost during timing: {final}"
    return dt / (dispatches * k), state
