"""Weights from the seed, made on the device in ONE jitted call, in the type
they are used in. The benchmark makes them (not the program), so the plain
reference can be given the same values without taking anything the program
made. Rules come from the configuration's file ("weights": a list tried in
order; each may match by name suffix and/or rank, and gives mean and std;
std "fan_in" means 1/sqrt(product of all but the last dimension))."""

from __future__ import annotations

import math
import zlib
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

DEFAULT_RULES = (
    {"suffix": ".scale", "mean": 1.0, "std": 0.1},
    {"ndim": 1, "mean": 0.0, "std": 0.1},
    {"mean": 0.0, "std": "fan_in"},
)


def _rule_for(name: str, shape: Tuple[int, ...], rules: Sequence[dict]) -> dict:
    for r in rules:
        if "suffix" in r and not name.endswith(r["suffix"]):
            continue
        if "ndim" in r and len(shape) != r["ndim"]:
            continue
        return r
    raise ValueError(f"no weight rule matches {name} {shape}")


def make_weights(
    shapes: Dict[str, Tuple[int, ...]],
    seed: int,
    rules: Optional[Sequence[dict]] = None,
    dtype=jnp.float32,
) -> Dict[str, jax.Array]:
    rules = tuple(rules) if rules else DEFAULT_RULES
    plan = {}
    for name, shape in shapes.items():
        r = _rule_for(name, tuple(shape), rules)
        std = r["std"]
        if std == "fan_in":
            std = 1.0 / math.sqrt(max(1, math.prod(shape[:-1])))
        plan[name] = (tuple(shape), float(r.get("mean", 0.0)), float(std))

    def gen(key):
        out = {}
        for name, (shape, mean, std) in plan.items():
            k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
            out[name] = (mean + std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)
        return out

    # seeds run past 2**31: fold the high bits in rather than truncate
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return jax.jit(gen)(key)
