"""The optimizers' update rules, written out (FirstOrderOptimizer semantics:
momentum SGD v = mu v - lr g, p += v; Adam with bias correction)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def init(kind: str, params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    if kind == "sgd_momentum":
        return {"v": zeros}
    if kind == "adam":
        return {"m": zeros, "v": jax.tree.map(jnp.zeros_like, params), "t": jnp.zeros((), jnp.float32)}
    raise ValueError(f"unknown optimizer {kind!r}")


def update(opt: dict, grads, state, params):
    kind, lr = opt["kind"], opt["lr"]
    if kind == "sgd_momentum":
        mu = opt["momentum"]
        v = jax.tree.map(lambda v, g: mu * v - lr * g, state["v"], grads)
        return jax.tree.map(lambda p, v: p + v, params, v), {"v": v}
    b1, b2, eps = opt.get("beta1", 0.9), opt.get("beta2", 0.999), opt.get("epsilon", 1e-8)
    t = state["t"] + 1.0
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"], grads)
    def step(p, m, v):
        return p - lr * (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
    return jax.tree.map(step, params, m, v), {"m": m, "v": v, "t": t}


def first_gradient(opt: dict, first_slot):
    """The first step's gradient as the optimizer got it, worked out from the
    PROGRAM's first optimizer slot (momentum's velocity, Adam's first moment)
    after one step from zero state."""
    if opt["kind"] == "sgd_momentum":
        return -first_slot / opt["lr"]
    return first_slot / (1.0 - opt.get("beta1", 0.9))
