"""The comparison that decides `correct` for training cells.

Readings are per-leaf norms. A gap is |program's norm - reference's norm|
(not the norm of the difference) over the reference's norm of that leaf or
of the median leaf, whichever is larger, taken by the worst leaf. Leaves
whose reference gradient is under a thousandth of the median leaf's are left
out of the parameter-change gaps (they move by round-off alone)."""

from __future__ import annotations

import statistics
from typing import Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp


@jax.jit
def leaf_norms(tree: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """Device scalars, one program for the whole tree: fetched once by the
    caller."""
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))) for k, v in tree.items()}


@jax.jit
def diff_norms(a: Dict[str, jax.Array], b: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    return leaf_norms({k: a[k].astype(jnp.float32) - b[k].astype(jnp.float32) for k in a})


@jax.jit
def snapshot(tree: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """A copy that survives the donation of the original, in one program."""
    return jax.tree.map(jnp.copy, tree)


def to_floats(norms: Dict[str, jax.Array]) -> Dict[str, float]:
    return {k: float(v) for k, v in jax.device_get(norms).items()}


def worst_leaf_gap(
    prog: Dict[str, float], ref: Dict[str, float], leaves: Optional[Iterable[str]] = None
) -> Tuple[float, str]:
    names = sorted(leaves if leaves is not None else ref)
    med = statistics.median(ref[k] for k in names)
    worst, at = 0.0, ""
    for k in names:
        gap = abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
        if not gap == gap:
            return float("nan"), k
        if gap > worst:
            worst, at = gap, k
    return worst, at


def moving_leaves(ref_grad: Dict[str, float]) -> list:
    med = statistics.median(ref_grad.values())
    return [k for k, g in ref_grad.items() if g >= 1e-3 * med]


def loss_gap(prog: Iterable[float], ref: Iterable[float]) -> float:
    return max(abs(p - r) / max(abs(r), 1e-30) for p, r in zip(prog, ref))


def grad_diff(prog: dict, ref: dict) -> Tuple[float, str]:
    """The norm of the DIFFERENCE between step 1's gradients, leaf by leaf,
    over the reference's norm of that leaf or of the median leaf: rounding is
    zero-mean and cancels in a large leaf's norm, so the gap of norms cannot
    tell one precision from the next below; the difference can."""
    diff = to_floats(diff_norms(prog["grad_full"], ref["grad_full"]))
    med = statistics.median(ref["grad"].values())
    return max((diff[k] / max(ref["grad"][k], med, 1e-30), k) for k in sorted(diff))


def training_readings(prog: dict, ref: dict) -> Dict[str, Tuple[float, str]]:
    """prog/ref: {"losses": [l1, l2, l3, (l_fused_last)], "grad": norms of
    step 1's gradient, "grad_full": that gradient itself, "delta3": norms of
    p3 - p0, "delta_fused": norms of p(3+K) - p3} -> each number compared,
    with the leaf it was read on ("" for a loss)."""
    moving = moving_leaves(ref["grad"])
    out = {
        "loss_gap": (loss_gap(prog["losses"][:3], ref["losses"][:3]), ""),
        "grad_gap": worst_leaf_gap(prog["grad"], ref["grad"]),
        "update_gap": worst_leaf_gap(prog["delta3"], ref["delta3"], moving),
    }
    if "grad_full" in prog and "grad_full" in ref:
        out["grad_diff"] = grad_diff(prog, ref)
    if "delta_fused" in ref:
        out["fused_loss_gap"] = (loss_gap(prog["losses"][3:4], ref["losses"][3:4]), "")
        out["fused_update_gap"] = worst_leaf_gap(prog["delta_fused"], ref["delta_fused"], moving)
    return out


def training_numbers(prog: dict, ref: dict) -> Dict[str, float]:
    return {k: v for k, (v, _) in training_readings(prog, ref).items()}


def follow(loss_fn, params, batches, opt: dict, steps_then: Tuple[int, int]) -> dict:
    """Drive the plain reference from `params` over `batches`: `steps_then[0]`
    steps, readings, then `steps_then[1]` more. Returns the same readings as
    the program's check drive gives."""
    from perfbench.reference import optim

    @jax.jit
    def step(p, s, batch):
        loss, grads = jax.value_and_grad(loss_fn)(p, batch)
        new_p, new_s = optim.update(opt, grads, s, p)
        return loss, grads, new_p, new_s

    n_first, n_more = steps_then
    state = optim.init(opt["kind"], params)
    p0, p, losses, grad = params, params, [], None
    with jax.default_matmul_precision("highest"):
        for i in range(n_first):
            loss, g, p, state = step(p, state, batches[i])
            losses.append(loss)
            if i == 0:
                grad = g
            del g
        out = {"grad": to_floats(leaf_norms(grad)), "grad_full": grad,
               "delta3": to_floats(diff_norms(p, p0))}
        if n_more:
            p3 = p
            for i in range(n_first, n_first + n_more):
                loss, _, p, state = step(p, state, batches[i])
            losses.append(loss)
            out["delta_fused"] = to_floats(diff_norms(p, p3))
    out["losses"] = [float(x) for x in jax.device_get(losses)]
    return out
