"""The routed expert block two served decoders share (HybridMoELM and
WindowMoELM): route every token to `top_k` experts, sort the assignments by
expert, and run the experts' two products as grouped products
(`jax.lax.ragged_dot`) over the assignments that landed on an expert this
chip holds. No capacity and no dropped token.

Two routings, the model's to declare (`choose`):

    softmax after top-k (bias None):  idx, val = top_k(r);  g = softmax(val)
    sigmoid (a selection bias b):      s = sigmoid(r);  idx = top_k(s + b)
                                       g = scale * s[idx] / (sum s[idx] + 1e-20)

with r = h W_r in float32; b moves which experts are chosen and never how
much each one weighs.

The expert stacks are taken WHOLE, [L, E, ...] over the model's L expert
layers, and the layer is an argument: the grouped product takes every
layer's experts as its groups and this layer's alone have rows, so the
weights are read where they lie. A slice of the stack cut for the custom
call is a copy of the layer's experts a layer and step (0.68 GB, a third of
granite's step on the chip; PERF.md)."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array
F32 = jnp.float32


def choose(logits: Array, top_k: int, bias: Optional[Array] = None,
           route_scale: float = 1.0):
    """(gates [T, k] float32, expert ids [T, k]) from the router's float32
    logits [T, E] (module docstring)."""
    if bias is None:
        val, idx = jax.lax.top_k(logits, top_k)
        return jax.nn.softmax(val, -1), idx                   # over the CHOSEN logits
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + bias.astype(F32), top_k)  # the bias selects only
    chosen = jnp.take_along_axis(scores, idx, -1)
    return route_scale * chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20), idx


def expert_block(h: Array, valid: Array, router: Array, wi: Array, wo: Array, layer,
                 *, top_k: int, local_of: np.ndarray, dtype, bias=None,
                 route_scale: float = 1.0):
    """h [T, D], valid [T]; router [D, E routed]; wi [L, E held, D, 2F] and
    wo [L, E held, F, D] the whole stacks, `layer` the one to run (may be
    traced); `local_of` [E routed] an expert's place among the held, E held
    where it is absent. Returns (the held experts' part of the block [T, D]
    in `dtype`, counts of the valid tokens' assignments by held expert [E
    held], (landed here, went to an absent expert) [2])."""
    t, k, e = h.shape[0], top_k, wi.shape[1]
    n_layers = wi.shape[0]
    wi = wi.reshape((n_layers * e,) + wi.shape[2:])
    wo = wo.reshape((n_layers * e,) + wo.shape[2:])
    logits = jnp.matmul(h, router, preferred_element_type=F32)
    gate, idx = choose(logits, k, bias, route_scale)          # [T, k]
    # an assignment's group: its expert's place among the held; group e,
    # which has no weights and gets no work, for an absent expert and for
    # what is no token
    group = jnp.where(valid[:, None], jnp.asarray(local_of)[idx], e).reshape(-1)
    order = jnp.argsort(group, stable=True)                   # [T * k]
    sizes = jnp.sum(group[:, None] == jnp.arange(e)[None, :], 0, dtype=jnp.int32)
    rows = h[order // k]                                      # [T * k, D], by expert
    groups = jax.lax.dynamic_update_slice(
        jnp.zeros(n_layers * e, jnp.int32), sizes, (layer * e,))
    a, b = jnp.split(
        jax.lax.ragged_dot(rows, wi, groups, preferred_element_type=F32)
        .astype(dtype), 2, -1)
    out = jax.lax.ragged_dot(
        jax.nn.silu(a) * b, wo, groups, preferred_element_type=F32
    ).astype(dtype)
    # back in (token, choice) order and summed in that order, so a token's
    # sum does not depend on who shares the batch; rows past the groups
    # hold nothing that counts
    back = jnp.argsort(order)
    here = (group < e).reshape(t, k)
    out = jnp.where(here[..., None], out[back].reshape(t, k, -1).astype(F32), 0.0)
    out = jnp.sum(out * gate[..., None], 1).astype(dtype)
    landed = jnp.sum(sizes)
    absent = jnp.sum(valid) * k - landed
    return out, sizes.astype(jnp.uint32), jnp.stack([landed, absent]).astype(jnp.uint32)
