"""The routed expert block two served decoders share (HybridMoELM and
WindowMoELM): route every token to `top_k` experts, sort the assignments by
expert, and run the experts' two products as grouped products over the
assignments that landed on an expert this chip holds. No capacity and no
dropped token.

Two routings, the model's to declare (`choose`):

    softmax after top-k (bias None):  idx, val = top_k(r);  g = softmax(val)
    sigmoid (a selection bias b):      s = sigmoid(r);  idx = top_k(s + b)
                                       g = scale * s[idx] / (sum s[idx] + 1e-20)

with r = h W_r in float32; b moves which experts are chosen and never how
much each one weighs.

The expert stacks are taken WHOLE, [L, E, ...] over the model's L expert
layers, and the layer is an argument: ragged-dot takes every layer's experts
as its groups and this layer's alone have rows, the kernel takes the layer
as an index of its blocks, so the weights are read where they lie. A slice
of the stack cut for the custom call is a copy of the layer's experts a
layer and step (0.68 GB, a third of granite's step on the chip; PERF.md).

Which grouped product runs is one rule on shapes the trace sees, the mean
rows a held expert gets, T k / E held, and what fits VMEM. Both forms are
the Pallas kernel's (ops/pallas/grouped_matmul.py), which streams each held
expert's weights back to back: at or under `KERNEL_ROWS_PER_EXPERT`, where
all the rows fit VMEM at once, they stay there while the experts go by
(Trinity's decode step, 64 slots x 8 over 128 experts, 4 an expert;
granite's decode step and its prefills to 512 tokens, 17.8 to 142.2 an
expert); elsewhere the rows and the output stream through VMEM in tiles
(Trinity's 2048-token chunk, 16,384 rows, 128 an expert; granite's
1024-token prefill, 284). On CPU `ops.pallas.enabled()` is off and
`jax.lax.ragged_dot`, the kernel's oracle, runs everywhere."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.obs import metrics as obs_metrics
from paddle_tpu.ops import pallas
from paddle_tpu.ops.pallas import grouped_matmul as gm

Array = jax.Array
F32 = jnp.float32

# the most rows a held expert gets on average, T k / E held, at which the
# grouped products keep every row in VMEM where they fit: that form beat
# ragged-dot at every mean timed on the chip where its blocks fit, 3.6 to
# 142.2 (granite's 512-token prefill); past it the rows stream in tiles
# (benchmarks/grouped_matmul_bench.py; PERF.md)
KERNEL_ROWS_PER_EXPERT = 144


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
    logits = jnp.matmul(h, router, preferred_element_type=F32)
    gate, idx = choose(logits, k, bias, route_scale)          # [T, k]
    # an assignment's group: its expert's place among the held; group e,
    # which has no weights and gets no work, for an absent expert and for
    # what is no token
    group = jnp.where(valid[:, None], jnp.asarray(local_of)[idx], e).reshape(-1)
    order = jnp.argsort(group, stable=True)                   # [T * k]
    sizes = jnp.sum(group[:, None] == jnp.arange(e)[None, :], 0, dtype=jnp.int32)
    rows = h[order // k]                                      # [T * k, D], by expert
    a, b = jnp.split(_grouped(rows, wi, sizes, layer).astype(dtype), 2, -1)
    out = _grouped(jax.nn.silu(a) * b, wo, sizes, layer).astype(dtype)
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


def _grouped(rows: Array, w: Array, sizes: Array, layer) -> Array:
    """rows [M, K] sorted by expert times layer `layer` of the whole stack w
    [L, E, K, N], sizes [E] each held expert's rows: float32 [M, N], rows
    past the groups 0. The kernel's two forms or ragged-dot by the module's
    rule; runs where a step is traced, so the counter moves once a product a
    trace."""
    (m, kd), (n_layers, e, _, n) = rows.shape, w.shape
    if pallas.enabled():
        if m <= KERNEL_ROWS_PER_EXPERT * e and gm.fits(m, kd, n, rows.dtype, w.dtype):
            obs_metrics.observe_grouped_matmul("kernel")
            return gm.grouped_matmul(rows, w, sizes, layer)
        if gm.streams(m, kd, n, e, rows.dtype, w.dtype):
            obs_metrics.observe_grouped_matmul("streamed")
            return gm.grouped_matmul_streamed(rows, w, sizes, layer)
    obs_metrics.observe_grouped_matmul("ragged_dot")
    # every layer's experts are groups, and only this layer's have rows
    groups = jax.lax.dynamic_update_slice(jnp.zeros(n_layers * e, jnp.int32), sizes, (layer * e,))
    return jax.lax.ragged_dot(rows, w.reshape((n_layers * e,) + w.shape[2:]), groups,
                              preferred_element_type=F32)
