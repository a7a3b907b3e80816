"""Recurrent cells + time scans.

The TPU replacement for the fused CUDA recurrences: hl_cuda_lstm.cu (872 LoC,
all four gates fused per step), hl_gpu_gru.cuh, and the batching transform
SequenceToBatch.h:41. Design shift: instead of reordering ragged sequences into
per-timestep dense batches, we keep padded [B, T, ...] arrays time-major inside
`lax.scan` and carry a mask — XLA fuses the per-step gate math into a single
kernel, and the big input projections are hoisted OUT of the scan as one large
[B*T, 4H] matmul on the MXU (the reference does the same hoist: the layer
projects via Mixed/fc before LstmLayer).

Gate conventions — NOTE the LSTM block order intentionally differs from the
reference: here the 4H weight/bias blocks are [input, forget, cell(candidate),
output], while the reference packs [candidate(In), input(Ig), forget(Fg),
output(Og)] (hl_cpu_lstm.cuh:42-45, hl_gpu_lstm.cuh). The math is identical;
only the block layout differs — any loader interchanging LSTM weights with
reference-trained models MUST permute the 4H blocks accordingly (no such
loader exists yet; reference-format weights cannot currently be loaded into
LSTM layers unpermuted). GRU gates [update(z), reset(r), candidate(c)] match
GruCompute.cu. Optional peephole ("check") weights as in the reference."""

from __future__ import annotations

import logging
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.nn import activations as act_mod
from paddle_tpu.ops import linalg

Array = jax.Array

log = logging.getLogger("paddle_tpu")


def _use_fused(standard_config: bool, kind: str, b: int, h: int) -> bool:
    """Route to the pallas whole-sequence kernel when on TPU (or forced) and
    the layer uses the reference-default activations (no peepholes).

    The kernels keep the recurrent weights and their gradient resident in
    VMEM; a [b, h] carry whose blocks would not fit the budget takes the
    lax.scan path — decided here from the shapes, never by catching the
    compiler's refusal."""
    from paddle_tpu.ops import pallas as pal
    from paddle_tpu.ops.pallas import rnn_kernels

    if not standard_config or not pal.enabled():
        return False
    vmem_bytes = {
        "lstm": rnn_kernels.lstm_vmem_bytes, "gru": rnn_kernels.gru_vmem_bytes
    }
    need = vmem_bytes[kind](b, h)
    if need > rnn_kernels.VMEM_BUDGET:
        log.info(
            "fused %s kernel at batch %d hidden %d needs %d MiB of VMEM "
            "(budget %d MiB): taking the lax.scan path",
            kind, b, h, need >> 20, rnn_kernels.VMEM_BUDGET >> 20,
        )
        return False
    return True


def _run_fused(proj: Array, mask: Array, reverse: bool, fn: Callable) -> Tuple:
    """Shared fused-kernel dispatch: batch-major → time-major (+flip for
    reverse), call `fn(proj_tm, mask_tm) -> (hs_tm, *finals)`, restore layout
    and the caller's dtype."""
    ptm = jnp.swapaxes(proj, 0, 1)
    mtm = jnp.swapaxes(mask, 0, 1)[:, :, None]
    if reverse:
        ptm, mtm = jnp.flip(ptm, 0), jnp.flip(mtm, 0)
    hs, *finals = fn(ptm, mtm)
    if reverse:
        hs = jnp.flip(hs, 0)
    hs = jnp.swapaxes(hs, 0, 1).astype(proj.dtype)
    return (hs, *(f.astype(proj.dtype) for f in finals))


class LstmParams(NamedTuple):
    w_hh: Array  # [H, 4H] recurrent weights
    bias: Array  # [4H]
    check_i: Optional[Array] = None  # peephole [H] for input gate
    check_f: Optional[Array] = None
    check_o: Optional[Array] = None


def lstm_step(
    proj_t: Array,  # [B, 4H] (x_t already projected)
    h: Array,
    c: Array,
    p: LstmParams,
    gate_act: str = "sigmoid",
    cell_act: str = "tanh",
    state_act: str = "tanh",
) -> Tuple[Array, Array]:
    """One LSTM step (hl_lstm fused kernel semantics, incl. peepholes)."""
    hdim = h.shape[-1]
    # params are f32 masters; compute in the activations' dtype so bf16
    # carries stay bf16 through lax.scan (carry dtypes must be invariant)
    gates = proj_t + linalg.matmul(h, p.w_hh) + p.bias.astype(proj_t.dtype)
    gi, gf, gc, go = jnp.split(gates, 4, axis=-1)
    ga = act_mod.get(gate_act)
    if p.check_i is not None:
        gi = gi + c * p.check_i.astype(c.dtype)
        gf = gf + c * p.check_f.astype(c.dtype)
    i = ga(gi)
    f = ga(gf)
    cand = act_mod.get(cell_act)(gc)
    c_new = f * c + i * cand
    if p.check_o is not None:
        go = go + c_new * p.check_o.astype(c_new.dtype)
    o = ga(go)
    h_new = o * act_mod.get(state_act)(c_new)
    return h_new, c_new


def lstm_scan(
    proj: Array,  # [B, T, 4H]
    mask: Array,  # [B, T]
    p: LstmParams,
    h0: Optional[Array] = None,
    c0: Optional[Array] = None,
    reverse: bool = False,
    gate_act: str = "sigmoid",
    cell_act: str = "tanh",
    state_act: str = "tanh",
    return_cell_seq: bool = False,
) -> Tuple[Array, Array, Array]:
    """Full-sequence LSTM → (h_seq [B,T,H], h_last, c_last). Masked steps
    carry the previous state through (ragged batches stay correct).

    `return_cell_seq=True` returns (h_seq, c_seq [B,T,H], h_last) instead —
    the fluid lstm_op contract (full cell sequence in its 'Cell' slot). The
    fused pallas kernel only materializes final states, so that mode always
    takes the scan path."""
    b, t, h4 = proj.shape
    hdim = h4 // 4
    h0 = h0 if h0 is not None else jnp.zeros((b, hdim), proj.dtype)
    c0 = c0 if c0 is not None else jnp.zeros((b, hdim), proj.dtype)

    if not return_cell_seq and _use_fused(
        gate_act == "sigmoid" and cell_act == "tanh" and state_act == "tanh"
        and p.check_i is None and p.check_f is None and p.check_o is None,
        "lstm", b, hdim,
    ):
        from paddle_tpu.ops.pallas.rnn_kernels import lstm_seq_fused

        return _run_fused(
            proj, mask, reverse,
            lambda ptm, mtm: lstm_seq_fused(ptm, mtm, p.w_hh, p.bias, h0, c0),
        )

    def step(carry, xs):
        h, c = carry
        proj_t, m_t = xs
        h_new, c_new = lstm_step(proj_t, h, c, p, gate_act, cell_act, state_act)
        m = m_t[:, None].astype(h_new.dtype)
        h = m * h_new + (1 - m) * h
        c = m * c_new + (1 - m) * c
        return (h, c), ((h, c) if return_cell_seq else h)

    xs = (jnp.swapaxes(proj, 0, 1), jnp.swapaxes(mask, 0, 1))
    (h_last, c_last), out = lax.scan(step, (h0, c0), xs, reverse=reverse)
    if return_cell_seq:
        hs, cs = out
        return jnp.swapaxes(hs, 0, 1), jnp.swapaxes(cs, 0, 1), h_last
    return jnp.swapaxes(out, 0, 1), h_last, c_last


class GruParams(NamedTuple):
    w_hzr: Array  # [H, 2H] recurrent weights for update+reset gates
    w_hc: Array  # [H, H] recurrent weight for candidate
    bias: Array  # [3H]


def gru_step(
    proj_t: Array,  # [B, 3H] in gate order [z, r, c]
    h: Array,
    p: GruParams,
    gate_act: str = "sigmoid",
    cand_act: str = "tanh",
) -> Array:
    """One GRU step (GruCompute / hl_gpu_gru.cuh semantics: reset gate applies
    to the *recurrent* candidate term)."""
    hdim = h.shape[-1]
    pz, pr, pc = jnp.split(proj_t + p.bias.astype(proj_t.dtype), 3, axis=-1)
    rz = linalg.matmul(h, p.w_hzr)
    ga = act_mod.get(gate_act)
    z = ga(pz + rz[:, :hdim])
    r = ga(pr + rz[:, hdim:])
    c = act_mod.get(cand_act)(pc + linalg.matmul(r * h, p.w_hc))
    return (1.0 - z) * h + z * c


def gru_scan(
    proj: Array,  # [B, T, 3H]
    mask: Array,  # [B, T]
    p: GruParams,
    h0: Optional[Array] = None,
    reverse: bool = False,
    gate_act: str = "sigmoid",
    cand_act: str = "tanh",
) -> Tuple[Array, Array]:
    """Full-sequence GRU → (h_seq [B,T,H], h_last)."""
    b, t, h3 = proj.shape
    hdim = h3 // 3
    h0 = h0 if h0 is not None else jnp.zeros((b, hdim), proj.dtype)

    if _use_fused(gate_act == "sigmoid" and cand_act == "tanh", "gru", b, hdim):
        from paddle_tpu.ops.pallas.rnn_kernels import gru_seq_fused

        return _run_fused(
            proj, mask, reverse,
            lambda ptm, mtm: gru_seq_fused(ptm, mtm, p.w_hzr, p.w_hc, p.bias, h0),
        )

    def step(h, xs):
        proj_t, m_t = xs
        h_new = gru_step(proj_t, h, p, gate_act, cand_act)
        m = m_t[:, None].astype(h_new.dtype)
        h = m * h_new + (1 - m) * h
        return h, h

    xs = (jnp.swapaxes(proj, 0, 1), jnp.swapaxes(mask, 0, 1))
    h_last, hs = lax.scan(step, h0, xs, reverse=reverse)
    return jnp.swapaxes(hs, 0, 1), h_last


def simple_rnn_scan(
    proj: Array,  # [B, T, H] (input already projected)
    mask: Array,
    w_hh: Array,  # [H, H]
    bias: Optional[Array],
    act: str = "tanh",
    h0: Optional[Array] = None,
    reverse: bool = False,
) -> Tuple[Array, Array]:
    """Vanilla RNN (RecurrentLayer.cpp): h_t = act(x_t + W h_{t-1} + b)."""
    b, t, hdim = proj.shape
    h0 = h0 if h0 is not None else jnp.zeros((b, hdim), proj.dtype)
    a = act_mod.get(act)

    def step(h, xs):
        proj_t, m_t = xs
        pre = proj_t + linalg.matmul(h, w_hh)
        if bias is not None:
            pre = pre + bias
        h_new = a(pre)
        m = m_t[:, None].astype(h_new.dtype)
        h = m * h_new + (1 - m) * h
        return h, h

    xs = (jnp.swapaxes(proj, 0, 1), jnp.swapaxes(mask, 0, 1))
    h_last, hs = lax.scan(step, h0, xs, reverse=reverse)
    return jnp.swapaxes(hs, 0, 1), h_last
