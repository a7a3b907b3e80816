"""Fused whole-sequence LSTM/GRU Pallas kernels.

Parity target: hl_cuda_lstm.cu (all four gates fused per step, 872 LoC) and
hl_gpu_gru.cuh. TPU design: ONE pallas_call runs the entire time loop as a
sequential grid over T; the recurrent state (h, c) lives in VMEM scratch for
the whole sequence — zero HBM round-trips for the carry, one [B,H]x[H,4H]
MXU matmul per step, VPU for the gate math. The backward pass is a second
kernel walking the grid in reverse, accumulating dW in VMEM scratch.

Time-major layout [T, B, ...] so each grid step's block is one timestep.
Activations are fixed sigmoid/tanh (the reference's defaults); layers with
exotic activations or peepholes use the lax.scan path (ops/rnn.py), which is
also the numerical oracle for these kernels' tests."""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import interpret_mode

Array = jax.Array

# Scoped-VMEM accounting for the recurrent kernels, in bytes of f32: blocks
# whose index moves with the grid are double-buffered, resident blocks (the
# recurrent weights, initial states, dW outputs) and scratch are held once.
# Checked against Mosaic's own figure by AOT-compiling for v5e (64x1280 LSTM
# backward: 84.4 MiB here, 82.5 MiB reported). The backward kernel holds
# three weight-sized buffers (w, dW out, dW accumulator), so it decides.
# ops/rnn.py routes a shape past VMEM_BUDGET to the lax.scan path BEFORE the
# call; under it, the kernel asks Mosaic for what it needs instead of the
# 16 MiB default.
_VMEM_DEFAULT = 16 << 20
VMEM_BUDGET = 100 << 20  # of v5e's 128 MiB; the rest stays XLA's


def lstm_vmem_bytes(b: int, h: int) -> int:
    return 4 * (30 * b * h + 12 * h * h + 8 * h + 256 * b)


def gru_vmem_bytes(b: int, h: int) -> int:
    return 4 * (19 * b * h + 9 * h * h + 6 * h + 256 * b)


def _vmem_params(need: int) -> pltpu.CompilerParams:
    return pltpu.CompilerParams(
        vmem_limit_bytes=min(VMEM_BUDGET, max(_VMEM_DEFAULT, need * 5 // 4))
    )


def _sig(x):
    return jax.nn.sigmoid(x)


# ===========================================================================
# LSTM
# ===========================================================================


def _lstm_fwd_kernel(proj_ref, mask_ref, whh_ref, b_ref, h0_ref, c0_ref,
                     hs_ref, gates_ref, ct_ref, cs_ref, h_scr, c_scr):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_scr[:] = h0_ref[:]
        c_scr[:] = c0_ref[:]

    h = h_scr[:]
    c = c_scr[:]
    gates = proj_ref[0] + jnp.dot(
        h, whh_ref[:], preferred_element_type=jnp.float32
    ) + b_ref[:]
    hdim = h.shape[-1]
    i = _sig(gates[:, :hdim])
    f = _sig(gates[:, hdim : 2 * hdim])
    g = jnp.tanh(gates[:, 2 * hdim : 3 * hdim])
    o = _sig(gates[:, 3 * hdim :])
    c_tilde = f * c + i * g
    h_tilde = o * jnp.tanh(c_tilde)
    m = mask_ref[0]
    h_new = m * h_tilde + (1.0 - m) * h
    c_new = m * c_tilde + (1.0 - m) * c
    # saved for backward: post-activation gates, pre-mask cell, masked cell
    gates_ref[0] = jnp.concatenate([i, f, g, o], axis=-1)
    ct_ref[0] = c_tilde
    cs_ref[0] = c_new
    hs_ref[0] = h_new
    h_scr[:] = h_new
    c_scr[:] = c_new


def _lstm_bwd_kernel(gates_ref, ct_ref, hprev_ref, cprev_ref, mask_ref,
                     whh_ref, dhs_ref, dhlast_ref, dclast_ref,
                     dproj_ref, dw_ref, db_ref, dh0_ref, dc0_ref,
                     dh_scr, dc_scr, dw_scr, db_scr):
    ti = pl.program_id(0)  # 0 .. T-1, walking t = T-1-ti via index maps
    nt = pl.num_programs(0)

    @pl.when(ti == 0)
    def _init():
        dh_scr[:] = dhlast_ref[:]
        dc_scr[:] = dclast_ref[:]
        dw_scr[:] = jnp.zeros_like(dw_scr)
        db_scr[:] = jnp.zeros_like(db_scr)

    hdim = ct_ref.shape[-1]
    gates = gates_ref[0]
    i = gates[:, :hdim]
    f = gates[:, hdim : 2 * hdim]
    g = gates[:, 2 * hdim : 3 * hdim]
    o = gates[:, 3 * hdim :]
    c_tilde = ct_ref[0]
    c_prev = cprev_ref[0]
    h_prev = hprev_ref[0]
    m = mask_ref[0]

    dh = dh_scr[:] + dhs_ref[0]
    dc = dc_scr[:]
    tanh_ct = jnp.tanh(c_tilde)
    dh_tilde = m * dh
    dc_tilde = m * dc + dh_tilde * o * (1.0 - tanh_ct * tanh_ct)
    do = dh_tilde * tanh_ct
    di = dc_tilde * g
    dg = dc_tilde * i
    df = dc_tilde * c_prev
    # pre-activation grads
    dgi = di * i * (1.0 - i)
    dgf = df * f * (1.0 - f)
    dgg = dg * (1.0 - g * g)
    dgo = do * o * (1.0 - o)
    dgates = jnp.concatenate([dgi, dgf, dgg, dgo], axis=-1)

    dproj_ref[0] = dgates
    dh_prev = jnp.dot(
        dgates, whh_ref[:].T, preferred_element_type=jnp.float32
    ) + (1.0 - m) * dh
    dc_prev = dc_tilde * f + (1.0 - m) * dc
    dw_scr[:] = dw_scr[:] + jnp.dot(
        h_prev.T, dgates, preferred_element_type=jnp.float32
    )
    db_scr[:] = db_scr[:] + jnp.sum(dgates, axis=0)
    dh_scr[:] = dh_prev
    dc_scr[:] = dc_prev

    @pl.when(ti == nt - 1)
    def _finish():
        dw_ref[:] = dw_scr[:]
        db_ref[:] = db_scr[:]
        dh0_ref[:] = dh_scr[:]
        dc0_ref[:] = dc_scr[:]


def _lstm_fwd(proj_tm: Array, mask_tm: Array, w_hh: Array, bias: Array,
              h0: Array, c0: Array):
    t, b, h4 = proj_tm.shape
    h = h4 // 4
    f32 = jnp.float32
    args = (proj_tm.astype(f32), mask_tm.astype(f32), w_hh.astype(f32),
            bias.astype(f32), h0.astype(f32), c0.astype(f32))
    out_shape = (
        jax.ShapeDtypeStruct((t, b, h), f32),   # hs
        jax.ShapeDtypeStruct((t, b, 4 * h), f32),  # post-act gates
        jax.ShapeDtypeStruct((t, b, h), f32),   # c_tilde (pre-mask)
        jax.ShapeDtypeStruct((t, b, h), f32),   # c sequence (masked)
    )
    step_specs = lambda width: pl.BlockSpec((1, b, width), lambda i: (i, 0, 0))
    hs, gates, ct, cs = pl.pallas_call(
        _lstm_fwd_kernel,
        grid=(t,),
        in_specs=[
            step_specs(4 * h),                      # proj
            pl.BlockSpec((1, b, 1), lambda i: (i, 0, 0)),  # mask
            pl.BlockSpec((h, 4 * h), lambda i: (0, 0)),    # w_hh
            pl.BlockSpec((4 * h,), lambda i: (0,)),        # bias
            pl.BlockSpec((b, h), lambda i: (0, 0)),        # h0
            pl.BlockSpec((b, h), lambda i: (0, 0)),        # c0
        ],
        out_specs=(
            pl.BlockSpec((1, b, h), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, b, 4 * h), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, b, h), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, b, h), lambda i: (i, 0, 0)),
        ),
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((b, h), f32),
            pltpu.VMEM((b, h), f32),
        ],
        compiler_params=_vmem_params(lstm_vmem_bytes(b, h)),
        interpret=interpret_mode(),
        name="lstm_seq_fwd",
    )(*args)
    return hs, gates, ct, cs


@functools.partial(jax.custom_vjp)
def lstm_seq_fused(proj_tm: Array, mask_tm: Array, w_hh: Array, bias: Array,
                   h0: Array, c0: Array) -> Tuple[Array, Array, Array]:
    """Time-major fused LSTM: proj_tm [T,B,4H], mask_tm [T,B,1] →
    (hs [T,B,H], h_last, c_last)."""
    hs, gates, ct, cs = _lstm_fwd(proj_tm, mask_tm, w_hh, bias, h0, c0)
    return hs, hs[-1], cs[-1]


def _lstm_vjp_fwd(proj_tm, mask_tm, w_hh, bias, h0, c0):
    hs, gates, ct, cs = _lstm_fwd(proj_tm, mask_tm, w_hh, bias, h0, c0)
    # zero-size carriers: dtype objects aren't valid pytree leaves
    dtypes = tuple(jnp.zeros((0,), a.dtype) for a in (proj_tm, bias, h0, c0))
    res = (proj_tm.shape, dtypes, mask_tm, w_hh, h0, c0, hs, gates, ct, cs)
    return (hs, hs[-1], cs[-1]), res


def _lstm_vjp_bwd(res, grads):

    proj_shape, dtypes, mask_tm, w_hh, h0, c0, hs, gates, ct, cs = res
    dhs, dh_last, dc_last = grads
    t, b, h4 = proj_shape
    h = h4 // 4
    f32 = jnp.float32
    # grads on the hs output plus the explicit last-state grads
    dhs = dhs.astype(f32).at[-1].add(dh_last.astype(f32))

    # previous-step states (shift by one; cs is the masked cell sequence
    # the forward kernel saved — no reconstruction scan needed)
    h_prev = jnp.concatenate([h0.astype(f32)[None], hs[:-1]], axis=0)
    c_prev = jnp.concatenate([c0.astype(f32)[None], cs[:-1]], axis=0)

    rev = lambda i: (t - 1 - i, 0, 0)
    dproj, dw, db, dh0, dc0 = pl.pallas_call(
        _lstm_bwd_kernel,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, b, 4 * h), rev),   # gates
            pl.BlockSpec((1, b, h), rev),       # c_tilde
            pl.BlockSpec((1, b, h), rev),       # h_prev
            pl.BlockSpec((1, b, h), rev),       # c_prev
            pl.BlockSpec((1, b, 1), rev),       # mask
            pl.BlockSpec((h, 4 * h), lambda i: (0, 0)),  # w_hh
            pl.BlockSpec((1, b, h), rev),       # dhs
            pl.BlockSpec((b, h), lambda i: (0, 0)),  # dh_last → consumed via dhs[-1]; zeros
            pl.BlockSpec((b, h), lambda i: (0, 0)),  # dc_last
        ],
        out_specs=(
            pl.BlockSpec((1, b, 4 * h), rev),        # dproj
            pl.BlockSpec((h, 4 * h), lambda i: (0, 0)),
            pl.BlockSpec((4 * h,), lambda i: (0,)),
            pl.BlockSpec((b, h), lambda i: (0, 0)),
            pl.BlockSpec((b, h), lambda i: (0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((t, b, 4 * h), f32),
            jax.ShapeDtypeStruct((h, 4 * h), f32),
            jax.ShapeDtypeStruct((4 * h,), f32),
            jax.ShapeDtypeStruct((b, h), f32),
            jax.ShapeDtypeStruct((b, h), f32),
        ),
        scratch_shapes=[
            pltpu.VMEM((b, h), f32),
            pltpu.VMEM((b, h), f32),
            pltpu.VMEM((h, 4 * h), f32),
            pltpu.VMEM((4 * h,), f32),
        ],
        compiler_params=_vmem_params(lstm_vmem_bytes(b, h)),
        interpret=interpret_mode(),
        name="lstm_seq_bwd",
    )(
        gates, ct, h_prev, c_prev, mask_tm.astype(f32), w_hh.astype(f32),
        dhs, jnp.zeros((b, h), f32), dc_last.astype(f32),
    )
    proj_dt, bias_dt, h0_dt, c0_dt = (a.dtype for a in dtypes)
    # cotangent dtypes must match the primals (bf16 policy runs)
    return (dproj.astype(proj_dt), jnp.zeros_like(mask_tm),
            dw.astype(w_hh.dtype), db.astype(bias_dt),
            dh0.astype(h0_dt), dc0.astype(c0_dt))


lstm_seq_fused.defvjp(_lstm_vjp_fwd, _lstm_vjp_bwd)


# ===========================================================================
# GRU
# ===========================================================================


def _gru_fwd_kernel(proj_ref, mask_ref, wzr_ref, wc_ref, b_ref, h0_ref,
                    hs_ref, zrc_ref, h_scr):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_scr[:] = h0_ref[:]

    h = h_scr[:]
    hdim = h.shape[-1]
    p = proj_ref[0] + b_ref[:]
    rz = jnp.dot(h, wzr_ref[:], preferred_element_type=jnp.float32)
    z = _sig(p[:, :hdim] + rz[:, :hdim])
    r = _sig(p[:, hdim : 2 * hdim] + rz[:, hdim:])
    c = jnp.tanh(p[:, 2 * hdim :] + jnp.dot(
        r * h, wc_ref[:], preferred_element_type=jnp.float32
    ))
    h_tilde = (1.0 - z) * h + z * c
    m = mask_ref[0]
    h_new = m * h_tilde + (1.0 - m) * h
    zrc_ref[0] = jnp.concatenate([z, r, c], axis=-1)
    hs_ref[0] = h_new
    h_scr[:] = h_new


def _gru_bwd_kernel(zrc_ref, hprev_ref, mask_ref, wzr_ref, wc_ref,
                    dhs_ref, dhlast_ref,
                    dproj_ref, dwzr_ref, dwc_ref, db_ref, dh0_ref,
                    dh_scr, dwzr_scr, dwc_scr, db_scr):
    ti = pl.program_id(0)
    nt = pl.num_programs(0)

    @pl.when(ti == 0)
    def _init():
        dh_scr[:] = dhlast_ref[:]
        dwzr_scr[:] = jnp.zeros_like(dwzr_scr)
        dwc_scr[:] = jnp.zeros_like(dwc_scr)
        db_scr[:] = jnp.zeros_like(db_scr)

    hdim = hprev_ref.shape[-1]
    zrc = zrc_ref[0]
    z = zrc[:, :hdim]
    r = zrc[:, hdim : 2 * hdim]
    c = zrc[:, 2 * hdim :]
    h_prev = hprev_ref[0]
    m = mask_ref[0]

    dh = dh_scr[:] + dhs_ref[0]
    dht = m * dh  # grad into h_tilde
    dz = dht * (c - h_prev)
    dc = dht * z
    dgc = dc * (1.0 - c * c)  # pre-tanh candidate grad
    # candidate path: c = tanh(pc + (r*h) Wc)
    d_rh = jnp.dot(dgc, wc_ref[:].T, preferred_element_type=jnp.float32)
    dr = d_rh * h_prev
    dgz = dz * z * (1.0 - z)
    dgr = dr * r * (1.0 - r)
    dgzr = jnp.concatenate([dgz, dgr], axis=-1)

    dproj_ref[0] = jnp.concatenate([dgz, dgr, dgc], axis=-1)
    dh_prev = (
        dht * (1.0 - z)
        + d_rh * r
        + jnp.dot(dgzr, wzr_ref[:].T, preferred_element_type=jnp.float32)
        + (1.0 - m) * dh
    )
    dwzr_scr[:] = dwzr_scr[:] + jnp.dot(
        h_prev.T, dgzr, preferred_element_type=jnp.float32
    )
    dwc_scr[:] = dwc_scr[:] + jnp.dot(
        (r * h_prev).T, dgc, preferred_element_type=jnp.float32
    )
    db_scr[:] = db_scr[:] + jnp.sum(
        jnp.concatenate([dgz, dgr, dgc], axis=-1), axis=0
    )
    dh_scr[:] = dh_prev

    @pl.when(ti == nt - 1)
    def _finish():
        dwzr_ref[:] = dwzr_scr[:]
        dwc_ref[:] = dwc_scr[:]
        db_ref[:] = db_scr[:]
        dh0_ref[:] = dh_scr[:]


def _gru_fwd(proj_tm, mask_tm, w_hzr, w_hc, bias, h0):

    t, b, h3 = proj_tm.shape
    h = h3 // 3
    f32 = jnp.float32
    hs, zrc = pl.pallas_call(
        _gru_fwd_kernel,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, b, 3 * h), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, b, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((h, 2 * h), lambda i: (0, 0)),
            pl.BlockSpec((h, h), lambda i: (0, 0)),
            pl.BlockSpec((3 * h,), lambda i: (0,)),
            pl.BlockSpec((b, h), lambda i: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, b, h), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, b, 3 * h), lambda i: (i, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((t, b, h), f32),
            jax.ShapeDtypeStruct((t, b, 3 * h), f32),
        ),
        scratch_shapes=[pltpu.VMEM((b, h), f32)],
        compiler_params=_vmem_params(gru_vmem_bytes(b, h)),
        interpret=interpret_mode(),
        name="gru_seq_fwd",
    )(proj_tm.astype(f32), mask_tm.astype(f32), w_hzr.astype(f32),
      w_hc.astype(f32), bias.astype(f32), h0.astype(f32))
    return hs, zrc


@jax.custom_vjp
def gru_seq_fused(proj_tm, mask_tm, w_hzr, w_hc, bias, h0):
    """Time-major fused GRU: proj_tm [T,B,3H] (gate order z,r,c), mask
    [T,B,1] → (hs [T,B,H], h_last)."""
    hs, _ = _gru_fwd(proj_tm, mask_tm, w_hzr, w_hc, bias, h0)
    return hs, hs[-1]


def _gru_vjp_fwd(proj_tm, mask_tm, w_hzr, w_hc, bias, h0):
    hs, zrc = _gru_fwd(proj_tm, mask_tm, w_hzr, w_hc, bias, h0)
    dtypes = tuple(jnp.zeros((0,), a.dtype) for a in (proj_tm, bias, h0))
    return (hs, hs[-1]), (proj_tm.shape, dtypes, mask_tm, w_hzr, w_hc, h0, hs, zrc)


def _gru_vjp_bwd(res, grads):

    proj_shape, dtypes, mask_tm, w_hzr, w_hc, h0, hs, zrc = res
    dhs, dh_last = grads
    t, b, h3 = proj_shape
    h = h3 // 3
    f32 = jnp.float32
    dhs = dhs.astype(f32).at[-1].add(dh_last.astype(f32))
    h_prev = jnp.concatenate([h0.astype(f32)[None], hs[:-1]], axis=0)
    rev = lambda i: (t - 1 - i, 0, 0)
    dproj, dwzr, dwc, db, dh0 = pl.pallas_call(
        _gru_bwd_kernel,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, b, 3 * h), rev),
            pl.BlockSpec((1, b, h), rev),
            pl.BlockSpec((1, b, 1), rev),
            pl.BlockSpec((h, 2 * h), lambda i: (0, 0)),
            pl.BlockSpec((h, h), lambda i: (0, 0)),
            pl.BlockSpec((1, b, h), rev),
            pl.BlockSpec((b, h), lambda i: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, b, 3 * h), rev),
            pl.BlockSpec((h, 2 * h), lambda i: (0, 0)),
            pl.BlockSpec((h, h), lambda i: (0, 0)),
            pl.BlockSpec((3 * h,), lambda i: (0,)),
            pl.BlockSpec((b, h), lambda i: (0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((t, b, 3 * h), f32),
            jax.ShapeDtypeStruct((h, 2 * h), f32),
            jax.ShapeDtypeStruct((h, h), f32),
            jax.ShapeDtypeStruct((3 * h,), f32),
            jax.ShapeDtypeStruct((b, h), f32),
        ),
        scratch_shapes=[
            pltpu.VMEM((b, h), f32),
            pltpu.VMEM((h, 2 * h), f32),
            pltpu.VMEM((h, h), f32),
            pltpu.VMEM((3 * h,), f32),
        ],
        compiler_params=_vmem_params(gru_vmem_bytes(b, h)),
        interpret=interpret_mode(),
        name="gru_seq_bwd",
    )(zrc, h_prev, mask_tm.astype(f32), w_hzr.astype(f32), w_hc.astype(f32),
      dhs, jnp.zeros((b, h), f32))
    proj_dt, bias_dt, h0_dt = (a.dtype for a in dtypes)
    return (dproj.astype(proj_dt), jnp.zeros_like(mask_tm),
            dwzr.astype(w_hzr.dtype), dwc.astype(w_hc.dtype),
            db.astype(bias_dt), dh0.astype(h0_dt))


gru_seq_fused.defvjp(_gru_vjp_fwd, _gru_vjp_bwd)


# ===========================================================================
# Fused scaled-dot attention forward (ISSUE 9)
# ===========================================================================
#
# One pallas_call per batch row fuses the whole attention forward —
# scores = scale * q @ k^T, mask, numerically-stable softmax (f32), and the
# context matmul — so the [Tq, Tk] score/weight tensors live only in VMEM and
# never round-trip HBM between the four ops XLA would otherwise emit. The
# jnp path in ops/attention.dot_product_attention stays the CPU oracle (and
# the source of the backward below: the VJP recomputes the forward in jnp
# and differentiates it, so training through the fused op is exact-adjoint
# against the oracle while the kernel accelerates the forward).

# must equal ops/sequence.NEG_INF: a fully-masked row then degrades to the
# same uniform weights as the oracle instead of NaN
_ATTN_NEG_INF = -1e9


def _attn_fwd_kernel(q_ref, k_ref, v_ref, mask_ref, out_ref, *, scale):
    q = q_ref[0]  # [Tq, D] f32
    k = k_ref[0]  # [Tk, D]
    v = v_ref[0]  # [Tk, Dv]
    m = mask_ref[0]  # [Mq, Tk] 0/1, Mq in {1, Tq} (broadcast over rows)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    s = jnp.where(m > 0.0, s, _ATTN_NEG_INF)
    mx = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - mx)
    w = e / jnp.sum(e, axis=-1, keepdims=True)
    out_ref[0] = jnp.dot(w, v, preferred_element_type=jnp.float32)


def _attn_fwd(scale: float, q, k, v, mask):
    b, tq, d = q.shape
    tk = k.shape[1]
    dv = v.shape[2]
    mq = mask.shape[1]
    f32 = jnp.float32
    out = pl.pallas_call(
        functools.partial(_attn_fwd_kernel, scale=scale),
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, tq, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, tk, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, tk, dv), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, mq, tk), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, tq, dv), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, tq, dv), f32),
        interpret=interpret_mode(),
        name="attention_seq_fwd",
    )(q.astype(f32), k.astype(f32), v.astype(f32), mask.astype(f32))
    return out.astype(v.dtype)


def _attn_oracle(scale: float, q, k, v, mask):
    """The jnp reference this kernel must match — kept in lockstep with
    ops/attention.dot_product_attention (the public oracle); the fused op's
    backward is the exact vjp of THIS function."""
    logits = jnp.einsum("bqd,bkd->bqk", q, k) * scale
    logits = jnp.where(mask > 0.0, logits, _ATTN_NEG_INF)
    w = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(v.dtype)
    return jnp.einsum("bqk,bkv->bqv", w, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _attn_fused(scale: float, q, k, v, mask):
    return _attn_fwd(scale, q, k, v, mask)


def _attn_vjp_fwd(scale, q, k, v, mask):
    return _attn_fwd(scale, q, k, v, mask), (q, k, v, mask)


def _attn_vjp_bwd(scale, res, g):
    q, k, v, mask = res
    # recompute-in-backward: differentiate the jnp oracle (cheap VPU math
    # relative to storing [Tq, Tk] weights per row) — cotangents are the
    # oracle's exact adjoints, in the primals' dtypes
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _attn_oracle(scale, q_, k_, v_, mask), q, k, v
    )
    dq, dk, dv = vjp(g.astype(v.dtype))
    return dq, dk, dv, jnp.zeros_like(mask)


_attn_fused.defvjp(_attn_vjp_fwd, _attn_vjp_bwd)


def attention_seq_fused(q: Array, k: Array, v: Array, mask: Array,
                        scale: float) -> Array:
    """Fused scaled-dot attention forward: q [B,Tq,D], k [B,Tk,D],
    v [B,Tk,Dv], mask [B,Mq,Tk] (0/1 float; Mq in {1,Tq}) → [B,Tq,Dv] in
    v's dtype. `scale` must be a static Python float (it is folded into the
    kernel). Kernel math runs f32; softmax reductions are f32 regardless of
    the input dtype (the mixed-precision contract of ops/xent.py applied to
    attention weights)."""
    return _attn_fused(float(scale), q, k, v, mask.astype(jnp.float32))
