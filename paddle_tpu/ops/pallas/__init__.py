"""Pallas TPU kernels for the hot fused ops (SURVEY §7: "Pallas kernels only
where fusion matters — LSTM/GRU step"; ISSUE 9 fused attention; ISSUE 11
ragged paged-attention decode, `paged_attention.py`).

Dispatch policy: `enabled()` is on when running on TPU (or when
PADDLE_TPU_PALLAS=1/interpret is forced); the lax.scan implementations in
ops/rnn.py and the jnp gather path in serving/model.py remain the oracles
and the path for exotic activations / peepholes / non-TPU backends / shapes
whose blocks do not fit VMEM. Which path runs is decided from the flag, the
backend and the shapes BEFORE the call: a backend that fails to start or a
kernel the compiler refuses raises — it is never caught to take the other
path."""

from __future__ import annotations

import os

import jax


def _flag() -> str:
    return os.environ.get("PADDLE_TPU_PALLAS", "auto").lower()


def enabled() -> bool:
    f = _flag()
    if f in ("0", "off", "false"):
        return False
    if f in ("1", "on", "true", "interpret"):
        return True
    return jax.default_backend() == "tpu"


def interpret_mode() -> bool:
    """Interpret on non-TPU backends so the same kernels are testable on CPU."""
    return _flag() == "interpret" or jax.default_backend() != "tpu"
