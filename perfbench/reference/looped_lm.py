"""A looped decoder's full-context forward in plain jax.numpy: Ouro's block
("Scaling Latent Reasoning via Looped Language Models", arXiv:2510.25741;
https://huggingface.co/ByteDance/Ouro-2.6B) as paddle_tpu's LoopedLM serves
it. Token embedding; T passes over the SAME L layers, each layer
x += RMS(Attn(RMS(x))) then x += RMS(MLP(RMS(x))) (sandwich norms: a second
RMS norm on each branch's output), causal attention of n_heads heads with
rotary positions over the whole head (rotate-half pairing, lane i with lane
i + head_dim/2) and scale 1/sqrt(head_dim), a gated MLP
(silu(m Wg) * (m Wu)) Wd, no biases; the final RMS norm closes EVERY pass and
feeds the next; untied unembedding of the last pass. float32 at `highest`.

Departures from the published description, each also under the
configuration's `assumed`: the exit gate (a d -> 1 projection read after each
pass) is not built, because at the published early_exit_threshold of 1 no
pass exits early and it changes no output; what config.json does not key
(no biases, the sandwich norms, the final norm closing every pass, the
pairing of the rotation) is from the model's public modelling code and the
paper's architecture section, from memory.

No cache, no paging, no scan: every pass recomputes its own keys and values
from its own hidden states, which is what a cache entry per (pass, layer)
holds. Parameters are LoopedLM's: each kind of weight stacked `[L, ...]`,
read a layer at a time."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
F32 = jnp.float32


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale.astype(F32)


def _rope(x, positions, theta):
    """x [B, T, H, hd], positions [T]: lane i rotates with lane i + hd/2."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None, None] * inv          # [T, 1, hd/2]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def one_layer(w, x, n_heads: int, cast, theta: float, eps: float, kv=None):
    """One layer over x [B, T, D] (float32); `w` holds that layer's weights.
    Returns (x, its (k, v)). `kv`, for a test only: keys and values to
    attend over in place of the layer's own."""
    b, t, _ = x.shape

    def mm(a, m):
        return jnp.matmul(cast(a), cast(m.astype(F32)), precision=HI)

    causal = jnp.tril(jnp.ones((t, t), bool))
    pos = jnp.arange(t)
    a = _rms(x, w["ln1"], eps)
    q = mm(a, w["wq"]).reshape(b, t, n_heads, -1)
    k = mm(a, w["wk"]).reshape(b, t, n_heads, -1)
    v = mm(a, w["wv"]).reshape(b, t, n_heads, -1)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    own = (k, v)
    if kv is not None:
        k, v = kv
    s = jnp.einsum("bqhd,bkhd->bhqk", cast(q), cast(k), precision=HI) / jnp.sqrt(float(q.shape[-1]))
    p = jax.nn.softmax(jnp.where(causal[None, None], s, -1e9), -1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", cast(p), cast(v), precision=HI).reshape(b, t, -1)
    x = x + _rms(mm(ctx, w["wo"]), w["ln2"], eps)
    m = _rms(x, w["ln3"], eps)
    u = mm(jax.nn.silu(mm(m, w["wg"])) * mm(m, w["wu"]), w["wd"])
    return x + _rms(u, w["ln4"], eps), own


LAYER_KEYS = ("wq", "wk", "wv", "wo", "wg", "wu", "wd", "ln1", "ln2", "ln3", "ln4")


def layer_weights(p, l):
    return {k: p[k][l] for k in LAYER_KEYS}


def one_pass(p, x, n_heads: int, cast, theta: float, eps: float, kv=None):
    """The L layers once over x [B, T, D] (float32), then the final norm.
    Returns (x, this pass's (k, v) a layer)."""
    kept = []
    for l in range(p["wq"].shape[0]):
        x, own = one_layer(layer_weights(p, l), x, n_heads, cast, theta, eps,
                           None if kv is None else kv[l])
        kept.append(own)
    return final_norm(p, x, eps), kept


def final_norm(p, x, eps: float):
    return _rms(x, p["lnf"], eps)


def embed(p, tokens):
    return p["embed"][tokens].astype(F32)


def unembed_at(p, x, positions, cast):
    """Logits [B, N, V] (float32) at `positions` [B, N] of x [B, T, D]."""
    picked = jnp.take_along_axis(x, positions[..., None], axis=1)
    return jnp.matmul(cast(picked), cast(p["unembed"].astype(F32)), precision=HI)


def logits_at(p, tokens, positions, n_heads: int, ut_steps: int, cast,
              theta: float = 1e6, eps: float = 1e-6, share_cache: bool = False):
    """Logits [B, N, V] at `positions` [B, N] of `tokens` [B, T] after
    `ut_steps` passes. `share_cache`, for a test only: pass t attends over
    pass t-1's keys and values (a cache one pass deep), which is NOT the
    model."""
    x, kv = embed(p, tokens), None
    for _ in range(ut_steps):
        x, kept = one_pass(p, x, n_heads, cast, theta, eps, kv if share_cache else None)
        kv = kept
    return unembed_at(p, x, positions, cast)
