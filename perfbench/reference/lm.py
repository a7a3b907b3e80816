"""A decoder-only transformer's full-context forward in plain jax.numpy, as
ServableLM defines the model it serves (a configuration such as servable_lm_2048.json
lists where that departs from Pythia): token embedding + learned position
embedding; per layer x += Attn(RMSNorm(x)) then x += MLP(RMSNorm(x))
(sequential residual, pre-norm, eps 1e-6); causal multi-head attention with
scale 1/sqrt(head_dim); MLP d -> 4d -> d with tanh-approximated GELU and
biases; final RMSNorm; untied unembedding. float32 at `highest` precision.

No cache, no paging, no batching tricks: one forward over prompt + served
tokens gives the logits every served token was chosen from."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST


def _rms(x, scale):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * scale


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x ** 3)))


def hidden_states(p, tokens, n_layers: int, n_heads: int, cast):
    """tokens [B, T] -> final-normed hidden states [B, T, D]."""
    b, t = tokens.shape

    def mm(a, w):
        return jnp.matmul(cast(a), cast(w), precision=HI)

    x = p["embed"][tokens] + p["pos"][:t][None]
    d = x.shape[-1]
    hd = d // n_heads
    causal = jnp.tril(jnp.ones((t, t), bool))
    for i in range(n_layers):
        h = _rms(x, p[f"l{i}.ln1"])
        q = mm(h, p[f"l{i}.wq"]).reshape(b, t, n_heads, hd)
        k = mm(h, p[f"l{i}.wk"]).reshape(b, t, n_heads, hd)
        v = mm(h, p[f"l{i}.wv"]).reshape(b, t, n_heads, hd)
        s = jnp.einsum("bqhd,bkhd->bhqk", cast(q), cast(k), precision=HI) / jnp.sqrt(float(hd))
        s = jnp.where(causal[None, None], s, -1e9)
        w = jax.nn.softmax(s, -1)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", cast(w), cast(v), precision=HI).reshape(b, t, d)
        x = x + mm(ctx, p[f"l{i}.wo"])
        h = _rms(x, p[f"l{i}.ln2"])
        u = _gelu(mm(h, p[f"l{i}.w1"]) + p[f"l{i}.b1"])
        x = x + mm(u, p[f"l{i}.w2"]) + p[f"l{i}.b2"]
    return _rms(x, p["lnf"])


def logits_at(p, tokens, positions, n_layers: int, n_heads: int, cast):
    """Logits [B, N, V] (float32) at `positions` [B, N] of `tokens` [B, T]."""
    hs = hidden_states(p, tokens, n_layers, n_heads, cast)
    picked = jnp.take_along_axis(hs, positions[..., None], axis=1)
    return jnp.matmul(cast(picked), cast(p["unembed"]), precision=HI)
