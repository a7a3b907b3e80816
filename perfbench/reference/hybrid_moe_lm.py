"""A hybrid Mamba-2 / attention decoder with routed experts, its full-context
forward in plain jax.numpy: IBM Granite 4.0-H's block (`granitemoehybrid`,
https://huggingface.co/ibm-granite/granite-4.0-h-small; the mixer is
Mamba-2's, "Transformers are SSMs", arXiv:2405.21060) as paddle_tpu's
HybridMoELM serves it, seen from ONE expert-parallel rank. Token embedding
times `embedding_multiplier`; each layer x += r * Mixer(RMS(x)) then
x += r * (MoE(RMS(x)) + Shared(RMS(x))) with r the `residual_multiplier`; the
final RMS norm; the tied head over `logits_scaling`. float32 at `highest`.

No cache, no paging, no chunking, no sort: the recurrence is a `lax.scan`
over tokens, the convolution four shifted adds, attention a full causal
softmax with each K/V head repeated for its query heads, the expert block
dense over the experts it is GIVEN under a top-k mask. One layer's function
at a time, so a caller can run a row and a layer at a time beside the
weights.

Departures from the published code, each also under the configuration's
`assumed`: the block is given the ids of the experts it holds, routes over
all of them as published, and leaves out what the absent experts would add
(the cut: the other rank's part; `held=None` is the uncut layer). The head
runs over the vocabulary slice held. What config.json does not key is from
the public modelling code and the paper, from memory: the gate multiplies
BEFORE the mixer's norm, which runs over all d_inner channels as one group;
the router's softmax is over the k chosen logits; A_log, dt_bias and D are
float32; no biases but the convolution's.

Parameters are HybridMoELM's: each kind of weight stacked over the layers
that have it, read a layer at a time (`layer_weights`)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
F32 = jnp.float32

EVERY = ("ln1", "ln2", "router", "moe_wi", "moe_wo", "sh_wi", "sh_wo")
MAMBA = ("m_in", "m_conv_w", "m_conv_b", "m_dt_bias", "m_a_log", "m_d", "m_norm", "m_out")
ATTN = ("a_wq", "a_wk", "a_wv", "a_wo")


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale.astype(F32)


def index_among_kind(layer_types, l: int) -> int:
    """Layer l's index in its own kind's stacks: the layers of that kind before it."""
    return sum(1 for t in layer_types[:l] if t == layer_types[l])


def layer_weights(p, l, i, kind: str):
    """Layer l's weights, float32: its slice of the stacks every layer has
    and slice i of its own kind's (`index_among_kind`). l and i may be
    traced: one program then serves every layer of a kind."""
    w = {k: p[k][l].astype(F32) for k in EVERY}
    w.update({k: p[k][i].astype(F32) for k in (MAMBA if kind == "mamba" else ATTN)})
    return w


def mamba_mixer(w, u, heads: int, head_dim: int, state: int, eps: float, mm):
    """u [T, D] -> [T, D]: the recurrence token by token from the empty state."""
    t = u.shape[0]
    d_in = heads * head_dim
    z, xbc, dt = jnp.split(mm(u, w["m_in"]), [d_in, 2 * d_in + 2 * state], -1)
    taps = w["m_conv_w"].shape[0]                       # tap K-1 on the current input
    padded = jnp.pad(xbc, [(taps - 1, 0), (0, 0)])
    conv = w["m_conv_b"] + sum(w["m_conv_w"][i] * padded[i: i + t] for i in range(taps))
    x, b, c = jnp.split(jax.nn.silu(conv), [d_in, d_in + state], -1)
    x = x.reshape(t, heads, head_dim)
    dt = jax.nn.softplus(dt + w["m_dt_bias"])           # [T, H]
    decay = jnp.exp(dt * -jnp.exp(w["m_a_log"]))

    def step(s, at):
        a_t, dt_t, x_t, b_t, c_t = at
        s = a_t[:, None, None] * s + (dt_t[:, None] * x_t)[..., None] * b_t
        return s, jnp.sum(s * c_t, -1)                  # [H, P]

    _, y = lax.scan(step, jnp.zeros((heads, head_dim, state), F32), (decay, dt, x, b, c))
    y = (y + w["m_d"][:, None] * x).reshape(t, d_in)
    return mm(_rms(y * jax.nn.silu(z), w["m_norm"], eps), w["m_out"])


def attention_mixer(w, u, n_heads: int, n_kv_heads: int, scale: float, mm, cast):
    """u [T, D] -> [T, D]: causal, no position signal, K/V heads repeated."""
    t = u.shape[0]
    q = mm(u, w["a_wq"]).reshape(t, n_heads, -1)
    k = jnp.repeat(mm(u, w["a_wk"]).reshape(t, n_kv_heads, -1), n_heads // n_kv_heads, 1)
    v = jnp.repeat(mm(u, w["a_wv"]).reshape(t, n_kv_heads, -1), n_heads // n_kv_heads, 1)
    s = jnp.einsum("qhd,khd->hqk", cast(q), cast(k), precision=HI) * scale
    p = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e9), -1)
    return mm(jnp.einsum("hqk,khd->qhd", cast(p), cast(v), precision=HI).reshape(t, -1), w["a_wo"])


def expert_block(w, h, top_k: int, held, mm, cast):
    """h [T, D] -> the part of the routed block that the experts GIVEN add
    (`held`: their ids among the router's outputs, in the order `moe_wi`
    holds them; None: moe_wi holds every expert), plus the shared MLP once."""
    logits = mm(h, w["router"])                                    # [T, E]
    val, idx = lax.top_k(logits, top_k)
    gate = jax.nn.softmax(val, -1)                                 # over the chosen
    dense = jnp.sum(jax.nn.one_hot(idx, logits.shape[-1], dtype=F32) * gate[..., None], 1)
    if held is not None:
        dense = dense[:, jnp.asarray(held)]                        # [T, held]
    a, b = jnp.split(jnp.einsum("td,edf->tef", cast(h), cast(w["moe_wi"]), precision=HI), 2, -1)
    out = jnp.einsum("tef,efd->ted", cast(jax.nn.silu(a) * b), cast(w["moe_wo"]), precision=HI)
    routed = jnp.sum(out * dense[..., None], 1)
    sa, sb = jnp.split(mm(h, w["sh_wi"]), 2, -1)
    return routed + mm(jax.nn.silu(sa) * sb, w["sh_wo"])


def one_layer(w, x, kind: str, c: dict, cast):
    """One layer over x [T, D] (float32); `w` that layer's weights, `c` the
    model's numbers (the configuration's keys, `experts_held` among them)."""

    def mm(a, m):
        return jnp.matmul(cast(a), cast(m), precision=HI)

    eps, r = float(c["rms_norm_eps"]), float(c["residual_multiplier"])
    u = _rms(x, w["ln1"], eps)
    if kind == "mamba":
        h = mamba_mixer(w, u, int(c["mamba_n_heads"]), int(c["mamba_d_head"]),
                        int(c["mamba_d_state"]), eps, mm)
    else:
        h = attention_mixer(w, u, int(c["num_attention_heads"]), int(c["num_key_value_heads"]),
                            float(c["attention_multiplier"]), mm, cast)
    x = x + r * h
    return x + r * expert_block(w, _rms(x, w["ln2"], eps), int(c["num_experts_per_tok"]),
                                c["experts_held"], mm, cast)


def embed(p, tokens, c: dict):
    return p["embed"][tokens].astype(F32) * float(c["embedding_multiplier"])


def unembed_at(p, x, positions, c: dict, cast):
    """Logits [N, V] (float32) at `positions` [N] of x [T, D]."""
    picked = _rms(x[positions], p["lnf"], float(c["rms_norm_eps"]))
    return jnp.matmul(cast(picked), cast(p["embed"].astype(F32)).T, precision=HI) / float(
        c["logits_scaling"])


def logits_at(p, tokens, positions, c: dict, cast):
    """Logits [N, V] at `positions` [N] of ONE sequence `tokens` [T]."""
    x = embed(p, tokens, c)
    for l, kind in enumerate(c["layer_types"]):
        w = layer_weights(p, l, index_among_kind(c["layer_types"], l), kind)
        x = one_layer(w, x, kind, c, cast)
    return unembed_at(p, x, positions, c, cast)
