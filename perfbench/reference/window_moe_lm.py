"""A decoder of window and full attention layers with sigmoid-routed experts,
its full-context forward in plain jax.numpy: Arcee's Trinity block
(`afmoe`, https://huggingface.co/arcee-ai/Trinity-Mini) as paddle_tpu's
WindowMoELM serves it. float32 at `highest`, no cache, no paging, no kernel,
no sort:

    x = sqrt(hidden) * E[token]                   (mup_enabled)
    each layer: h = RMS(x); q, k, v, g = h Wq, h Wk, h Wv, h Wg
      q, k = RMS over each head's 128 (q_norm, k_norm); RoPE on window layers
      a = softmax(q k^T / sqrt(hd), causal; a window layer's key > query - W) v
          with each K/V head repeated for its query heads
      x += RMS((a * sigmoid(g)) Wo)
      x += RMS(SwiGLU(RMS(x))) on the first num_dense_layers, else
      x += RMS(sum over the top 8 of route_scale * s_e / sum s * SwiGLU_e + shared)
          with s = sigmoid(h W_r), chosen by s + bias
    logits = RMS(x) W_head

The experts are dense under the top-k mask, sixteen at a time. The forward
runs a layer at a time over blocks of rows (`logits_at`): each block's keys and
values first, then each block's attention against every key of the layer,
then its MLP, so that one request of 17,920 positions fits beside the
weights and each program compiles once for every length.

Departures from the published code, each also under the configuration's
`assumed`: it is written from memory of the public `afmoe` modelling code
(no network here): rotate-half RoPE over the whole head on window layers
only, the norms sandwiching attention and MLP, the gate a sigmoid of its own
projection multiplying the context before Wo, the bias selecting only.

Parameters are WindowMoELM's, read a layer at a time (`layer_weights`)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
F32 = jnp.float32

EVERY = ("attn_in_norm", "attn_out_norm", "mlp_in_norm", "mlp_out_norm", "q_norm",
         "k_norm", "wq", "wk", "wv", "wg", "wo")
EXPERT_GROUP = 16     # experts a product at a time
ROWS = 256            # rows a block


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale.astype(F32)


def _rope(x, positions, theta):
    """x [T, H, hd] at positions [T]: lane i rotates with lane i + hd/2."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None, None] * inv
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def windows(c: dict):
    """Each layer's window, 0 for a full layer."""
    return [int(c["sliding_window"]) if t == "sliding_attention" else 0 for t in c["layer_types"]]


MOE = ("router", "expert_bias", "moe_wi", "moe_wo", "shared_wi", "shared_wo")


def layer_weights(p, l, dense: bool, c: dict):
    """Layer l's weights; l may be traced (one program a kind: dense or not)."""
    w = {k: p[k][l] for k in EVERY}
    if dense:
        w.update(dense_wi=p["dense_wi"][l], dense_wo=p["dense_wo"][l])
    else:
        w.update({k: p[k][l - int(c["num_dense_layers"])] for k in MOE})
    return w


def _mm(a, m, cast):
    return jnp.matmul(cast(a), cast(m.astype(F32)), precision=HI)


def _swiglu(h, wi, wo, cast):
    a, b = jnp.split(_mm(h, wi, cast), 2, -1)
    return _mm(jax.nn.silu(a) * b, wo, cast)


def project(w, x, positions, c: dict, window: int, cast):
    """A block's q [T, H, hd], k, v [T, KV, hd] and gate [T, H*hd]."""
    eps, hd = float(c["rms_norm_eps"]), int(c["head_dim"])
    t = x.shape[0]
    h = _rms(x, w["attn_in_norm"], eps)
    q = _rms(_mm(h, w["wq"], cast).reshape(t, -1, hd), w["q_norm"], eps)
    k = _rms(_mm(h, w["wk"], cast).reshape(t, -1, hd), w["k_norm"], eps)
    v = _mm(h, w["wv"], cast).reshape(t, -1, hd)
    if window:
        theta = float(c["rope_theta"])
        q, k = _rope(q, positions, theta), _rope(k, positions, theta)
    return q, k, v, _mm(h, w["wg"], cast)


def attend(q, keys, values, positions, c: dict, window: int, cast):
    """q [T, H, hd] at positions [T] against every key [N, KV, hd] of the
    layer (position = index): causal, a window layer's last `window`."""
    group = q.shape[1] // keys.shape[1]
    k = jnp.repeat(keys, group, 1)
    v = jnp.repeat(values, group, 1)
    s = jnp.einsum("qhd,khd->hqk", cast(q), cast(k), precision=HI) / jnp.sqrt(F32(q.shape[-1]))
    back = positions[:, None] - jnp.arange(k.shape[0])[None, :]
    seen = (back >= 0) & ((back < window) if window else True)
    p = jax.nn.softmax(jnp.where(seen[None], s, -1e9), -1)
    return jnp.einsum("hqk,khd->qhd", cast(p), cast(v), precision=HI).reshape(q.shape[0], -1)


def experts(w, h, c: dict, cast):
    """The routed experts' sum over h [T, D], dense under the top-k mask, a
    group of EXPERT_GROUP experts a product."""
    logits = _mm(h, w["router"], cast)                                     # [T, E]
    s = jax.nn.sigmoid(logits)
    _, idx = lax.top_k(s + w["expert_bias"].astype(F32), int(c["num_experts_per_tok"]))
    chosen = jnp.sum(jax.nn.one_hot(idx, s.shape[-1], dtype=F32), 1)       # [T, E] of 0/1
    gate = float(c["route_scale"]) * s * chosen / (jnp.sum(s * chosen, -1, keepdims=True) + 1e-20)
    n = w["moe_wi"].shape[0]
    g = EXPERT_GROUP if n % EXPERT_GROUP == 0 else n

    def group(j, acc):
        wi = lax.dynamic_slice_in_dim(w["moe_wi"], j * g, g).astype(F32)
        wo = lax.dynamic_slice_in_dim(w["moe_wo"], j * g, g).astype(F32)
        a, b = jnp.split(jnp.einsum("td,edf->tef", cast(h), cast(wi), precision=HI), 2, -1)
        act = (jax.nn.silu(a) * b) * lax.dynamic_slice_in_dim(gate, j * g, g, 1)[..., None]
        return acc + jnp.einsum("tef,efd->td", cast(act), cast(wo), precision=HI)

    return lax.fori_loop(0, n // g, group, jnp.zeros(h.shape, F32))


def finish(w, x, a, g, c: dict, dense: bool, cast):
    """x [T, D] after its attention a [T, H*hd] with gate g, and the MLP."""
    eps = float(c["rms_norm_eps"])
    x = x + _rms(_mm(a * jax.nn.sigmoid(g), w["wo"], cast), w["attn_out_norm"], eps)
    h = _rms(x, w["mlp_in_norm"], eps)
    if dense:
        m = _swiglu(h, w["dense_wi"], w["dense_wo"], cast)
    else:
        m = experts(w, h, c, cast) + _swiglu(h, w["shared_wi"], w["shared_wo"], cast)
    return x + _rms(m, w["mlp_out_norm"], eps)


def embed(p, tokens, c: dict):
    scale = float(c["hidden_size"]) ** 0.5 if c.get("mup_enabled") else 1.0
    return p["embed"][tokens].astype(F32) * scale


def unembed_at(p, x, positions, c: dict, cast):
    """Logits [N, V] (float32) at `positions` [N] of x [T, D]."""
    return _mm(_rms(x[positions], p["final_norm"], float(c["rms_norm_eps"])), p["lm_head"], cast)


@functools.lru_cache(maxsize=None)
def _programs(c_items, cast):
    """The block programs of one configuration and cast, jitted once; the
    layer is an argument, its kind (window or not, dense or not) static."""
    c = dict(c_items)
    proj = jax.jit(lambda p, x, pos, l, window: project(
        {k: p[k][l] for k in EVERY}, x, pos, c, window, cast), static_argnums=4)
    att = jax.jit(lambda q, k, v, pos, window: attend(q, k, v, pos, c, window, cast),
                  static_argnums=4)
    fin = jax.jit(lambda p, x, a, g, l, dense: finish(
        layer_weights(p, l, dense, c), x, a, g, c, dense, cast), static_argnums=5)
    head = jax.jit(lambda p, x, pos: unembed_at(p, x, pos, c, cast))
    return proj, att, fin, head


def logits_at(p, tokens, n: int, positions, c: dict, cast, rows: int = ROWS):
    """Logits [N, V] at `positions` [N] of ONE sequence: its first n of
    `tokens` [T] (T a multiple of `rows`; what lies past n is never read by
    a position before it). A layer at a time, `rows` rows a block."""
    items = tuple(sorted((k, v) for k, v in c.items() if isinstance(v, (int, float, str, bool))))
    proj, att, fin, head = _programs(items, cast)
    t = tokens.shape[0]
    if t % rows:
        raise ValueError(f"a sequence of {t} positions is no multiple of {rows} rows")
    nd, kv, hd = int(c["num_dense_layers"]), int(c["num_key_value_heads"]), int(c["head_dim"])
    blocks = range(0, -(-n // rows) * rows, rows)
    x = embed(p, tokens, c)
    pos = jnp.arange(t, dtype=jnp.int32)
    for l, window in enumerate(windows(c)):
        keys = jnp.zeros((t, kv, hd), F32)
        values = jnp.zeros((t, kv, hd), F32)
        qg = {}
        for b in blocks:
            q, k, v, g = proj(p, x[b: b + rows], pos[b: b + rows], l, window)
            keys = lax.dynamic_update_slice_in_dim(keys, k, b, 0)
            values = lax.dynamic_update_slice_in_dim(values, v, b, 0)
            qg[b] = (q, g)
        out = []
        for b in blocks:
            q, g = qg.pop(b)
            a = att(q, keys, values, pos[b: b + rows], window)
            out.append(fin(p, x[b: b + rows], a, g, l, l < nd))
        x = jnp.concatenate(out + [x[len(out) * rows:]])
    return head(p, x, positions)
