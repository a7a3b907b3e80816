"""LoopedLM: a served decoder whose stack of layers runs several times.

The second served architecture (Ouro's: "Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741), through the same ServingSession,
scheduler, page pool and paged-attention kernel as ServableLM:

    x = E[token]
    for t in 0..T-1:                    # the SAME L layers' weights every pass
      for l in 0..L-1:
        a = RMS(x; ln1_l);  q, k, v = a wq_l, a wk_l, a wv_l
        q, k = RoPE(q, pos), RoPE(k, pos)      # whole head, rotate-half pairs
        K[t*L+l, pos], V[t*L+l, pos] = k, v    # a cache layer a (pass, layer)
        o = softmax(q K[t*L+l, <=pos]^T / sqrt(hd)) V[t*L+l, <=pos] wo_l
        x = x + RMS(o; ln2_l)                  # sandwich norm on the branch
        m = RMS(x; ln3_l);  u = (silu(m wg_l) * (m wu_l)) wd_l
        x = x + RMS(u; ln4_l)
      x = RMS(x; lnf)                   # closes every pass and feeds the next
    logits = x unembed                  # of the last pass: no early exit

The hidden state differs from pass to pass, so pass t's keys and values are
not pass t-1's: the cache is T * L layers deep (`cache_layers`), and a token
costs `2 * T * L * n_heads * head_dim` cache elements. The exit gate of the
published model changes no output at its published threshold of 1 and is not
built; per-token early exit and the paper's cache-sharing variants are other
configurations (PERF.md section 7).

Shape of the programs. T * L layer applications cannot be unrolled as
ServableLM's L are (a compile that grows with depth): each kind of weight is
STACKED `[L, ...]`, one layer body is traced, `lax.scan` runs it over the
stack and a second scan runs that over the passes. In `decode_step` the
pools ride in the carry and are written in place a cache layer at a time,
`at[t*L+l, page, offs]`, the form PagedLM.commit_prefill writes in; the
paged-attention kernel takes the cache layer as a traced scalar.

Precision is the configuration's, stated once (`dtype`): weights, pools and
the activations handed from op to op are of that type; every product
accumulates in float32 (`preferred_element_type`); the arithmetic inside
each RMS norm, the softmax and the rotation are float32; logits are float32.
With `dtype="float32"` (the CPU tests) the same code is a float32 model."""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.serving.model import (
    NEG_INF, POOL_LOGICAL_AXES, PagedLM, ServableLM,
)

Array = jax.Array

# the stacked parameters' logical axes, layer dimension first (never split)
_LAYER_AXES: Dict[str, Tuple[Optional[str], ...]] = {
    "wq": (None, "embed", "heads"),
    "wk": (None, "embed", "kv_heads"),
    "wv": (None, "embed", "kv_heads"),
    "wo": (None, "heads", "embed"),
    "wg": (None, "embed", "mlp"),
    "wu": (None, "embed", "mlp"),
    "wd": (None, "mlp", "embed"),
    "ln1": (None, "embed"),
    "ln2": (None, "embed"),
    "ln3": (None, "embed"),
    "ln4": (None, "embed"),
}


@dataclasses.dataclass(frozen=True)
class LoopedLMConfig:
    vocab: int
    n_layers: int = 3
    d_model: int = 64
    n_heads: int = 2
    head_dim: int = 32
    d_ff: int = 96
    ut_steps: int = 4
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    max_len: int = 512
    dtype: str = "bfloat16"
    bos_id: int = 1
    eos_id: int = 2


class LoopedLM(PagedLM):
    def __init__(self, cfg: LoopedLMConfig, mesh=None, rules=None):
        super().__init__(cfg, mesh=mesh, rules=rules)
        if cfg.head_dim % 2:
            raise ValueError(f"rotary positions pair a head's lanes: head_dim {cfg.head_dim} is odd")
        self.dtype = jnp.dtype(cfg.dtype)
        if self.mesh is not None and cfg.d_ff % self.tp_size:
            raise ValueError(
                f"tensor parallelism over {self.tp_size} chips needs d_ff % "
                f"{self.tp_size} == 0 (got d_ff={cfg.d_ff})"
            )

    # -- the cache this model needs -----------------------------------------
    @property
    def cache_layers(self) -> int:
        return self.cfg.ut_steps * self.cfg.n_layers

    @property
    def cache_dtype(self):
        return self.dtype

    # -- params -------------------------------------------------------------
    def param_logical_axes(self) -> Dict[str, Tuple[Optional[str], ...]]:
        return {
            "embed": ("vocab", "embed"),
            "lnf": ("embed",),
            "unembed": ("embed", "vocab"),
            **_LAYER_AXES,
        }

    def _shapes(self) -> Dict[str, Tuple[int, ...]]:
        c = self.cfg
        n, d, kd, f = c.n_layers, c.d_model, c.n_heads * c.head_dim, c.d_ff
        return {
            "embed": (c.vocab, d), "lnf": (d,), "unembed": (d, c.vocab),
            "wq": (n, d, kd), "wk": (n, d, kd), "wv": (n, d, kd), "wo": (n, kd, d),
            "wg": (n, d, f), "wu": (n, d, f), "wd": (n, f, d),
            "ln1": (n, d), "ln2": (n, d), "ln3": (n, d), "ln4": (n, d),
        }

    def init_params(self, rng: Array) -> Dict[str, Array]:
        """Norm scales 1, matrices normal at 1/sqrt(fan-in) (a stacked
        leaf's fan-in is its second dimension), the embedding at 1: keyed by
        name-stable fold_in, as ServableLM's."""
        p = {}
        for j, (name, shape) in enumerate(sorted(self._shapes().items())):
            if name.startswith("ln"):
                p[name] = jnp.ones(shape, self.dtype)
                continue
            std = 1.0 if name == "embed" else float(shape[-2]) ** -0.5
            w = std * jax.random.normal(jax.random.fold_in(rng, j), shape, jnp.float32)
            p[name] = w.astype(self.dtype)
        return p

    def save(self, path: str, params: Dict[str, Array]) -> None:
        """Canonical full arrays beside the architecture (`__arch__`, which
        `load_checkpoint` and the CLI dispatch on). numpy has no bfloat16:
        arrays are stored float32, which holds every bfloat16 exactly."""
        meta = {f"__{k}__": v for k, v in dataclasses.asdict(self.cfg).items()}
        np.savez(path, __arch__="looped_lm", **meta,
                 **{k: np.asarray(v, np.float32) for k, v in params.items()})

    @classmethod
    def load(cls, path: str, mesh=None, rules=None) -> Tuple["LoopedLM", Dict[str, Array]]:
        with np.load(path) as z:
            fields = {f.name: f.type for f in dataclasses.fields(LoopedLMConfig)}
            cast = {"int": int, "float": float, "str": str}
            cfg = LoopedLMConfig(**{
                k: cast[t](z[f"__{k}__"]) for k, t in fields.items()
            })
            dtype = jnp.dtype(cfg.dtype)
            params = {k: jnp.asarray(z[k], dtype) for k in z.files if not k.startswith("__")}
        return cls(cfg, mesh=mesh, rules=rules), params

    # -- the layer's pieces -------------------------------------------------
    def _rms(self, x: Array, scale: Array) -> Array:
        xf = x.astype(jnp.float32)
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + self.cfg.rms_eps)
        return (y * scale.astype(jnp.float32)).astype(x.dtype)

    def _mm(self, a: Array, w: Array) -> Array:
        """One product: float32 accumulation, handed on in the model's type."""
        return jnp.matmul(a, w, preferred_element_type=jnp.float32).astype(self.dtype)

    def _rope_table(self, pos: Array) -> Tuple[Array, Array]:
        """cos, sin [..., 1, head_dim / 2] (float32) of integer positions."""
        half = self.cfg.head_dim // 2
        inv = self.cfg.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
        ang = pos.astype(jnp.float32)[..., None, None] * inv
        return jnp.cos(ang), jnp.sin(ang)

    def _rope(self, x: Array, table: Tuple[Array, Array]) -> Array:
        """x [..., H * hd] rotated a head at a time, lane i paired with lane
        i + hd/2 (rotate-half), in float32."""
        cos, sin = table
        h = x.reshape(x.shape[:-1] + (-1, self.cfg.head_dim)).astype(jnp.float32)
        a, b = jnp.split(h, 2, -1)
        out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
        return out.reshape(x.shape).astype(x.dtype)

    def _layer(self, w: Dict[str, Array], x: Array, table, attend):
        """One layer. `attend(q, k, v)` gets the rotated q and k and v, each
        [..., H * hd], keeps or commits k and v as its program needs, and
        returns (context [..., H * hd], what it carries on)."""
        a = self._rms(x, w["ln1"])
        q = self._rope(self._mm(a, w["wq"]), table)
        k = self._rope(self._mm(a, w["wk"]), table)
        v = self._mm(a, w["wv"])
        ctx, kept = attend(q, k, v)
        # TP resharding points, as ServableLM's: the row-parallel wo and wd
        # all-reduce where their outputs are constrained replicated
        x = self._constrain(x + self._rms(self._mm(ctx, w["wo"]), w["ln2"]))
        m = self._rms(x, w["ln3"])
        u = self._mm(jax.nn.silu(self._mm(m, w["wg"])) * self._mm(m, w["wu"]), w["wd"])
        return self._constrain(x + self._rms(u, w["ln4"])), kept

    def _stack(self, params, x: Array, carry, layer_fn):
        """The looped stack: `layer_fn(w, x, carry, i) -> (x, carry, y)` over
        the L stacked layers, that over the T passes, the final norm closing
        each pass; i = t * L + l is the cache layer. Returns (x, carry, ys
        with a leading [T * L])."""
        n = self.cfg.n_layers
        stacked = {k: params[k] for k in _LAYER_AXES}

        def one_pass(state, t):
            def one_layer(state, lw):
                x, carry = state
                l, w = lw
                x, carry, y = layer_fn(w, x, carry, t * n + l)
                return (x, carry), y

            (x, carry), ys = jax.lax.scan(
                one_layer, state, (jnp.arange(n, dtype=jnp.int32), stacked)
            )
            return (self._rms(x, params["lnf"]), carry), ys

        (x, carry), ys = jax.lax.scan(
            one_pass, (x, carry), jnp.arange(self.cfg.ut_steps, dtype=jnp.int32)
        )
        ys = jax.tree.map(lambda y: y.reshape((-1,) + y.shape[2:]), ys)
        return x, carry, ys

    def _logits(self, params, x: Array) -> Array:
        # replicated float32 logits: the one all-gather under TP, sampling local
        return self._constrain(
            jnp.matmul(x, params["unembed"], preferred_element_type=jnp.float32)
        )

    # -- the two forwards ---------------------------------------------------
    def _context_forward(self, params, tokens: Array) -> Tuple[Array, Array, Array]:
        """Padded [B, T] tokens -> (logits [B, T, V] float32, kc, vc
        [T_ut * L, B, T, KD]): the whole-prompt prefill and forward_logits."""
        cfg = self.cfg
        b, t = tokens.shape
        h_, hd = cfg.n_heads, cfg.head_dim
        x = self._constrain(params["embed"][tokens])
        table = self._rope_table(jnp.arange(t)[None, :])
        causal = jnp.tril(jnp.ones((t, t), bool))

        def attend(q, k, v):
            qh, kh, vh = (a.reshape(b, t, h_, hd) for a in (q, k, v))
            s = jnp.einsum("bqhd,bkhd->bhqk", qh, kh,
                           preferred_element_type=jnp.float32) * self.scale
            w = jax.nn.softmax(jnp.where(causal[None, None], s, NEG_INF), -1)
            ctx = jnp.einsum("bhqk,bkhd->bqhd", w.astype(self.dtype), vh,
                             preferred_element_type=jnp.float32)
            return ctx.astype(self.dtype).reshape(b, t, -1), (k, v)

        def layer_fn(w, x, carry, i):
            x, kv = self._layer(w, x, table, attend)
            return x, carry, kv

        x, _, (kc, vc) = self._stack(params, x, (), layer_fn)
        return (
            self._logits(params, x),
            self._constrain(kc, None, None, None, "kv_heads"),
            self._constrain(vc, None, None, None, "kv_heads"),
        )

    def _chunk_forward(
        self,
        params,
        k_pages: Array,      # [T_ut * L, NP, PS, KD]
        v_pages: Array,
        tokens: Array,       # [1, C] int32
        starts: Array,       # [1] int32 — position of tokens[:, 0]
        block_rows: Array,   # [1, max_pages_per_seq] int32
    ) -> Tuple[Array, Array, Array]:
        """The chunk-shaped forward of `prefill_chunk` and `verify_chunk`:
        attention = (the slot's committed pages of THIS cache layer, masked
        to positions < start) ++ (causal within the chunk). The pools are
        only read; returns (logits [1, C, V], kc, vc [T_ut * L, 1, C, KD])."""
        cfg = self.cfg
        b, c = tokens.shape
        h_, hd = cfg.n_heads, cfg.head_dim
        ps = k_pages.shape[2]
        x = self._constrain(params["embed"][tokens])
        table = self._rope_table(starts[:, None] + jnp.arange(c)[None, :])
        t_ctx = block_rows.shape[1] * ps
        past = jnp.arange(t_ctx)[None, None, :] < starts[:, None, None]  # [1, 1, T_ctx]
        causal = jnp.tril(jnp.ones((c, c), bool))

        def layer_fn(w, x, carry, i):
            def attend(q, k, v):
                qh, kh, vh = (a.reshape(b, c, h_, hd) for a in (q, k, v))
                k_past = k_pages[i][block_rows].reshape(b, t_ctx, h_, hd)
                v_past = v_pages[i][block_rows].reshape(b, t_ctx, h_, hd)
                sp = jnp.einsum("bqhd,bkhd->bhqk", qh, k_past,
                                preferred_element_type=jnp.float32) * self.scale
                ss = jnp.einsum("bqhd,bkhd->bhqk", qh, kh,
                                preferred_element_type=jnp.float32) * self.scale
                s_all = jnp.concatenate([
                    jnp.where(past[:, None], sp, NEG_INF),
                    jnp.where(causal[None, None], ss, NEG_INF),
                ], -1)                                           # [1, H, C, T_ctx + C]
                wts = jax.nn.softmax(s_all, -1).astype(self.dtype)
                ctx = (
                    jnp.einsum("bhqk,bkhd->bqhd", wts[..., :t_ctx], v_past,
                               preferred_element_type=jnp.float32)
                    + jnp.einsum("bhqk,bkhd->bqhd", wts[..., t_ctx:], vh,
                                 preferred_element_type=jnp.float32)
                )
                return ctx.astype(self.dtype).reshape(b, c, -1), (k, v)

            x, kv = self._layer(w, x, table, attend)
            return x, carry, kv

        x, _, (kc, vc) = self._stack(params, x, (), layer_fn)
        return self._logits(params, x), kc, vc

    # -- the ONE decode executable ------------------------------------------
    def decode_step(
        self,
        params,
        k_pages: Array,      # [T_ut * L, NP, PS, KD] (donated)
        v_pages: Array,
        tokens: Array,       # [S] int32: each slot's last token
        positions: Array,    # [S] int32: that token's position
        active: Array,       # [S] bool
        block_table: Array,  # [S, max_pages_per_seq] int32
        seeds: Array,        # [S] uint32
        steps: Array,        # [S] int32
        temps: Array,        # [S] f32
        top_ks: Array,       # [S] int32
    ) -> Tuple[Array, Array, Array]:
        """One token for all slots at the fixed [max_slots] shape, as
        ServableLM.decode_step: every cache layer's step K/V goes into each
        active slot's current page (inactive slots dump into page 0), then
        the slot attends over its own pages of that cache layer through the
        `_paged_attention` seam, inside the scan, the pools in its carry."""
        ps = k_pages.shape[2]
        x = self._constrain(params["embed"][tokens])
        table = self._rope_table(positions)
        cur_page = jnp.take_along_axis(
            block_table, (positions // ps)[:, None], axis=1
        )[:, 0]
        cur_page = jnp.where(active, cur_page, 0)
        offs = positions % ps

        def layer_fn(w, x, pools, i):
            def attend(q, k, v):
                kp, vp = pools
                kp = kp.at[i, cur_page, offs].set(k)
                vp = vp.at[i, cur_page, offs].set(v)
                ctx = self._paged_attention(
                    q, kp, vp, block_table, positions, layer=i
                )
                return ctx, (kp, vp)

            x, pools = self._layer(w, x, table, attend)
            return x, pools, None

        x, (k_pages, v_pages), _ = self._stack(
            params, x, (k_pages, v_pages), layer_fn
        )
        next_tok = self._sample(self._logits(params, x), seeds, steps, temps, top_ks)
        return (
            self._constrain(k_pages, *POOL_LOGICAL_AXES),
            self._constrain(v_pages, *POOL_LOGICAL_AXES),
            next_tok,
        )


def load_checkpoint(path: str, mesh=None, rules=None):
    """(model, params) of a served checkpoint, whichever architecture its
    `.npz` records: `__arch__` names it, and a file without the key is a
    ServableLM's, as every checkpoint was before there were two."""
    with np.load(path) as z:
        arch = str(z["__arch__"]) if "__arch__" in z.files else "servable_lm"
    from paddle_tpu.serving.hybrid_moe_lm import HybridMoELM

    classes = {"servable_lm": ServableLM, "looped_lm": LoopedLM,
               "hybrid_moe_lm": HybridMoELM}
    if arch not in classes:
        raise ValueError(f"{path}: unknown served architecture {arch!r}; known: {sorted(classes)}")
    return classes[arch].load(path, mesh=mesh, rules=rules)
