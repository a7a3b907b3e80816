"""WindowMoELM: a served decoder of window and full attention layers, its
first layers dense and the rest sparse experts with a shared one.

The fourth served architecture (Arcee's Trinity, `afmoe`), through the same
ServingSession, scheduler, page pool and paged-attention kernel as the other
three:

    x = embedding_scale * E[token]
    for layer l:
      h = RMS(x; attn_in_l)
      q = h Wq, k = h Wk, v = h Wv (n_kv_heads), g = h Wg
      q, k = RMS_head(q; q_norm_l), RMS_head(k; k_norm_l)     # a head at a time
      window layer: q, k = RoPE(q, k; position)               # full layer: none
      a = softmax(q k^T / sqrt(hd) + mask) v   # query head j reads K/V head j // group;
                                               # a window layer sees its last W keys
      x = x + RMS((a * sigmoid(g)) Wo; attn_out_l)
      h = RMS(x; mlp_in_l)
      m = SwiGLU(h) for the first n_dense layers, else MoE(h) + SwiGLU_shared(h)
      x = x + RMS(m; mlp_out_l)
    logits = RMS(x; final) W_head                             # untied

MoE is serving/moe.py's block, routed by sigmoid scores with a selection-only
bias, normalised over the chosen and scaled by `route_scale`; this chip
holds every expert.

The cache has two kinds of layer (`cache_windows`): a full layer keeps every
page of the context in the session's pool, a window layer a RING of pages a
slot (serving/kv_cache.py), so what a window layer holds stops growing at
its window. Pools and block-table rows are pairs: (full, ring) pools, and
each row is the slot's P logical pages followed by its R ring entries.
Decode runs the paged-attention kernel over each (with the window for a
window layer); a chunk of a long prompt attends to the slot's earlier pages
in blocks, an online softmax carried from block to block (a full layer's
context is 17,920 positions at the served cell: one softmax over it would
be gigabytes), then to itself.

Weights are stacked by kind (attention and norms over every layer, the dense
MLP over the first layers, router, bias, experts and shared expert over the
rest). Precision is the configuration's `dtype`: weights, pools and the
activations handed from op to op; every product accumulates float32; the
router, the bias, top-k, every norm's arithmetic, RoPE, the softmax and the
logits float32. The expert counters of `counter_spec` ride the session's
carried state as HybridMoELM's do."""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.serving import moe
from paddle_tpu.serving.kv_cache import ring_pages
from paddle_tpu.serving.model import NEG_INF, PagedLM

Array = jax.Array
F32 = jnp.float32

WINDOW, FULL = "sliding_attention", "full_attention"
# float32 whatever `dtype`: the router's selection bias
_FLOAT32 = ("expert_bias",)
# positions of the past a chunk's attention takes at a time
PAST_BLOCK = 2048


@dataclasses.dataclass(frozen=True)
class WindowMoEConfig:
    vocab: int
    layer_types: Tuple[str, ...] = (WINDOW, WINDOW, FULL, WINDOW)
    d_model: int = 64
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    window: int = 16
    rope_theta: float = 1e4
    n_dense: int = 1
    dense_width: int = 96
    num_experts: int = 8
    top_k: int = 2
    expert_width: int = 32
    shared_width: int = 32
    route_scale: float = 1.0
    embedding_scale: float = 1.0
    rms_eps: float = 1e-5
    max_len: int = 512
    dtype: str = "bfloat16"
    bos_id: int = 1
    eos_id: int = 2

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)


class WindowMoELM(PagedLM):
    def __init__(self, cfg: WindowMoEConfig, mesh=None, rules=None):
        if mesh is not None:
            raise ValueError(
                "WindowMoELM serves on one chip: window layers' rings are not "
                "built under a mesh")
        cfg = dataclasses.replace(cfg, layer_types=tuple(cfg.layer_types))
        super().__init__(cfg)
        unknown = set(cfg.layer_types) - {WINDOW, FULL}
        if unknown:
            raise ValueError(f"layer_types may hold {WINDOW!r} and {FULL!r}, not {sorted(unknown)}")
        if cfg.n_heads % cfg.n_kv_heads:
            raise ValueError(f"n_heads {cfg.n_heads} is no multiple of n_kv_heads {cfg.n_kv_heads}")
        if not 0 <= cfg.n_dense < cfg.n_layers or cfg.window < 1:
            raise ValueError(f"n_dense {cfg.n_dense} of {cfg.n_layers} layers (one at least of "
                             f"experts), window {cfg.window}")
        self.scale = 1.0 / float(np.sqrt(cfg.head_dim))
        self.dtype = jnp.dtype(cfg.dtype)
        self.n_moe = cfg.n_layers - cfg.n_dense
        self._local_of = np.arange(cfg.num_experts, dtype=np.int32)   # every expert held
        # each layer's window (0: full), whether it rotates q and k, and its
        # index in its kind's pool
        self._windows = tuple(cfg.window if t == WINDOW else 0 for t in cfg.layer_types)
        self._rotary = tuple(t == WINDOW for t in cfg.layer_types)
        self._slot = tuple(
            sum(1 for t in cfg.layer_types[:l] if t == kind)
            for l, kind in enumerate(cfg.layer_types))

    # -- what a request holds -----------------------------------------------
    @property
    def cache_layers(self) -> int:
        return self.cfg.n_layers

    @property
    def cache_windows(self) -> Tuple[int, ...]:
        return self._windows

    @property
    def cache_width(self) -> int:
        return self.cfg.n_kv_heads * self.cfg.head_dim

    @property
    def cache_dtype(self):
        return self.dtype

    @property
    def kv_group(self) -> int:
        return self.cfg.n_heads // self.cfg.n_kv_heads

    def counter_spec(self) -> Dict[str, Tuple[Tuple[int, ...], object]]:
        """Accumulated on the device, wrapping at 2**32: read as differences.
        By MoE layer: tokens a held expert got, and (landed, absent)."""
        return {
            "moe_expert_tokens": ((self.n_moe, self.cfg.num_experts), jnp.uint32),
            "moe_assignments": ((self.n_moe, 2), jnp.uint32),
        }

    # -- params -------------------------------------------------------------
    def param_logical_axes(self):
        return {name: (None,) * len(shape) for name, shape in self._shapes().items()}

    def _shapes(self) -> Dict[str, Tuple[int, ...]]:
        c = self.cfg
        n, d, hd = c.n_layers, c.d_model, c.head_dim
        qd, kd = c.n_heads * hd, c.n_kv_heads * hd
        nd, ne, e = c.n_dense, self.n_moe, c.num_experts
        return {
            "embed": (c.vocab, d), "lm_head": (d, c.vocab), "final_norm": (d,),
            "attn_in_norm": (n, d), "attn_out_norm": (n, d), "mlp_in_norm": (n, d),
            "mlp_out_norm": (n, d), "q_norm": (n, hd), "k_norm": (n, hd),
            "wq": (n, d, qd), "wk": (n, d, kd), "wv": (n, d, kd), "wg": (n, d, qd),
            "wo": (n, qd, d),
            "dense_wi": (nd, d, 2 * c.dense_width), "dense_wo": (nd, c.dense_width, d),
            "router": (ne, d, e), "expert_bias": (ne, e),
            "moe_wi": (ne, e, d, 2 * c.expert_width), "moe_wo": (ne, e, c.expert_width, d),
            "shared_wi": (ne, d, 2 * c.shared_width), "shared_wo": (ne, c.shared_width, d),
        }

    def param_dtype(self, name: str):
        return F32 if name in _FLOAT32 else self.dtype

    def init_params(self, rng: Array) -> Dict[str, Array]:
        """Norm scales 1, the expert bias 0, matrices normal at 1/sqrt(fan-in)
        (a stacked leaf's fan-in is its second-last dimension), the
        embedding at 1/sqrt(d_model)."""
        p = {}
        for j, (name, shape) in enumerate(sorted(self._shapes().items())):
            if name.endswith("_norm"):
                w = jnp.ones(shape, F32)
            elif name == "expert_bias":
                w = jnp.zeros(shape, F32)
            else:
                fan_in = self.cfg.d_model if name == "embed" else shape[-2]
                w = float(fan_in) ** -0.5 * jax.random.normal(jax.random.fold_in(rng, j), shape, F32)
            p[name] = w.astype(self.param_dtype(name))
        return p

    # -- the layer's pieces -------------------------------------------------
    def _rms(self, x: Array, scale: Array) -> Array:
        xf = x.astype(F32)
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + self.cfg.rms_eps)
        return (y * scale.astype(F32)).astype(self.dtype)

    def _mm(self, a: Array, w: Array) -> Array:
        """One product: float32 accumulation, handed on in the model's type."""
        return jnp.matmul(a, w, preferred_element_type=F32).astype(self.dtype)

    def _swiglu(self, h: Array, wi: Array, wo: Array) -> Array:
        a, b = jnp.split(self._mm(h, wi), 2, -1)
        return self._mm(jax.nn.silu(a) * b, wo)

    def _rope(self, x: Array, pos: Array) -> Array:
        """x [..., H, hd] rotated at integer positions pos [...], lane i
        paired with lane i + hd/2 (rotate-half), in float32."""
        half = self.cfg.head_dim // 2
        inv = self.cfg.rope_theta ** (-jnp.arange(half, dtype=F32) / half)
        ang = pos.astype(F32)[..., None, None] * inv
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        a, b = jnp.split(x.astype(F32), 2, -1)
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1).astype(self.dtype)

    def _qkvg(self, params, l: int, x: Array, pos: Array):
        """Layer l's q [..., H, hd], k, v [..., KV, hd] and gate [..., H*hd]
        from x [..., D] at positions pos [...]: what the cache keeps is k
        normed (and rotated, in a window layer) and v."""
        c = self.cfg
        h = self._rms(x, params["attn_in_norm"][l])
        lead = h.shape[:-1]
        q = self._mm(h, params["wq"][l]).reshape(lead + (c.n_heads, c.head_dim))
        k = self._mm(h, params["wk"][l]).reshape(lead + (c.n_kv_heads, c.head_dim))
        v = self._mm(h, params["wv"][l]).reshape(lead + (c.n_kv_heads, c.head_dim))
        q, k = self._rms(q, params["q_norm"][l]), self._rms(k, params["k_norm"][l])
        if self._rotary[l]:
            q, k = self._rope(q, pos), self._rope(k, pos)
        return q, k, v, self._mm(h, params["wg"][l])

    def _attn_out(self, params, l: int, x: Array, ctx: Array, g: Array) -> Array:
        """x plus the normed output of the gated context ctx [..., H*hd]."""
        a = (ctx.astype(F32) * jax.nn.sigmoid(g.astype(F32))).astype(self.dtype)
        o = self._rms(self._mm(a, params["wo"][l]), params["attn_out_norm"][l])
        return (x.astype(F32) + o.astype(F32)).astype(self.dtype)

    def _mlp(self, params, l: int, x: Array, valid: Array):
        """x [..., D] plus layer l's normed MLP: (x, (tokens by expert [E],
        (landed, absent)), None for a dense layer)."""
        c = self.cfg
        h = self._rms(x, params["mlp_in_norm"][l])
        if l < c.n_dense:
            m, counts = self._swiglu(h, params["dense_wi"][l], params["dense_wo"][l]), None
        else:
            i = l - c.n_dense
            flat = h.reshape(-1, h.shape[-1])
            routed, by_expert, where = moe.expert_block(
                flat, valid.reshape(-1), params["router"][i], params["moe_wi"],
                params["moe_wo"], i, top_k=c.top_k, local_of=self._local_of,
                dtype=self.dtype, bias=params["expert_bias"][i], route_scale=c.route_scale)
            shared = self._swiglu(h, params["shared_wi"][i], params["shared_wo"][i])
            m = (routed.reshape(h.shape).astype(F32) + shared.astype(F32)).astype(self.dtype)
            counts = (by_expert, where)
        out = self._rms(m, params["mlp_out_norm"][l])
        return (x.astype(F32) + out.astype(F32)).astype(self.dtype), counts

    def _embed(self, params, tokens: Array) -> Array:
        return (params["embed"][tokens].astype(F32) * self.cfg.embedding_scale).astype(self.dtype)

    def _logits(self, params, x: Array) -> Array:
        return jnp.matmul(self._rms(x, params["final_norm"]), params["lm_head"],
                          preferred_element_type=F32)

    @staticmethod
    def _counted(counts) -> Dict[str, Array]:
        kept = [c for c in counts if c is not None]
        return {"moe_expert_tokens": jnp.stack([c[0] for c in kept]),
                "moe_assignments": jnp.stack([c[1] for c in kept])}

    # -- the cache's two kinds ----------------------------------------------
    def _ring(self, page_size: int) -> int:
        return ring_pages(self.cfg.window, page_size) if any(self._windows) else 0

    def _split(self, pools, rows: Array):
        """(full pool, ring pool, full rows, ring rows): the pair a pool and
        a table row are where the model has both kinds (kv_cache.py)."""
        if not isinstance(pools, tuple):
            return pools, None, rows, None
        r = self._ring(pools[0].shape[2])
        return pools[0], pools[1], rows[:, :-r], rows[:, -r:]

    def _commit(self, k_pages, v_pages, kc, vc, lengths, block_rows, starts):
        """Each kind's K/V [A, B, T, KD] (the layers in order) into its own
        pool: the full layers' through their pages, the window layers'
        through their rings."""
        kf, kw, full_rows, ring_rows = self._split(k_pages, block_rows)
        vf, vw = (v_pages if isinstance(v_pages, tuple) else (v_pages, None))
        full = [l for l, w in enumerate(self._windows) if not w]
        win = [l for l, w in enumerate(self._windows) if w]
        if full:
            kf, vf = self.commit_prefill(
                kf, vf, kc[jnp.asarray(full)], vc[jnp.asarray(full)], lengths, full_rows, starts)
        if not win:
            return kf, vf
        kw, vw = self.commit_prefill(
            kw, vw, kc[jnp.asarray(win)], vc[jnp.asarray(win)], lengths, ring_rows, starts,
            ring=ring_rows.shape[1])
        return (kf, kw), (vf, vw)

    # -- attention over a prompt or a chunk of one --------------------------
    def _segment(self, q: Array, k: Array, v: Array, mask: Array):
        """One segment of keys for an online softmax: q [B, T, KV, G, hd], k
        and v [B, S, KV, hd], mask [B, T, S]. Returns (max, sum, weighted
        values) [B, KV, G, T, 1 | 1 | hd], float32; a masked key weighs 0."""
        s = jnp.einsum("btcgd,bscd->bcgts", q, k, preferred_element_type=F32) * self.scale
        s = jnp.where(mask[:, None, None], s, NEG_INF)
        m = jnp.max(s, -1, keepdims=True)
        p = jnp.where(mask[:, None, None], jnp.exp(s - m), 0.0)
        acc = jnp.einsum("bcgts,bscd->bcgtd", p.astype(self.dtype), v, preferred_element_type=F32)
        return m, jnp.sum(p, -1, keepdims=True), acc

    @staticmethod
    def _merge(a, b):
        m = jnp.maximum(a[0], b[0])
        wa, wb = jnp.exp(a[0] - m), jnp.exp(b[0] - m)
        return m, a[1] * wa + b[1] * wb, a[2] * wa + b[2] * wb

    def _past(self, q, l: int, qpos, starts, kp, vp, rows, carry):
        """Fold the slot's committed positions before `starts` [B] into the
        online softmax `carry`: a window layer's from its ring [B, R] in one
        segment, a full layer's from its pages [B, P] a block at a time."""
        c, ps = self.cfg, kp.shape[2]
        b = rows.shape[0]
        i, window = self._slot[l], self._windows[l]

        def keys(pages):
            n = pages.shape[1] * ps
            return (kp[i][pages].reshape(b, n, c.n_kv_heads, c.head_dim),
                    vp[i][pages].reshape(b, n, c.n_kv_heads, c.head_dim))

        if window:
            r = rows.shape[1]
            top = ((starts - 1) // ps)[:, None]
            page = top - (top - jnp.arange(r)[None, :]) % r                # [B, R]
            at = (page[:, :, None] * ps + jnp.arange(ps)).reshape(b, -1)   # [B, R*PS]
            mask = (((at >= 0) & (at < starts[:, None]))[:, None, :]
                    & (at[:, None, :] > qpos[:, :, None] - window))
            return self._merge(carry, self._segment(q, *keys(rows), mask))
        n_pages = rows.shape[1]
        blk = max(1, min(n_pages, PAST_BLOCK // ps))

        def body(j, carry):
            j0 = jnp.minimum(j * blk, n_pages - blk)      # the last block may overlap
            pages = jax.lax.dynamic_slice_in_dim(rows, j0, blk, axis=1)
            at = ((j0 + jnp.arange(blk))[:, None] * ps + jnp.arange(ps)).reshape(-1)
            mask = (at[None, :] < starts[:, None]) & (at[None, :] >= j * blk * ps)
            return self._merge(carry, self._segment(q, *keys(pages), mask[:, None, :]))

        n = (jnp.max(starts) + blk * ps - 1) // (blk * ps)
        return jax.lax.fori_loop(0, n, body, carry)

    def _forward(self, params, tokens: Array, starts: Array, n_valid: Array, past=None):
        """tokens [B, T] at positions starts + [0, T), the first n_valid [B]
        of them tokens. `past`: a chunk's (k_pages, v_pages, block_rows), the
        slot's committed pages its attention also reads. Returns (x [B, T,
        D], kc, vc [L, B, T, KD], expert counts by MoE layer)."""
        c = self.cfg
        bsz, t = tokens.shape
        qpos = starts[:, None] + jnp.arange(t)[None, :]                   # [B, T]
        valid = jnp.arange(t)[None, :] < n_valid[:, None]
        back = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]            # q - k
        x = self._embed(params, tokens)
        kc, vc, counts = [], [], []
        for l in range(c.n_layers):
            q, k, v, g = self._qkvg(params, l, x, qpos)
            qh = q.reshape(bsz, t, c.n_kv_heads, self.kv_group, c.head_dim)
            own = (back >= 0) & ((back < self._windows[l]) if self._windows[l] else True)
            state = self._segment(qh, k, v, jnp.broadcast_to(own, (bsz, t, t)))
            if past is not None:
                k_pages, v_pages, rows = past
                kp, kw, full_rows, ring_rows = self._split(k_pages, rows)
                vp, vw = v_pages if isinstance(v_pages, tuple) else (v_pages, None)
                if self._windows[l]:
                    state = self._past(qh, l, qpos, starts, kw, vw, ring_rows, state)
                else:
                    state = self._past(qh, l, qpos, starts, kp, vp, full_rows, state)
            ctx = (state[2] / state[1]).astype(self.dtype)                 # [B, KV, G, T, hd]
            ctx = jnp.moveaxis(ctx, 3, 1).reshape(bsz, t, -1)
            x = self._attn_out(params, l, x, ctx, g)
            x, n = self._mlp(params, l, x, valid)
            kc.append(k.reshape(bsz, t, -1))
            vc.append(v.reshape(bsz, t, -1))
            counts.append(n)
        return x, jnp.stack(kc), jnp.stack(vc), counts

    def forward_logits(self, params, tokens: Array) -> Array:
        """Causal forward over [B, T] tokens from position 0 -> logits [B, T,
        V] float32 (the sequential reference path of the tests)."""
        b, t = tokens.shape
        zeros = jnp.zeros((b,), jnp.int32)
        x = self._forward(params, tokens, zeros, zeros + t)[0]
        return self._logits(params, x)

    def _last_logits(self, params, x: Array, last: Array) -> Array:
        return self._logits(params, jnp.take_along_axis(x, last[:, None, None], 1)[:, 0])

    def prefill(self, params, tokens, lengths, seeds, temps, top_ks):
        """Bucket-padded prompt forward: (first_tok [B], kc, vc [L, B, T, KD],
        the expert counts `commit_prefill_state` adds)."""
        x, kc, vc, counts = self._forward(params, tokens, jnp.zeros_like(lengths), lengths)
        first = self._sample(self._last_logits(params, x, lengths - 1),
                             seeds, jnp.zeros_like(lengths), temps, top_ks)
        return first, kc, vc, self._counted(counts)

    def commit_prefill_state(self, k_pages, v_pages, state, kc, vc, new,
                             lengths, block_rows, starts, slots):
        """The prompts' K/V into each kind's pool, the counters added."""
        k_pages, v_pages = self._commit(k_pages, v_pages, kc, vc, lengths, block_rows, starts)
        return k_pages, v_pages, {k: state[k] + new[k] for k in state}

    def prefill_chunk(self, params, k_pages, v_pages, state, tokens, starts,
                      lengths, block_rows, slots, seeds, temps, top_ks):
        """One [1, C] chunk of a long prompt, attending to the slot's
        committed positions (a window layer's last W of them) and within
        itself; its K/V commit here. Returns (k_pages, v_pages, state, tok
        [1], meaningful on the final chunk)."""
        c = tokens.shape[1]
        x, kc, vc, counts = self._forward(
            params, tokens, starts, jnp.clip(lengths - starts, 0, c),
            past=(k_pages, v_pages, block_rows))
        tok = self._sample(
            self._last_logits(params, x, jnp.clip(lengths - 1 - starts, 0, c - 1)),
            seeds, jnp.zeros_like(lengths), temps, top_ks)
        k_pages, v_pages = self._commit(k_pages, v_pages, kc, vc, lengths, block_rows, starts)
        new = self._counted(counts)
        return k_pages, v_pages, {k: state[k] + new[k] for k in state}, tok

    # -- the ONE decode executable ------------------------------------------
    def decode_step(self, params, k_pages, v_pages, state, tokens, positions,
                    active, block_table, seeds, steps, temps, top_ks):
        """One token for all slots at the fixed [max_slots] shape: each layer
        writes the step's K/V into the slot's page (a window layer's into
        its ring) and attends through the paged-attention seam, with the
        window where the layer has one. Returns (k_pages, v_pages, state,
        next_tok [S])."""
        c = self.cfg
        kf, kw, full_rows, ring_rows = self._split(k_pages, block_table)
        vf, vw = v_pages if isinstance(v_pages, tuple) else (v_pages, None)
        ps = kf.shape[2]
        s = tokens.shape[0]
        page = positions // ps
        at_full = jnp.take_along_axis(full_rows, jnp.minimum(page, full_rows.shape[1] - 1)[:, None], 1)[:, 0]
        at_full = jnp.where(active, at_full, 0)
        if ring_rows is not None:
            at_ring = jnp.take_along_axis(ring_rows, (page % ring_rows.shape[1])[:, None], 1)[:, 0]
            at_ring = jnp.where(active, at_ring, 0)
        offs = positions % ps
        x = self._embed(params, tokens)
        counts = []
        for l in range(c.n_layers):
            q, k, v, g = self._qkvg(params, l, x, positions)
            i, window = self._slot[l], self._windows[l]
            if window:
                kw = kw.at[i, at_ring, offs].set(k.reshape(s, -1))
                vw = vw.at[i, at_ring, offs].set(v.reshape(s, -1))
                ctx = self._paged_attention(q.reshape(s, -1), kw, vw, ring_rows, positions,
                                            layer=i, window=window)
            else:
                kf = kf.at[i, at_full, offs].set(k.reshape(s, -1))
                vf = vf.at[i, at_full, offs].set(v.reshape(s, -1))
                ctx = self._paged_attention(q.reshape(s, -1), kf, vf, full_rows, positions, layer=i)
            x = self._attn_out(params, l, x, ctx.astype(self.dtype), g)
            x, n = self._mlp(params, l, x, active)
            counts.append(n)
        next_tok = self._sample(self._logits(params, x), seeds, steps, temps, top_ks)
        new = self._counted(counts)
        pools = ((kf, kw), (vf, vw)) if ring_rows is not None else (kf, vf)
        return pools[0], pools[1], {k: state[k] + new[k] for k in state}, next_tok

    def verify_chunk(self, *args, **kwargs):
        raise ValueError(
            "speculation rolls a rejected draft back by trimming pages; a "
            "window layer's ring keeps no page to trim")
