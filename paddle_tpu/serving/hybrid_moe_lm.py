"""HybridMoELM: a served decoder of Mamba-2 and attention layers, each with a
routed expert block of which this chip holds a part.

The third served architecture (IBM Granite 4.0-H's `granitemoehybrid`:
"Transformers are SSMs", arXiv:2405.21060, for the mixer), through the same
ServingSession, scheduler, page pool and paged-attention kernel as ServableLM
and LoopedLM, as ONE expert-parallel rank sees it:

    x = embedding_multiplier * E[token]
    for l in layers:
      h = RMS(x; ln1_l)
      h = Mamba2_l(h) if layer_types[l] == "mamba" else Attn_l(h)
      x = x + residual_multiplier * h
      h = RMS(x; ln2_l)
      x = x + residual_multiplier * (MoE_l(h) + Shared_l(h))
    logits = (RMS(x; lnf) E^T) / logits_scaling       # tied, over the slice held

    Mamba2(u):  [z | xBC | dt] = u W_in;  xBC = silu(conv1d(xBC));  [x | B | C] = xBC
                dt = softplus(dt + dt_bias);  S <- exp(dt A) S + dt x (outer) B
                y = S C + D x;  out = RMS(y * silu(z); g) W_out
    Attn(u):    n_heads query heads over n_kv_heads K/V heads, no position
                signal, softmax(q K^T * attention_multiplier) V
    MoE(h):     r = h W_r (float32);  idx, val = top_k(r);  g = softmax(val)
                sum over idx of g_e * (silu(a) * b) Wo_e,  [a | b] = h Wi_e
    Shared(h):  the same gated form, every token, ungated

Two kinds of per-request state. K/V of the attention layers live in the page
pool (`cache_layers` is the number of ATTENTION layers; `layer_passes`, what a
token costs, is every layer). Each Mamba layer keeps, a slot, its recurrent
state [H, P, N] float32 and the last K-1 inputs of its convolution: the model
declares them (`state_spec`), the session allocates them `[max_slots, ...]`
and carries them, donated, through `decode_step`, `prefill_chunk` and
`commit_prefill_state`; a prompt's final state is written whole into the slot
it is admitted to. A bucket-padded prompt leaves the state of its LAST REAL
token: padded positions run with dt = 0 and the convolution's tail is read at
the prompt's length. A recurrence has no page to trim or alias, so the session
refuses the prefix cache and speculation for a model that declares state.

The expert block (serving/moe.py, shared with WindowMoELM; softmax after
top-k here) is TOLD which experts it holds (`experts_held`). It routes over
all `num_experts_routed`, sorts the assignments by expert, and runs the held
experts' two products as grouped products (the Pallas grouped matmul, its
rows in VMEM whole or, past serving/moe.py's cut, in tiles) over the
assignments that landed here: no capacity, no dropped token. What the
absent experts would add is left out, and the shared MLP is counted once;
the counters of `counter_spec` (assignments by held expert, here and absent)
accumulate on the device in the carried state.

Shape of the programs. Each kind of weight is STACKED over the layers that
have it; a run of consecutive Mamba layers is one `lax.scan` over its slice
of the stacks (one traced body a run), an attention layer is traced on its
own. Precision is the configuration's (`dtype`): weights, pool and the
activations handed from op to op of that type; every product accumulates
float32; router logits, top-k and its softmax, softplus, exp(dt A), the state
and its update, every norm, the attention softmax and the logits float32."""

from __future__ import annotations

import dataclasses
import json
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops import mamba2
from paddle_tpu.serving import moe
from paddle_tpu.serving.model import NEG_INF, PagedLM

Array = jax.Array
F32 = jnp.float32

# which stack a layer's weight is read from, by the index it is read at
_EVERY = ("ln1", "ln2", "router", "sh_wi", "sh_wo")
# read WHOLE by every layer, the layer an argument (HybridMoELM._moe says why)
_EXPERTS = ("moe_wi", "moe_wo")
_MAMBA = ("m_in", "m_conv_w", "m_conv_b", "m_dt_bias", "m_a_log", "m_d",
          "m_norm", "m_out")
_ATTN = ("a_wq", "a_wk", "a_wv", "a_wo")
# kept float32 whatever `dtype`: three scalars a head
_FLOAT32 = ("m_dt_bias", "m_a_log", "m_d")


@dataclasses.dataclass(frozen=True)
class HybridMoEConfig:
    vocab: int
    layer_types: Tuple[str, ...] = ("mamba", "mamba", "attention", "mamba")
    d_model: int = 64
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    mamba_heads: int = 4
    mamba_head_dim: int = 16
    mamba_state: int = 16
    mamba_groups: int = 1
    mamba_conv: int = 4
    mamba_chunk: int = 256
    num_experts_routed: int = 8
    experts_held: Tuple[int, ...] = (0, 1, 2, 3)
    top_k: int = 3
    expert_width: int = 32
    shared_width: int = 64
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.0625
    logits_scaling: float = 16.0
    rms_eps: float = 1e-5
    max_len: int = 512
    dtype: str = "bfloat16"
    bos_id: int = 1
    eos_id: int = 2

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)


class HybridMoELM(PagedLM):
    def __init__(self, cfg: HybridMoEConfig, mesh=None, rules=None):
        if mesh is not None:
            raise ValueError(
                "HybridMoELM serves one expert-parallel rank on one chip: it "
                "is told the experts it holds and takes no mesh (placing "
                "experts over an `expert` mesh axis is not built)"
            )
        cfg = dataclasses.replace(
            cfg, layer_types=tuple(cfg.layer_types),
            experts_held=tuple(int(e) for e in cfg.experts_held),
        )
        super().__init__(cfg)
        unknown = set(cfg.layer_types) - {"mamba", "attention"}
        if unknown:
            raise ValueError(f"layer_types may hold 'mamba' and 'attention', not {sorted(unknown)}")
        if cfg.mamba_groups != 1:
            raise ValueError(f"one group of B and C a layer is built, not mamba_groups={cfg.mamba_groups}")
        if cfg.n_heads % cfg.n_kv_heads:
            raise ValueError(f"n_heads {cfg.n_heads} is no multiple of n_kv_heads {cfg.n_kv_heads}")
        held = cfg.experts_held
        if len(set(held)) != len(held) or not all(0 <= e < cfg.num_experts_routed for e in held):
            raise ValueError(f"experts_held {held} are not distinct ids below {cfg.num_experts_routed}")
        self.scale = float(cfg.attention_multiplier)   # data of the model, not 1/sqrt(hd)
        self.dtype = jnp.dtype(cfg.dtype)
        self.n_mamba = sum(t == "mamba" for t in cfg.layer_types)
        self.n_attn = cfg.n_layers - self.n_mamba
        if not self.n_mamba or not self.n_attn:
            raise ValueError(
                f"a hybrid stack has layers of both kinds, not {cfg.layer_types}")
        self.d_inner = cfg.mamba_heads * cfg.mamba_head_dim
        self.conv_dim = self.d_inner + 2 * cfg.mamba_groups * cfg.mamba_state
        # a routed expert's place among the held, len(held) where it is absent
        local = np.full(cfg.num_experts_routed, len(held), np.int32)
        local[list(held)] = np.arange(len(held), dtype=np.int32)
        self._local_of = local
        # runs of one kind: (kind, first layer, first index in its stack, count)
        self._runs: List[Tuple[str, int, int, int]] = []
        seen = {"mamba": 0, "attention": 0}
        for l, kind in enumerate(cfg.layer_types):
            last = self._runs[-1] if self._runs else None
            if kind == "mamba" and last is not None and last[0] == "mamba":
                self._runs[-1] = (kind, last[1], last[2], last[3] + 1)
            else:
                self._runs.append((kind, l, seen[kind], 1))
            seen[kind] += 1

    # -- what a request holds -----------------------------------------------
    @property
    def cache_layers(self) -> int:
        """Page-pool layers: the attention layers alone leave K/V."""
        return self.n_attn

    @property
    def cache_width(self) -> int:
        return self.cfg.n_kv_heads * self.cfg.head_dim

    @property
    def cache_dtype(self):
        return self.dtype

    @property
    def kv_group(self) -> int:
        return self.cfg.n_heads // self.cfg.n_kv_heads

    @property
    def layer_passes(self) -> int:
        return self.cfg.n_layers

    def state_spec(self) -> Dict[str, Tuple[Tuple[int, ...], object]]:
        c = self.cfg
        return {
            "ssm": ((self.n_mamba, c.mamba_heads, c.mamba_head_dim, c.mamba_state), F32),
            "conv": ((self.n_mamba, c.mamba_conv - 1, self.conv_dim), self.dtype),
        }

    def counter_spec(self) -> Dict[str, Tuple[Tuple[int, ...], object]]:
        """Accumulated on the device, wrapping at 2**32: read as differences.
        `moe_assignments[l]` is (landed on a held expert, went to an absent one)."""
        n = self.cfg.n_layers
        return {
            "moe_expert_tokens": ((n, len(self.cfg.experts_held)), jnp.uint32),
            "moe_assignments": ((n, 2), jnp.uint32),
        }

    # -- params -------------------------------------------------------------
    def param_logical_axes(self):
        return {name: (None,) * len(shape) for name, shape in self._shapes().items()}

    def _shapes(self) -> Dict[str, Tuple[int, ...]]:
        c = self.cfg
        n, m, a, d = c.n_layers, self.n_mamba, self.n_attn, c.d_model
        e, fe, fs = len(c.experts_held), c.expert_width, c.shared_width
        qd, kd = c.n_heads * c.head_dim, c.n_kv_heads * c.head_dim
        in_w = 2 * self.d_inner + 2 * c.mamba_groups * c.mamba_state + c.mamba_heads
        return {
            "embed": (c.vocab, d), "lnf": (d,),
            "ln1": (n, d), "ln2": (n, d), "router": (n, d, c.num_experts_routed),
            "moe_wi": (n, e, d, 2 * fe), "moe_wo": (n, e, fe, d),
            "sh_wi": (n, d, 2 * fs), "sh_wo": (n, fs, d),
            "m_in": (m, d, in_w), "m_conv_w": (m, c.mamba_conv, self.conv_dim),
            "m_conv_b": (m, self.conv_dim), "m_dt_bias": (m, c.mamba_heads),
            "m_a_log": (m, c.mamba_heads), "m_d": (m, c.mamba_heads),
            "m_norm": (m, self.d_inner), "m_out": (m, self.d_inner, d),
            "a_wq": (a, d, qd), "a_wk": (a, d, kd), "a_wv": (a, d, kd), "a_wo": (a, qd, d),
        }

    def param_dtype(self, name: str):
        return F32 if name in _FLOAT32 else self.dtype

    def init_params(self, rng: Array) -> Dict[str, Array]:
        """Norm scales 1, matrices normal at 1/sqrt(fan-in) (a stacked leaf's
        fan-in is its second-last dimension; the embedding's, a lookup's, is
        its rows), and the Mamba-2 paper's ranges for the recurrence: A
        uniform in [1, 16], dt_bias such that softplus of it is log-uniform
        in [1e-3, 1e-1], D 1."""
        p = {}
        for j, (name, shape) in enumerate(sorted(self._shapes().items())):
            key = jax.random.fold_in(rng, j)
            if name.startswith("ln") or name in ("m_norm", "m_d"):
                w = jnp.ones(shape, F32)
            elif name == "m_conv_b":
                w = jnp.zeros(shape, F32)
            elif name == "m_a_log":
                w = jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0))
            elif name == "m_dt_bias":
                dt = jnp.exp(jax.random.uniform(key, shape, F32, np.log(1e-3), np.log(1e-1)))
                w = dt + jnp.log(-jnp.expm1(-dt))        # softplus's inverse
            else:
                w = float(shape[-2]) ** -0.5 * jax.random.normal(key, shape, F32)  # embed's -2: its rows
            p[name] = w.astype(self.param_dtype(name))
        return p

    def save(self, path: str, params: Dict[str, Array]) -> None:
        """Canonical full arrays beside the architecture and its config (one
        JSON string). numpy has no bfloat16: arrays are stored float32."""
        np.savez(path, __arch__="hybrid_moe_lm",
                 __cfg__=json.dumps(dataclasses.asdict(self.cfg)),
                 **{k: np.asarray(v, np.float32) for k, v in params.items()})

    @classmethod
    def load(cls, path: str, mesh=None, rules=None):
        with np.load(path) as z:
            model = cls(HybridMoEConfig(**json.loads(str(z["__cfg__"]))), mesh=mesh, rules=rules)
            params = {k: jnp.asarray(z[k], model.param_dtype(k))
                      for k in z.files if not k.startswith("__")}
        return model, params

    # -- the layer's pieces -------------------------------------------------
    def _rms(self, x: Array, scale: Array) -> Array:
        xf = x.astype(F32)
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + self.cfg.rms_eps)
        return (y * scale.astype(F32)).astype(self.dtype)

    def _mm(self, a: Array, w: Array) -> Array:
        """One product: float32 accumulation, handed on in the model's type."""
        return jnp.matmul(a, w, preferred_element_type=F32).astype(self.dtype)

    def _gated(self, h: Array, wi: Array, wo: Array) -> Array:
        a, b = jnp.split(self._mm(h, wi), 2, -1)
        return self._mm(jax.nn.silu(a) * b, wo)

    def _weights_of(self, params, names, i) -> Dict[str, Array]:
        """Layer i's slice of each named stack; i may be traced (a scan's)."""
        return {k: jax.lax.dynamic_index_in_dim(params[k], i, 0, keepdims=False)
                for k in names}

    def _moe(self, w, h: Array, valid: Array):
        """h [T, D], valid [T]: serving/moe.py's expert block over this
        layer (`w["layer"]`) of the WHOLE stacks `w["moe_wi"]`,
        `w["moe_wo"]`, routed softmax after top-k."""
        return moe.expert_block(
            h, valid, w["router"], w["moe_wi"], w["moe_wo"], w["layer"],
            top_k=self.cfg.top_k, local_of=self._local_of, dtype=self.dtype)

    def _ffn(self, w, x: Array, valid: Array):
        """The second half of a layer over x [..., D]: (x, expert counts)."""
        h = self._rms(x, w["ln2"])
        flat = h.reshape(-1, h.shape[-1])
        moe, by_expert, where = self._moe(w, flat, valid.reshape(-1))
        both = moe.reshape(h.shape).astype(F32) + self._gated(h, w["sh_wi"], w["sh_wo"]).astype(F32)
        x = (x.astype(F32) + self.cfg.residual_multiplier * both).astype(self.dtype)
        return x, (by_expert, where)

    def _residual(self, x: Array, h: Array) -> Array:
        return (x.astype(F32) + self.cfg.residual_multiplier * h.astype(F32)).astype(self.dtype)

    def _mamba(self, w, u: Array, tail: Array, valid: Array, recur):
        """The Mamba-2 mixer over u [B, T, D] from the convolution's tail
        [B, K-1, C]; valid [B, T] marks the tokens, which lead each row.
        `recur(x, dt, a_neg, b, c)` runs the recurrence over x [B, T, H, P]
        from the state the caller holds and returns (y [B, T, H, P] float32,
        the state after it). Returns (out [B, T, D], new tail, that state)."""
        c = self.cfg
        bsz, t, _ = u.shape
        heads, p, n = c.mamba_heads, c.mamba_head_dim, c.mamba_state
        z, xbc, dt = jnp.split(
            self._mm(u, w["m_in"]), [self.d_inner, self.d_inner + self.conv_dim], -1)
        conv, tail = mamba2.causal_conv(
            xbc, tail, w["m_conv_w"], w["m_conv_b"], jnp.sum(valid, 1, dtype=jnp.int32))
        xbc = jax.nn.silu(conv).astype(self.dtype)
        x, b, cc = jnp.split(xbc, [self.d_inner, self.d_inner + n], -1)
        x = x.reshape(bsz, t, heads, p)
        dt = jax.nn.softplus(dt.astype(F32) + w["m_dt_bias"])
        dt = jnp.where(valid[..., None], dt, 0.0)                 # no token: decay 1, input 0
        y, state = recur(x, dt, -jnp.exp(w["m_a_log"]), b, cc)
        y = y + w["m_d"][:, None] * x.astype(F32)
        g = y.reshape(bsz, t, -1) * jax.nn.silu(z.astype(F32))    # the gate BEFORE the norm
        g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + c.rms_eps)
        return self._mm((g * w["m_norm"].astype(F32)).astype(self.dtype), w["m_out"]), tail, state

    @staticmethod
    def _ssm_decode(ssm: Array, m, x: Array, dt: Array, a_neg: Array, b: Array,
                    c: Array, active: Array):
        """One token a slot through Mamba layer m of the WHOLE stacked state
        ssm [S, M, H, P, N]: (y [S, H, P] float32, the stack with layer m
        advanced where `active`; a lane with no request keeps its state bit
        for bit). The Pallas kernel (ops/pallas/ssm_decode.py: one pass over
        the layer's state, written in place) when `pallas.enabled()`, else
        `mamba2.ssm_step` on the layer's slice, which is also the kernel's
        CPU ORACLE (tests/test_ssm_decode_kernel.py). Runs where a decode
        step is traced, so the counter moves once a call a trace."""
        from paddle_tpu.obs import metrics as obs_metrics
        from paddle_tpu.ops import pallas as _pallas

        if _pallas.enabled():
            from paddle_tpu.ops.pallas.ssm_decode import ssm_decode

            obs_metrics.observe_ssm_decode("kernel")
            return ssm_decode(ssm, m, x, dt, a_neg, b, c, active)
        obs_metrics.observe_ssm_decode("oracle")
        held = jax.lax.dynamic_index_in_dim(ssm, m, 1, keepdims=False)
        y, new = mamba2.ssm_step(held, x, dt, a_neg, b, c)
        new = jnp.where(active[:, None, None, None], new, held)
        return y, jax.lax.dynamic_update_index_in_dim(ssm, new, m, 1)

    def _split_heads(self, q: Array, k: Array, v: Array):
        c = self.cfg
        lead = q.shape[:-1]
        return (q.reshape(lead + (c.n_kv_heads, self.kv_group, c.head_dim)),
                k.reshape(k.shape[:-1] + (c.n_kv_heads, c.head_dim)),
                v.reshape(v.shape[:-1] + (c.n_kv_heads, c.head_dim)))

    def _stack(self, params, x: Array, carry, valid: Array, mamba_fn, attn_fn):
        """Every layer over x. `mamba_fn(w, h, carry, m)` and `attn_fn(w, h,
        carry, a)` are the mixers over the normed h, m and a the layer's index
        among its kind: each returns (h, carry, what to keep). Returns (x,
        carry, kept of the Mamba layers stacked [M, ...], kept of the
        attention layers [A, ...], expert counts [L, ...])."""
        kept = {"mamba": [], "attention": []}
        counts = []

        def layer(x, carry, l, i, names, mixer):
            w = self._weights_of(params, _EVERY, l)
            w.update(self._weights_of(params, names, i))
            w.update({k: params[k] for k in _EXPERTS}, layer=l)
            h, carry, keep = mixer(w, self._rms(x, w["ln1"]), carry, i)
            x, n = self._ffn(w, self._residual(x, h), valid)
            return x, carry, keep, n

        for kind, l0, i0, count in self._runs:
            if kind == "attention":
                for j in range(count):
                    x, carry, keep, n = layer(x, carry, l0 + j, i0 + j, _ATTN, attn_fn)
                    kept[kind].append(jax.tree.map(lambda y: y[None], keep))
                    counts.append(jax.tree.map(lambda y: y[None], n))
                continue

            def body(state, li):
                x, carry, keep, n = layer(*state, li[0], li[1], _MAMBA, mamba_fn)
                return (x, carry), (keep, n)

            (x, carry), (keep, n) = jax.lax.scan(
                body, (x, carry),
                (jnp.arange(l0, l0 + count, dtype=jnp.int32),
                 jnp.arange(i0, i0 + count, dtype=jnp.int32)),
            )
            kept[kind].append(keep)
            counts.append(n)

        def cat(parts):
            return jax.tree.map(lambda *ys: jnp.concatenate(ys), *parts)

        return x, carry, cat(kept["mamba"]), cat(kept["attention"]), cat(counts)

    def _embed(self, params, tokens: Array) -> Array:
        return (params["embed"][tokens].astype(F32) * self.cfg.embedding_multiplier).astype(self.dtype)

    def _logits(self, params, x: Array) -> Array:
        x = self._rms(x, params["lnf"])
        return jnp.einsum(
            "...d,vd->...v", x, params["embed"], preferred_element_type=F32
        ) / self.cfg.logits_scaling

    # -- the forward over a prompt or a chunk of one ------------------------
    def _forward(self, params, tokens: Array, n_valid: Array, tail: Array,
                 state: Array, past=None):
        """tokens [B, T] of which the first n_valid [B] are tokens, from the
        recurrent state (tail [B, M, K-1, C], state [B, M, H, P, N]) they
        continue. `past`, a chunk's: (k_pages, v_pages, block_rows, starts),
        the committed pages its attention also reads. Returns (x [B, T, D],
        {"ssm", "conv"} after the last token, kc, vc [A, B, T, KD], expert
        counts [L, ...])."""
        c = self.cfg
        bsz, t = tokens.shape
        valid = jnp.arange(t)[None, :] < n_valid[:, None]
        causal = jnp.tril(jnp.ones((t, t), bool))

        def mamba_fn(w, h, carry, m):
            def recur(x, dt, a_neg, b, cc):
                held = state[:, m]
                y, new = mamba2.ssd_chunked(x, dt, a_neg, b, cc, held, c.mamba_chunk)
                # rows with no token keep their state bit for bit
                return y, jnp.where(valid[:, :1, None, None], new, held)

            out, new_tail, new_state = self._mamba(w, h, tail[:, m], valid, recur)
            return out, carry, (new_state, new_tail)

        def attn_fn(w, h, carry, a):
            q, k, v = self._mm(h, w["a_wq"]), self._mm(h, w["a_wk"]), self._mm(h, w["a_wv"])
            qh, kh, vh = self._split_heads(q, k, v)
            s = jnp.einsum("bqcgd,bkcd->bcgqk", qh, kh, preferred_element_type=F32) * self.scale
            s = jnp.where(causal, s, NEG_INF)
            if past is None:
                wts = jax.nn.softmax(s, -1).astype(self.dtype)
                ctx = jnp.einsum("bcgqk,bkcd->bqcgd", wts, vh, preferred_element_type=F32)
            else:
                k_pages, v_pages, block_rows, starts = past
                t_ctx = block_rows.shape[1] * k_pages.shape[2]
                kp = k_pages[a][block_rows].reshape(bsz, t_ctx, c.n_kv_heads, c.head_dim)
                vp = v_pages[a][block_rows].reshape(bsz, t_ctx, c.n_kv_heads, c.head_dim)
                sp = jnp.einsum("bqcgd,bkcd->bcgqk", qh, kp, preferred_element_type=F32) * self.scale
                before = jnp.arange(t_ctx)[None, :] < starts[:, None]          # [B, T_ctx]
                sp = jnp.where(before[:, None, None, None, :], sp, NEG_INF)
                wts = jax.nn.softmax(jnp.concatenate([sp, s], -1), -1).astype(self.dtype)
                ctx = (
                    jnp.einsum("bcgqk,bkcd->bqcgd", wts[..., :t_ctx], vp, preferred_element_type=F32)
                    + jnp.einsum("bcgqk,bkcd->bqcgd", wts[..., t_ctx:], vh, preferred_element_type=F32)
                )
            ctx = ctx.astype(self.dtype).reshape(bsz, t, -1)
            return self._mm(ctx, w["a_wo"]), carry, (k, v)

        x, _, rec, kv, counts = self._stack(
            params, self._embed(params, tokens), (), valid, mamba_fn, attn_fn)
        new = {"ssm": jnp.moveaxis(rec[0], 0, 1), "conv": jnp.moveaxis(rec[1], 0, 1)}
        return x, new, kv[0], kv[1], counts

    def _fresh(self, bsz: int) -> Dict[str, Array]:
        return {k: jnp.zeros((bsz,) + shape, dtype)
                for k, (shape, dtype) in self.state_spec().items()}

    def forward_logits(self, params, tokens: Array) -> Array:
        """Causal forward over [B, T] tokens from the empty state -> logits
        [B, T, V] float32 (the sequential reference path of the tests)."""
        fresh = self._fresh(tokens.shape[0])
        n = jnp.full(tokens.shape[:1], tokens.shape[1], jnp.int32)
        x = self._forward(params, tokens, n, fresh["conv"], fresh["ssm"])[0]
        return self._logits(params, x)

    def _last_logits(self, params, x: Array, last: Array) -> Array:
        """Logits [B, V] at position last [B] of x [B, T, D]: the head runs
        over the one position a prompt samples at, not over the bucket."""
        return self._logits(params, jnp.take_along_axis(x, last[:, None, None], 1)[:, 0])

    @staticmethod
    def _counted(counts) -> Dict[str, Array]:
        return {"moe_expert_tokens": counts[0], "moe_assignments": counts[1]}

    def prefill(self, params, tokens, lengths, seeds, temps, top_ks):
        """Bucket-padded prompt forward from the empty state: (first_tok [B],
        kc, vc [A, B, T, KD], what `commit_prefill_state` writes: the state
        after each prompt's last token and the expert counts to add)."""
        fresh = self._fresh(tokens.shape[0])
        x, new, kc, vc, counts = self._forward(
            params, tokens, lengths, fresh["conv"], fresh["ssm"])
        first = self._sample(
            self._last_logits(params, x, lengths - 1),
            seeds, jnp.zeros_like(lengths), temps, top_ks)
        return first, kc, vc, dict(new, **self._counted(counts))

    def commit_prefill_state(self, k_pages, v_pages, state, kc, vc, new,
                             lengths, block_rows, starts, slots):
        """`commit_prefill`, and the prompts' final recurrent state written
        WHOLE into `slots` [B] (what the slot's last tenant left is gone),
        the counters added."""
        k_pages, v_pages = self.commit_prefill(
            k_pages, v_pages, kc, vc, lengths, block_rows, starts)
        return k_pages, v_pages, self._put(state, new, slots)

    def _put(self, state, new, slots):
        out = dict(state)
        for name in self.state_spec():
            out[name] = state[name].at[slots].set(new[name])
        for name in self.counter_spec():
            out[name] = state[name] + new[name]
        return out

    def prefill_chunk(self, params, k_pages, v_pages, state, tokens, starts,
                      lengths, block_rows, slots, seeds, temps, top_ks):
        """One [1, C] chunk of a long prompt, continuing the recurrent state
        the slot holds (the empty state where the chunk is the prompt's
        first) and attending over the slot's committed pages; K/V and the
        state after the chunk's last token commit here. Returns (k_pages,
        v_pages, state, tok [1], meaningful on the final chunk)."""
        c = tokens.shape[1]
        first = (starts == 0)
        held = {k: jnp.where(first.reshape((-1,) + (1,) * (state[k].ndim - 1)),
                             jnp.zeros((), state[k].dtype), state[k][slots])
                for k in self.state_spec()}
        x, new, kc, vc, counts = self._forward(
            params, tokens, jnp.clip(lengths - starts, 0, c), held["conv"], held["ssm"],
            past=(k_pages, v_pages, block_rows, starts))
        tok = self._sample(
            self._last_logits(params, x, jnp.clip(lengths - 1 - starts, 0, c - 1)),
            seeds, jnp.zeros_like(lengths), temps, top_ks)
        k_pages, v_pages = self.commit_prefill(
            k_pages, v_pages, kc, vc, lengths, block_rows, starts)
        return k_pages, v_pages, self._put(state, dict(new, **self._counted(counts)), slots), tok

    # -- the ONE decode executable ------------------------------------------
    def decode_step(self, params, k_pages, v_pages, state, tokens, positions,
                    active, block_table, seeds, steps, temps, top_ks):
        """One token for all slots at the fixed [max_slots] shape: each Mamba
        layer advances every active slot's state in place (a lane with no
        request, or one whose prompt is still being committed in chunks,
        keeps its own bit for bit), the attention layers write the step's K/V
        into each slot's current page and attend through the paged-attention
        seam. Returns (k_pages, v_pages, state, next_tok [S])."""
        ps = k_pages.shape[2]
        cur_page = jnp.take_along_axis(block_table, (positions // ps)[:, None], axis=1)[:, 0]
        cur_page = jnp.where(active, cur_page, 0)
        offs = positions % ps
        valid = active[:, None]

        def mamba_fn(w, h, carry, m):
            kp, vp, ssm, conv = carry

            def recur(x, dt, a_neg, b, cc):
                y, stack = self._ssm_decode(
                    ssm, m, x[:, 0], dt[:, 0], a_neg, b[:, 0], cc[:, 0], active)
                return y[:, None], stack

            out, tail, ssm = self._mamba(
                w, h, jax.lax.dynamic_index_in_dim(conv, m, 1, keepdims=False), valid, recur)
            conv = jax.lax.dynamic_update_index_in_dim(conv, tail, m, 1)
            return out, (kp, vp, ssm, conv), ()

        def attn_fn(w, h, carry, a):
            kp, vp, ssm, conv = carry
            h = h[:, 0]
            q, k, v = self._mm(h, w["a_wq"]), self._mm(h, w["a_wk"]), self._mm(h, w["a_wv"])
            kp = kp.at[a, cur_page, offs].set(k)
            vp = vp.at[a, cur_page, offs].set(v)
            ctx = self._paged_attention(q, kp, vp, block_table, positions, layer=a)
            return self._mm(ctx, w["a_wo"])[:, None], (kp, vp, ssm, conv), ()

        x, (k_pages, v_pages, ssm, conv), _, _, counts = self._stack(
            params, self._embed(params, tokens)[:, None],
            (k_pages, v_pages, state["ssm"], state["conv"]), valid, mamba_fn, attn_fn)
        next_tok = self._sample(self._logits(params, x[:, 0]), seeds, steps, temps, top_ks)
        new = dict(state, ssm=ssm, conv=conv)
        for name, n in self._counted(counts).items():
            new[name] = state[name] + n
        return k_pages, v_pages, new, next_tok

    def verify_chunk(self, *args, **kwargs):
        raise ValueError(
            "speculation rolls a rejected draft back by trimming pages; a "
            "recurrence keeps no state to roll back to")
