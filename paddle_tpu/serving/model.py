"""ServableLM: a decode-oriented causal LM with paged attention.

The serving runtime is split the way TPU inference engines split it
("Ragged Paged Attention", PAPERS.md):

  * `prefill`     — full-context forward over a *bucket-padded* prompt
                    [B, T_bucket]; returns the first sampled token plus the
                    per-position K/V to commit into the page pool. One
                    executable per bucket (a handful, fixed up front).
  * `prefill_chunk` — ONE fixed-size chunk [1, C] of a long prompt: attends
                    over the slot's already-committed pages (positions <
                    chunk start) plus causally within the chunk, so a prompt
                    of any length is committed C tokens per engine step
                    interleaved with decode (ISSUE 11 chunked prefill; the
                    Orca-style continuous-batching refinement, PAPERS.md).
                    One executable for every prompt length.
  * `commit_prefill` — writes prompt K/V into the slot's pages at an
                    arbitrary `starts` offset (whole prompts, chunks and
                    speculation's verify share this one write), a layer
                    at a time into the donated pools. Never one scatter
                    over all layers: XLA:TPU gives a scatter whose window
                    spans the layer dim its operand pages-major, two
                    relayouts of each whole pool a call (PERF.md, PR 32).
  * `decode_step` — ONE token for ALL slots at the fixed [max_slots] shape:
                    write the step K/V into each slot's current page, gather
                    each slot's pages through its block-table row, masked
                    attention up to its own position. Sequence length, batch
                    occupancy and sequence age are data, not shape — the
                    whole serving lifetime runs this single executable.

On TPU the decode gather+softmax runs as the Pallas ragged paged-attention
kernel (ops/pallas/paged_attention.py) — the jnp gather path here stays the
CPU oracle, asserted equivalent in interpret mode (tests/test_decode_fastpath).
The kernel walks a slot in BLOCKS of B pages: its own async copies gather the
B physical pages the block table names into one [B*PS, KD] VMEM tile for K
and one for V, double-buffered from block to block and from slot to slot, and
the two products and the online-softmax update run once a block. A slot's
trip count is ceil(pages it holds / B), read from its position, so an empty
slot costs one block and a page past a slot's position is never fetched. B
comes from the shapes (page size, KD and the table's width against a VMEM
budget the kernel's file states; 8 pages = 128 tokens at 16 heads of 128 and
pages of 16) and is logged once a geometry. At that geometry, 20 of 32 slots
live over 796 pages, the 24 calls of one decode step take 8.7 ms where the
page-a-grid-step kernel before it took 25.2 ms (chip run, PR 30).

Sampling (ISSUE 11) happens ON DEVICE in every token-emitting executable:
`_sample` draws through a per-request key `fold_in(PRNGKey(seed), step)` with
per-slot temperature / top-k riding as DATA, so the one compiled decode
program serves greedy (temperature 0 — bitwise the old argmax) and sampled
requests side by side, and an engine-crash replay that reuses the request's
seed and step index regenerates bitwise-identical tokens (PR 10's
result-transparent restart extends to sampling).

Per-slot computation is strictly batched-independent (every einsum keeps the
slot dimension; no cross-slot reduction), which is what makes continuous
batching *bitwise* transparent: a request's tokens are identical whether it
ran alone or joined a full batch mid-stream (tests/test_serving.py).

Tensor parallelism (ISSUE 12) rides the named sharding-rules mesh
(parallel/rules.py): every parameter declares LOGICAL axes once
(`param_logical_axes`), the rules table maps them to the mesh `model` axis
(heads/kv_heads/mlp/vocab split, embed replicated), and the per-layer
resharding points carry `with_sharding_constraint`s so XLA's partitioner
emits exactly one all-reduce per row-parallel projection (wo, w2) and one
logits all-gather at the unembed output — sampling then runs on REPLICATED
logits, so the greedy branch stays collective-free and tokens are identical
to the single-chip oracle. The paged KV pool shards its kv_heads dim over
the same axis (per-chip pool bytes drop ~TPx), block tables stay replicated
host state, and `_paged_attention` runs per-shard over the LOCAL head slice
under shard_map — the Pallas kernel and the jnp gather oracle take the same
specs, so the CPU tests exercise the TP code structure bit-for-bit.
With no mesh (or model axis 1) every path is bitwise the PR-11 single-chip
program — TP support costs the one-chip deployment nothing.

All methods are pure functions of (params, inputs) — the serving session owns
jit + donation. The model is deliberately small-config-friendly (the repo's
CPU oracle discipline) but structurally a real transformer LM: pre-RMSNorm,
multi-head causal attention, GELU MLP, learned positions, tied nothing."""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

Array = jax.Array

NEG_INF = -1e9

# the paged KV pools' logical axes [n_layers, num_pages, page_size, kv_dim]:
# only the flattened (kv_heads * head_dim) dim shards, over the model axis
POOL_LOGICAL_AXES = (None, None, None, "kv_heads")


@dataclasses.dataclass(frozen=True)
class LMConfig:
    vocab: int
    n_layers: int = 2
    d_model: int = 64
    n_heads: int = 2
    max_len: int = 512
    bos_id: int = 1
    eos_id: int = 2

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads


def _rms(x: Array, scale: Array) -> Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * scale


def _kth_largest(x: Array, k: Array) -> Array:
    """The k-th largest of float32 x [V] (k traced, 1 <= k <= V), exactly
    the value `jnp.sort(x)[::-1][k - 1]` holds, found bit by bit over the
    floats' order as unsigned ints: 32 counts over x, no sort. A sort of a
    vocabulary of 200k took 32 s of each serving program's compile for v5e
    (ahead of time, without a chip); this takes a second."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    # order-preserving: negatives reversed below the positives
    key = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))

    def bit(i, t):
        cand = t | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        return jnp.where(jnp.sum(key >= cand) >= k, cand, t)

    t = jax.lax.fori_loop(0, 32, bit, jnp.uint32(0))
    back = jnp.where(t >> 31 == 1, t & jnp.uint32(0x7FFFFFFF), ~t)
    return jax.lax.bitcast_convert_type(back, jnp.float32)


class PagedLM:
    """What every served decoder shares and no architecture owns: placement
    on the TP mesh, on-device sampling, the three programs that are a
    forward plus a commit (`prefill`, `prefill_chunk`, `verify_chunk`), the
    in-place commit into the page pool and the paged-attention seam. A model
    derives from it and brings its parameters (`param_logical_axes`,
    `init_params`, `save` / `load`), its two forwards (`_context_forward`,
    `_chunk_forward`) and its `decode_step`; its config has `vocab`,
    `n_heads`, `head_dim`, `max_len`, `bos_id` and `eos_id`. The session asks
    the model for the cache it needs (`cache_layers`, `cache_width`,
    `cache_dtype`) and knows nothing else of its shape."""

    def __init__(self, cfg: LMConfig, mesh=None, rules=None):
        from paddle_tpu.parallel.rules import ShardingRules

        self.cfg = cfg
        self.scale = 1.0 / float(np.sqrt(cfg.head_dim))
        self.rules = rules if rules is not None else ShardingRules()
        self._axes_cache: Optional[Dict[str, Tuple[Optional[str], ...]]] = None
        # a mesh whose model axis is 1 (or absent) is the single-chip path:
        # drop it so every program stays bitwise the unsharded PR-11 one
        tp = int(dict(mesh.shape).get("model", 1)) if mesh is not None else 1
        self.mesh = mesh if tp > 1 else None
        if self.mesh is not None:
            for what, n in (("n_heads", cfg.n_heads), ("vocab", cfg.vocab)):
                if n % tp:
                    raise ValueError(
                        f"tensor parallelism over {tp} chips needs "
                        f"{what} % {tp} == 0 (got {what}={n}): heads and "
                        "vocab split over the mesh 'model' axis"
                    )

    @property
    def tp_size(self) -> int:
        return int(dict(self.mesh.shape)["model"]) if self.mesh is not None else 1

    # -- named sharding (ISSUE 12) ------------------------------------------
    def param_sharding(self, name: str, ndim: int):
        """One param's NamedSharding through the rules table, or None when
        there is no TP mesh (single-chip: the session device_puts plainly).
        A param MISSING from param_logical_axes raises: silently replicating
        it would quietly erode the per-chip memory win the table exists to
        deliver — same contract as the rules table's unknown-name error."""
        if self.mesh is None:
            return None
        axes = self.param_logical_axes().get(name)
        if axes is None:
            raise KeyError(
                f"param {name!r} has no entry in param_logical_axes — every "
                "tensor must declare its logical axes (use ('embed',)-style "
                "replicated entries explicitly, never by omission)"
            )
        return self.rules.sharding_for(self.mesh, axes, ndim=ndim, param=name)

    def shard_params(self, params: Dict[str, Array]) -> Dict[str, Array]:
        """Place params on the TP mesh per the rules (identity on 1 chip)."""
        if self.mesh is None:
            return jax.device_put(params)
        return {
            k: jax.device_put(v, self.param_sharding(k, jnp.ndim(v)))
            for k, v in params.items()
        }

    def pool_sharding(self):
        """The paged KV pools' placement: kv_heads (inside the flattened KD
        dim) over the model axis — per-chip pool bytes drop ~TPx. None on a
        single chip."""
        if self.mesh is None:
            return None
        return self.rules.sharding_for(
            self.mesh, POOL_LOGICAL_AXES, param="k_pages"
        )

    def _constrain(self, x: Array, *logical: Optional[str]) -> Array:
        """`with_sharding_constraint` through the rules table — the explicit
        resharding points that pin where the partitioner places collectives.
        Identity without a TP mesh, so single-chip programs are untouched."""
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, self.rules.sharding_for(self.mesh, logical, ndim=jnp.ndim(x))
        )

    # -- the cache this model needs (the session sizes the pool from it) ----
    @property
    def cache_layers(self) -> int:
        """Layers of the page pool: one for every K/V entry a token leaves
        (a looped stack leaves one a pass and layer)."""
        return self.cfg.n_layers

    @property
    def cache_width(self) -> int:
        """The pool's minor dimension, kv_heads * head_dim."""
        return self.cfg.n_heads * self.cfg.head_dim

    @property
    def cache_dtype(self):
        return jnp.float32

    @property
    def cache_windows(self) -> Tuple[int, ...]:
        """A window a cache layer (0: the layer attends to the whole
        context): the layers with one keep their pages in a ring a slot
        (serving/kv_cache.py), the pools and every block-table row then the
        pair (full, ring). No windows here."""
        return (0,) * self.cache_layers

    @property
    def kv_group(self) -> int:
        """Query heads that read one K/V head: the pool is `n_heads //
        kv_group` heads wide under a query of `n_heads`."""
        return 1

    @property
    def layer_passes(self) -> int:
        """Layer applications a token costs. Every one leaves a cache entry
        unless the model says otherwise (a layer that keeps no K/V)."""
        return self.cache_layers

    # -- what a request holds beside K/V (the session allocates and carries it)
    def state_spec(self) -> Dict[str, Tuple[Tuple[int, ...], object]]:
        """name -> (shape a SLOT, dtype) of per-request state that lives in
        no page (a recurrence's). None here: a model that declares some takes
        and returns it, as `state` behind `v_pages`, in `decode_step` and
        `prefill_chunk`, returns a prompt's from `prefill`, and writes that
        into a slot in `commit_prefill_state`."""
        return {}

    def counter_spec(self) -> Dict[str, Tuple[Tuple[int, ...], object]]:
        """name -> (shape, dtype) of counters the programs accumulate on the
        device, carried with the state and fetched only when read."""
        return {}

    # -- on-device sampling -------------------------------------------------
    def _sample(
        self,
        logits: Array,   # [B, V]
        seeds: Array,    # [B] uint32 per-request seed
        steps: Array,    # [B] int32 token index within the request (0 = first)
        temps: Array,    # [B] f32; 0 = greedy argmax (bitwise the old path)
        top_ks: Array,   # [B] int32; 0 = no top-k truncation
    ) -> Array:
        """Per-slot token sampling, batched-independent (vmap keeps the slot
        dimension, so a slot's token never depends on its batch-mates — the
        continuous-batching transparency contract extends to sampling). The
        key is `fold_in(PRNGKey(seed), step)`: a crash replay that re-runs
        (seed, step) draws the same gumbel noise, hence the same token.

        The sampled branch (per-slot full-vocab sort + gumbel draw) sits
        behind a lax.cond on `any(temps > 0)`: an all-greedy batch — the
        default serving config — skips it entirely at runtime, so sampling
        support costs the greedy decode hot loop nothing."""
        greedy = jnp.argmax(logits, -1).astype(jnp.int32)

        def _sampled(_):
            def one(lg, seed, step, temp, k):
                key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
                # top-k as a threshold: keep logits >= the k-th largest
                # (ties keep all — deterministic, no index shuffling)
                thr = _kth_largest(lg, jnp.clip(k, 1, lg.shape[-1]))
                keep = (k <= 0) | (lg >= thr)
                safe_t = jnp.where(temp > 0, temp, 1.0)
                z = jnp.where(keep, lg / safe_t, NEG_INF).astype(jnp.float32)
                # gumbel-max: argmax(z + g) ~ softmax(z) — one pass, no cumsum
                u = jax.random.uniform(key, lg.shape, jnp.float32, 1e-20, 1.0)
                return jnp.argmax(z - jnp.log(-jnp.log(u))).astype(jnp.int32)

            sampled = jax.vmap(one)(logits, seeds, steps, temps, top_ks)
            return jnp.where(temps > 0.0, sampled, greedy)

        return jax.lax.cond(
            jnp.any(temps > 0.0), _sampled, lambda _: greedy, operand=None
        )

    # -- full-context forward (prefill + the sequential reference path) -----
    def forward_logits(self, params, tokens: Array) -> Array:
        """Causal forward over padded [B, T] prompts -> logits [B, T, V].
        Padding positions produce garbage logits but cannot leak into valid
        ones: causal masking means position t only sees positions <= t, all
        of which are real tokens whenever t is — masking is positional, so
        no lengths argument exists (ISSUE 11 removed the dead parameter)."""
        return self._context_forward(params, tokens)[0]

    def prefill(
        self, params, tokens: Array, lengths: Array,
        seeds: Array, temps: Array, top_ks: Array,
    ) -> Tuple[Array, Array, Array]:
        """Bucket-padded prompt forward.

        tokens [B, T_bucket] int32, lengths [B] -> (first_tok [B] int32 —
        sampled on device at each prompt's last valid position (step 0 of
        the request's key; temperature 0 = greedy argmax), so the host never
        fetches a logits tensor — kc, vc [L, B, T, kv_dim] to commit)."""
        logits, kc, vc = self._context_forward(params, tokens)
        last = jnp.take_along_axis(
            logits, (lengths - 1)[:, None, None], axis=1
        )[:, 0]  # [B, V]
        first_tok = self._sample(
            last, seeds, jnp.zeros_like(lengths), temps, top_ks
        )
        return first_tok, kc, vc

    # -- chunked prefill (ISSUE 11) -----------------------------------------
    def prefill_chunk(
        self,
        params,
        k_pages: Array,      # [L, NP, PS, KD] (donated: chunk KV commits here)
        v_pages: Array,
        tokens: Array,       # [1, C] int32 — chunk tokens, zero-padded
        starts: Array,       # [1] int32 — chunk's first position
        lengths: Array,      # [1] int32 — the FULL prompt length
        block_rows: Array,   # [1, max_pages_per_seq] int32 — the slot's row
        seeds: Array,        # [1] uint32   (sampling: used on the final chunk)
        temps: Array,        # [1] f32
        top_ks: Array,       # [1] int32
    ) -> Tuple[Array, Array, Array]:
        """One C-token chunk of a long prompt: attention = (already-committed
        pages, masked to positions < start) ++ (causal within the chunk), so
        iterating chunks reproduces the whole-prompt causal forward exactly —
        the K/V committed per chunk equals the corresponding slice of
        `prefill`'s, and the final chunk's last-position logits equal the
        whole prompt's. ONE executable serves every prompt length (chunk
        geometry is fixed [1, C]; start/length are data).

        The chunk's K/V commits via `commit_prefill` INSIDE this program
        (pages donated in/out, the decode_step convention): reading and
        writing the pool in one executable lets XLA update it in place,
        where a separate commit dispatch would copy the whole pool — the
        donated input would still be pinned by this program's in-flight read.

        Returns (k_pages, v_pages, tok [1] int32 — sampled at position
        length-1, meaningful only on the final chunk; the host fetches it
        exactly once, there)."""
        b, c = tokens.shape
        logits, kc, vc = self._chunk_forward(
            params, k_pages, v_pages, tokens, starts, block_rows
        )
        # last valid position falls in this chunk only on the final chunk;
        # clamp keeps the index in range for the earlier ones (tok unused)
        last_in_chunk = jnp.clip(lengths - 1 - starts, 0, c - 1)
        last = jnp.take_along_axis(
            logits, last_in_chunk[:, None, None], axis=1
        )[:, 0]
        tok = self._sample(
            last, seeds, jnp.zeros_like(lengths), temps, top_ks
        )
        k_pages, v_pages = self.commit_prefill(
            k_pages, v_pages, kc, vc, lengths, block_rows, starts,
        )
        return k_pages, v_pages, tok

    # -- speculative decoding (ISSUE 16) ------------------------------------
    def verify_chunk(
        self,
        params,
        k_pages: Array,      # [L, NP, PS, KD] (donated: chunk KV commits here)
        v_pages: Array,
        tokens: Array,       # [1, K+1] int32: last committed token + K drafts
        starts: Array,       # [1] int32 — position of the last committed token
        block_rows: Array,   # [1, max_pages_per_seq] int32 — the slot's row
        seeds: Array,        # [1] uint32 — the request's sampling seed
        steps0: Array,       # [1] int32 — emitted-token index of sampled[0]
        temps: Array,        # [1] f32
        top_ks: Array,       # [1] int32
    ) -> Tuple[Array, Array, Array]:
        """Score K drafted tokens in ONE prefill-chunk-shaped call
        (prompt-lookup speculative decoding, ISSUE 16). The chunk is
        [last_token, draft_1..K] at positions [starts .. starts+K]: the
        logits at chunk position i are exactly what a sequential decode
        would see after emitting drafts 1..i, so `sampled[i]` is the token
        the model WOULD emit there — the host accepts draft_{i+1} while
        sampled[i] == draft_{i+1} and takes the first divergent token free.

        The replay/determinism contract is carried by `steps0`: position i
        samples through fold_in(PRNGKey(seed), steps0 + i) — keyed by the
        EMITTED TOKEN INDEX, never the engine step — so a crash replay or
        router failover that re-runs speculation from the prompt re-draws
        the same keys in the same order and regenerates bitwise-identical
        tokens even at temperature > 0.

        All K+1 positions' K/V commit into the slot's pages here (fused,
        pools donated — the prefill_chunk convention). Rejected positions
        leave stale K/V behind, which is harmless by construction: every
        attention mask excludes positions at/after the committed frontier
        (`ctx_idx < starts` here, `ctx_idx <= positions` in decode), and
        the next verify/decode step REWRITES each position before it can
        become visible. Returns (k_pages, v_pages, sampled [K+1] int32)."""
        b, c = tokens.shape
        logits, kc, vc = self._chunk_forward(
            params, k_pages, v_pages, tokens, starts, block_rows
        )
        lane = jnp.arange(c, dtype=jnp.int32)
        sampled = self._sample(
            logits[0],                                   # [K+1, V]
            jnp.broadcast_to(seeds, (c,)),
            steps0 + lane,                               # emitted-token index
            jnp.broadcast_to(temps, (c,)),
            jnp.broadcast_to(top_ks, (c,)),
        )
        # commit every chunk position (lengths = starts + K + 1): positions
        # past the slot's reserved pages fall through the block-table row's
        # zero entries into dump page 0, so over-speculation near the budget
        # end can never corrupt a neighbour
        k_pages, v_pages = self.commit_prefill(
            k_pages, v_pages, kc, vc, starts + c, block_rows, starts,
        )
        return k_pages, v_pages, sampled

    # -- page pool plumbing -------------------------------------------------
    def commit_prefill(
        self,
        k_pages: Array,  # [L, NP, PS, KD] (donated)
        v_pages: Array,
        kc: Array,  # [L, B, T, KD] from prefill / prefill_chunk
        vc: Array,
        lengths: Array,  # [B] — the FULL prompt length
        block_rows: Array,  # [B, max_pages_per_seq] int32
        starts: Array,  # [B] — position of kc[..., 0, :] (0 = whole prompt)
        ring: int = 0,
    ) -> Tuple[Array, Array]:
        """Write prompt K/V into the slots' pages at offset `starts`
        (whole-prompt prefill passes zeros; chunked prefill and speculation
        commit each chunk at its own offset). Positions past a prompt's
        length land in dump page 0 (never read unmasked).

        One scatter a LAYER, the form `decode_step` writes in, never one
        `at[:, page, offs]` over all of them: XLA:TPU lays a scatter's
        operand out with the indexed dims major, so a window that spans the
        layer dim costs two relayouts of each whole pool a call (four
        `copy f32[24,833,16,2048]`, 32-53 ms a prefill and 2.62 GB of
        temporaries at the served cell's geometry; PERF.md).

        `ring` R > 0: `block_rows` [B, R] are rings of pages (window layers,
        serving/kv_cache.py), logical page j written to entry j % R, and a
        position the same write would overwrite R pages later goes to the
        dump page instead: no two positions of one scatter share a place."""
        ps = k_pages.shape[2]
        l, b, t, kd = kc.shape
        pos = starts[:, None] + jnp.arange(t)[None, :]  # [B, T] absolute
        valid = pos < lengths[:, None]  # [B, T]
        if ring:
            last = jnp.minimum(lengths, starts + t)[:, None] - 1
            valid = valid & (pos > last - ring * ps)
            logical = (pos // ps) % ring
        else:
            logical = jnp.minimum(pos // ps, block_rows.shape[1] - 1)
        page = jnp.take_along_axis(block_rows, logical, axis=1)
        page = jnp.where(valid, page, 0).reshape(-1)  # [B*T]
        offs = (pos % ps).reshape(-1)
        kf = kc.reshape(l, b * t, kd)
        vf = vc.reshape(l, b * t, kd)

        def write_layer(i, pools):
            k, v = pools
            return k.at[i, page, offs].set(kf[i]), v.at[i, page, offs].set(vf[i])

        k_pages, v_pages = jax.lax.fori_loop(
            0, l, write_layer, (k_pages, v_pages)
        )
        # pool placement pinned at every producing seam: the scatters keep
        # the kv_heads dim sharded (indices touch page/offset dims only), so
        # donated pools round-trip their TP layout with no resharding
        return (
            self._constrain(k_pages, *POOL_LOGICAL_AXES),
            self._constrain(v_pages, *POOL_LOGICAL_AXES),
        )

    # -- the paged-attention seam ------------------------------------------
    def _paged_attention_local(
        self,
        q: Array,            # [S, KD_local] — this shard's head slice
        k_pages: Array,      # [L, NP, PS, KD_local] — the whole pool
        v_pages: Array,
        block_table: Array,  # [S, P]
        positions: Array,    # [S]
        layer,               # int, or a traced scalar inside a scanned stack
        n_heads: int,
        group: int = 1,
        window: int = 0,
    ) -> Array:
        """Ragged paged attention over `n_heads` heads of layer `layer` (the
        FULL head count on one chip; the LOCAL slice per shard under TP —
        heads are batched-independent, so the per-shard math is bitwise the
        single-chip math for those heads). `group` query heads read each K/V
        head (query head j reads K/V head j // group): q is then `group`
        times as wide as the pool.

        Two numerically-equivalent paths behind one seam: the Pallas kernel
        (ops/pallas/paged_attention.py — the block table names the pages its
        copies gather, B a block and only those a slot holds, online f32
        softmax in VMEM) when `pallas.enabled()`
        (TPU, or PADDLE_TPU_PALLAS=1/interpret), else the dense jnp gather —
        which is also the kernel's CPU ORACLE: interpret-mode equality across
        mixed lengths/block tables is pinned in tests/test_decode_fastpath.

        `window` W > 0: each slot attends to its last W positions through
        `block_table` [S, R], its ring of pages (serving/kv_cache.py).
        Either path counts itself where it is traced
        (`paddle_tpu_paged_attention_decode_total`, by path and window)."""
        from paddle_tpu.obs import metrics as obs_metrics
        from paddle_tpu.ops import pallas as _pallas

        s = q.shape[0]
        h_, hd = n_heads, self.cfg.head_dim
        if _pallas.enabled():
            from paddle_tpu.ops.pallas.paged_attention import (
                paged_attention_decode,
            )

            obs_metrics.observe_paged_attention_decode("kernel", window)
            return paged_attention_decode(
                q, k_pages, v_pages, block_table, positions,
                layer=layer, scale=self.scale, n_heads=h_, group=group,
                window=window,
            ).astype(q.dtype)
        obs_metrics.observe_paged_attention_decode("oracle", window)
        ps = k_pages.shape[2]
        if group > 1 or window:
            return self._grouped_attention_oracle(
                q, k_pages[layer][block_table], v_pages[layer][block_table],
                positions, h_ // group, group, window,
            )
        qh = q.reshape(s, h_, hd)
        # dense gather: [S, P, PS, KD] -> [S, T_ctx, H, hd]
        k_seq = k_pages[layer][block_table].reshape(s, -1, h_, hd)
        v_seq = v_pages[layer][block_table].reshape(s, -1, h_, hd)
        ctx_idx = jnp.arange(block_table.shape[1] * ps)
        att_mask = ctx_idx[None, :] <= positions[:, None]  # [S, T_ctx]
        # float32 accumulation whatever the pool holds (a no-op on float32)
        sc = jnp.einsum(
            "shd,sthd->sht", qh, k_seq, preferred_element_type=jnp.float32
        ) * self.scale
        sc = jnp.where(att_mask[:, None, :], sc, NEG_INF)
        w = jax.nn.softmax(sc.astype(jnp.float32), -1).astype(q.dtype)
        return jnp.einsum(
            "sht,sthd->shd", w, v_seq, preferred_element_type=jnp.float32
        ).astype(q.dtype).reshape(s, -1)

    def _grouped_attention_oracle(self, q, k_seq, v_seq, positions, n_kv, group,
                                  window=0):
        """The dense gather path with `group` query heads a K/V head: k_seq,
        v_seq [S, P, PS, n_kv * hd] the slot's gathered pages; with a window,
        [S, R, PS, ...] its ring, entry r holding the latest logical page j
        <= pos // PS with j % R == r."""
        s, hd = q.shape[0], self.cfg.head_dim
        ps = k_seq.shape[2]
        qh = q.reshape(s, n_kv, group, hd)
        k_seq = k_seq.reshape(s, -1, n_kv, hd)
        v_seq = v_seq.reshape(s, -1, n_kv, hd)
        if window:
            ring = k_seq.shape[1] // ps
            top = (positions // ps)[:, None]
            page = top - (top - jnp.arange(ring)[None, :]) % ring          # [S, R]
            at = (page[:, :, None] * ps + jnp.arange(ps)).reshape(s, -1)   # [S, R*PS]
            seen = ((at >= 0) & (at <= positions[:, None])
                    & (at > positions[:, None] - window))
        else:
            seen = jnp.arange(k_seq.shape[1])[None, :] <= positions[:, None]
        sc = jnp.einsum(
            "scgd,stcd->scgt", qh, k_seq, preferred_element_type=jnp.float32
        ) * self.scale
        sc = jnp.where(seen[:, None, None, :], sc, NEG_INF)
        w = jax.nn.softmax(sc, -1).astype(q.dtype)
        return jnp.einsum(
            "scgt,stcd->scgd", w, v_seq, preferred_element_type=jnp.float32
        ).astype(q.dtype).reshape(s, -1)

    def _paged_attention(
        self,
        q: Array,            # [S, KD] — this layer's queries
        k_pages: Array,      # [L, NP, PS, KD] — the whole page pools
        v_pages: Array,
        block_table: Array,  # [S, P]
        positions: Array,    # [S]
        layer: int,
        window: int = 0,
    ) -> Array:
        """The TP dispatch seam over `_paged_attention_local`.

        Single chip: the local body at the full head count (unchanged PR-11
        program). Under TP: shard_map over the mesh 'model' axis — each
        shard runs the SAME body (Pallas kernel on TPU, jnp gather oracle on
        CPU, identical in_specs) on its resident kv_heads slice of the page
        pool, with the block table and positions replicated; attention never
        crosses heads, so the seam adds ZERO collectives and the kernel's
        scalar-prefetch block-table operand is the same per shard as on one
        chip — fewer heads a page, so narrower pages and more of them a
        block (B comes from the shard's own shapes)."""
        if self.mesh is None:
            return self._paged_attention_local(
                q, k_pages, v_pages, block_table, positions,
                layer=layer, n_heads=self.cfg.n_heads, group=self.kv_group,
                window=window,
            )
        local = functools.partial(
            self._paged_attention_local,
            layer=layer, n_heads=self.cfg.n_heads // self.tp_size,
        )
        return jax.shard_map(
            local,
            mesh=self.mesh,
            in_specs=(
                P(None, "model"),              # q: head slice
                P(None, None, None, "model"),  # k_pages: kv_heads slice
                P(None, None, None, "model"),  # v_pages
                P(None, None),           # block table: replicated host state
                P(None),                 # positions: replicated
            ),
            out_specs=P(None, "model"),
            check_vma=False,
        )(q, k_pages, v_pages, block_table, positions)


class ServableLM(PagedLM):
    """The first served architecture (module docstring): learned positions,
    as many K/V heads as query heads, a GELU MLP of 4 * d, float32, its
    layers unrolled."""

    # -- named sharding (ISSUE 12) ------------------------------------------
    def param_logical_axes(self) -> Dict[str, Tuple[Optional[str], ...]]:
        """Every parameter's LOGICAL axes — declared once, resolved through
        the rules table (parallel/rules.py DEFAULT_RULES). Megatron-style TP:
        qkv/w1 column-parallel (heads/mlp), wo/w2 row-parallel, embed rows +
        unembed columns over vocab; norms/biases/positions replicated.
        Built once and cached: shard_params resolves every parameter
        through here (O(P) placements, not O(P^2) dict rebuilds)."""
        if self._axes_cache is not None:
            return self._axes_cache
        axes: Dict[str, Tuple[Optional[str], ...]] = {
            "embed": ("vocab", "embed"),
            "pos": ("length", "embed"),
            "lnf": ("embed",),
            "unembed": ("embed", "vocab"),
        }
        for i in range(self.cfg.n_layers):
            axes.update({
                f"l{i}.wq": ("embed", "heads"),
                f"l{i}.wk": ("embed", "kv_heads"),
                f"l{i}.wv": ("embed", "kv_heads"),
                f"l{i}.wo": ("heads", "embed"),
                f"l{i}.w1": ("embed", "mlp"),
                f"l{i}.w2": ("mlp", "embed"),
                f"l{i}.b1": ("mlp",),
                f"l{i}.b2": ("embed",),
                f"l{i}.ln1": ("embed",),
                f"l{i}.ln2": ("embed",),
            })
        self._axes_cache = axes
        return axes

    # -- params -------------------------------------------------------------
    def init_params(self, rng: Array) -> Dict[str, Array]:
        cfg = self.cfg
        d, v = cfg.d_model, cfg.vocab
        # per-tensor keys derived by name-stable fold_in so adding a tensor
        # never reshuffles the others (checkpoint/test determinism)
        p: Dict[str, Array] = {
            "embed": 0.1 * jax.random.normal(
                jax.random.fold_in(rng, 1), (v, d), jnp.float32
            ),
            "pos": 0.02 * jax.random.normal(
                jax.random.fold_in(rng, 2), (cfg.max_len, d), jnp.float32
            ),
            "lnf": jnp.ones((d,)),
            "unembed": 0.1 * jax.random.normal(
                jax.random.fold_in(rng, 3), (d, v), jnp.float32
            ),
        }
        for i in range(cfg.n_layers):
            for j, (name, shape) in enumerate((
                ("wq", (d, d)), ("wk", (d, d)), ("wv", (d, d)), ("wo", (d, d)),
                ("w1", (d, 4 * d)), ("w2", (4 * d, d)),
            )):
                k = jax.random.fold_in(jax.random.fold_in(rng, 1000 + i), j)
                p[f"l{i}.{name}"] = 0.1 * jax.random.normal(k, shape, jnp.float32)
            p[f"l{i}.b1"] = jnp.zeros((4 * d,))
            p[f"l{i}.b2"] = jnp.zeros((d,))
            p[f"l{i}.ln1"] = jnp.ones((d,))
            p[f"l{i}.ln2"] = jnp.ones((d,))
        return p

    def save(self, path: str, params: Dict[str, Array]) -> None:
        np.savez(path, __vocab__=self.cfg.vocab, __n_layers__=self.cfg.n_layers,
                 __d_model__=self.cfg.d_model, __n_heads__=self.cfg.n_heads,
                 __max_len__=self.cfg.max_len, __bos__=self.cfg.bos_id,
                 __eos__=self.cfg.eos_id,
                 **{k: np.asarray(v) for k, v in params.items()})

    @classmethod
    def load(
        cls, path: str, mesh=None, rules=None
    ) -> Tuple["ServableLM", Dict[str, Array]]:
        """Checkpoints are CANONICAL full arrays (save() materializes every
        shard), so the same .npz loads onto any layout: single chip, TP=2,
        TP=4 — the cross-layout contract tests/test_tp_serving.py pins."""
        with np.load(path) as z:
            cfg = LMConfig(
                vocab=int(z["__vocab__"]), n_layers=int(z["__n_layers__"]),
                d_model=int(z["__d_model__"]), n_heads=int(z["__n_heads__"]),
                max_len=int(z["__max_len__"]), bos_id=int(z["__bos__"]),
                eos_id=int(z["__eos__"]),
            )
            params = {
                k: jnp.asarray(z[k]) for k in z.files if not k.startswith("__")
            }
        return cls(cfg, mesh=mesh, rules=rules), params

    # -- shared block body --------------------------------------------------
    def _mlp(self, params, i: int, x: Array) -> Array:
        h = _rms(x, params[f"l{i}.ln2"])
        out = x + (
            jax.nn.gelu(h @ params[f"l{i}.w1"] + params[f"l{i}.b1"])
            @ params[f"l{i}.w2"] + params[f"l{i}.b2"]
        )
        # TP resharding point: w2 is row-parallel (contraction dim sharded
        # over 'model'), so the partitioner all-reduces the partial sums
        # HERE — one collective per layer's MLP, activations replicated out
        return self._constrain(out)

    # -- the two forwards ---------------------------------------------------
    def _context_forward(self, params, tokens: Array) -> Tuple[Array, Array, Array]:
        """The ONE causal-forward implementation: padded [B, T] tokens ->
        (logits [B, T, V], kc, vc [L, B, T, kv_dim]). Both the sequential
        reference path (forward_logits) and the serving prefill call this,
        so the attention math the equivalence tests compare against cannot
        drift between them. Unused outputs are DCE'd under jit."""
        cfg = self.cfg
        b, t = tokens.shape
        h_, hd = cfg.n_heads, cfg.head_dim
        x = self._constrain(params["embed"][tokens] + params["pos"][:t][None])
        causal = jnp.tril(jnp.ones((t, t), bool))
        kcs, vcs = [], []
        for i in range(cfg.n_layers):
            h = _rms(x, params[f"l{i}.ln1"])
            q = (h @ params[f"l{i}.wq"]).reshape(b, t, h_, hd)
            kf = h @ params[f"l{i}.wk"]
            vf = h @ params[f"l{i}.wv"]
            kcs.append(kf)
            vcs.append(vf)
            k = kf.reshape(b, t, h_, hd)
            v = vf.reshape(b, t, h_, hd)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * self.scale
            s = jnp.where(causal[None, None], s, NEG_INF)
            w = jax.nn.softmax(s.astype(jnp.float32), -1).astype(x.dtype)
            ctx = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(b, t, -1)
            # TP resharding point: wo is row-parallel — all-reduce here
            x = self._constrain(x + ctx @ params[f"l{i}.wo"])
            x = self._mlp(params, i, x)
        # the unembed is column-parallel (vocab sharded): constraining the
        # logits REPLICATED places one all-gather here, so sampling below is
        # collective-free and bitwise the single-chip math
        logits = self._constrain(_rms(x, params["lnf"]) @ params["unembed"])
        kc = self._constrain(jnp.stack(kcs), None, None, None, "kv_heads")
        vc = self._constrain(jnp.stack(vcs), None, None, None, "kv_heads")
        return logits, kc, vc

    def _chunk_forward(
        self,
        params,
        k_pages: Array,      # [L, NP, PS, KD]
        v_pages: Array,
        tokens: Array,       # [1, C] int32
        starts: Array,       # [1] int32 — position of tokens[:, 0]
        block_rows: Array,   # [1, max_pages_per_seq] int32
    ) -> Tuple[Array, Array, Array]:
        """The ONE chunk-shaped forward: attention = (already-committed
        pages, masked to positions < start) ++ (causal within the chunk).
        Shared by `prefill_chunk` (long-prompt prefill) and `verify_chunk`
        (speculative-decode scoring, ISSUE 16), so the two cannot drift —
        the verify call literally IS a prefill-chunk forward over
        [last_token, draft_1..K]. Returns (logits [1, C, V], kc, vc
        [L, 1, C, KD]); the pools are only READ here — each caller commits
        through `commit_prefill` itself."""
        cfg = self.cfg
        b, c = tokens.shape
        h_, hd = cfg.n_heads, cfg.head_dim
        ps = k_pages.shape[2]
        pos = starts[:, None] + jnp.arange(c)[None, :]          # [1, C]
        # padded tail may run past max_len; clamp the INDEX only (those
        # positions are causally invisible to every valid one)
        x = self._constrain(
            params["embed"][tokens]
            + params["pos"][jnp.minimum(pos, cfg.max_len - 1)]
        )
        t_ctx = block_rows.shape[1] * ps
        ctx_idx = jnp.arange(t_ctx)
        # committed-context mask: this chunk sees pages strictly before it
        past = ctx_idx[None, None, :] < starts[:, None, None]   # [1, 1, T_ctx]
        causal = jnp.tril(jnp.ones((c, c), bool))
        kcs, vcs = [], []
        for i in range(cfg.n_layers):
            h = _rms(x, params[f"l{i}.ln1"])
            q = (h @ params[f"l{i}.wq"]).reshape(b, c, h_, hd)
            kf = h @ params[f"l{i}.wk"]
            vf = h @ params[f"l{i}.wv"]
            kcs.append(kf)
            vcs.append(vf)
            k_self = kf.reshape(b, c, h_, hd)
            v_self = vf.reshape(b, c, h_, hd)
            k_past = k_pages[i][block_rows].reshape(b, t_ctx, h_, hd)
            v_past = v_pages[i][block_rows].reshape(b, t_ctx, h_, hd)
            sp = jnp.einsum("bqhd,bkhd->bhqk", q, k_past) * self.scale
            sp = jnp.where(past[:, None], sp, NEG_INF)
            ss = jnp.einsum("bqhd,bkhd->bhqk", q, k_self) * self.scale
            ss = jnp.where(causal[None, None], ss, NEG_INF)
            s_all = jnp.concatenate([sp, ss], -1)               # [1,H,C,T+C]
            w = jax.nn.softmax(s_all.astype(jnp.float32), -1).astype(x.dtype)
            ctx = (
                jnp.einsum("bhqk,bkhd->bqhd", w[..., :t_ctx], v_past)
                + jnp.einsum("bhqk,bkhd->bqhd", w[..., t_ctx:], v_self)
            ).reshape(b, c, -1)
            # TP resharding point: row-parallel wo all-reduces here
            x = self._constrain(x + ctx @ params[f"l{i}.wo"])
            x = self._mlp(params, i, x)
        # replicated logits: the one all-gather, sampling collective-free
        logits = self._constrain(_rms(x, params["lnf"]) @ params["unembed"])
        return logits, jnp.stack(kcs), jnp.stack(vcs)

    # -- the ONE decode executable ------------------------------------------
    def decode_step(
        self,
        params,
        k_pages: Array,  # [L, NP, PS, KD] (donated)
        v_pages: Array,
        tokens: Array,  # [S] int32: each slot's last token
        positions: Array,  # [S] int32: that token's position
        active: Array,  # [S] bool
        block_table: Array,  # [S, max_pages_per_seq] int32
        seeds: Array,  # [S] uint32 per-request sampling seed
        steps: Array,  # [S] int32 token index within the request
        temps: Array,  # [S] f32 temperature (0 = greedy)
        top_ks: Array,  # [S] int32 top-k truncation (0 = off)
    ) -> Tuple[Array, Array, Array]:
        """One decode step for all slots at the fixed [max_slots] shape.

        Writes each active slot's step K/V into its current page (inactive
        slots dump into page 0), then attends over the slot's own gathered
        pages masked to positions <= its own (the _paged_attention seam:
        Pallas ragged kernel on TPU, jnp gather oracle elsewhere) and samples
        on device through each request's own key. Returns (k_pages, v_pages,
        next_tok [S] int32). Every op keeps the slot dimension batched (no
        cross-slot reduction), so a slot's result is bitwise independent of
        the rest of the batch."""
        cfg = self.cfg
        ps = k_pages.shape[2]
        # the embed table is row-sharded over vocab: the token gather's
        # cross-shard combine happens here, activations replicated after
        x = self._constrain(
            params["embed"][tokens] + params["pos"][positions]
        )
        cur_page = jnp.take_along_axis(
            block_table, (positions // ps)[:, None], axis=1
        )[:, 0]
        cur_page = jnp.where(active, cur_page, 0)
        offs = positions % ps
        for i in range(cfg.n_layers):
            h = _rms(x, params[f"l{i}.ln1"])
            q = h @ params[f"l{i}.wq"]  # [S, KD]
            k_new = h @ params[f"l{i}.wk"]  # [S, KD]
            v_new = h @ params[f"l{i}.wv"]
            k_pages = k_pages.at[i, cur_page, offs].set(k_new)
            v_pages = v_pages.at[i, cur_page, offs].set(v_new)
            ctx = self._paged_attention(
                q, k_pages, v_pages, block_table, positions, layer=i
            )
            # TP resharding point: row-parallel wo all-reduces here
            x = self._constrain(x + ctx @ params[f"l{i}.wo"])
            x = self._mlp(params, i, x)
        # replicated logits (the one all-gather): sampling below then runs
        # entirely locally — no collective in the greedy branch, tokens
        # bitwise the single-chip oracle's
        logits = self._constrain(_rms(x, params["lnf"]) @ params["unembed"])
        next_tok = self._sample(logits, seeds, steps, temps, top_ks)
        return (
            self._constrain(k_pages, *POOL_LOGICAL_AXES),
            self._constrain(v_pages, *POOL_LOGICAL_AXES),
            next_tok,
        )