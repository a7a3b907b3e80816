"""Paged KV cache: one physical page pool + per-slot block tables.

The device layout follows the TPU paged-attention kernel convention
(jax.experimental.pallas.ops.tpu.paged_attention; "Ragged Paged Attention",
PAPERS.md): every sequence shares ONE pool

    k_pages, v_pages : [n_layers, num_pages, page_size, kv_dim]

and each decode slot owns a row of the block table
[max_slots, max_pages_per_seq] mapping logical page j -> physical page id.
Page 0 is reserved as the dump page: inactive slots write their (discarded)
step KV there and unused block-table entries point there, so the compiled
decode program always runs at one fixed shape — which slots are live and how
long each sequence is are pure *data*, never *shape*. That is what lets a
mixed-age, mixed-length batch share a single executable with zero recompiles
(asserted via stats.RecompileStats in the serving session).

Allocation is a host-side free list over REFCOUNTED pages (ISSUE 19), and
pages are handed out AS TOKENS ARE WRITTEN (ISSUE 34): a slot holds the pages
its written tokens need plus the page its next write needs, and nothing more.
Admission gives a slot its prompt's pages (`reserve`); before every decode
step, and before a verify round's K+1 positions, the scheduler grows each
writing slot by the page its write crosses into (`grow`), and `trim` gives
back what a rejected draft leaves over. So `free_pages` counts pages free NOW,
not pages unpromised, and a running sequence CAN find the pool dry: `grow`
then returns False and the scheduler preempts the request admitted last
(scheduler.Scheduler.grow), whose pages come back here through `release`.
`can_admit` is the one admission predicate: the pages of what the request has
to (re)write plus one, and one free page left for every slot already live.
Without the prefix cache every page has refcount 1.

With `prefix_cache=True` the shared-prefix index (prefix_cache.py) rides on
top: reserve() first walks the tenant's chain and ALIASES every matching
committed full page into the new slot's block table read-only (+1 ref each
— a handful of host ints; the compiled executables never know), then pops
fresh pages only for the uncached suffix. Committed prompt pages register
into the index (the index holds its own +1 ref), so they outlive their
request and serve later ones; a page only returns to the free list when its
LAST reference drops — a slot releasing, a trim, or an LRU eviction of an
unreferenced cached page under pool pressure. Copy-on-write falls out of
page granularity: only FULL immutable prompt pages are ever shared, and the
first divergent page is a fresh private page the request's own chunked
prefill writes. Retirement/cancel recycling is counted in PHYSICAL frees
(a shared page decrefs without freeing), so the leak-watch counters stay
exact under aliasing.

Window layers (a model whose `cache_windows` give some layers a window of W
positions) keep their K/V apart, in a RING of pages a slot: W positions span
at most `ring_pages(W, page_size)` pages, so slot s owns ring pages
1 + s * R .. (s + 1) * R of a second pool [window_layers, 1 + S * R, PS, KD]
(its page 0 the dump page), and logical page j lands in ring entry j % R: a
page that slides out of the window is the one the next page reuses. The ring
is STATIC: no free list, no allocation and no preemption on its side; every
table snapshot (`block_table`, `slot_row`) carries the slot's R ring entries
after its P logical pages, and the pools are the pair (full, ring)."""

from __future__ import annotations

import threading
from typing import List, Optional, Sequence

import numpy as np

from paddle_tpu.obs import metrics as obs_metrics
from paddle_tpu.serving.prefix_cache import PrefixIndex


def ring_pages(window: int, page_size: int) -> int:
    """Pages a slot's ring holds for a window of `window` positions: the most
    pages the window spans (its first position mid-page), so that the page a
    step writes is never one the same step still reads."""
    return -(-(int(window) - 1) // int(page_size)) + 1


class PagedKVCache:
    """Host-side page allocator + device-resident page pool.

    The device arrays are created lazily (jax import deferred) and are
    *owned by the serving session* once handed out: the compiled decode/commit
    steps donate and replace them, so this class only tracks the host-side
    free list, refcounts, block tables and (optionally) the prefix index."""

    def __init__(
        self,
        n_layers: int,
        kv_dim: int,
        num_pages: int,
        page_size: int,
        max_slots: int,
        max_pages_per_seq: int,
        pool_sharding=None,
        pool_dtype=None,
        prefix_cache: bool = False,
        prefix_cache_pages: Optional[int] = None,
        window_layers: int = 0,
        window: int = 0,
    ):
        if num_pages < 2:
            raise ValueError("need at least 2 pages (page 0 is the dump page)")
        self.n_layers = n_layers
        self.kv_dim = kv_dim
        self.num_pages = num_pages
        self.page_size = page_size
        self.max_slots = max_slots
        self.max_pages_per_seq = max_pages_per_seq
        # TP placement (ISSUE 12): a NamedSharding splitting the kv_dim's
        # kv_heads over the mesh 'model' axis — per-chip pool bytes drop
        # ~TPx. Stored here so every make_pools call (init AND the crash-
        # recovery re-init) lands the pools on the same layout. None =
        # single-chip default placement.
        self.pool_sharding = pool_sharding
        # the pools' element type, the model's to say (None: float32)
        self.pool_dtype = pool_dtype
        # pop() hands out ascending ids; page 0 is never allocatable
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self._slot_pages: List[List[int]] = [[] for _ in range(max_slots)]
        # the block table rides to the device as step *data* each decode —
        # same shape every step, so it never perturbs the executable cache
        self._table = np.zeros((max_slots, max_pages_per_seq), np.int32)
        # refcounts (ISSUE 19): slots + the prefix index each hold one
        # reference; a page recycles only at zero. Prefix off => every page
        # is refcount<=1 and the accounting is bitwise the old free list's.
        self._refcount: List[int] = [0] * num_pages
        # shared-prefix index (None = disabled). The _prefix_lock guards the
        # index STRUCTURE against the one cross-thread access — a submit
        # thread's admission-pricing peek racing the engine thread's
        # insert/evict; free-list/refcount mutations stay engine-thread-only
        # (under the scheduler lock), exactly as before.
        self.prefix: Optional[PrefixIndex] = (
            PrefixIndex(page_size) if prefix_cache else None
        )
        self.prefix_cache_pages = (
            None if prefix_cache_pages is None else int(prefix_cache_pages)
        )
        self._prefix_lock = threading.Lock()
        # per-slot prefix state: hit tokens aliased at reserve, prompt pages
        # registered so far, and the chain node registration continues from
        self._slot_hit: List[int] = [0] * max_slots
        self._slot_reg: List[int] = [0] * max_slots
        self._slot_node: List[int] = [0] * max_slots
        # window layers' static ring (module docstring): R entries a slot
        self.window_layers = int(window_layers)
        self.window = int(window) if self.window_layers else 0
        self.ring = ring_pages(self.window, page_size) if self.window else 0
        self._ring_table = (
            1 + np.arange(max_slots * self.ring, dtype=np.int32)
        ).reshape(max_slots, self.ring)

    # -- device pool --------------------------------------------------------
    def make_pools(self):
        """Fresh zeroed (k_pages, v_pages) device arrays of `pool_dtype`,
        placed on `pool_sharding` when the cache is tensor-parallel."""
        import jax
        import jax.numpy as jnp

        shape = (self.n_layers, self.num_pages, self.page_size, self.kv_dim)
        dtype = self.pool_dtype or jnp.float32
        if self.ring:
            # the pair (full, ring): one pool a kind of layer
            if self.pool_sharding is not None:
                raise ValueError("window layers' pages are not built under a mesh")
            rings = (self.window_layers, 1 + self.max_slots * self.ring) + shape[2:]
            return ((jnp.zeros(shape, dtype), jnp.zeros(rings, dtype)),
                    (jnp.zeros(shape, dtype), jnp.zeros(rings, dtype)))
        if self.pool_sharding is None:
            return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)
        zeros = jax.jit(
            lambda: jnp.zeros(shape, dtype),
            out_shardings=self.pool_sharding,
        )
        return zeros(), zeros()

    # -- accounting ---------------------------------------------------------
    def pages_needed(self, total_len: int) -> int:
        return -(-int(total_len) // self.page_size)  # ceil div

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return (self.num_pages - 1) - len(self._free)

    def _decref(self, page: int) -> bool:
        """Drop one reference; True when the page physically recycled."""
        rc = self._refcount[page] - 1
        self._refcount[page] = rc
        if rc == 0:
            self._free.append(page)
            return True
        return False

    def _available(self) -> int:
        avail = len(self._free)
        if self.prefix is not None:
            # unreferenced cached pages are reclaimable on demand (reserve
            # and grow evict LRU under pressure), so they count as free
            with self._prefix_lock:
                avail += self.prefix.evictable(self._refcount)
        return avail

    def can_admit(self, written_len: int, total_len: int,
                  live_slots: int) -> bool:
        """The admission predicate: can the pool take a request that has
        `written_len` tokens to write before it decodes on (its prompt; for
        a preempted request its prompt and what it had generated) and
        `total_len` in its whole life? It needs the pages of `written_len`
        plus one (never more than `total_len`'s, so a request that fits the
        pool alone is admitted into an empty one), and `live_slots` pages
        must stay free besides: every slot already holding a request crosses
        a page boundary within `page_size` steps. Aliased prefix pages are
        counted as needed AND, while unreferenced, as available."""
        whole = self.pages_needed(total_len)
        n = min(self.pages_needed(written_len) + 1, whole)
        return (whole <= self.max_pages_per_seq
                and n + live_slots <= self._available())

    def _evict_for(self, need: int) -> None:
        """Under pool pressure, LRU-evict unreferenced cached prefix pages
        until `need` pages are free or the index has nothing left to give."""
        if self.prefix is None or need <= len(self._free):
            return
        evicted = 0
        with self._prefix_lock:
            while need > len(self._free):
                page = self.prefix.evict_lru(self._refcount)
                if page is None:
                    break
                self._decref(page)  # the index's own reference
                evicted += 1
        if evicted:
            obs_metrics.observe_prefix_evictions(evicted)

    # -- reserve / release --------------------------------------------------
    def reserve(
        self,
        slot: int,
        total_len: int,
        tenant: str = "default",
        prompt: Optional[Sequence[int]] = None,
    ) -> List[int]:
        """Give the empty `slot` pages covering its first `total_len` tokens
        (admission passes the prompt's length; `grow` adds the rest as they
        are written); returns the physical page ids. Raises if the slot is
        occupied or pages are short — callers gate on can_admit.

        With the prefix cache enabled and `prompt` given, the leading pages
        come ALIASED from the tenant's chain (read-only, +1 ref each) and
        only the uncached suffix pops fresh pages — `hit_tokens(slot)` then
        reports how many prompt tokens the slot skipped prefilling. Under
        pool pressure, unreferenced cached pages are LRU-evicted to make
        room before giving up."""
        if self._slot_pages[slot]:
            raise RuntimeError(f"slot {slot} already holds pages")
        n = self.pages_needed(total_len)
        if n > self.max_pages_per_seq:
            raise ValueError(
                f"sequence of {total_len} tokens needs {n} pages > "
                f"max_pages_per_seq={self.max_pages_per_seq}"
            )
        matched: List[int] = []
        node = 0
        if self.prefix is not None and prompt is not None:
            with self._prefix_lock:
                cow0 = self.prefix.cow_events
                matched, node = self.prefix.match(tenant, prompt)
                cow = self.prefix.cow_events - cow0
            # alias the cached prefix BEFORE any eviction below: ref >= 2
            # makes these pages invisible to evict_lru
            for p in matched:
                self._refcount[p] += 1
            if matched:
                obs_metrics.observe_prefix_hit(len(matched))
            if cow:
                obs_metrics.observe_prefix_cow(cow)
        # the aliased prefix first, then the uncached suffix as `grow` gives
        # any page; the aliases (ref >= 2) are invisible to its eviction
        self._slot_pages[slot] = list(matched)
        self._table[slot, :] = 0
        self._table[slot, : len(matched)] = matched
        if not self.grow(slot, total_len):
            self._slot_pages[slot] = []
            self._table[slot, :] = 0
            for p in matched:  # roll the aliases back — nothing reserved
                self._decref(p)
            raise RuntimeError(
                f"KV pool exhausted: need {n - len(matched)} pages, "
                f"{len(self._free)} free"
            )
        pages = self._slot_pages[slot]
        self._slot_hit[slot] = len(matched) * self.page_size
        self._slot_reg[slot] = len(matched)
        self._slot_node[slot] = node
        return pages

    def hit_tokens(self, slot: int) -> int:
        """Prompt tokens slot `slot` aliased from the prefix cache at its
        reservation — the chunked prefill starts at exactly this offset."""
        return self._slot_hit[slot]

    def peek_hit_tokens(self, tenant: str, prompt: Sequence[int]) -> int:
        """Admission-pricing probe (Scheduler.submit): leading prompt tokens
        cached right now. Read-only — no recency bump, no counters — so the
        load estimate never perturbs eviction order. 0 when disabled."""
        if self.prefix is None:
            return 0
        with self._prefix_lock:
            return self.prefix.peek_hit_tokens(tenant, prompt)

    def commit_prefix(self, slot: int, tenant: str,
                      prompt: Sequence[int], committed_len: int) -> int:
        """Register slot `slot`'s prompt pages fully covered by
        `committed_len` committed tokens into the tenant's chain (the index
        takes one reference per NEWLY registered page, which is what lets
        the pages outlive the request). Incremental: called after the
        whole-prompt commit and after every prefill chunk, it only walks the
        pages added since the last call. Returns pages newly registered."""
        if self.prefix is None:
            return 0
        upto = min(int(committed_len), len(prompt)) // self.page_size
        frm = self._slot_reg[slot]
        if upto <= frm:
            return 0
        pages = self._slot_pages[slot]
        with self._prefix_lock:
            node, registered = self.prefix.extend(
                tenant, self._slot_node[slot], prompt, frm, upto, pages
            )
            for p in registered:
                self._refcount[p] += 1  # the index's reference
            self._slot_node[slot] = node
            self._slot_reg[slot] = upto
            evicted = self._enforce_cap_locked()
        if evicted:
            obs_metrics.observe_prefix_evictions(evicted)
        return len(registered)

    def _enforce_cap_locked(self) -> int:
        """Best-effort `prefix_cache_pages` cap (caller holds _prefix_lock):
        LRU-evict unreferenced entries until the index fits. Entries still
        aliased by live slots pin — the cap re-checks when those slots
        release. Returns pages evicted."""
        if self.prefix_cache_pages is None:
            return 0
        evicted = 0
        while len(self.prefix) > self.prefix_cache_pages:
            page = self.prefix.evict_lru(self._refcount)
            if page is None:
                break
            self._decref(page)
            evicted += 1
        return evicted

    def grow(self, slot: int, total_len: int) -> bool:
        """Extend the slot's pages to cover `total_len` tokens — the pages a
        write about to happen lands in. All or nothing: False, with nothing
        changed, when the pool is dry even after the prefix index has given
        up what it can evict; the caller (Scheduler.grow) then preempts a
        request and asks again. A few host ints a step: no clock, no device
        work."""
        pages = self._slot_pages[slot]
        have = len(pages)
        need = self.pages_needed(total_len) - have
        if need <= 0:
            return True
        if have + need > self.max_pages_per_seq:
            raise ValueError(
                f"sequence of {total_len} tokens needs {have + need} pages > "
                f"max_pages_per_seq={self.max_pages_per_seq}"
            )
        self._evict_for(need)
        if need > len(self._free):
            return False
        for _ in range(need):
            p = self._free.pop()
            self._refcount[p] = 1
            pages.append(p)
        self._table[slot, have:have + need] = pages[have:]
        return True

    def can_grow(self, wants) -> bool:
        """Would `grow` give every slot of `wants` [(slot, tokens its pages
        must cover)] its pages with no preemption? The free list answers on
        every step but a few; the prefix index is asked what it could evict
        only when the free list is short. Host ints, nothing changed."""
        need = sum(
            max(0, self.pages_needed(total) - len(self._slot_pages[slot]))
            for slot, total in wants
        )
        return need <= len(self._free) or need <= self._available()

    def trim(self, slot: int, total_len: int) -> int:
        """Release the slot's surplus tail pages beyond what `total_len`
        tokens need (speculative-decode rollback, ISSUE 16): a verify round
        grows the slot to its K+1 positions first, and what a rejected draft
        leaves past the accepted frontier recycles here instead of riding to
        retirement. Tail pages are always private
        (aliased prefix pages sit at the FRONT and registration never
        reaches past the prompt), so the decref frees them physically.
        Returns how many pages were freed; idempotent."""
        pages = self._slot_pages[slot]
        keep = self.pages_needed(total_len)
        if not pages or keep >= len(pages):
            return 0
        surplus = pages[keep:]
        self._slot_pages[slot] = pages[:keep]
        freed = sum(1 for p in surplus if self._decref(p))
        self._table[slot, keep:] = 0
        return freed

    def release(self, slot: int) -> int:
        """Drop the slot's references (KV recycling); returns how many pages
        PHYSICALLY returned to the free list — a page another slot still
        aliases, or one the prefix index caches, only decrefs (satellite 2:
        cancel/retire accounting counts each physical free exactly once).
        Idempotent for an empty slot."""
        pages = self._slot_pages[slot]
        self._slot_pages[slot] = []
        freed = sum(1 for p in pages if self._decref(p))
        self._table[slot, :] = 0
        self._slot_hit[slot] = 0
        self._slot_reg[slot] = 0
        self._slot_node[slot] = 0
        if self.prefix is not None and self.prefix_cache_pages is not None:
            # this release may have unpinned cached entries past the cap
            with self._prefix_lock:
                evicted = self._enforce_cap_locked()
            if evicted:
                obs_metrics.observe_prefix_evictions(evicted)
        return freed

    def flush_prefix(self) -> int:
        """Drop every prefix-index entry and release the index's references;
        pages no slot holds return to the free list (the rest recycle when
        their slots release). Benches/tests use this for the zero-leak gate;
        live slots keep decoding untouched — their aliased pages stay
        referenced, only un-cacheable from now on."""
        if self.prefix is None:
            return 0
        with self._prefix_lock:
            pages = self.prefix.drop_all()
        freed = sum(1 for p in pages if self._decref(p))
        # chain continuation points are gone: let still-prefilling slots
        # re-register from the root on their next commit
        self._slot_reg = [0] * self.max_slots
        self._slot_node = [0] * self.max_slots
        return freed

    def reset(self) -> None:
        """Rebuild the allocator to its just-constructed state (engine crash
        recovery): every page free, every slot empty, table zeroed — and the
        prefix index INVALIDATED, because every cached page id points into
        the dead pool; replayed requests re-populate it against the fresh
        one (no stale aliases). The device pools are NOT touched here — the
        session re-creates them via make_pools(), because a failed donated
        decode/commit step has already consumed the old buffers."""
        self._free = list(range(self.num_pages - 1, 0, -1))
        self._refcount = [0] * self.num_pages
        self._slot_pages = [[] for _ in range(self.max_slots)]
        self._slot_hit = [0] * self.max_slots
        self._slot_reg = [0] * self.max_slots
        self._slot_node = [0] * self.max_slots
        self._table[:] = 0
        if self.prefix is not None:
            with self._prefix_lock:
                self.prefix.drop_all()

    def slot_pages(self, slot: int) -> List[int]:
        return list(self._slot_pages[slot])

    def held_pages(self, slots) -> tuple:
        """(full-pool pages, ring pages) that `slots` hold with K/V in them
        or about to be: a ring holds at most R of a slot's pages."""
        full = [len(self._slot_pages[s]) for s in slots]
        return sum(full), sum(min(n, self.ring) for n in full)

    def page_refcount(self, page: int) -> int:
        return self._refcount[page]

    def prefix_stats(self) -> dict:
        """The prefix-cache telemetry block session.stats() embeds — stable
        keys whether or not the cache is enabled."""
        if self.prefix is None:
            return {
                "prefix_cache_enabled": False,
                "prefix_hit_rate": 0.0,
                "prefix_pages_shared": 0,
                "prefix_pages_cached": 0,
                "prefix_pages_cow": 0,
                "prefix_evictions": 0,
                "prefix_hit_rate_by_tenant": {},
            }
        with self._prefix_lock:
            d = self.prefix.stats()
            d["prefix_pages_unreferenced"] = self.prefix.evictable(
                self._refcount
            )
        d["prefix_cache_enabled"] = True
        d["prefix_cache_pages_cap"] = self.prefix_cache_pages
        return d

    def block_table(self) -> np.ndarray:
        """The [max_slots, max_pages_per_seq] int32 table, as a SNAPSHOT: the
        engine releases and regrows rows while the step it dispatched with
        this table may still be waiting for its operands (ISSUE 36), and a
        host array handed to a dispatch must not change under it. On TPU
        this same table is
        the SCALAR-PREFETCH operand of the ragged paged-attention kernel
        (ops/pallas/paged_attention.py): its rows name the physical page of
        each copy the kernel issues, a block of pages at a time."""
        if self.ring:
            return np.concatenate([self._table, self._ring_table], 1)
        return self._table.copy()

    def slot_row(self, slot: int) -> np.ndarray:
        """One slot's [1, max_pages_per_seq] block-table row — the shape the
        per-slot prefill/commit/chunk executables take (a snapshot, as
        `block_table` is)."""
        if self.ring:
            return np.concatenate(
                [self._table[slot : slot + 1], self._ring_table[slot : slot + 1]], 1
            )
        return self._table[slot : slot + 1].copy()
