"""ServingSession: a long-lived serving engine that owns device state.

The anti-pattern this replaces: `run_generation` rebuilt the Network,
re-initialized params and reloaded the checkpoint on EVERY call, and
`InferenceMachine.forward` compiled per batch shape and blocked the host per
request. Here the session loads parameters ONCE, compiles THREE kinds of
executable ONCE, and then serves any number of requests of any mixed lengths
against them:

  * decode  — the single fixed-[max_slots] continuous-batching step
              (pages donated in/out; the only executable in the hot loop;
              on TPU its attention runs the Pallas ragged paged-attention
              kernel, the jnp gather path staying the CPU oracle)
  * prefill — one per length bucket (a handful: `prefill_buckets`)
  * commit  — one per bucket + one chunk shape (scatter prompt KV into pages)
  * chunk   — ONE [1, prefill_chunk] program serving every long prompt:
              chunked prefill (ISSUE 11) commits a long prompt C tokens per
              engine step interleaved with decode, so a long prompt joining
              mid-stream never stalls the running streams' inter-token
              latency the way a whole-prompt prefill does

Sampling (ISSUE 11) is on-device and rides the SAME decode executable:
per-request (seed, temperature, top_k) are [max_slots] data lanes, the key
is fold_in(PRNGKey(seed), token_index), so greedy and sampled requests mix
freely with zero recompiles and the PR 10 crash replay stays bitwise even
at temperature > 0.

Shape discipline is *asserted*, not hoped for: every decode step's input
signature is recorded into a serving-local stats.RecompileStats (the PR-1
telemetry) and `decode_shape_signatures()` must stay at 1 over any request
mix — the zero-recompile gate in tests/test_serving.py.

Hot-loop discipline matches the trainer's (README "Async execution"): the
decode loop performs exactly ONE device->host fetch per step — the sampled
token ids, which the autoregressive loop inherently needs to detect EOS and
stream results — and, since ISSUE 10, exactly ONE wall-clock read per step
(the step-boundary timestamp that batches every deadline/cancellation
check). tests/test_lint_hotloop.py lints this loop body the same way it
lints the train loop.

The engine keeps ONE decode step in flight (ISSUE 36): `step()` dispatches
decode step N while step N-1's sampled tokens are still on the device and
fetches N-1's AFTER that dispatch (`_decode_once`, `_collect`), so the
fetch, the per-slot bookkeeping, the stream wake-up, the caller's work
between two `step()` calls, the reap and the next lanes' building run UNDER
the device's step. A lane whose token the host does not know yet takes it on
the device (`decode_step_in_flight`); its position, sampling index, budget
and pages are host integers that advance by one whatever was sampled. What
is learned a step late (an EOS, a cancel, an expiry) costs one lane-step,
`stats()["wasted_lanes"]`, never a token. What needs the VALUES drains the
step first, which is the old order: a speculation round, a dry pool about to
preempt, an engine restart, `stop()`. `stats()["overlapped_steps"]` beside
`decode_steps` says how often the new order engaged.

KV pages (ISSUE 34) are handed out as tokens are written: an admission gets
its prompt's pages, and before each decode step or verify round every slot
that writes is grown by the page its write crosses into (`_ensure_pages`: a
few host ints a step, no clock, no fetch). When the pool is dry the request
admitted last is preempted (scheduler.Scheduler.grow) and, admitted again,
REPLAYS: its prompt is prefilled again and its known tokens ride ordinary
decode lanes until its K/V is rebuilt. Every program is the one it ran the
first time on the same inputs, so the rebuilt K/V and every later token are
bitwise what they would have been, greedy and sampled; `stats()` counts
`preemptions` and `replayed_tokens`, and a flight span `serve.preempt`
names the engine step that paid for one.

The step accounts for its own time (ISSUE 37) on the always-on span ring
(obs/trace.py `flight`): one `serve.step` a call that did work, whose int
attrs say what it ran (`admitted`, `chunks`, `decoded`, `slots`,
`preempted`) and `wait_ns`, the ns it spent blocked in `_fetch`, the one
helper every device->host fetch of the engine goes through. That is the host
WAITING for the device; the rest of the span is the host WORKING, which no
device trace shows while the device is never idle. Its children: `serve.admit`
(a whole-prompt prefill, dispatch to first token: `bucket`, `prompt`,
`queued_ms`, `replay`, `wait_ns`), `serve.chunk` (`start`, `tokens`,
`wait_ns`), `serve.preempt` (`written`, `pages`) and `serve.decode` (the
dispatch and the fetch of the step before: `slots`, `layer_passes`,
`replaying`, `wait_ns`). A fetch outside a child (a `_drain` before a
preemption, at the end of the work or before a speculation round) shows as
the step's `wait_ns` beyond its children's.

Resilience (ISSUE 10): in server mode the engine thread runs under a
SUPERVISOR. When the engine faults (seeded sites `decode_raise` /
`page_exhaust`) or stalls past `engine_stall_timeout_s` without a step
(seeded site `engine_stall`), the supervisor supersedes it, re-initializes
the page pool (a failed donated step consumed the old buffers anyway), and
replays every in-flight request from its prompt — greedy decode is
deterministic, so completed requests are unaffected and replayed ones are
result-transparent; requests past their deadline fail with the named reason
`deadline`. Past `engine_restart_max` restarts the engine gives up and every
outstanding request fails `engine_error` (the pre-supervisor behavior)."""

from __future__ import annotations

import logging
import threading
import time
from typing import Dict, Optional, Sequence

import numpy as np

from paddle_tpu.core import faults as _faults
from paddle_tpu.core import stats
from paddle_tpu.obs import metrics as obs_metrics
from paddle_tpu.obs import trace
from paddle_tpu.serving.kv_cache import PagedKVCache
from paddle_tpu.serving.model import LMConfig, PagedLM, ServableLM
from paddle_tpu.serving.quota import TenantQuotas
from paddle_tpu.serving.scheduler import RequestHandle, Scheduler

# serving-side counters (sibling of stats.FT_EVENTS/DATA_EVENTS): admissions,
# retirements, quota rejections, decode steps — unconditional telemetry;
# the "serving" name registers the group with the obs metrics exporter
SERVING_EVENTS = stats.EventCounter("serving")

# time-to-first-token distribution (PADDLE_TPU_TRACE not required: histograms
# are unconditional telemetry like the event counters above)
TTFT_HISTOGRAM = obs_metrics.REGISTRY.histogram(
    "paddle_tpu_serving_ttft_seconds",
    "submit → first sampled token, per request",
)


def _bucket_for(buckets: Sequence[int], n: int) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"prompt of {n} tokens exceeds largest bucket {buckets[-1]}")


def decode_step_in_flight(model: PagedLM):
    """`model.decode_step` behind the one thing the SESSION adds to it (ISSUE
    36), as the function the session jits: after the params, the pools (and
    the state the model declares) come `tokens`, `prev_tok`, `from_prev`,
    then the model's other lanes. A lane whose last token is still on the
    device takes it from `prev_tok`, the array the step before returned; the
    others take the host's. Two [max_slots] operands, data like the rest:
    one signature, and the models' `decode_step` signatures unchanged."""
    carried = 3 + bool(model.state_spec() or model.counter_spec())

    def decode_step(*operands):
        import jax.numpy as jnp

        tokens, prev_tok, from_prev, *lanes = operands[carried:]
        return model.decode_step(
            *operands[:carried], jnp.where(from_prev, prev_tok, tokens), *lanes
        )

    return decode_step


class ServingSession:
    def __init__(
        self,
        model: PagedLM,
        params: Dict,
        *,
        max_slots: int = 8,
        page_size: int = 16,
        num_pages: Optional[int] = None,
        prefill_buckets: Sequence[int] = (16, 32, 64),
        max_new_limit: int = 64,
        max_queue: int = 256,
        quotas: Optional[TenantQuotas] = None,
        default_deadline_s: Optional[float] = None,
        default_ttft_deadline_s: Optional[float] = None,
        engine_restart_max: int = 3,
        engine_stall_timeout_s: float = 10.0,
        prefill_chunk: Optional[int] = None,
        default_temperature: float = 0.0,
        default_top_k: int = 0,
        speculate_k: int = 0,
        prefix_cache: bool = False,
        prefix_cache_pages: Optional[int] = None,
    ):
        import jax

        self.model = model
        self.cfg = model.cfg
        # TP (ISSUE 12): params resolve through the model's logical-axes
        # table + sharding rules — heads/mlp/vocab split over the mesh
        # 'model' axis, per-chip param bytes ~1/TP. Identity on one chip.
        self.params = model.shard_params(params)
        self.buckets = tuple(sorted(set(int(b) for b in prefill_buckets)))
        self.max_new_limit = int(max_new_limit)
        max_ctx = self.buckets[-1] + self.max_new_limit
        if max_ctx > self.cfg.max_len:
            raise ValueError(
                f"largest bucket + max_new_limit = {max_ctx} exceeds the "
                f"model's max_len {self.cfg.max_len}"
            )
        # chunked prefill (ISSUE 11) lifts the bucket cap on prompt length:
        # any prompt up to max_len - 1 is admissible (committed one C-token
        # chunk per engine step), so the page pool must cover max_len, not
        # just the largest bucket
        self.prefill_chunk = None if not prefill_chunk else int(prefill_chunk)
        if self.prefill_chunk is not None:
            max_ctx = self.cfg.max_len
        # session-wide sampling defaults; per-request values win (ISSUE 11)
        self.default_temperature = float(default_temperature)
        self.default_top_k = int(default_top_k)
        # speculative decoding (ISSUE 16): K drafted tokens verified per
        # round through ONE [1, K+1] prefill-chunk-shaped executable.
        # 0 (the default) compiles nothing extra and takes exactly today's
        # code path — `--speculate_k 0` bitwise-recovers PR-15 behavior.
        self.speculate_k = max(0, int(speculate_k))
        # shared-prefix cache (ISSUE 19): cached prompt pages alias into new
        # slots read-only and the chunked prefill starts at the first
        # un-cached token — which is why the cache REQUIRES chunked prefill
        # (the whole-prompt executables have no notion of a partial start).
        # Purely host-side block-table state: zero new executables, decode
        # signature stays 1, and it rides TP's replicated-table dispatch.
        self.prefix_cache = bool(prefix_cache)
        if self.prefix_cache and self.prefill_chunk is None:
            raise ValueError(
                "prefix_cache requires prefill_chunk: cache hits resume "
                "prefill mid-prompt, which only the chunked path can do"
            )
        # per-request state that lives in no page (a recurrence's): the model
        # declares it, and what cannot work through it without snapshots of
        # it is refused here, not built
        declared = model.state_spec()
        if declared and (self.prefix_cache or self.speculate_k):
            raise ValueError(
                f"{type(model).__name__} carries a recurrence's state "
                f"({', '.join(sorted(declared))}) from token to "
                "token, and the session keeps no snapshot of it: "
                + ("prefix_cache would alias pages whose tokens the "
                   "recurrence never ran over" if self.prefix_cache else
                   "speculate_k rolls a rejected draft back by trimming "
                   "pages, and the recurrence cannot be rolled back")
            )
        # window layers keep a ring of pages a slot (kv_cache.py): nothing
        # there to alias a prefix into or to trim a rejected draft out of
        windows = tuple(int(w) for w in model.cache_windows)
        if any(windows) and (self.prefix_cache or self.speculate_k):
            raise ValueError(
                f"{type(model).__name__} has window layers, whose pages are a "
                "ring a slot that the session neither aliases nor trims: "
                + ("prefix_cache would alias pages the ring has already "
                   "reused" if self.prefix_cache else
                   "speculate_k rolls a rejected draft back by trimming pages")
            )
        if len(set(w for w in windows if w)) > 1:
            raise ValueError(f"one window for every window layer, not {windows}")
        # per-seq page budget covers the verify chunk's K-token overshoot
        pages_per_seq = -(-(max_ctx + self.speculate_k) // page_size)
        if num_pages is None:
            # worst case every slot at full context, plus the dump page
            num_pages = max_slots * pages_per_seq + 1
        # the MODEL declares the cache it needs: how many K/V entries a token
        # leaves (a looped stack leaves one a pass and layer), how wide, and
        # in what type
        self.cache = PagedKVCache(
            n_layers=windows.count(0),
            window_layers=len(windows) - windows.count(0),
            window=max(windows, default=0),
            kv_dim=model.cache_width,
            pool_dtype=model.cache_dtype,
            num_pages=num_pages,
            page_size=page_size,
            max_slots=max_slots,
            max_pages_per_seq=pages_per_seq,
            # kv_heads over the mesh 'model' axis under TP (~1/TP pool bytes
            # per chip); the cache re-applies it on crash-recovery re-init
            pool_sharding=model.pool_sharding(),
            prefix_cache=self.prefix_cache,
            prefix_cache_pages=prefix_cache_pages,
        )
        self.scheduler = Scheduler(
            self.cache, max_queue=max_queue, quotas=quotas,
            prefill_chunk=self.prefill_chunk, largest_bucket=self.buckets[-1],
            speculate_k=self.speculate_k,
        )
        self.k_pages, self.v_pages = self.cache.make_pools()
        # the state and the counters the model declares, [max_slots, ...] a
        # slot's and whole the counters', carried (donated) through its
        # programs beside the pools; None for a model that declares neither
        self.state = self._make_state()
        self._counters_read: Dict = {}
        # layer applications a token costs, and the K/V entries it leaves:
        # two declarations (a layer that keeps a recurrence leaves no K/V)
        self.layer_passes = int(model.layer_passes)
        obs_metrics.set_kv_bytes_per_token(
            2 * int(model.cache_layers) * model.cache_width
            * jax.tree.leaves(self.k_pages)[0].dtype.itemsize
        )
        obs_metrics.set_recurrent_state_bytes_per_slot(sum(
            int(np.prod(shape)) * np.dtype(dtype).itemsize
            for shape, dtype in model.state_spec().values()
        ))

        # warmup detection (ISSUE 17): each wrapped body runs ONLY while jax
        # traces it — exactly once per new input signature per executable,
        # i.e. precisely when a compile happens (prefill buckets included,
        # which the per-signature RecompileStats below never see) — so the
        # counter is a "this step compiled something" signal at zero
        # steady-state cost, on any backend, with or without the persistent
        # compile cache
        self._jit_traces = 0

        def _traced(fn):
            def wrapped(*a, **kw):
                self._jit_traces += 1
                return fn(*a, **kw)
            return wrapped

        # the executables; jit's shape cache turns the bucket list into
        # "a few padded lengths" -> a few compiles, decode into exactly one,
        # and the chunk program ([1, C] fixed shape) into exactly one more
        # (a model with state takes and returns it behind the pools, donated
        # with them, and commits a prompt's through commit_prefill_state)
        held = (3,) if self.state is not None else ()
        self._decode = jax.jit(_traced(decode_step_in_flight(model)),
                               donate_argnums=(1, 2) + held)
        self._prefill = jax.jit(_traced(model.prefill))
        self._commit = (
            jax.jit(_traced(model.commit_prefill), donate_argnums=(0, 1))
            if self.state is None else
            jax.jit(_traced(model.commit_prefill_state),
                    donate_argnums=(0, 1, 2))
        )
        self._prefill_chunk = jax.jit(_traced(model.prefill_chunk),
                                      donate_argnums=(1, 2) + held)
        # the verify executable only exists when speculation is on: K=0
        # compiles nothing and the engine step never calls _speculate's body
        self._verify = (
            jax.jit(_traced(model.verify_chunk), donate_argnums=(1, 2))
            if self.speculate_k else None
        )
        # compile-heavy steps observe second-scale "service times" that
        # poison the load estimator's EWMA (PR 10); the step loop resets it
        # automatically at the FIRST step that ran clean after any compile,
        # so benches and drills no longer reset by hand
        self._load_est_dirty = False

        self.recompiles = stats.RecompileStats(warn_threshold=2)
        # the verify chunk's own one-signature gate ([1, K+1] fixed shape:
        # drafts, starts and sampling identity are data, never shape)
        self.verify_recompiles = stats.RecompileStats(warn_threshold=2)
        self.decode_steps = 0
        # the decode step in flight (ISSUE 36): (its sampled tokens, still
        # on the device; the [(slot, ActiveSeq)] lanes it ran), or None.
        # `_prev_tok` is the token array the last decode dispatch returned,
        # fetched or not: the next dispatch's `prev_tok` operand
        self._in_flight: Optional[tuple] = None
        self._prev_tok = self._no_tokens()
        # decode dispatches made with the previous step's tokens unfetched,
        # and lanes whose token was dropped at the fetch because the
        # request had gone since the dispatch (EOS a step earlier, a cancel,
        # an expiry): one lane-step each, never a token
        self.overlapped_steps = 0
        self.wasted_lanes = 0
        # ns the engine step under way has spent blocked in `_fetch`: the
        # host WAITING for the device; the rest of the step is the host
        # working. step() zeroes it, `serve.step` and its children report it
        self._wait_ns = 0
        self.tokens_generated = 0
        self.prefill_chunks_committed = 0
        self._chunk_rr_slot = -1  # round-robin cursor over prefilling slots
        # speculative-decode telemetry (acceptance rate = accepted / drafted)
        self.spec_rounds = 0
        self.spec_tokens_drafted = 0
        self.spec_tokens_accepted = 0
        self.spec_pages_trimmed = 0
        # dry-pool telemetry (ISSUE 34): requests that lost their slot to an
        # older one's page, and the token-steps spent rebuilding their K/V
        self.replayed_tokens = 0
        # adaptive-K telemetry: sum of the effective draft length actually
        # used per round — spec_effective_k = sum / rounds
        self.spec_k_eff_sum = 0
        # per-slot prompt-lookup drafters, keyed (slot -> (request_id,
        # drafter)); lazily built, dropped at retirement / engine recovery
        self._drafters: Dict[int, tuple] = {}
        # push-streaming seam (ISSUE 16): the engine bumps a sequence number
        # once per step and wakes pusher threads; ALL socket writes happen on
        # those threads (server.py), so frame emission never blocks a step
        self._stream_cv = threading.Condition()
        self._stream_seq = 0
        # session-level request deadline defaults; per-tenant quota defaults
        # (quota.py deadlines_for) take precedence, explicit per-request
        # values beat both
        self.default_deadline_s = default_deadline_s
        self.default_ttft_deadline_s = default_ttft_deadline_s
        # supervisor state (server mode): restart budget, stall watchdog,
        # and the engine GENERATION — a superseded (stalled) engine thread
        # re-checks the generation when it wakes and exits without touching
        # session state, so recovery never races a zombie
        self.engine_restart_max = int(engine_restart_max)
        self.engine_stall_timeout_s = float(engine_stall_timeout_s)
        self.engine_restarts = 0
        self.engine_error: Optional[BaseException] = None
        self._engine_gen = 0
        # serializes the supersede handshake: the engine flips
        # _engine_in_step only after re-checking its generation UNDER this
        # lock, and the stall recovery bumps the generation under the same
        # lock only while the engine is BETWEEN steps — so a wedged thread
        # that wakes at the wrong moment can never run a step concurrently
        # with the supervisor's pool re-init (check-then-act closed)
        self._gen_lock = threading.Lock()
        self._engine_fault: Optional[BaseException] = None
        self._engine_in_step = False
        self._last_progress = time.monotonic()
        self._stop = threading.Event()
        self._work = threading.Condition()
        self._thread: Optional[threading.Thread] = None

    # -- intake -------------------------------------------------------------
    def submit(
        self,
        prompt: Sequence[int],
        max_new_tokens: Optional[int] = None,
        tenant: str = "default",
        deadline_s: Optional[float] = None,
        ttft_deadline_s: Optional[float] = None,
        temperature: Optional[float] = None,
        top_k: Optional[int] = None,
        seed: Optional[int] = None,
    ) -> RequestHandle:
        """Queue one generation request; raises QuotaExceeded at the front
        door when admission control says no (including a load-aware shed
        when the estimated queue wait exceeds the request's deadline
        budget). Deadlines resolve explicit arg → tenant quota default →
        session default; None all the way down means none. Sampling knobs
        resolve explicit arg → session default (temperature 0 = greedy,
        top_k 0 = off); `seed` defaults to a request-stable derivation so
        crash replay is bitwise (ISSUE 11). Thread-safe."""
        if self.engine_error is not None:
            raise RuntimeError(
                "serving engine died; no new requests accepted"
            ) from self.engine_error
        prompt = list(prompt)
        if not prompt:
            raise ValueError("empty prompt")
        max_new = min(
            self.max_new_limit,
            self.max_new_limit if max_new_tokens is None else int(max_new_tokens),
        )
        if max_new <= 0:
            raise ValueError("max_new_tokens must be positive")
        # the silent-overflow guard (ISSUE 11 satellite): a position past
        # max_len would index params["pos"] out of range inside jit, which
        # XLA CLAMPS silently — wrong tokens, no error. Reject here, named.
        if len(prompt) + max_new > self.cfg.max_len:
            raise ValueError(
                f"max_len exceeded: prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new}) = {len(prompt) + max_new} tokens > the model's "
                f"max_len {self.cfg.max_len}; clamped position embeddings "
                f"would silently corrupt the output"
            )
        if not self._chunked_prompt(prompt):
            # whole-prompt (bucketed) prefill path: prompt must fit a bucket
            _bucket_for(self.buckets, len(prompt))
        need = self.cache.pages_needed(
            len(prompt) + max_new + self.speculate_k
        )
        if need > min(self.cache.max_pages_per_seq, self.cache.num_pages - 1):
            # an undersized pool must reject at the front door, not leave the
            # queue head unadmittable forever
            raise ValueError(
                f"request needs {need} KV pages; pool allows "
                f"{min(self.cache.max_pages_per_seq, self.cache.num_pages - 1)}"
            )
        if deadline_s is None or ttft_deadline_s is None:
            qd = qtd = None
            if self.scheduler.quotas is not None:
                qd, qtd = self.scheduler.quotas.deadlines_for(tenant)
            if deadline_s is None:
                deadline_s = qd if qd is not None else self.default_deadline_s
            if ttft_deadline_s is None:
                ttft_deadline_s = (
                    qtd if qtd is not None else self.default_ttft_deadline_s
                )
        # request trace context: the submitter's current span (the RPC
        # handler's server span, or whatever the caller has open) — the
        # engine thread's queue-wait/prefill/ttft spans stitch under it.
        # Captured BEFORE submit: the engine can admit the request the
        # moment it is queued, so a post-submit assignment would race
        handle = self.scheduler.submit(
            prompt, max_new, tenant, trace_ctx=trace.wire_context(),
            deadline_s=deadline_s, ttft_deadline_s=ttft_deadline_s,
            seed=seed,
            temperature=(
                self.default_temperature if temperature is None
                else float(temperature)
            ),
            top_k=self.default_top_k if top_k is None else int(top_k),
        )
        # the full prompt rides the handle (ISSUE 18): a router takeover
        # sweep reads it back via the `outstanding` RPC so a request whose
        # OWNING replica also dies can be re-submitted to a survivor
        # token-identically — prompt + pinned seed are the whole sampling
        # identity, and after a router death the replica is the only
        # surviving holder of both
        handle.prompt_tokens = prompt
        SERVING_EVENTS.incr("serving_submitted")
        with self._work:
            self._work.notify()
        return handle

    # -- engine steps -------------------------------------------------------
    def _chunked_prompt(self, prompt) -> bool:
        """True when this prompt prefills chunk-by-chunk: longer than the
        per-step chunk budget, OR longer than every bucket (with chunking
        on, NO prompt up to max_len is unservable — a prompt in the gap
        between the largest bucket and a larger chunk size must not be
        rejected where a longer one would be admitted)."""
        return self.prefill_chunk is not None and (
            len(prompt) > self.prefill_chunk or len(prompt) > self.buckets[-1]
        )

    def _sampling_row(self, h) -> tuple:
        """(seeds, temps, top_ks) [1]-shaped device-data for one request's
        prefill — its sampled first token draws through
        fold_in(PRNGKey(seed), 0)."""
        return (
            np.array([h.seed], np.uint32),
            np.array([h.temperature], np.float32),
            np.array([h.top_k], np.int32),
        )

    def _observe_ttft(self, h, ctx) -> None:
        """Time-to-first-token bookkeeping, shared by the whole-prompt and
        chunked prefill paths. Latched once per REQUEST: a crash-replayed
        admission must not observe a second sample (or double-count a miss)
        for the same id."""
        if not h.ttft_observed:
            h.ttft_observed = True
            ttft_s = (h.t_first_token or h.t_submit) - h.t_submit
            TTFT_HISTOGRAM.observe(ttft_s)
            if (h.t_ttft_deadline is not None
                    and h.t_first_token is not None
                    and h.t_first_token > h.t_ttft_deadline):
                # TTFT deadline missed: counted (the client-hedging
                # signal) but NOT fatal — the request has its first token
                # now and only the total deadline cancels work
                obs_metrics.observe_deadline_miss("ttft")
                SERVING_EVENTS.incr("serving_ttft_deadline_missed")
        trace.span_from_monotonic(
            "serving.ttft", h.t_submit,
            trace_id=ctx and ctx.get("t"), parent_id=ctx and ctx.get("s"),
            attrs={"request_id": h.request_id},
        )

    def _fetch(self, on_device) -> np.ndarray:
        """Every blocking device->host fetch of the engine goes through
        here (tests/test_lint_hotloop.py pins it): the value, and the ns the
        host waited for it added to the step's `_wait_ns`, on the span
        ring's clock. What a step's duration holds beyond that is the
        host's own work, which the device trace cannot see once the device
        is never idle."""
        t0 = time.time_ns()
        # sync-ok: the ONE np.asarray of a device value in the engine; each
        # caller names why it may block where it does
        out = np.asarray(on_device)
        self._wait_ns += time.time_ns() - t0
        return out

    def _admit(self, now: Optional[float] = None) -> int:
        """Run prefill for every request joining at this step boundary;
        returns how many whole-prompt prefills ran.
        Prompts longer than `prefill_chunk` (when set) only MARK the slot
        as prefilling here — their K/V commits one chunk per engine step in
        _prefill_chunks, interleaved with decode, so a long prompt joining
        never stalls the already-decoding slots for a whole-prompt forward."""
        import jax.numpy as jnp

        if _faults.get().active and self.scheduler.queue_depth():
            # chaos site: the page pool fails at admission (exhaustion /
            # corruption analog) — the supervisor must re-init the pool and
            # replay; gated on queued work so step=N counts admission
            # ATTEMPTS, not idle engine spins
            _faults.get().maybe_raise("page_exhaust")
        admitted = 0
        for slot, act in self.scheduler.pop_admissions(now):
            h = act.handle
            ctx = h.trace_ctx
            if not act.preempted_s:
                # queue-wait: submit → this admission boundary, under the
                # request's own trace id (measured on the scheduler's
                # monotonic clock, re-anchored to wall-clock for the export);
                # a preempted request's readmission is no second queue wait
                # span-ok: gated, one a REQUEST, under the request's trace
                trace.span_from_monotonic(
                    "serving.queue_wait", h.t_submit,
                    trace_id=ctx and ctx.get("t"),
                    parent_id=ctx and ctx.get("s"),
                    attrs={"request_id": h.request_id},
                )
            if act.prefix_hit or self._chunked_prompt(act.prompt):
                # chunked path: _prefill_chunks advances this slot one chunk
                # per engine step from here on. A prefix-cache hit ALWAYS
                # routes here, starting at the first un-cached token — the
                # aliased pages' KV is already committed, so the hit tokens
                # are prefill work this request simply never does (the page-
                # alignment cap guarantees >= 1 suffix token remains, so the
                # final chunk still emits the sampled first token)
                act.prefill_pos = act.prefix_hit
                continue
            bucket = _bucket_for(self.buckets, len(act.prompt))
            seeds, temps, top_ks = self._sampling_row(h)
            waited = self._wait_ns
            # span-ok: the flight recorder's one ring write an ADMISSION, int
            # attrs: from the prefill's dispatch to the first token on the
            # handle, under the request's own trace where it brought one
            with trace.activate(ctx), trace.flight(
                "serve.admit", request_id=h.request_id, bucket=bucket,
                prompt=len(act.prompt), replay=int(act.replaying),
                queued_ms=int(1e3 * (h.t_admitted - h.t_submit)),
            ) as sp:
                toks = np.zeros((1, bucket), np.int32)
                toks[0, : len(act.prompt)] = act.prompt
                lengths = np.array([len(act.prompt)], np.int32)
                first_tok, *kept = self._prefill(
                    self.params, toks, lengths, seeds, temps, top_ks
                )
                rows = self.cache.slot_row(slot)
                # tp-ok: per-ADMISSION placement of one request's commit
                # operands (never per decode step); the block table the
                # decode loop uses rides the jit dispatch untouched
                where = (
                    jnp.asarray(lengths), jnp.asarray(rows),
                    jnp.zeros((1,), jnp.int32),
                )
                if self.state is None:
                    self.k_pages, self.v_pages = self._commit(
                        self.k_pages, self.v_pages, *kept, *where
                    )
                else:
                    # the prompt's final state goes WHOLE into the slot:
                    # nothing of its last tenant's survives an admission
                    self.k_pages, self.v_pages, self.state = self._commit(
                        self.k_pages, self.v_pages, self.state, *kept,
                        *where, np.array([slot], np.int32),
                    )
                # sync-ok: one tiny fetch per ADMISSION (not per decode step):
                # the prompt's first token, sampled on device (a replay
                # re-derives the one its handle has). The ARRAY is fetched, as
                # the prefill left it: indexing it on the device first would
                # queue an op behind the commit the host can work under
                fresh = act.append(int(self._fetch(first_tok)[0]))
                sp.attrs["wait_ns"] = self._wait_ns - waited
            admitted += 1
            # the whole prompt is committed: register its full pages into
            # the tenant's prefix chain (no-op with the cache off)
            self.cache.commit_prefix(slot, h.tenant, act.prompt,
                                     len(act.prompt))
            # time-to-first-token: prefill emits the first sampled token, so
            # TTFT completes here — span under the request trace + histogram
            if fresh:
                self._observe_ttft(h, ctx)
            SERVING_EVENTS.incr("serving_prefills")
            obs_metrics.observe_layer_passes(
                "prefill", len(act.prompt) * self.layer_passes
            )
            reason = act.finished(self.cfg.eos_id)
            if reason is not None:
                self.scheduler.retire(slot, reason)
        return admitted

    def _prefill_chunks(self) -> None:
        """Advance ONE prefilling slot by exactly one [1, C] chunk — the
        chunked-prefill half of the engine step (ISSUE 11). The chunk size
        IS the per-step prefill budget: each engine step spends at most C
        prompt tokens on prefill no matter how many long prompts are in
        flight (round-robin across prefilling slots keeps them all moving),
        and _decode_once still runs for every fully-prefilled slot in the
        same engine step — so no decode step is ever skipped for a prefill
        and the decode streams' inter-token latency is bounded by decode +
        ONE chunk, not by a whole-prompt forward. The final chunk emits the
        request's first sampled token (one host fetch per REQUEST, there)."""
        prefilling = [
            (slot, act) for slot, act in self.scheduler.active_slots()
            if act.prefilling
        ]
        if not prefilling:
            return
        # round-robin: resume after the last slot serviced so co-resident
        # long prompts share the per-step budget fairly (deterministic —
        # and result-irrelevant: per-slot math never crosses slots)
        prefilling.sort(
            key=lambda sa: (sa[0] <= self._chunk_rr_slot, sa[0])
        )
        for slot, act in prefilling[:1]:
            self._chunk_rr_slot = slot
            h = act.handle
            c = self.prefill_chunk
            start = act.prefill_pos
            piece = act.prompt[start : start + c]
            toks = np.zeros((1, c), np.int32)
            toks[0, : len(piece)] = piece
            lengths = np.array([len(act.prompt)], np.int32)
            starts = np.array([start], np.int32)
            seeds, temps, top_ks = self._sampling_row(h)
            rows = self.cache.slot_row(slot)
            waited = self._wait_ns
            # the first position the chunk's window layers read
            window_from = (max(0, start - self.cache.window + 1)
                           if self.cache.window else 0)
            # span-ok: the flight recorder's one ring write a CHUNK, int
            # attrs: the chunk's dispatch and, behind the prompt's last, the
            # first token's fetch; under the request's trace where it has one
            with trace.activate(h.trace_ctx), trace.flight(
                "serve.chunk", request_id=h.request_id, start=start,
                tokens=len(piece), window_from=window_from,
            ) as sp:
                # ONE dispatch per chunk: forward + commit fused, pages
                # donated through (see model.prefill_chunk docstring)
                tok = self._dispatch_chunk(
                    slot, toks, starts, lengths, rows, seeds, temps, top_ks
                )
                act.prefill_pos = min(start + c, len(act.prompt))
                # incremental registration (ISSUE 19): every full prompt page
                # this chunk just committed enters the tenant's prefix chain
                # NOW — a concurrent same-prefix admission aliases it one
                # step later (only COMMITTED pages ever register, so an alias
                # can never see half-written KV). No-op with the cache off.
                self.cache.commit_prefix(slot, h.tenant, act.prompt,
                                         act.prefill_pos)
                self.prefill_chunks_committed += 1
                SERVING_EVENTS.incr("serving_prefill_chunks")
                obs_metrics.observe_layer_passes(
                    "prefill", (act.prefill_pos - start) * self.layer_passes
                )
                if not act.prefilling:
                    # sync-ok: one host fetch per REQUEST (not per chunk, not
                    # per step) — the FINAL chunk's sampled first token,
                    # which the autoregressive loop needs on host;
                    # intermediate chunks never fetch (their `tok` stays
                    # device-resident and unused)
                    if act.append(int(self._fetch(tok)[0])):
                        self._observe_ttft(h, h.trace_ctx)
                    SERVING_EVENTS.incr("serving_prefills")
                    reason = act.finished(self.cfg.eos_id)
                    if reason is not None:
                        self.scheduler.retire(slot, reason)
                sp.attrs["wait_ns"] = self._wait_ns - waited

    def _ensure_pages(self, wants) -> set:
        """Grow every slot of `wants` [(slot, tokens its pages must cover)]
        to the pages the step about to run writes into (ISSUE 34); returns
        the slots that were PREEMPTED to find them (empty on a pool with
        room, which is every step but a few), which sit this step out.
        Host ints only: the step's timestamp is the one step() took."""
        preempted = self.scheduler.grow(wants, self._last_progress)
        for slot, act, freed in preempted:
            # span-ok: the flight recorder's one ring write a PREEMPTION
            # (not a step), int attrs: the victim's written tokens and the
            # pages it gave back, so a trace names the step that paid
            with trace.flight(
                "serve.preempt", written=act.written, pages=freed
            ):
                self._drafters.pop(slot, None)
                obs_metrics.observe_preemption()
        return {slot for slot, _, _ in preempted}

    def _no_tokens(self):
        """`prev_tok` for a decode dispatch no step went before (the first,
        an engine restart's): zeros no lane reads, placed as a decode step's
        tokens come out (replicated over a TP mesh, plainly on one chip), so
        that dispatch runs the one executable and no second lowering of it."""
        import jax
        import jax.numpy as jnp

        zeros = jnp.zeros((self.cache.max_slots,), jnp.int32)
        if self.model.mesh is None:
            return zeros
        return jax.device_put(zeros, jax.sharding.NamedSharding(
            self.model.mesh, jax.sharding.PartitionSpec()
        ))

    def _make_state(self):
        """Zeroed per-slot state [max_slots, ...] and counters, as the model
        declares them; None where it declares neither. Rebuilt with the
        pools on an engine restart (the replayed prompts rewrite it)."""
        import jax.numpy as jnp

        slots = self.cache.max_slots
        state = {k: jnp.zeros((slots,) + tuple(shape), dtype)
                 for k, (shape, dtype) in self.model.state_spec().items()}
        state.update({k: jnp.zeros(shape, dtype)
                      for k, (shape, dtype) in self.model.counter_spec().items()})
        return state or None

    def _dispatch_decode(self, *lanes):
        """The decode executable over the carried pools (and state): the
        sampled tokens, still on the device, where `_collect` finds them
        one dispatch later."""
        if self.state is None:
            self.k_pages, self.v_pages, tok = self._decode(
                self.params, self.k_pages, self.v_pages, *lanes
            )
        else:
            self.k_pages, self.v_pages, self.state, tok = self._decode(
                self.params, self.k_pages, self.v_pages, self.state, *lanes
            )
        return tok

    def _dispatch_chunk(self, slot, toks, starts, lengths, rows, *sampling):
        """The chunk executable, likewise; a model with state continues the
        slot's own (from the empty state at a prompt's first chunk)."""
        if self.state is None:
            self.k_pages, self.v_pages, tok = self._prefill_chunk(
                self.params, self.k_pages, self.v_pages, toks, starts,
                lengths, rows, *sampling,
            )
        else:
            self.k_pages, self.v_pages, self.state, tok = self._prefill_chunk(
                self.params, self.k_pages, self.v_pages, self.state, toks,
                starts, lengths, rows, np.array([slot], np.int32), *sampling,
            )
        return tok

    def read_counters(self) -> Dict[str, np.ndarray]:
        """The model's device counters, fetched NOW (never by a step), as
        totals since the session began: each read adds what the device
        counted since the last one, modulo the counter's 2**32, to a host
        total, and feeds the difference to obs.metrics. Off the hot loop:
        a scrape's, a benchmark reader's, a test's."""
        state = self.state
        if state is None:
            return {}
        out = {}
        for name in self.model.counter_spec():
            try:
                now = np.asarray(state[name])
            except RuntimeError:
                # read from another thread than the engine's while a step
                # donated the buffer: this read adds nothing, the next one
                # finds what was counted meanwhile
                now = None
            last, total = self._counters_read.get(name, (None, None))
            if last is None and now is not None:
                last, total = np.zeros_like(now), np.zeros(now.shape, np.int64)
            if now is not None:
                delta = (now - last).astype(np.int64)  # unsigned: wraps as the device's
                total = total + delta
                self._counters_read[name] = (now, total)
                obs_metrics.observe_moe_counters(name, delta)
            if total is not None:
                out[name] = total
        return out

    def _drafter_for(self, slot: int, act):
        """This slot's (drafter, adaptive-K cell), rebuilt when the slot was
        recycled to a different request (stale entries are bounded by
        max_slots; retirement and engine recovery drop them eagerly). The
        K cell is derived state exactly like the drafter: a replay regrows
        the same acceptance history, hence the same K at every round —
        which keeps crash recovery bitwise with adaptive K on."""
        from paddle_tpu.serving.speculation import PromptLookupDrafter

        rid = act.handle.request_id
        ent = self._drafters.get(slot)
        if ent is None or ent[0] != rid:
            ent = (rid, PromptLookupDrafter(), [self.speculate_k])
            self._drafters[slot] = ent
        return ent[1], ent[2]

    def _speculate(self) -> set:
        """One prompt-lookup draft/verify round for EVERY eligible slot
        (ISSUE 16): the slot's drafter proposes up to K continuation tokens
        from the request's own committed n-grams, one [1, K+1] verify_chunk
        call scores them all against the paged cache, and the matched prefix
        commits — the first divergent token comes free from the verify
        logits, so a round always advances the slot by >= 1 token. Slots
        with no draft (or exhausted budget) fall through to _decode_once.

        Eligibility is a pure function of the REQUEST's own state (its
        committed tokens decide whether a draft exists), never of batch
        composition or engine scheduling — that is what keeps crash replay
        and router failover bitwise at temperature > 0: a replay regrows the
        same committed prefix, drafts the same tokens, samples through the
        same (seed, emitted-token-index) keys, and accepts the same prefix.
        Returns the slots advanced this round (skipped by _decode_once)."""
        from paddle_tpu.serving.speculation import next_draft_k

        advanced: set = set()
        if not self.speculate_k:
            return advanced
        # the drafters read the handles' tokens on the host: the step in
        # flight ends before the round
        self._drain()
        # a replaying slot rebuilds its K/V through the decode lanes first
        candidates = [
            (slot, act) for slot, act in self.scheduler.active_slots()
            if not act.prefilling and not act.replaying
        ]
        if candidates and _faults.get().active:
            # chaos site (spec_replay): the engine faults mid-speculation —
            # recovery must replay the in-flight drafts bitwise; gated on
            # live candidates so step=N counts real verify attempts
            _faults.get().maybe_raise("decode_raise")
        k = self.speculate_k
        for slot, act in candidates:
            h = act.handle
            remaining = h.max_new_tokens - act.generated
            if remaining <= 1 or self.scheduler.slots[slot] is not act:
                continue  # nothing left to draft for; or preempted this step
            drafter, kcell = self._drafter_for(slot, act)
            drafter.sync(act.prompt, h.tokens)
            # adaptive K (ROADMAP 1a): draft up to this request's CURRENT
            # effective K — grown/shrunk from its own acceptance history by
            # the pure next_draft_k rule — while the verify call below stays
            # [1, K_max+1] (short drafts zero-pad, signature stays 1)
            draft = drafter.draft(min(k, kcell[0]))
            if not draft:
                continue
            # the round scatters K+1 positions: grow to them first (a dry
            # pool may preempt this very slot, the youngest)
            if slot in self._ensure_pages([(slot, act.next_pos + k + 1)]):
                continue
            toks = np.zeros((1, k + 1), np.int32)
            toks[0, 0] = act.last_token
            toks[0, 1:1 + len(draft)] = draft  # short drafts zero-pad
            starts = np.array([act.next_pos], np.int32)
            steps0 = np.array([act.generated], np.int32)
            seeds, temps, top_ks = self._sampling_row(h)
            rows = self.cache.slot_row(slot)
            # one-signature assertion data: the verify shape is [1, K+1]
            # no matter the draft, the request mix, or the round
            self.verify_recompiles.record(
                stats.batch_signature(
                    {"tokens": toks, "starts": starts, "block_rows": rows,
                     "seeds": seeds, "steps0": steps0, "temps": temps,
                     "top_ks": top_ks}
                )
            )
            # span-ok: ring-buffer write only, constant name, int attrs —
            # the verify loop is hot-path like the decode loop (lint-pinned)
            with trace.span(
                "serving.verify_chunk", request_id=h.request_id,
                drafted=len(draft),
            ):
                self.k_pages, self.v_pages, sampled = self._verify(
                    self.params, self.k_pages, self.v_pages, toks,
                    starts, rows, seeds, steps0, temps, top_ks,
                )
                # sync-ok: ONE fetch per verify round — the K+1 sampled
                # tokens, which the host needs to run acceptance (the
                # autoregressive loop's EOS/budget checks ride the same
                # fetch); pages stay donated through, logits never land
                out = self._fetch(sampled)
            act.engine_steps += 1
            obs_metrics.observe_layer_passes(
                "decode", (k + 1) * self.layer_passes
            )
            limit = min(len(draft), remaining - 1)
            n_match = 0
            while n_match < limit and int(out[n_match]) == draft[n_match]:
                n_match += 1
            emit = [int(out[i]) for i in range(n_match + 1)]
            # never commit past EOS: a drafted continuation that crosses the
            # stop token truncates there (the tail was never "emitted")
            for j, t in enumerate(emit):
                if t == self.cfg.eos_id:
                    emit = emit[: j + 1]
                    break
            for t in emit:
                act.append(t)
            self.tokens_generated += len(emit)
            self.spec_rounds += 1
            self.spec_tokens_drafted += len(draft)
            self.spec_tokens_accepted += max(0, len(emit) - 1)
            self.spec_k_eff_sum += len(draft)
            kcell[0] = next_draft_k(
                kcell[0], k, len(draft), max(0, len(emit) - 1)
            )
            SERVING_EVENTS.incr("serving_spec_rounds")
            SERVING_EVENTS.incr("serving_spec_accepted", max(0, len(emit) - 1))
            advanced.add(slot)
            reason = act.finished(self.cfg.eos_id)
            if reason is not None:
                self._drafters.pop(slot, None)
                self.scheduler.retire(slot, reason)
            else:
                # what the rejection left past the accepted frontier goes
                # back: the slot keeps its written tokens' pages and the
                # page of its next write
                self.spec_pages_trimmed += self.cache.trim(
                    slot, act.next_pos + 1
                )
        return advanced

    def _decode_lanes(self, skip: frozenset) -> list:
        """[(slot, ActiveSeq)] the next decode step runs: every occupied,
        fully-prefilled slot outside `skip` that still has a token to draw.
        A lane whose step in flight reaches its budget is not dispatched
        again, so a length finish wastes nothing."""
        return [
            (slot, act) for slot, act in self.scheduler.active_slots()
            if not act.prefilling and slot not in skip
            and act.generated + act.in_flight < act.handle.max_new_tokens
        ]

    def _decode_once(self, skip: frozenset = frozenset()) -> int:
        """One continuous-batching decode step: every active, fully-prefilled
        slot advances by one token inside the single fixed-shape executable
        (slots mid-chunked-prefill sit this one out as inactive lanes — their
        KV is still being committed; slots in `skip` already advanced through
        a speculative verify round this step).

        The step is DISPATCHED, and the tokens fetched are those of the step
        dispatched before it (`_collect`), so the device runs this step
        under everything the host does until the next dispatch. A lane with
        a step in flight is built from what the host knows without that
        step's token: position, sampling index and pages one further, the
        token itself taken on the device. Returns the lanes dispatched (0
        where nothing was)."""
        def writes(lanes):
            # every lane writes the position behind its last token (the one
            # in flight counted): the page it lands in first
            return [(slot, act.next_pos + act.in_flight + 1)
                    for slot, act in lanes]

        active = self._decode_lanes(skip)
        if self._in_flight is not None and not self.cache.can_grow(
            writes(active)
        ):
            # the pool is about to preempt: the step in flight ends first, so
            # that the victim's last token is on its handle before it
            # replays, and an EOS among those tokens gives its pages back
            # before anybody is preempted for want of them
            self._drain()
            active = self._decode_lanes(skip)
        if not active:
            # nothing to run ahead of: the step in flight, if any, ends here
            self._drain()
            return 0
        if _faults.get().active:
            # chaos site: the engine faults mid-decode — the supervisor must
            # restart it, re-init the page pool and replay in-flight work;
            # gated on live slots so step=N counts real decode attempts
            _faults.get().maybe_raise("decode_raise")
        lost = self._ensure_pages(writes(active))
        if lost:
            active = [sa for sa in active if sa[0] not in lost]
            if not active:
                return 0
        s = self.cache.max_slots
        tokens = np.zeros(s, np.int32)
        from_prev = np.zeros(s, bool)
        positions = np.zeros(s, np.int32)
        act_mask = np.zeros(s, bool)
        seeds = np.zeros(s, np.uint32)
        steps = np.zeros(s, np.int32)
        temps = np.zeros(s, np.float32)
        top_ks = np.zeros(s, np.int32)
        replaying = 0  # lanes that rebuild K/V and can complete no token
        for slot, act in active:
            ahead = act.in_flight
            replaying += act.generated + ahead < len(act.handle.tokens)
            if not ahead:
                tokens[slot] = act.last_token
            elif act.replaying:
                # the token the step in flight rebuilds: the handle has it
                tokens[slot] = act.handle.tokens[act.generated]
            else:
                from_prev[slot] = True  # sampled by the step in flight
            positions[slot] = act.next_pos + ahead
            act_mask[slot] = True
            # sampling identity rides as DATA: the token this step emits for
            # the slot is draw `generated` of request `seed` — exactly what a
            # crash replay re-draws (bitwise), and still one decode signature
            seeds[slot] = act.handle.seed
            steps[slot] = act.generated + ahead
            temps[slot] = act.handle.temperature
            top_ks[slot] = act.handle.top_k
        bt = self.cache.block_table()
        prev_tok = self._prev_tok
        # what the lanes hold: pages of the full pool and of the rings, and
        # the positions their attention reads (perfbench's
        # kv_bytes_per_context_token)
        pages_full, pages_window = self.cache.held_pages(
            [slot for slot, _ in active])
        context = int(positions.sum()) + len(active)
        # zero-recompile assertion data: the decode signature must be the
        # same every step no matter the request mix (fixed [max_slots] shape)
        self.recompiles.record(
            stats.batch_signature(
                {"tokens": tokens, "prev_tok": prev_tok, "from_prev": from_prev,
                 "positions": positions, "active": act_mask,
                 "block_table": bt, "seeds": seeds, "steps": steps,
                 "temps": temps, "top_ks": top_ks}
            )
        )
        waited = self._wait_ns
        # span-ok: the flight recorder's one ring write a decode step, int
        # attrs: the dispatch and the fetch of the step BEFORE it (its
        # `wait_ns`), the batch the step's weight traffic is shared over
        # (perfbench: `slots`) and how much of it only rebuilds K/V
        with trace.flight(
            "serve.decode", slots=len(active), layer_passes=self.layer_passes,
            replaying=replaying, pages_full=pages_full,
            pages_window=pages_window, context_tokens=context,
        ) as sp:
            next_tok = self._dispatch_decode(
                tokens, prev_tok, from_prev, positions, act_mask, bt, seeds,
                steps, temps, top_ks,
            )
            for _, act in active:
                act.in_flight += 1
            before, self._in_flight = self._in_flight, (next_tok, active)
            self._prev_tok = next_tok
            self.decode_steps += 1
            SERVING_EVENTS.incr("serving_decode_steps")
            obs_metrics.observe_decode_step(len(active), self.layer_passes)
            if before is not None:
                self.overlapped_steps += 1
                obs_metrics.observe_decode_overlapped()
                self._collect(before)
            sp.attrs["wait_ns"] = self._wait_ns - waited
        return len(active)

    def _drain(self) -> None:
        """End the step in flight now: what reads the sampled VALUES on the
        host calls this before it dispatches anything more, and a step that
        found nothing to dispatch ends here. No-op with nothing in flight."""
        flight, self._in_flight = self._in_flight, None
        if flight is not None:
            self._collect(flight)

    def _collect(self, flight: tuple) -> None:
        """Fetch one decode step's sampled tokens and hand each lane's to its
        request: `append`, `finished`, `retire`. A lane whose request left
        its slot since the dispatch (an EOS in the step before, a cancel or
        an expiry reaped meanwhile) is DROPPED: its token never reaches a
        handle. That lane's K/V write, and in a model with state its state
        update, landed in a page and a slot the request still held when the
        step was dispatched, with the block table of that moment; whoever
        is given the page or the slot next writes it by a commit or a step
        dispatched LATER on the same device stream, and reads nothing there
        that it did not write itself, so the order is safe."""
        next_tok, lanes = flight
        slots = self.scheduler.slots
        live = [(slot, act) for slot, act in lanes if slots[slot] is act]
        for _, act in lanes:
            act.in_flight -= 1
        wasted = len(lanes) - len(live)
        replayed = 0
        if live:
            # sync-ok: the ONE sanctioned fetch in the serving hot loop — the
            # sampled token ids, which the autoregressive loop needs on host to
            # detect EOS/budget and stream tokens; everything else stays device-
            # resident (pages are donated through, logits never leave the device)
            toks = self._fetch(next_tok)
            for slot, act in live:
                if act.append(toks[slot]):
                    self.tokens_generated += 1
                else:
                    replayed += 1  # a preempted request's K/V, rebuilt
                act.engine_steps += 1
                reason = act.finished(self.cfg.eos_id)
                if reason is not None:
                    self._drafters.pop(slot, None)
                    self.scheduler.retire(slot, reason)
        behind = self._in_flight
        if behind is not None and not any(
            slots[slot] is act for slot, act in behind[1]
        ):
            # the step dispatched behind this one has lost its every lane to
            # what was just learned: nobody waits for it, so it is not
            # fetched, and nothing stays in flight on an idle engine
            self._in_flight = None
            for _, act in behind[1]:
                act.in_flight -= 1
            wasted += len(behind[1])
        if replayed:
            self.replayed_tokens += replayed
            obs_metrics.observe_replayed_tokens(replayed)
        if wasted:
            self.wasted_lanes += wasted
            obs_metrics.observe_wasted_lanes(wasted)

    def step(self, now: Optional[float] = None) -> bool:
        """One engine iteration: reap expired/cancelled requests, then
        retire/admit at the boundary, then one prefill chunk per prefilling
        slot, then one decode step — chunked prefill and decode INTERLEAVE
        inside every engine step rather than alternate across them. The
        decode step is dispatched and left in flight; the tokens this call
        hands to the handles are those of the step the call BEFORE it
        dispatched (an admission's prefill and a chunk are dispatched behind
        the step in flight, and their own first-token fetch waits for both).
        Returns True when any work was done."""
        # span-ok: the flight recorder's one ring write an engine step that
        # did work, int attrs: what the step ran and `wait_ns`, the part of
        # it the host spent blocked in `_fetch` (the rest is the host's own
        # work). `serve.admit`, `serve.chunk`, `serve.preempt` and
        # `serve.decode` are its children. An idle call records nothing
        with trace.flight("serve.step") as sp:
            if now is None:
                # clock-ok: the ONE sanctioned wall-clock read per engine
                # step — deadline expiry, cancellation reaping and admission
                # stamps all batch off this single timestamp (a per-request
                # read would scale with occupancy;
                # tests/test_lint_hotloop.py pins this site)
                now = time.monotonic()
            self._last_progress = now  # supervisor stall-watchdog heartbeat
            self._wait_ns = 0
            traces_before = self._jit_traces
            in_flight = self._in_flight is not None  # dispatched on, or collected
            chunks_before = self.prefill_chunks_committed
            preempted_before = self.scheduler.preemptions
            self.scheduler.reap(now)
            admitted = self._admit(now)
            self._prefill_chunks()
            before = self.decode_steps
            spec_before = self.spec_rounds
            advanced = self._speculate()
            slots = self._decode_once(advanced)
            obs_metrics.set_kv_pages_in_use(self.cache.pages_in_use)
            self._notify_streams()
            # auto EWMA reset (ISSUE 17): a step that compiled an executable
            # retired requests with second-scale service times; the first
            # CLEAN step afterwards forgets the poisoned estimate and lets
            # steady-state retirements re-seed it — a later first-hit bucket
            # compile re-arms the same healing
            if self._jit_traces != traces_before:
                self._load_est_dirty = True
            elif self._load_est_dirty:
                self._load_est_dirty = False
                self.scheduler.reset_load_estimate()
            did = {
                "admitted": admitted,
                "chunks": self.prefill_chunks_committed - chunks_before,
                "decoded": self.decode_steps - before,
                "slots": slots,
                "preempted": self.scheduler.preemptions - preempted_before,
                "wait_ns": self._wait_ns,
            }
            if any(did.values()):
                sp.attrs = did
            else:
                sp.drop()
        return (
            self.decode_steps != before
            or self.spec_rounds != spec_before
            or in_flight
            or bool(self.scheduler.active_slots())
        )

    # -- push-streaming seam (ISSUE 16) -------------------------------------
    def _notify_streams(self) -> None:
        """Wake frame pushers at this step boundary. The engine's entire
        contribution to push streaming is this sequence-number bump: no
        socket writes, no file I/O, no per-stream work — pusher threads
        (server.py) diff token lists and emit frames on their own time, so
        a slow or dead client can never block an engine step."""
        with self._stream_cv:
            self._stream_seq += 1
            self._stream_cv.notify_all()

    def stream_wait(self, seq: int, timeout: float = 0.1) -> int:
        """Block (pusher-thread side) until the engine advances past step
        sequence `seq` or `timeout` elapses; returns the current sequence.
        The timeout doubles as the liveness tick — pushers re-check their
        handles even when the engine idles (cancellations complete without
        a step)."""
        with self._stream_cv:
            if self._stream_seq == seq:
                self._stream_cv.wait(timeout)
            return self._stream_seq

    def run_until_idle(self) -> None:
        """Drive the engine on the calling thread until queue + slots drain
        (the single-threaded harness used by tests and the bench)."""
        while self.scheduler.has_work():
            self.step()

    # -- supervised engine thread (server mode) -----------------------------
    def serve_forever(self) -> "ServingSession":
        """Start the SUPERVISED engine: a supervisor thread spawns the
        engine thread and watches it — a fault or stall triggers recovery
        (pool re-init + in-flight replay) up to `engine_restart_max` times,
        after which every outstanding request fails `engine_error` (the
        trainer's precedent: fail loudly, never look healthy-but-slow).

        Idempotent: a second call while supervised is a no-op — two
        supervisors would race two engine threads over the same donated
        page pools (ServingServer.start + a manual caller is the easy way
        to get here)."""
        if self._thread is not None:
            return self
        self._thread = threading.Thread(
            target=self._supervise, name="serving-supervisor", daemon=True
        )
        self._thread.start()
        return self

    def _engine_loop(self, gen: int) -> None:
        """The engine proper, pinned to generation `gen`: superseded threads
        (a stall recovery bumped the generation while this one was wedged)
        notice at the loop guard and exit WITHOUT touching session state."""
        while not self._stop.is_set() and self._engine_gen == gen:
            if not self.scheduler.has_work():
                with self._work:
                    self._work.wait(timeout=0.05)
                continue
            if _faults.maybe_stall(
                "engine_stall", env="PADDLE_TPU_SERVING_STALL_S",
                default_s=300.0,
            ):
                continue  # woke superseded: the loop guard re-checks gen
            # _engine_in_step gates the stall watchdog: a slow step (first-
            # step jit compile can take seconds) must never read as a stall —
            # only a wedge BETWEEN steps (the seeded site above, the only
            # place recovery can safely supersede this thread) counts. The
            # gen re-check and the flag flip are ATOMIC under _gen_lock: a
            # zombie waking between the loop guard and here would otherwise
            # race the supervisor's bump-then-recover into a concurrent step
            with self._gen_lock:
                if self._stop.is_set() or self._engine_gen != gen:
                    return
                self._engine_in_step = True
            try:
                self.step()
            except Exception as e:  # noqa: BLE001 — hand the fault to the
                # supervisor (recovery or give-up happens there, off the
                # engine thread); BaseException stays fatal on purpose
                self._engine_fault = e
                return
            finally:
                self._engine_in_step = False

    def _supervise(self) -> None:
        log = logging.getLogger("paddle_tpu.serving")
        poll_s = max(0.02, min(0.25, self.engine_stall_timeout_s / 4.0))
        while not self._stop.is_set():
            gen = self._engine_gen
            self._engine_fault = None
            # clock-ok: once per engine (re)start — the watchdog anchor
            self._last_progress = time.monotonic()
            eng = threading.Thread(
                target=self._engine_loop, args=(gen,),
                name="serving-engine", daemon=True,
            )
            eng.start()
            cause: Optional[str] = None
            busy_since: Optional[float] = None
            stale_polls = 0
            while not self._stop.is_set():
                eng.join(timeout=poll_s)
                if not eng.is_alive():
                    if self._engine_fault is None:
                        return  # clean stop
                    cause = "fault"
                    break
                # stall watchdog: only meaningful while work is pending AND
                # the engine sits between steps (an in-flight step may be a
                # multi-second first compile — and a mid-step thread cannot
                # be superseded safely anyway); anchored at the LATER of
                # last step start / when the queue last became non-empty, so
                # idle periods never read as stalls and a flood of submits
                # cannot mask a real one. Two consecutive stale samples
                # required, closing the microsecond between-steps window.
                now = time.monotonic()  # clock-ok: watchdog poll (4-16 Hz)
                if not self.scheduler.has_work():
                    busy_since = None
                    stale_polls = 0
                    continue
                if busy_since is None:
                    busy_since = now
                if (not self._engine_in_step
                        and now - max(self._last_progress, busy_since)
                        > self.engine_stall_timeout_s):
                    stale_polls += 1
                    if stale_polls >= 2:
                        # atomic supersede: bump the generation under the
                        # same lock the engine takes to enter a step, and
                        # only while it is still BETWEEN steps — a zombie
                        # that slipped into step() since the last sample
                        # keeps its generation and we go back to watching
                        # instead of re-initializing pools under its feet
                        with self._gen_lock:
                            if not self._engine_in_step:
                                self._engine_gen += 1
                                cause = "stall"
                        if cause is not None:
                            break
                        stale_polls = 0
                else:
                    stale_polls = 0
            if self._stop.is_set():
                return
            if cause == "fault":
                # the engine thread exited on its own (we saw it dead), so
                # no zombie can race recovery — bump for uniform invariants
                with self._gen_lock:
                    self._engine_gen += 1
            err = self._engine_fault
            if self.engine_restarts >= self.engine_restart_max:
                self.engine_error = err or RuntimeError(
                    f"serving engine stalled >"
                    f"{self.engine_stall_timeout_s}s and the restart budget "
                    f"({self.engine_restart_max}) is exhausted"
                )
                log.error(
                    "serving engine %s and restart budget (%d) exhausted; "
                    "failing %d outstanding request(s) and stopping",
                    cause, self.engine_restart_max,
                    len(self.scheduler.active_slots())
                    + self.scheduler.queue_depth(),
                )
                self._fail_outstanding()
                self._stop.set()
                return
            self._recover(cause, err, log)

    def _recover(self, cause: str, err: Optional[BaseException],
                 log: logging.Logger) -> None:
        """Engine restart: fresh page pool (the dead engine's donated
        buffers are consumed), in-flight requests replayed from their
        prompts (greedy decode is deterministic — result-transparent),
        past-deadline ones failed with the named reason."""
        t0 = time.monotonic()  # clock-ok: once per engine restart
        self.engine_restarts += 1
        SERVING_EVENTS.incr("serving_engine_restarts")
        obs_metrics.observe_engine_restart(cause)
        # the step in flight ends first, as far as the dead engine left its
        # tokens readable: a request it finished is complete and is not
        # replayed; the others replay from their prompts whatever it sampled
        try:
            self._drain()
        except Exception:  # noqa: BLE001 — the step died with the engine,
            pass           # and _drain had let go of it before it fetched
        self._prev_tok = self._no_tokens()
        requeued, expired = self.scheduler.requeue_active(t0)
        self.cache.reset()
        self.k_pages, self.v_pages = self.cache.make_pools()
        if self.state is not None:
            # what was read so far stays counted; what the dead engine's
            # (consumed) buffers counted since the last read is lost
            self.state = self._make_state()
            self._counters_read = {
                k: (np.zeros_like(last), total)
                for k, (last, total) in self._counters_read.items()
            }
        # drafters are derived state: replayed requests regrow them from
        # the prompt (deterministically — same drafts, same acceptances)
        self._drafters.clear()
        SERVING_EVENTS.incr("serving_requests_replayed", requeued)
        trace.span_from_monotonic(
            "serving.engine_restart", t0,
            attrs={"cause": cause, "requeued": requeued, "expired": expired},
        )
        log.warning(
            "serving engine %s (%r); restart %d/%d: page pool re-initialized, "
            "%d in-flight request(s) replayed, %d failed past-deadline",
            cause, err, self.engine_restarts, self.engine_restart_max,
            requeued, expired,
        )

    def _fail_outstanding(self) -> None:
        """Complete every waiting + running handle as CANCELLED('engine_error')
        so result() raises instead of timing out; pages are released for
        accounting hygiene even though the engine is done."""
        sch = self.scheduler
        self._in_flight = None  # nobody is left to take its tokens
        with sch.lock:
            waiting = list(sch.waiting)
            sch.waiting.clear()
            running = [(i, a) for i, a in enumerate(sch.slots) if a is not None]
            for slot, _ in running:
                sch.slots[slot] = None
                self.cache.release(slot)
        for w in waiting:
            if sch.quotas is not None:
                sch.quotas.release(w.handle.tenant)
            w.handle._complete(RequestHandle.CANCELLED, "engine_error")
        for _, act in running:
            if sch.quotas is not None:
                sch.quotas.release(act.handle.tenant)
            act.handle._complete(RequestHandle.CANCELLED, "engine_error")

    def stop(self) -> None:
        self._stop.set()
        with self._gen_lock:
            self._engine_gen += 1  # supersede any wedged engine thread
        with self._work:
            self._work.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
        if self._thread is None or not self._thread.is_alive():
            # nothing stays in flight behind a stopped engine: the last
            # step's tokens reach their handles
            try:
                self._drain()
            except Exception:  # noqa: BLE001 — the engine died holding it,
                pass           # and _drain had let go of it before it fetched

    def cancel_tenant(self, tenant: str) -> int:
        return self.scheduler.cancel_tenant(tenant)

    # -- telemetry ----------------------------------------------------------
    def progress_marker(self) -> tuple:
        """A tuple that changes whenever the engine makes ANY observable
        progress (decode steps, prefill chunks, retirements, cancellations).
        The fleet ReplicaAgent compares successive markers to self-fence a
        wedged engine: work pending + an unchanged marker past the fence
        window + the engine parked between steps = stop claiming liveness
        (serving/fleet.py)."""
        sch = self.scheduler
        return (
            self.decode_steps,
            self.prefill_chunks_committed,
            sch.completed,
            sch.cancelled,
            self.engine_restarts,
            # a single-stream speculative workload can advance through
            # verify rounds alone (decode skipped every step) — without
            # this term the fleet agent would self-fence a healthy engine
            self.spec_rounds,
        )

    def decode_shape_signatures(self) -> int:
        """Distinct decode-step input signatures seen — 1 means the entire
        serving lifetime shared one compiled decode program."""
        return self.recompiles.total_signatures()

    def decode_step_hlo(self) -> str:
        """Compiled HLO text of the decode executable at this session's
        fixed shapes — for inspection (chip_smoke.py reads which attention
        path the step took out of it). Lowers from avals only, so it is
        safe beside a live engine; it does trace, so the warm-up detector
        sees one more compile."""
        import jax

        def aval(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)

        s = self.cache.max_slots
        i32, f32 = np.zeros(s, np.int32), np.zeros(s, np.float32)
        lane = np.zeros(s, bool)
        held = () if self.state is None else (jax.tree.map(aval, self.state),)
        return self._decode.lower(
            jax.tree.map(aval, self.params), jax.tree.map(aval, self.k_pages),
            jax.tree.map(aval, self.v_pages), *held, i32, aval(self._prev_tok), lane, i32,
            lane, self.cache.block_table(), np.zeros(s, np.uint32), i32, f32,
            i32,
        ).compile().as_text()

    def verify_shape_signatures(self) -> int:
        """Distinct verify_chunk input signatures seen — 1 means every
        speculative round shared one compiled [1, K+1] program (0 when
        speculation never ran)."""
        return self.verify_recompiles.total_signatures()

    def stats(self) -> Dict:
        sch = self.scheduler
        return {
            "decode_steps": self.decode_steps,
            # ISSUE 36: decode steps dispatched with the step before still
            # unfetched (over decode_steps: the share the device ran under
            # the host's work), and lanes dropped at a fetch because their
            # request had gone since the dispatch
            "overlapped_steps": self.overlapped_steps,
            "wasted_lanes": self.wasted_lanes,
            # TP accounting from SHARDING METADATA, not trust: what one chip
            # actually holds (replicated leaves count fully, sharded 1/N)
            "tp": self.model.tp_size,
            "param_bytes_per_chip": stats.per_chip_tree_bytes(self.params),
            "pool_bytes_per_chip": stats.per_chip_tree_bytes(
                [self.k_pages, self.v_pages]
            ),
            # what the model's declared state holds beside the pools
            "state_bytes_per_chip": stats.per_chip_tree_bytes(self.state or []),
            "tokens_generated": self.tokens_generated,
            "decode_shape_signatures": self.decode_shape_signatures(),
            "queue_depth": sch.queue_depth(),
            "active_slots": len(sch.active_slots()),
            "max_slots": self.cache.max_slots,
            # pages free NOW and pages some slot or the prefix index holds
            # NOW (ISSUE 34): a request holds what it has written plus the
            # page of its next write, so neither counts what live requests
            # will still ask for
            "free_pages": self.cache.free_pages,
            "pages_in_use": self.cache.pages_in_use,
            "preemptions": sch.preemptions,
            "replayed_tokens": self.replayed_tokens,
            "completed": sch.completed,
            "rejected": sch.rejected,
            "cancelled": sch.cancelled,
            "shed": sch.shed,
            "deadline_misses": sch.deadline_misses,
            "pages_recycled_on_cancel": sch.pages_recycled_on_cancel,
            "engine_restarts": self.engine_restarts,
            "estimated_queue_wait_s": round(sch.estimate_wait_s(), 4),
            "prefill_buckets": list(self.buckets),
            "prefill_chunk": self.prefill_chunk,
            "prefill_chunks_committed": self.prefill_chunks_committed,
            "default_temperature": self.default_temperature,
            "default_top_k": self.default_top_k,
            "speculate_k": self.speculate_k,
            "spec_rounds": self.spec_rounds,
            "spec_tokens_drafted": self.spec_tokens_drafted,
            "spec_tokens_accepted": self.spec_tokens_accepted,
            "spec_acceptance_rate": round(
                self.spec_tokens_accepted / self.spec_tokens_drafted, 4
            ) if self.spec_tokens_drafted else 0.0,
            "spec_pages_trimmed": self.spec_pages_trimmed,
            # adaptive draft length (ISSUE 19 satellite): mean tokens
            # actually DRAFTED per verify round — converges up toward K on
            # accepting streams, down toward 1 when drafts keep missing
            "spec_effective_k": round(
                self.spec_k_eff_sum / self.spec_rounds, 4
            ) if self.spec_rounds else 0.0,
            "verify_shape_signatures": self.verify_shape_signatures(),
            # shared-prefix cache (ISSUE 19): hit rate + sharing/COW/eviction
            # counters; stable keys (zeros) with the cache off
            **self.cache.prefix_stats(),
        }


def make_demo_session(
    vocab: int = 128,
    n_layers: int = 2,
    d_model: int = 32,
    n_heads: int = 2,
    seed: int = 0,
    tp: int = 0,
    **session_kw,
) -> ServingSession:
    """A small seeded model + session (CLI --demo, benches, tests).

    tp > 1 builds the 2-D ("data"=1, "model"=tp) rules mesh and serves
    tensor-parallel over tp chips: params and the KV page pool shard over
    the model axis, tokens stay identical to tp=0/1 (the single-chip
    oracle) — pinned in tests/test_tp_serving.py."""
    import jax

    buckets = session_kw.pop("prefill_buckets", (16, 32, 64))
    max_new = session_kw.pop("max_new_limit", 64)
    # chunked prefill serves prompts beyond the largest bucket, so callers
    # exercising it can ask for more position room than the bucket default
    max_len = session_kw.pop("max_len", None) or max(buckets) + max_new
    mesh = None
    if tp and int(tp) > 1:
        from paddle_tpu.parallel.rules import make_tp_mesh

        mesh = make_tp_mesh(int(tp))
    model = ServableLM(LMConfig(
        vocab=vocab, n_layers=n_layers, d_model=d_model, n_heads=n_heads,
        max_len=max_len,
    ), mesh=mesh)
    params = model.init_params(jax.random.PRNGKey(seed))
    return ServingSession(
        model, params, prefill_buckets=buckets, max_new_limit=max_new,
        **session_kw,
    )
