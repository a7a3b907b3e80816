"""Device-prefetching input pipeline.

The reference hides host input prep behind device execution with DoubleBuffer
(gserver/dataproviders/DataProvider.h:249) — a background thread that keeps
converted batches ahead of the GPU. On TPU two more host-side costs sit on
the step's critical path: batch sharding (the `DataParallel` placement) and
the H2D transfer itself. `DevicePrefetcher` moves all three off the hot loop:
a worker thread runs the feeder, applies the committed sharding, and
`jax.device_put`s up to `prefetch_depth` batches ahead, so host prep and H2D
overlap the donated compiled step ("RPC Considered Harmful" host/device
overlap discipline — chip-independent, it pays off on the CPU oracle too).

Composition: `DevicePrefetcher` subsumes `DoubleBuffer` (feeder + transfer on
one thread); it also accepts any reader that already yields feed-ready dict
batches — including a `DoubleBuffer` — and then only adds the device leg.
`SGDTrainer.train`/`test` recognize the already-on-device batches via
`is_device_batch` and skip their own coerce/shard work.
"""

from __future__ import annotations

import logging
import os
import queue
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from paddle_tpu.core import faults, stats
from paddle_tpu.obs import metrics as obs_metrics
from paddle_tpu.obs import trace

log = logging.getLogger("paddle_tpu.pipeline")

_STOP = object()
SKIP = object()  # prepare() return value meaning "drop this item"


class StackedBatch(dict):
    """K feed-ready batches stacked on a leading K axis — the payload of one
    fused K-step dispatch (SGDTrainer.train(steps_per_dispatch=K) feeds it
    straight to the lax.scan driver). Still a plain dict of device arrays,
    so is_device_batch() holds; `k` is the scan width."""

    k: int = 1


class _Group(list):
    """Marker: a stack_k-sized run of raw reader items (worker-side only);
    `first` is the index in reader order of its first item."""

    first: int = 0


class _Singles(list):
    """Marker: prepared single batches the consumer yields one by one — the
    degraded path for trailing remainders, shape churn inside a group, or
    groups that lost members to the divisibility filter."""


def iter_async(
    reader: Callable,
    prepare: Callable[[Any], Any],
    capacity: int,
    name: str = "paddle-tpu-async-producer",
    retries: int = 0,
    stall_warn_s: Optional[float] = None,
):
    """Shared background-producer loop (DoubleBuffer + DevicePrefetcher):
    a worker thread runs `prepare(raw)` over `reader()` and keeps up to
    `capacity` results ahead of the consumer. Items come out in reader
    order; `prepare` returning SKIP drops the item; worker exceptions
    re-raise in the consumer with the worker's original traceback attached;
    abandoning the generator (break/GeneratorExit) retires the worker via
    the bounded put's stop poll.

    retries: transient `prepare` exceptions (flaky storage, a hiccuping
    remote feeder) are retried that many times on the same item — with a
    short growing backoff — before the error propagates. reader() errors are
    never retried: the iterator's position is gone.

    stall_warn_s (default $PADDLE_TPU_STALL_WARN_S or 30; <= 0 disables):
    the consumer logs a warning whenever it has been starved that long
    waiting on the producer — the watchdog that distinguishes "feeder
    wedged" from "training slow".

    Spans: the worker adopts the span context of the thread that CALLED
    iter_async (a train pass, when the trainer called its reader), so
    whatever `prepare` records joins that trace; each blocking put is a
    `pipeline.queue_full` span (attrs: `item`, the queue item's index in
    reader order) — long when the feed runs ahead of its consumer, as
    `train.input_wait` is long when it runs behind."""
    # taken now, on the caller's thread: consume() below is a generator, and
    # its body runs only at the consumer's first next()
    ctx = trace.current_context()
    if stall_warn_s is None:
        stall_warn_s = float(os.environ.get("PADDLE_TPU_STALL_WARN_S", "30"))
    if stall_warn_s <= 0:  # disabled: plain blocking get, no watchdog
        stall_warn_s = None
    q: "queue.Queue" = queue.Queue(maxsize=capacity)
    err: List[BaseException] = []
    stop = threading.Event()

    def put(item, n: int) -> bool:
        # bounded put that notices consumer abandonment
        # span-ok: one ring write per queue item, constant name, int attr
        with trace.flight("pipeline.queue_full", item=n):
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

    def prepare_with_retry(raw):
        for attempt in range(retries + 1):
            try:
                faults.get().maybe_raise("feeder_raise")  # chaos hook
                return prepare(raw)
            except Exception as e:
                if attempt >= retries:
                    raise
                stats.FT_EVENTS.incr("feeder_retry")
                log.warning(
                    "%s: prepare failed (%s: %s) — retry %d/%d",
                    name, type(e).__name__, e, attempt + 1, retries,
                )
                time.sleep(min(0.05 * 2 ** attempt, 1.0))

    def work():
        n = -1
        with trace.activate(ctx):
            try:
                for n, raw in enumerate(reader()):
                    item = prepare_with_retry(raw)
                    if item is SKIP:
                        continue
                    if not put(item, n):
                        return
            except BaseException as e:  # surface worker errors to the consumer
                err.append(e)
            finally:
                put(_STOP, n + 1)

    def consume():
        t = threading.Thread(target=work, daemon=True, name=name)
        t.start()
        try:
            while True:
                try:
                    item = q.get(timeout=stall_warn_s)
                except queue.Empty:  # starved, not done: watchdog, then keep waiting
                    stats.FT_EVENTS.incr("pipeline_stall")
                    log.warning(
                        "%s: consumer starved for > %.1fs waiting on the producer "
                        "thread (feeder wedged or reader stalled?)",
                        name, stall_warn_s,
                    )
                    continue
                if item is _STOP:
                    break
                yield item
            t.join()
            if err:
                # the exception object still carries the worker's traceback, so
                # the failing feeder frame surfaces here, not just this loop
                # (locked in by test_worker_traceback_reaches_consumer)
                raise err[0]
        finally:
            stop.set()  # unblock and retire the producer on early exit

    return consume()


def is_device_batch(batch: Any) -> bool:
    """True when `batch` is a dict whose every slot already lives on device
    (committed jax.Arrays) — the trainer skips _coerce_batch/shard_batch."""
    return (
        isinstance(batch, dict)
        and bool(batch)
        and all(isinstance(v, jax.Array) for v in batch.values())
    )


def coerce_batch(batch: Dict[str, Any]) -> Dict[str, Any]:
    """numpy-ify a dict batch, failing fast on ragged/object slots instead of
    letting the jitted step produce an opaque shape error. Shared by the
    prefetcher worker and the trainer's synchronous path."""
    out: Dict[str, Any] = {}
    for k, v in batch.items():
        if isinstance(v, (np.ndarray, jax.Array)):
            out[k] = v
            continue
        arr = np.asarray(v)
        if arr.dtype == object:
            raise ValueError(
                f"batch slot {k!r} is ragged or non-numeric; feed it through "
                f"a DataFeeder (which pads sequences) instead of a raw dict"
            )
        out[k] = arr
    return out


class DevicePrefetcher:
    """Async host-feed + H2D pipeline in front of the compiled train step.

    reader: callable returning an iterator of raw batches (sample lists when
        `feeder` is given, else feed-ready dict batches — e.g. a DoubleBuffer).
    feeder: optional DataFeeder applied on the worker thread.
    parallel: optional parallel.DataParallel — batches are placed with its
        committed batch sharding (indivisible trailing batches are padded to
        the shard multiple with a row mask — DataParallel.pad_batch — so the
        sample stream matches the unsharded reader; only unpaddable ragged
        batches are dropped); without it, batches go to `device` (default:
        jax's default device) via plain device_put.
    prefetch_depth: how many device-resident batches to run ahead (N+1 are in
        flight counting the one the consumer holds). 2 hides a feeder that is
        as slow as the step; deeper only buys burst tolerance at the cost of
        device memory.
    feed_retries: transient worker exceptions (feeder/coerce/H2D) are retried
        this many times per batch before propagating (see iter_async);
        deterministic feeder bugs still surface — they just fail every retry.
    stack_k: >1 groups K consecutive batches on the worker thread, feeds each
        on host, stacks them into ONE [K, B, ...] array per slot and does ONE
        device put (shard_batches under DataParallel) — a StackedBatch the
        trainer runs as a single fused K-step dispatch
        (train(steps_per_dispatch=K)). Groups that cannot stack — trailing
        remainder, shape churn inside the group, members dropped by the
        divisibility filter — degrade to ordinary single device batches, so
        the sample stream is identical either way. The h2d_delay chaos site
        then fires once per GROUP (per-dispatch granularity).

    One iteration = one pass. Worker exceptions surface in the consumer;
    abandoning the iterator (break / GeneratorExit) retires the worker.

    Spans (always recorded, on the worker's thread row, in the trace of the
    pass whose trainer called this reader): `pipeline.hostFeed` (feeder +
    coerce, one per batch, counted in `paddle_tpu_pipeline_batches_total`),
    `pipeline.stack` (the np.stack of a stack_k group), `pipeline.h2d`
    (device_put dispatch), `pipeline.queue_full` (iter_async). Each carries
    `batch`, the index in reader order of the (first) batch it handled —
    the trainer's `train.input_wait` and `train.dispatch` carry the same
    index, unless the divisibility filter dropped a batch in between.
    """

    def __init__(
        self,
        reader: Callable,
        feeder: Optional[Callable] = None,
        parallel: Optional[Any] = None,
        prefetch_depth: int = 2,
        device: Optional[Any] = None,
        feed_retries: int = 2,
        stack_k: int = 1,
    ):
        if prefetch_depth < 1:
            raise ValueError(f"prefetch_depth must be >= 1, got {prefetch_depth}")
        if stack_k < 1:
            raise ValueError(f"stack_k must be >= 1, got {stack_k}")
        self.reader = reader
        self.feeder = feeder
        self.parallel = parallel
        self.prefetch_depth = prefetch_depth
        self.device = device
        self.feed_retries = feed_retries
        self.stack_k = stack_k

    def __call__(self):
        return iter(self)

    def rebind_parallel(self, parallel: Optional[Any]) -> None:
        """Point FUTURE batches at a new DataParallel plan (elastic resize).
        The swap is one attribute store and each worker batch captures the
        plan exactly once at preparation start, so no batch is ever padded
        for one mesh and sharded for another — batches already prepared (or
        mid-flight) under the old plan surface to the consumer as old-mesh
        stragglers, which the trainer rebuilds host-side for the current
        plan. At most prefetch_depth + 1 batches take that slow path; the
        rest of the run lands directly on the new mesh."""
        self.parallel = parallel

    def _feed(self, raw: Any, index: int) -> Dict[str, Any]:
        """Raw reader item → feed-ready host batch (the hostFeed leg)."""
        obs_metrics.observe_pipeline_batch()
        # span-ok: one ring write per batch, constant name, int attr
        with trace.flight("pipeline.hostFeed", batch=index):
            return (
                self.feeder(raw)
                if self.feeder is not None and not isinstance(raw, dict)
                else coerce_batch(raw)
            )

    def _device_put(
        self, batch: Dict[str, Any], par: Optional[Any], index: int,
        stacked: bool = False,
    ) -> Any:
        """Feed-ready batch → device-resident batch (the h2d leg) under the
        plan `par` the caller captured at preparation start (rebind_parallel
        may have swapped self.parallel since). stacked places a [K, B, ...]
        group with the scan-axis sharding; the chaos sleep fires once per
        call either way = once per dispatch."""
        faults.get().sleep("h2d_delay")  # chaos hook: slow transfer leg
        # span-ok: one ring write per put, constant name, int/bool attrs
        with trace.flight("pipeline.h2d", batch=index, stacked=stacked):
            if par is not None:
                put = par.shard_batches if stacked else par.shard_batch
                return put(batch)
            if self.device is not None:
                return {k: jax.device_put(v, self.device) for k, v in batch.items()}
            return {k: jax.device_put(v) for k, v in batch.items()}

    def _prepare(self, indexed: Any) -> Any:
        """(index, raw reader item) → device-resident batch (SKIP = drop)."""
        index, raw = indexed
        par = self.parallel  # one capture: pad and shard under ONE plan
        batch = self._feed(raw, index)
        if par is not None:
            # pad to the shard multiple with a row mask instead of
            # dropping (cost layers zero pad rows; see
            # DataParallel.pad_batch) — the sample stream now matches
            # the unsharded reader exactly; only unpaddable ragged
            # batches drop
            batch = par.maybe_pad_batch(batch, where="prefetcher")
            if batch is None:
                return SKIP
        return self._device_put(batch, par, index)

    def _grouped_reader(self):
        group = _Group()
        for i, raw in enumerate(self.reader()):
            if not group:
                group.first = i
            group.append(raw)
            if len(group) == self.stack_k:
                yield group
                group = _Group()
        if group:
            yield group  # trailing remainder; degrades to singles

    def _prepare_group(self, group: "_Group") -> Any:
        """A run of stack_k raw items → one StackedBatch (the fast path: one
        np.stack + one device put covering K steps), or _Singles/SKIP when
        the group cannot stack as a whole."""
        par = self.parallel  # one capture: the whole group under ONE plan
        batches = [self._feed(raw, group.first + j) for j, raw in enumerate(group)]
        if par is not None:
            # a padded batch gains a mask slot → its signature differs →
            # the group degrades to singles below
            batches = [
                b
                for b in (
                    par.maybe_pad_batch(b, where="prefetcher group")
                    for b in batches
                )
                if b is not None
            ]
        if not batches:
            return SKIP
        stackable = (
            len(batches) == self.stack_k
            and len({stats.batch_signature(b) for b in batches}) == 1
        )
        if not stackable:
            return _Singles(
                self._device_put(b, par, group.first + j)
                for j, b in enumerate(batches)
            )
        # span-ok: one ring write per group, constant name, int attrs
        with trace.flight("pipeline.stack", batch=group.first, k=self.stack_k):
            stacked = {
                k: np.stack([np.asarray(b[k]) for b in batches])
                for k in batches[0]
            }
        out = self._device_put(stacked, par, group.first, stacked=True)
        sb = StackedBatch(out)
        sb.k = self.stack_k
        return sb

    def __iter__(self):
        # iter_async is called HERE, on the consumer's thread, so that the
        # worker's spans join the consumer's trace (a train pass)
        if self.stack_k <= 1:
            return iter_async(
                lambda: enumerate(self.reader()), self._prepare,
                self.prefetch_depth,
                name="paddle-tpu-device-prefetch", retries=self.feed_retries,
            )
        return self._unpack_singles(iter_async(
            self._grouped_reader, self._prepare_group, self.prefetch_depth,
            name="paddle-tpu-device-prefetch", retries=self.feed_retries,
        ))

    @staticmethod
    def _unpack_singles(items):
        for item in items:
            if isinstance(item, _Singles):
                # degraded group: hand the batches over one by one — the
                # trainer re-buffers or single-steps them as appropriate
                for b in item:
                    yield b
            else:
                yield item
