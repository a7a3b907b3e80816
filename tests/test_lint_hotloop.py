"""Grep-lint for the hot loops: per-step host syncs must not regress.

ISSUE 4 removed every per-step device→host fetch from the train loop (the
old divergence guard called float(cost) on EVERY step — "the guard's price").
ISSUE 6 added a second hot loop with the same discipline: the serving decode
loop, whose per-step budget is exactly ONE fetch (the sampled token ids,
which the autoregressive loop inherently needs on host).

The remaining fetches are few, deliberate, and each carries a `sync-ok` tag
naming its justification:

  trainer (SGDTrainer.train / _train_one_pass):
  * the guard poll (_poll_guard, every guard_check_every steps),
  * the single pass-end fetch of the on-device cost sum,
  * the deferred log line (value copied to host asynchronously a dispatch
    earlier).

  serving (ServingSession._decode_once / _collect / step):
  * the sampled-token fetch, ONE a decode step: since ISSUE 36 of the step
    dispatched BEFORE the one just dispatched (`_collect`), so the device
    runs a step under the fetch and the bookkeeping and not beside them.
  * since ISSUE 37 every fetch of the engine is a `self._fetch(...)` call:
    the one helper that holds the `np.asarray` and times the host's wait
    for the device into the step's `wait_ns`. A call site is tagged and
    counted as the `np.asarray` it replaced was.

This test fails the build if a sync-forcing call — float(...),
np.isfinite(...), .item(...), jax.device_get(...), block_until_ready(...),
and for the serving loop also np.asarray(...) — appears inside a hot-loop
body without a `sync-ok` tag on the line or within the few lines above it,
so a per-step sync cannot sneak back in as an innocent-looking one-liner.

ISSUE 10 added a sibling discipline for the serving request path: deadline
enforcement batches off ONE wall-clock read per engine step, so untagged
time.monotonic()/time.time() in the engine/scheduler/supervisor bodies trip
the `clock-ok` lint below."""

import ast
import os
import re

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRAINER_PY = os.path.join(_REPO, "paddle_tpu", "trainer", "trainer.py")
SERVING_PY = os.path.join(_REPO, "paddle_tpu", "serving", "session.py")
SCHEDULER_PY = os.path.join(_REPO, "paddle_tpu", "serving", "scheduler.py")
KV_CACHE_PY = os.path.join(_REPO, "paddle_tpu", "serving", "kv_cache.py")
ROUTER_PY = os.path.join(_REPO, "paddle_tpu", "serving", "router.py")
SERVER_PY = os.path.join(_REPO, "paddle_tpu", "serving", "server.py")

# calls that force a device sync when applied to a device array; jnp.* ops
# (async, traced) are deliberately NOT matched — hence the lookbehinds
SYNC_CALL = re.compile(
    r"(?<![\w.])float\(|(?<![\w.])np\.isfinite\(|\.item\(|"
    r"jax\.device_get\(|block_until_ready\("
)
# the serving decode loop additionally bans untagged np.asarray — its one
# sanctioned fetch uses exactly that idiom, so an unreviewed second one
# must trip the lint
SERVING_SYNC_CALL = re.compile(
    SYNC_CALL.pattern + r"|(?<![\w.])np\.asarray\(|self\._fetch\("
)

# (file, class, hot methods, pattern, max sync-ok tags)
#
# ISSUE 11 extended the serving hot surface: _prefill_chunks runs once per
# engine step while a long prompt commits (its ONE sanctioned fetch is the
# final chunk's sampled first token — per REQUEST, not per chunk), so it
# obeys the same np.asarray/float( ban as the decode loop.
# ISSUE 16 added _speculate: its ONE sanctioned fetch is the verify round's
# K+1 sampled tokens (per ROUND per slot — acceptance runs on host), so the
# verify loop obeys the same budget discipline as the decode loop.
# ISSUE 34 added _ensure_pages, which the decode step and every verify round
# call before they write: growing a slot's page list is host ints (its
# scheduler half, Scheduler.grow, is pinned clock-free below), so it fetches
# nothing and the budget of three stands.
# ISSUE 36 moved the decode step's one fetch out of _decode_once into
# _collect (the step dispatched before the one just dispatched; _drain is
# its fetch-then-dispatch form) and added _decode_lanes and
# PagedKVCache.can_grow, host ints asked once a step: the fetch MOVED, so
# the budget of three stands (_collect, _prefill_chunks, _speculate).
# ISSUE 37 routed the three, and _admit's first-token fetch, through
# ServingSession._fetch: the sites are `self._fetch(` calls now, tagged as
# before: three in the per-step bodies; _admit's, one an ADMISSION, and the
# helper's own np.asarray are the second entry's two.
HOT_LOOPS = [
    (TRAINER_PY, "SGDTrainer", ("train", "_train_one_pass"), SYNC_CALL, 3),
    (SERVING_PY, "ServingSession",
     ("_decode_once", "_decode_lanes", "_collect", "_drain", "step",
      "_prefill_chunks", "_speculate", "_ensure_pages"),
     SERVING_SYNC_CALL, 3),
    (SERVING_PY, "ServingSession", ("_admit", "_fetch"), SERVING_SYNC_CALL, 2),
    (SCHEDULER_PY, "Scheduler", ("grow",), SERVING_SYNC_CALL, 0),
    (KV_CACHE_PY, "PagedKVCache", ("grow", "can_grow", "trim", "can_admit"),
     SERVING_SYNC_CALL, 0),
]

# a tag on the offending line or in the contiguous comment block above it
TAG = "sync-ok"
TAG_LOOKBACK = 6  # lines

# -- span-recording sites (ISSUE 7 observability) ----------------------------
#
# Spans in the hot loops must go through the obs ring buffer — trace.span /
# trace.record_span / trace.span_from_monotonic (a no-op truth test when
# PADDLE_TPU_TRACE is off), or trace.flight / trace.record_flight (the
# flight recorder: ALWAYS a ring write, so the count per dispatch is what
# is reviewed here) — and carry a `span-ok` tag naming the site; the
# count is pinned so a new per-step span forces a review here. Two hard bans
# ride along: no file I/O in a hot-loop body at all, and no string formatting
# inside a span call's arguments (f-strings/%/.format evaluate at the call
# site whether or not anything is recorded).
SPAN_CALL = re.compile(
    r"(?<![\w.])trace\."
    r"(?:span|record_span|span_from_monotonic|flight|record_flight)\("
)
SPAN_TAG = "span-ok"
# (file, class, hot methods, max span-ok tags)
#
# ISSUE 15 added the router's dispatch/pump/reap surface: spans there are
# per-ASSIGNMENT / per-FAILOVER / per-HEDGE (never per pump cycle — note
# _pump_once is in the list precisely to keep it span-free), and the file-IO
# + span-formatting bans below apply to those bodies too.
#
# ISSUE 26 made the train loop's spans a flight recorder (recorded without a
# switch): train.pass (once a pass), train.input_wait (once per item pulled),
# train.handler (one site, `emit`, around every event handed to the caller),
# train.dispatch (once per dispatch) and train.cost_fetch (once a pass,
# around the pass-end sync) — five sites, each a fixed number of ring
# writes per dispatch or per pass. The prefetch worker's sites are pinned below
# (PIPELINE_SPAN_SITES).
SPAN_HOT_LOOPS = [
    (TRAINER_PY, "SGDTrainer", ("train", "_train_one_pass"), 5),
    # ISSUE 33: `serve.decode`, a flight span around the decode dispatch and
    # its one fetch (one ring write a decode step, two int attrs), beside
    # the gated `serving.decode_step` it wraps: four sites.
    # ISSUE 34: `serve.preempt` in _ensure_pages, a flight span a PREEMPTION
    # (none on a pool with room, a few a minute on one that binds), two int
    # attrs: five sites.
    # ISSUE 36: _collect and _drain (the fetch behind the dispatch) are hot
    # bodies too and record nothing of their own: `serve.decode` still opens
    # once a decode dispatch, around it and the fetch of the step before.
    # ISSUE 37: `serve.step` in step (a flight span an engine step that did
    # work), `serve.chunk` in _prefill_chunks where the gated
    # `serving.prefill_chunk` was (a flight span now), and the gated
    # `serving.decode_step` beside `serve.decode` gone: five sites still
    # (step, _prefill_chunks, _ensure_pages, _speculate, _decode_once), at
    # most two ring writes a decode step. _admit's sites are one an
    # ADMISSION: `serve.admit` (flight, where the gated `serving.prefill`
    # was) and the gated `serving.queue_wait`.
    (SERVING_PY, "ServingSession",
     ("_decode_once", "_decode_lanes", "_collect", "_drain", "step",
      "_prefill_chunks", "_speculate", "_notify_streams", "_ensure_pages"),
     5),
    (SERVING_PY, "ServingSession", ("_admit",), 2),
    (ROUTER_PY, "Router",
     ("_forward", "_failover_requests", "_reap_once", "_pump_once"), 3),
]
HOT_IO_CALL = re.compile(r"(?<![\w.])open\(|\.write\(|json\.dump")
SPAN_FMT = re.compile(
    r"trace\.(?:span|record_span|span_from_monotonic|flight|record_flight)\("
    r"[^\n]*(?:f\"|f'|\.format\(|% ?\()"
)


def _hot_spans(tree: ast.Module, class_name: str, methods):
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name == class_name:
            for item in node.body:
                if (
                    isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and item.name in methods
                ):
                    yield item.name, item.lineno, item.end_lineno


def _scan(path, class_name, methods, pattern, tag=TAG):
    with open(path) as f:
        source = f.read()
    lines = source.splitlines()
    spans = list(_hot_spans(ast.parse(source), class_name, methods))
    assert {name for name, _, _ in spans} == set(methods), (
        f"hot-loop methods of {class_name} moved/renamed — update {__file__}"
    )
    violations, tagged = [], []
    for name, lo, hi in spans:
        for ln in range(lo, hi + 1):
            text = lines[ln - 1]
            if tag is not None and tag in text:
                tagged.append(ln)
            code = text.split("#", 1)[0]
            if not pattern.search(code):
                continue
            if tag is not None:
                window = lines[max(0, ln - TAG_LOOKBACK):ln]
                if tag in text or any(tag in w for w in window):
                    continue
            violations.append(f"{os.path.basename(path)}:{name}:{ln}: {text.strip()}")
    return violations, tagged


def test_no_untagged_device_sync_in_hot_loops():
    violations = []
    for path, cls, methods, pattern, _ in HOT_LOOPS:
        v, _ = _scan(path, cls, methods, pattern)
        violations += v
    assert not violations, (
        "device-sync call(s) in a hot-loop body without a `sync-ok` tag — "
        "per-step host syncs serialize the XLA async dispatch pipeline (see "
        "ISSUE 4 / README 'Async execution' and the serving decode-loop "
        "contract, README 'Serving'). Either move the fetch out of the hot "
        "loop or, if it is genuinely one of the sanctioned sites, tag the "
        "line with `# sync-ok: <why>`:\n  " + "\n  ".join(violations)
    )


def test_sanctioned_sync_sites_stay_rare():
    """The tag is a justification, not a loophole: the number of sync-ok
    sites in each hot loop is pinned so adding one forces a review here."""
    for path, cls, methods, pattern, budget in HOT_LOOPS:
        _, tagged = _scan(path, cls, methods, pattern)
        assert len(tagged) <= budget, (
            f"{len(tagged)} sync-ok tags in the {cls} hot loop (expected <= "
            f"{budget}): a new sanctioned sync site was added — confirm it "
            "is not per-step and bump this bound deliberately"
        )


# every body of the engine that may touch a device value
SERVING_ENGINE_BODIES = (
    "step", "_admit", "_prefill_chunks", "_speculate", "_decode_once",
    "_decode_lanes", "_collect", "_drain", "_ensure_pages",
)
RAW_FETCH = re.compile(r"(?<![\w.])np\.asarray\(")


def test_serving_fetches_go_through_the_timing_helper():
    """ISSUE 37: the engine's bodies hold no `np.asarray(` of their own,
    tagged or not: a device value reaches the host through
    ServingSession._fetch, which holds the one (HOT_LOOPS pins it) and adds
    the wait to the step's `wait_ns`. A fetch beside it would be host time
    that `serve.step` books as the host's own work."""
    raw, _ = _scan(SERVING_PY, "ServingSession", SERVING_ENGINE_BODIES,
                   RAW_FETCH, tag=None)
    assert not raw, (
        "np.asarray( in an engine body: fetch through self._fetch(...) so "
        "the wait is timed:\n  " + "\n  ".join(raw)
    )


def test_span_sites_in_hot_loops_tagged_and_pinned():
    """Span recording inside the train / serving-decode hot loops must go
    through the obs ring-buffer API and carry a `span-ok` tag; the tag count
    is pinned so a new per-step span site forces a review here."""
    for path, cls, methods, budget in SPAN_HOT_LOOPS:
        violations, tagged = _scan(path, cls, methods, SPAN_CALL, tag=SPAN_TAG)
        assert not violations, (
            "span-recording call(s) in a hot-loop body without a `span-ok` "
            "tag — every hot-loop span must be a gated ring-buffer write "
            "(obs/trace.py) and name its justification:\n  "
            + "\n  ".join(violations)
        )
        assert len(tagged) <= budget, (
            f"{len(tagged)} span-ok tags in the {cls} hot loop (expected <= "
            f"{budget}): a new sanctioned span site was added — confirm it "
            "records per-dispatch (not per-step work beyond a ring write) "
            "and bump this bound deliberately"
        )


PIPELINE_PY = os.path.join(_REPO, "paddle_tpu", "data", "pipeline.py")
# the prefetch worker's flight-recorder sites: hostFeed, h2d and stack in
# DevicePrefetcher (per batch / per put / per stack_k group), queue_full in
# iter_async (per queue item). iter_async is also DoubleBuffer's producer
# loop (data/provider.py), so a DoubleBuffer records queue_full per item
# through this same site: tests/test_obs.py pins that it records nothing else
PIPELINE_SPAN_SITES = 4


def test_prefetcher_span_sites_tagged_pinned_and_unformatted():
    """The prefetch worker's spans are always recorded, so each site is a
    fixed cost per batch: tagged, count-pinned, with literal arguments and
    no file I/O anywhere in the module."""
    with open(PIPELINE_PY) as f:
        lines = f.read().splitlines()
    sites = [i for i, text in enumerate(lines)
             if SPAN_CALL.search(text.split("#", 1)[0])]
    assert len(sites) == PIPELINE_SPAN_SITES, [lines[i].strip() for i in sites]
    for i in sites:
        window = lines[max(0, i - TAG_LOOKBACK):i + 1]
        assert any(SPAN_TAG in w for w in window), lines[i].strip()
        assert not SPAN_FMT.search(lines[i]), lines[i].strip()
    assert sum(SPAN_TAG in text for text in lines) == PIPELINE_SPAN_SITES
    code = [text.split("#", 1)[0] for text in lines]
    assert not [c for c in code if HOT_IO_CALL.search(c)]


# -- precision-cast sites (ISSUE 9 mixed precision) --------------------------
#
# Inside the COMPILED train-step body (SGDTrainer._build_step), every dtype
# cast must go through the Policy.cast boundary (core/dtypes.py) so the
# precision policy stays auditable — a raw `.astype(` there is either a
# policy cast that bypassed the seam or an unreviewed numeric change. The
# sanctioned exceptions (int counter casts, the f32 pin of the cost
# reduction) carry a `cast-ok` tag with the count pinned below.

CAST_CALL = re.compile(r"\.astype\(")
CAST_TAG = "cast-ok"
# (file, class, compiled-step methods, max cast-ok tags)
CAST_HOT_LOOPS = [(TRAINER_PY, "SGDTrainer", ("_build_step",), 4)]


def test_no_untagged_astype_in_compiled_step():
    """Raw `.astype(` in the compiled train-step body must be tagged: dtype
    boundaries go through Policy.cast (ops/linalg.py, ops/conv.py call it at
    the dot/conv inputs), and the few sanctioned non-policy casts — int
    counters, the f32 cost pin — name their justification."""
    violations = []
    for path, cls, methods, _budget in CAST_HOT_LOOPS:
        v, _ = _scan(path, cls, methods, CAST_CALL, tag=CAST_TAG)
        violations += v
    assert not violations, (
        "untagged `.astype(` in the compiled train-step body — route "
        "precision casts through Policy.cast (core/dtypes.py) or, for a "
        "genuinely policy-free cast (int counters, f32 reduction pins), tag "
        "the line with `# cast-ok: <why>`:\n  " + "\n  ".join(violations)
    )


def test_sanctioned_cast_sites_stay_rare():
    """cast-ok is a justification, not a loophole: the count is pinned so a
    new cast site in the compiled step forces a review here."""
    for path, cls, methods, budget in CAST_HOT_LOOPS:
        _, tagged = _scan(path, cls, methods, CAST_CALL, tag=CAST_TAG)
        assert len(tagged) <= budget, (
            f"{len(tagged)} cast-ok tags in {cls}._build_step (expected <= "
            f"{budget}): a new sanctioned cast was added to the compiled "
            "step — confirm it is not a policy cast bypassing Policy.cast "
            "and bump this bound deliberately"
        )


# -- wall-clock sites (ISSUE 10 serving resilience) ---------------------------
#
# Deadline enforcement batches off ONE wall-clock read per engine step: the
# session's step() takes the timestamp and hands it to reap / pop_admissions
# / the admission stamps, so expiry cost never scales with occupancy or
# queue depth. A per-request time.monotonic() in these bodies is exactly the
# regression this lint exists to catch. The sanctioned reads — the step
# stamp, the supervisor's watchdog poll (4-16 Hz, off the engine thread),
# the once-per-restart recovery stamp, the once-per-request TTFT stamp, and
# the test-only `now is None` fallbacks — carry `clock-ok` tags with the
# counts pinned below.

CLOCK_CALL = re.compile(
    r"(?<![\w.])time\.monotonic\(|(?<![\w.])time\.time\("
)
CLOCK_TAG = "clock-ok"
# (file, class, methods on the request path, max clock-ok tags)
CLOCK_HOT_LOOPS = [
    # ISSUE 34: page growth and preemption (_ensure_pages, Scheduler.grow)
    # stamp a victim with the step's own timestamp, handed in: no new read.
    (SERVING_PY, "ServingSession",
     ("step", "_admit", "_prefill_chunks", "_observe_ttft", "_decode_once",
      "_decode_lanes", "_collect", "_drain", "_speculate", "_ensure_pages",
      "_notify_streams", "_engine_loop", "_supervise", "_recover"), 4),
    (SCHEDULER_PY, "Scheduler",
     ("reap", "pop_admissions", "grow", "requeue_active", "retire"), 3),
    (KV_CACHE_PY, "PagedKVCache", ("grow", "can_grow", "trim", "can_admit"),
     0),
    (SCHEDULER_PY, "ActiveSeq", ("append", "finished"), 1),
    # router dispatch path (ISSUE 15): one read per submit (the admission
    # stamp deadlines/hedge/park all derive from), one per pump cycle, one
    # per reaper tick, and the per-EVENT stamps (eviction, failover batch,
    # cancel, drain order, the evicted pump's grace check) — never one per
    # request per cycle. ISSUE 18 adds the takeover sweep (register_replica
    # / _sweep_replica): one stamp per REGISTRATION EVENT covering the
    # whole adopted batch.
    (ROUTER_PY, "Router",
     ("submit", "cancel", "drain", "_evict", "_failover_requests",
      "_try_assign", "_choose_replica", "_forward", "_on_result",
      "_pump_loop", "_pump_once", "_reap_once", "register_replica",
      "_sweep_replica"), 9),
]


def test_no_untagged_wallclock_in_serving_loops():
    """Wall-clock syscalls in the serving engine/scheduler request path must
    be tagged: deadline checks batch off the single per-step timestamp, so
    an untagged read is either a per-request syscall (the cost regression)
    or a second clock that lets expiry decisions disagree within one step."""
    violations = []
    for path, cls, methods, _budget in CLOCK_HOT_LOOPS:
        v, _ = _scan(path, cls, methods, CLOCK_CALL, tag=CLOCK_TAG)
        violations += v
    assert not violations, (
        "untagged wall-clock read in the serving request path — thread the "
        "step() timestamp through instead (one read per engine step feeds "
        "every deadline/cancellation check), or tag a genuinely "
        "non-per-request site with `# clock-ok: <why>`:\n  "
        + "\n  ".join(violations)
    )


def test_sanctioned_clock_sites_stay_rare():
    """clock-ok is a justification, not a loophole: the count is pinned so a
    new clock read in the serving request path forces a review here."""
    for path, cls, methods, budget in CLOCK_HOT_LOOPS:
        _, tagged = _scan(path, cls, methods, CLOCK_CALL, tag=CLOCK_TAG)
        assert len(tagged) <= budget, (
            f"{len(tagged)} clock-ok tags in the {cls} request path "
            f"(expected <= {budget}): a new sanctioned wall-clock site was "
            "added — confirm it is not per-request/per-step-per-slot and "
            "bump this bound deliberately"
        )


# -- TP dispatch seam (ISSUE 12 tensor-parallel serving) ----------------------
#
# Under TP the decode step's inputs split two ways: params + KV pool live
# SHARDED on the mesh (placed once at session init / crash re-init), block
# tables + per-slot lanes stay REPLICATED host state that the jit dispatch
# transfers as step data. A host-side jax.device_put / jnp.asarray of the
# block table inside the engine loop would re-place (and under TP, reshard)
# it EVERY step — exactly the per-step transfer discipline the sync-ok lint
# exists for, now applied to placements. The sanctioned sites (per-ADMISSION
# placement of one request's commit operands, never per-step) carry `tp-ok`
# tags with the count pinned below.

PUT_CALL = re.compile(
    r"(?<![\w.])jax\.device_put\(|(?<![\w.])device_put\(|"
    r"(?<![\w.])jnp\.asarray\(|(?<![\w.])jnp\.array\(|"
    r"make_array_from_process_local_data\("
)
PUT_TAG = "tp-ok"
# (file, class, engine-loop methods, max tp-ok tags)
PUT_HOT_LOOPS = [
    (SERVING_PY, "ServingSession",
     ("step", "_admit", "_prefill_chunks", "_decode_once", "_collect",
      "_drain", "_speculate", "_ensure_pages"), 1),
]


def test_no_untagged_host_placement_in_serving_loops():
    """Host→device placements in the serving engine loop must be tagged:
    the block table and per-slot lanes ride the jit dispatch as replicated
    step data (one transfer, no explicit put), so an untagged device_put /
    jnp.asarray here is a per-step placement — under TP, a per-step
    RESHARD of host state."""
    violations = []
    for path, cls, methods, _budget in PUT_HOT_LOOPS:
        v, _ = _scan(path, cls, methods, PUT_CALL, tag=PUT_TAG)
        violations += v
    assert not violations, (
        "host->device placement in a serving engine-loop body without a "
        "`tp-ok` tag — pass host arrays straight to the jitted call (the "
        "dispatch owns the one transfer) or tag a genuinely per-admission "
        "site with `# tp-ok: <why>`:\n  " + "\n  ".join(violations)
    )


def test_sanctioned_placement_sites_stay_rare():
    """tp-ok is a justification, not a loophole: the count is pinned so a
    new placement site in the engine loop forces a review here."""
    for path, cls, methods, budget in PUT_HOT_LOOPS:
        _, tagged = _scan(path, cls, methods, PUT_CALL, tag=PUT_TAG)
        assert len(tagged) <= budget, (
            f"{len(tagged)} tp-ok tags in the {cls} engine loop (expected "
            f"<= {budget}): a new sanctioned placement site was added — "
            "confirm it is per-admission (not per-step) and bump this "
            "bound deliberately"
        )


# -- ZeRO resharding boundaries (ISSUE 14 sharded update) ---------------------
#
# Every with_sharding_constraint inside the updaters' compiled-step bodies is
# a potential COLLECTIVE (the scatter/gather boundaries the HLO pins in
# test_hlo_collectives.py count) or a placement pin. Each site carries a
# `reshard-ok` tag naming which it is, and the counts are pinned per body so
# a new resharding boundary — a second scatter, a stray gather-back under
# zero3, a per-parameter constraint replacing the concat — forces a review
# here before it silently multiplies wire traffic.

UPDATERS_PY = os.path.join(_REPO, "paddle_tpu", "parallel", "updaters.py")
WSC_CALL = re.compile(r"(?<![\w.])wsc\(|with_sharding_constraint\(")
WSC_TAG = "reshard-ok"
# (class or None for module functions, bodies, exact reshard-ok site count)
WSC_STEP_BODIES = [
    ("ShardedUpdater", ("apply",), 3),   # scatter, local-view pin, gather
    ("Zero3Updater", ("apply",), 3),     # scatter, resident pin, stay-pin
    (None, ("_z3_gather",), 2),          # owned-rows pin, THE param gather
]


def _updater_spans(tree: ast.Module, class_name, methods):
    if class_name is None:
        for node in tree.body:
            if (
                isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                and node.name in methods
            ):
                yield node.name, node.lineno, node.end_lineno
        return
    yield from _hot_spans(tree, class_name, methods)


def _scan_updaters(class_name, methods, pattern, tag):
    with open(UPDATERS_PY) as f:
        source = f.read()
    lines = source.splitlines()
    spans = list(_updater_spans(ast.parse(source), class_name, methods))
    assert {name for name, _, _ in spans} == set(methods), (
        f"updater step bodies {methods} moved/renamed — update {__file__}"
    )
    violations, tagged = [], 0
    for name, lo, hi in spans:
        for ln in range(lo, hi + 1):
            code = lines[ln - 1].split("#", 1)[0]
            if not pattern.search(code):
                continue
            window = lines[max(0, ln - TAG_LOOKBACK):ln]
            if tag in lines[ln - 1] or any(tag in w for w in window):
                tagged += 1
                continue
            violations.append(
                f"updaters.py:{name}:{ln}: {lines[ln - 1].strip()}"
            )
    return violations, tagged


def test_updater_reshard_sites_tagged_and_pinned():
    """Sanctioned gather/scatter sites in the sharded-update step bodies:
    every wsc() is tagged `reshard-ok` and the per-body counts are exact —
    the alias `wsc = jax.lax.with_sharding_constraint` line itself does not
    count (no call parens)."""
    for cls, methods, count in WSC_STEP_BODIES:
        violations, tagged = _scan_updaters(cls, methods, WSC_CALL, WSC_TAG)
        where = cls or "module"
        assert not violations, (
            f"untagged resharding constraint in {where} step body — a new "
            "collective boundary needs a `# reshard-ok: <why>` tag and a "
            "deliberate count bump here:\n  " + "\n  ".join(violations)
        )
        assert tagged == count, (
            f"{tagged} reshard-ok sites in {where}.{methods} (pinned "
            f"{count}): the sharded update's resharding structure changed — "
            "re-check the HLO collective pins and re-pin both"
        )


# -- router replica RPCs (ISSUE 15 multi-replica serving) ---------------------
#
# The router's whole reason to exist over "a proxy that asks each replica"
# is that its DISPATCH decisions run on piggybacked state: load/health ride
# replica heartbeats, results ride ONE batch poll per replica per pump
# cycle, and the only blocking replica RPCs on the request path are the
# submit forward itself, the pump's poll_many, and the cancel order (hedge
# losers / client cancels). A per-request `.call(` anywhere else in the
# assignment/pump/reap path is the "RPC Considered Harmful" regression this
# lint pins — a fleet-size cap smuggled in as an innocent health probe.

# ISSUE 20: `.call_many(` (the pipelined batch) and `.call_stream(` are
# round trips too — a batched RPC smuggled into a dispatch loop is still
# a blocking replica RPC and needs the same tag
RPC_CALL = re.compile(r"\.call(?:_many|_stream)?\(")
RPC_TAG = "rpc-ok"
# (file, class, dispatch-path methods, max rpc-ok tags)
#
# ISSUE 18 adds the takeover sweep to the pinned surface: register_replica /
# _sweep_replica make exactly ONE `outstanding` call per replica
# REGISTRATION EVENT (rebuilding the in-flight books after a router
# takeover) — pinned here so the sweep can never creep into the pump or
# dispatch cycles.
ROUTER_RPC_LOOPS = [
    (ROUTER_PY, "Router",
     ("submit", "_try_assign", "_choose_replica", "_forward", "_pump_once",
      "_on_result", "_reap_once", "_failover_requests", "_send_cancels",
      "register_replica", "_sweep_replica"), 4),
]


def test_no_untagged_replica_rpc_in_router_dispatch():
    """Blocking replica RPCs in the router's assignment/pump/reap path must
    be tagged: dispatch decisions read piggybacked state only, and the three
    sanctioned calls (submit forward, batch poll, cancel order) name
    themselves with `rpc-ok`."""
    violations = []
    for path, cls, methods, _budget in ROUTER_RPC_LOOPS:
        v, _ = _scan(path, cls, methods, RPC_CALL, tag=RPC_TAG)
        violations += v
    assert not violations, (
        "blocking replica RPC in the router dispatch path without an "
        "`rpc-ok` tag — route the signal over replica heartbeats / the "
        "pump's poll_many batch instead, or tag a genuinely per-event "
        "(never per-request-per-cycle) site with `# rpc-ok: <why>`:\n  "
        + "\n  ".join(violations)
    )


def test_sanctioned_router_rpc_sites_stay_rare():
    """rpc-ok is a justification, not a loophole: the count is pinned so a
    new blocking replica call in the dispatch path forces a review here."""
    for path, cls, methods, budget in ROUTER_RPC_LOOPS:
        _, tagged = _scan(path, cls, methods, RPC_CALL, tag=RPC_TAG)
        assert len(tagged) <= budget, (
            f"{len(tagged)} rpc-ok tags in the {cls} dispatch path "
            f"(expected <= {budget}): a new sanctioned replica RPC was "
            "added — confirm it is per-event (submit forward / batch poll "
            "/ cancel), not per-request-per-cycle, and bump this bound "
            "deliberately"
        )


def test_no_file_io_in_hot_loops():
    """No open()/.write()/json.dump in any hot-loop body, tagged or not —
    span export and metric scraping happen OUTSIDE the loops (export_chrome,
    the metrics/trace_export RPCs)."""
    violations = []
    for path, cls, methods, _budget in SPAN_HOT_LOOPS:
        v, _ = _scan(path, cls, methods, HOT_IO_CALL, tag=None)
        violations += v
    assert not violations, (
        "file I/O in a hot-loop body — move it behind the ring buffer / "
        "pass boundary:\n  " + "\n  ".join(violations)
    )


def test_span_args_not_formatted_in_hot_loops():
    """Span call arguments in hot loops must be cheap literals: an f-string
    or %/.format inside the call evaluates at the call site even when
    tracing is DISABLED, defeating the near-zero-cost gate."""
    violations = []
    for path, cls, methods, _budget in SPAN_HOT_LOOPS:
        v, _ = _scan(path, cls, methods, SPAN_FMT, tag=None)
        violations += v
    assert not violations, (
        "string formatting inside a hot-loop span call (evaluates even with "
        "tracing off) — pass raw ints/strings instead:\n  "
        + "\n  ".join(violations)
    )


# -- push-stream emit path (ISSUE 16 token streaming) -------------------------
#
# Push streaming splits in two on purpose: the ENGINE's entire contribution
# is a sequence-number bump under a condition variable (_notify_streams /
# stream_wait — same pair on the router's mirror), while every socket write
# happens on a server handler thread (server._Handler._push_frames; the
# router server reuses the same handler). That is what makes a slow or dead
# subscriber unable to block a decode step. Two pins keep the separation
# honest: the engine-side seam stays free of socket/frame emission, and
# encode_frame() — the framing seam call_stream() parses against — is called
# from the handler push loop only.

STREAM_EMIT = re.compile(
    r"\.sendall\(|(?<![\w.])encode_frame\(|\.makefile\(|\bwfile\b"
)
# (file, class, engine-side stream-seam methods)
STREAM_SEAM = [
    (SERVING_PY, "ServingSession",
     ("_notify_streams", "stream_wait", "step", "_decode_once", "_collect",
      "_drain", "_speculate", "_ensure_pages")),
    (ROUTER_PY, "Router",
     ("_notify_streams", "stream_wait", "_on_result", "_pump_once")),
]


def test_engine_stream_seam_is_socket_free():
    """No socket/frame emission in the engine-side stream seam: the engine
    and the router's pump announce progress with a seq bump + notify_all and
    NOTHING else — pusher threads (which own the sockets) do the writing, so
    backpressure from one subscriber never reaches the decode loop."""
    violations = []
    for path, cls, methods in STREAM_SEAM:
        v, _ = _scan(path, cls, methods, STREAM_EMIT, tag=None)
        violations += v
    assert not violations, (
        "socket/frame emission in the engine-side stream seam — frames are "
        "written by server handler threads (_Handler._push_frames) only; "
        "the engine/router signal progress via stream_wait's condition "
        "variable:\n  " + "\n  ".join(violations)
    )


# -- autoscaler controller loop (ISSUE 17) ------------------------------------
#
# The controller's contract is "zero new RPCs on anyone's hot path": its
# entire network footprint is one cold-path `stats` poll per endpoint per
# tick (_observe) plus one lever call per ADMITTED decision (_actuate's
# drain order / resize announce — cooldown-rate-limited, so never per-tick).
# The decision engine itself (ScaleDecider.decide/_admit) is PURE: no RPCs,
# no clock reads — every cooldown/flap/backoff comparison uses the single
# `now` stamp the tick takes once. These pins keep a "quick health probe"
# or a second clock from sneaking into the reconcile loop.

AUTOSCALER_PY = os.path.join(_REPO, "paddle_tpu", "runtime", "autoscaler.py")
# (file, class, methods, max rpc-ok tags)
AUTOSCALER_RPC_LOOPS = [
    (AUTOSCALER_PY, "AutoscalerController",
     ("_observe", "_actuate", "_watch_resize", "tick", "_drain_victim"), 4),
]
# (file, class, methods, max clock-ok tags)
AUTOSCALER_CLOCK_LOOPS = [
    (AUTOSCALER_PY, "AutoscalerController",
     ("_observe", "_actuate", "_watch_resize", "tick", "_drain_victim"), 1),
]
# the pure decision engine: no tags allowed at all — a single RPC or clock
# read in decide()/_admit() breaks both determinism and the test story
DECIDER_PURE = [
    (AUTOSCALER_PY, "ScaleDecider",
     ("decide", "_admit", "_suppress", "note_resize_rejected",
      "note_resize_ok")),
]


def test_no_untagged_rpc_in_controller_loop():
    """Blocking RPCs in the controller's reconcile loop must be tagged: the
    sanctioned four are the two once-per-tick stats polls (_observe) and the
    two per-admitted-decision lever calls (_actuate)."""
    violations = []
    for path, cls, methods, _budget in AUTOSCALER_RPC_LOOPS:
        v, _ = _scan(path, cls, methods, RPC_CALL, tag=RPC_TAG)
        violations += v
    assert not violations, (
        "blocking RPC in the autoscaler reconcile loop without an `rpc-ok` "
        "tag — observation rides the existing stats endpoints once per tick "
        "and actuation is one lever call per admitted decision; anything "
        "else is a new RPC on the control loop:\n  " + "\n  ".join(violations)
    )


def test_sanctioned_controller_rpc_sites_stay_rare():
    for path, cls, methods, budget in AUTOSCALER_RPC_LOOPS:
        _, tagged = _scan(path, cls, methods, RPC_CALL, tag=RPC_TAG)
        assert len(tagged) <= budget, (
            f"{len(tagged)} rpc-ok tags in the {cls} reconcile loop "
            f"(expected <= {budget}): a new sanctioned RPC site was added — "
            "confirm it is once-per-tick (observe) or per-admitted-decision "
            "(actuate) and bump this bound deliberately"
        )


def test_controller_tick_reads_the_clock_exactly_once():
    """One wall-clock read per tick, tagged: every cooldown / flap-window /
    backoff comparison inside the decision engine uses that single stamp, so
    rate-limit decisions cannot disagree within a tick."""
    for path, cls, methods, budget in AUTOSCALER_CLOCK_LOOPS:
        violations, tagged = _scan(path, cls, methods, CLOCK_CALL,
                                   tag=CLOCK_TAG)
        assert not violations, (
            "untagged wall-clock read in the controller loop — thread "
            "tick()'s single stamp through instead:\n  "
            + "\n  ".join(violations)
        )
        assert len(tagged) <= budget, (
            f"{len(tagged)} clock-ok tags in the {cls} loop (expected <= "
            f"{budget}): the controller should take ONE stamp per tick"
        )


def test_scale_decider_is_pure():
    """The decision engine makes no RPCs and reads no clocks, tagged or
    otherwise — `now` is an argument. That purity is what lets
    tests/test_autoscaler.py pin hysteresis/cooldown/flap/backoff behavior
    with a fake clock and zero sockets."""
    for path, cls, methods in DECIDER_PURE:
        for pattern, what in ((RPC_CALL, "RPC"), (CLOCK_CALL, "clock read")):
            v, _ = _scan(path, cls, methods, pattern, tag=None)
            assert not v, (
                f"{what} inside the pure decision engine ({cls}) — decide() "
                "takes signals and a caller-supplied `now`; move the side "
                "effect to the controller's observe/actuate phases:\n  "
                + "\n  ".join(v)
            )


# -- election loop + takeover sweep (ISSUE 18 control-plane HA) ---------------
#
# The standby watcher (runtime/election.py) is deliberately dumb: raw TCP
# connect probes, NO RPC protocol — so a standby can watch anything that
# listens and a wedged primary's RPC layer can't wedge its own watcher. Its
# entire clock footprint is the max_wait_s deadline (one stamp per watch,
# one expiry check per poll_s-paced cycle). An untagged `.call(` appearing
# in the watcher would mean election grew a protocol dependency; a new
# clock read would mean a second pacing source.

ELECTION_PY = os.path.join(_REPO, "paddle_tpu", "runtime", "election.py")
ELECTION_RPC_LOOPS = [
    (ELECTION_PY, "StandbyWatcher", ("wait_for_takeover", "_probe_once"), 0),
]
ELECTION_CLOCK_LOOPS = [
    (ELECTION_PY, "StandbyWatcher", ("wait_for_takeover", "_probe_once"), 2),
]


def test_election_watcher_probes_without_rpc():
    """The election loop holds zero rpc-ok tags: probes are raw socket
    connects (protocol-free on purpose), never MasterClient calls."""
    for path, cls, methods, budget in ELECTION_RPC_LOOPS:
        violations, tagged = _scan(path, cls, methods, RPC_CALL, tag=RPC_TAG)
        assert not violations and len(tagged) <= budget, (
            "RPC call inside the election watcher — the probe loop must "
            "stay protocol-free (a raw TCP connect) so it can watch any "
            "listener and can't be wedged by a wedged RPC layer:\n  "
            + "\n  ".join(violations)
        )


def test_election_watcher_clock_sites_pinned():
    """Two tagged clock sites in the watcher (the max_wait_s stamp and its
    per-cycle expiry check); pacing itself rides time.sleep(poll_s)."""
    for path, cls, methods, budget in ELECTION_CLOCK_LOOPS:
        violations, tagged = _scan(path, cls, methods, CLOCK_CALL,
                                   tag=CLOCK_TAG)
        assert not violations, (
            "untagged wall-clock read in the election watcher:\n  "
            + "\n  ".join(violations)
        )
        assert len(tagged) <= budget, (
            f"{len(tagged)} clock-ok tags in the {cls} loop (expected <= "
            f"{budget}): the watcher needs only the deadline stamp + check"
        )


def test_takeover_sweep_stays_out_of_pump_and_dispatch():
    """The takeover sweep runs once per replica REGISTRATION EVENT — never
    inside the pump/reap/assignment cycles. Pin the separation textually:
    the hot cycle bodies must not mention the sweep or its RPC method, so
    'just re-sweep every cycle' can't land without tripping this."""
    with open(ROUTER_PY) as f:
        source = f.read()
    spans = _hot_spans(
        ast.parse(source), "Router",
        ("_pump_once", "_reap_once", "_try_assign", "_forward",
         "_on_result"),
    )
    lines = source.splitlines()
    offenders = []
    for name, lo, hi in spans:
        body = "\n".join(lines[lo - 1:hi])
        for needle in ("_sweep_replica", '"outstanding"', "'outstanding'"):
            if needle in body:
                offenders.append(f"Router.{name}: contains {needle}")
    assert not offenders, (
        "takeover sweep reached a hot cycle body — reconciliation is a "
        "once-per-registration cold path (register_replica), not per-cycle "
        "work:\n  " + "\n  ".join(offenders)
    )


def test_frame_encoding_only_in_handler_push_loop():
    """encode_frame() has exactly one call site: _Handler._push_frames. Any
    second caller is a second framing implementation waiting to drift from
    what MasterClient.call_stream parses."""
    with open(SERVER_PY) as f:
        source = f.read()
    spans = list(_hot_spans(ast.parse(source), "_Handler", ("_push_frames",)))
    assert spans, f"_Handler._push_frames moved/renamed — update {__file__}"
    _, lo, hi = spans[0]
    call = re.compile(r"(?<![\w.])encode_frame\(")
    offenders = []
    for ln, text in enumerate(source.splitlines(), 1):
        code = text.split("#", 1)[0]
        if not call.search(code) or code.lstrip().startswith("def "):
            continue
        if not (lo <= ln <= hi):
            offenders.append(f"server.py:{ln}: {text.strip()}")
    assert not offenders, (
        "encode_frame() called outside _Handler._push_frames — keep one "
        "framing seam so pushed frames and call_stream's parser cannot "
        "drift apart:\n  " + "\n  ".join(offenders)
    )


# -- shared-prefix cache index (ISSUE 19 prefix caching) ----------------------
#
# The prefix index is pure host bookkeeping: a radix-over-pages dict keyed by
# (parent node, page token chunk) with a LOGICAL LRU tick. It runs under the
# scheduler's admission locks — including the submit-thread peek — so it must
# never read a clock (the logical tick exists precisely so eviction order is
# deterministic and lock hold times stay bounded), never make an RPC, and
# never place or touch a device array (aliasing is a block-table edit; the KV
# pools are neither read nor written). Zero tolerance, no tags.
#
# The admission path gets exactly ONE sanctioned per-submit hash computation
# (Scheduler.submit's peek_hit_tokens call — prices the wait estimate and the
# chunk count by the UNCACHED suffix) and exactly TWO registration sites
# (ServingSession._admit for whole-prompt commits, _prefill_chunks for
# per-chunk commits). The counts are pinned so a second hash walk cannot
# creep into a per-step body as an innocent-looking freshness check.

PREFIX_PY = os.path.join(_REPO, "paddle_tpu", "serving", "prefix_cache.py")
PREFIX_INDEX_METHODS = (
    "__init__", "__len__", "pages", "holds", "_root_for", "max_match_pages",
    "match", "_root_children", "peek_hit_tokens", "extend", "evictable",
    "evict_lru", "drop_all", "stats",
)


def test_prefix_index_is_pure():
    """The cache index never touches a clock, a socket, or a device array,
    tagged or otherwise — its LRU is a logical counter, its lookups are dict
    walks, and the one structure it influences (the block table) is edited
    by PagedKVCache, not by the index."""
    for pattern, what in (
        (CLOCK_CALL, "wall-clock read"),
        (RPC_CALL, "RPC"),
        (PUT_CALL, "device placement"),
    ):
        v, _ = _scan(PREFIX_PY, "PrefixIndex", PREFIX_INDEX_METHODS,
                     pattern, tag=None)
        assert not v, (
            f"{what} inside the prefix cache index — the index is pure host "
            "bookkeeping that runs under admission locks; move the side "
            "effect to the session/scheduler cold path:\n  " + "\n  ".join(v)
        )


def _call_sites(path, call: "re.Pattern"):
    with open(path) as f:
        source = f.read()
    sites = []
    for ln, text in enumerate(source.splitlines(), 1):
        code = text.split("#", 1)[0]
        if call.search(code) and not code.lstrip().startswith("def "):
            sites.append(ln)
    return source, sites


def test_prefix_admission_hash_sites_pinned():
    """Exactly one `.peek_hit_tokens(` site in the scheduler — inside
    submit(), the sanctioned per-admission hash computation — and exactly
    two `.commit_prefix(` sites in the session (whole-prompt commit in
    _admit, per-chunk commit in _prefill_chunks). Each computation walks the
    prompt once, so a second site is a second O(prompt) walk on the request
    path and needs a deliberate re-pin here."""
    peek = re.compile(r"\.peek_hit_tokens\(")
    source, sites = _call_sites(SCHEDULER_PY, peek)
    spans = list(_hot_spans(ast.parse(source), "Scheduler", ("submit",)))
    assert spans, f"Scheduler.submit moved/renamed — update {__file__}"
    _, lo, hi = spans[0]
    assert len(sites) == 1 and lo <= sites[0] <= hi, (
        f".peek_hit_tokens( call sites in scheduler.py at lines {sites} "
        "(pinned: exactly 1, inside Scheduler.submit) — the admission-path "
        "hash computation happens ONCE per submit; route any new consumer "
        "through handle.prefix_hint instead of re-hashing"
    )

    commit = re.compile(r"\.commit_prefix\(")
    source, sites = _call_sites(SERVING_PY, commit)
    spans = list(_hot_spans(
        ast.parse(source), "ServingSession", ("_admit", "_prefill_chunks")))
    assert len(spans) == 2, (
        f"ServingSession._admit/_prefill_chunks moved/renamed — "
        f"update {__file__}"
    )
    in_span = [ln for ln in sites
               if any(lo <= ln <= hi for _, lo, hi in spans)]
    assert len(sites) == 2 and in_span == sites, (
        f".commit_prefix( call sites in session.py at lines {sites} "
        "(pinned: exactly 2 — _admit's whole-prompt commit and "
        "_prefill_chunks' per-chunk commit) — registration covers COMMITTED "
        "pages only; a third site is either a duplicate registration or an "
        "uncommitted-page leak into the shared index"
    )


def test_decode_hot_bodies_stay_prefix_free():
    """The per-step decode/verify bodies never touch the prefix cache: all
    index work happens at admission (reserve/peek) and at prefill commit.
    Pin the separation textually so 'just refresh the LRU every step' or a
    per-step re-hash can't land without tripping this."""
    with open(SERVING_PY) as f:
        source = f.read()
    spans = _hot_spans(
        ast.parse(source), "ServingSession",
        ("step", "_decode_once", "_decode_lanes", "_collect", "_drain",
         "_speculate"),
    )
    lines = source.splitlines()
    offenders = []
    for name, lo, hi in spans:
        body = "\n".join(lines[lo - 1:hi])
        for needle in ("commit_prefix", "peek_hit_tokens", ".prefix"):
            if needle in body:
                offenders.append(f"ServingSession.{name}: contains {needle}")
    assert not offenders, (
        "prefix-cache work reached a per-step body — the index is an "
        "admission/commit-time structure (reserve aliases, commit_prefix "
        "registers); decode and verify only ever write pages past the "
        "prompt:\n  " + "\n  ".join(offenders)
    )


# -- binary control plane (ISSUE 20 framed wire) ------------------------------
#
# The framed transport exists to get per-token/per-task JSON encode cost OFF
# the hot paths: stream pushes ride frames.encode_stream (compact binary
# deltas), control replies ride frames.write_frame, and heartbeats piggyback
# on data frames. Two disciplines keep that true:
#
#   * the hot emission/dispatch bodies — router pump + dispatch, the
#     handler's frame loop and push loop, both heartbeat loops — never call
#     json.dumps/json.loads DIRECTLY (zero tolerance, no tag): every codec
#     decision lives behind the frames/encode_frame seams, so switching a
#     connection's wire can never leave a stray JSON encode on the hot path;
#   * the header struct is packed in exactly THREE places, all inside
#     frames.write_frame / frames.encode_stream, and server.py reaches
#     frames.encode_stream through exactly ONE call site (encode_frame, the
#     seam call_stream parses against) — one framing implementation, nothing
#     to drift.

FRAMES_PY = os.path.join(_REPO, "paddle_tpu", "runtime", "frames.py")
MASTER_PY = os.path.join(_REPO, "paddle_tpu", "runtime", "master.py")
FLEET_PY = os.path.join(_REPO, "paddle_tpu", "serving", "fleet.py")

JSON_CODEC_CALL = re.compile(r"(?<![\w.])json\.dumps\(|(?<![\w.])json\.loads\(")
# (file, class, wire-hot methods) — zero tolerance, no tags
WIRE_JSON_FREE = [
    (ROUTER_PY, "Router",
     ("_pump_once", "_on_result", "_try_assign", "_choose_replica",
      "_forward", "_send_cancels")),
    (SERVER_PY, "_Handler",
     ("_push_frames", "_serve_frames", "_reply_frame", "_dispatch")),
    (MASTER_PY, "_Heartbeater", ("_loop",)),
    (FLEET_PY, "ReplicaAgent", ("_loop",)),
]


def test_wire_hot_paths_free_of_direct_json_codec():
    """No direct json.dumps/json.loads in the wire-hot bodies, tagged or
    not — encoding decisions belong to the frames module / encode_frame
    seam, where the per-connection wire negotiation picks the codec."""
    violations = []
    for path, cls, methods in WIRE_JSON_FREE:
        v, _ = _scan(path, cls, methods, JSON_CODEC_CALL, tag=None)
        violations += v
    assert not violations, (
        "direct JSON codec call on a wire-hot path — route it through "
        "frames.write_frame / encode_frame so the negotiated wire (not the "
        "call site) owns the encoding:\n  " + "\n  ".join(violations)
    )


def _module_spans(tree: ast.Module, methods):
    """Module-level function spans (the _hot_spans sibling for functions
    that live outside any class)."""
    for node in tree.body:
        if (
            isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
            and node.name in methods
        ):
            yield node.name, node.lineno, node.end_lineno


def test_frame_header_packed_only_in_the_two_encoders():
    """`_HEADER.pack(` appears exactly 3 times in frames.py — once in
    write_frame (control/reply frames) and twice in encode_stream (the
    compact delta and the JSON-carrying stream frame). A fourth site is a
    second framing implementation."""
    source, sites = _call_sites(FRAMES_PY, re.compile(r"_HEADER\.pack\("))
    spans = {name: (lo, hi) for name, lo, hi in _module_spans(
        ast.parse(source), ("write_frame", "encode_stream"))}
    assert set(spans) == {"write_frame", "encode_stream"}, (
        f"frames.write_frame/encode_stream moved/renamed — update {__file__}"
    )
    in_wf = [ln for ln in sites
             if spans["write_frame"][0] <= ln <= spans["write_frame"][1]]
    in_es = [ln for ln in sites
             if spans["encode_stream"][0] <= ln <= spans["encode_stream"][1]]
    assert len(sites) == 3 and len(in_wf) == 1 and len(in_es) == 2, (
        f"_HEADER.pack( sites in frames.py at lines {sites} (pinned: 1 in "
        "write_frame + 2 in encode_stream) — every frame on the wire must "
        "come from one of the two encoders call sites parse against"
    )


def test_stream_binary_encoder_reached_through_one_seam():
    """server.py calls frames.encode_stream from exactly one place — inside
    encode_frame, the wire-switch seam — so the framed and line stream
    encodings can never diverge per call site."""
    source, sites = _call_sites(SERVER_PY, re.compile(r"encode_stream\("))
    spans = list(_module_spans(ast.parse(source), ("encode_frame",)))
    assert spans, f"server.encode_frame moved/renamed — update {__file__}"
    _, lo, hi = spans[0]
    assert len(sites) == 1 and lo <= sites[0] <= hi, (
        f"encode_stream( call sites in server.py at lines {sites} (pinned: "
        "exactly 1, inside encode_frame) — push frames pick their codec at "
        "the encode_frame seam only"
    )
