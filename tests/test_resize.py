"""Live elastic resize (ISSUE 8): grow/shrink the mesh mid-pass without
losing a step.

Equivalence contract (pinned here):
  * A pass that re-shards its data axis mid-pass lands allclose to the
    fixed-size run, with the SAME pass average (cross-device reduction
    order differs between world sizes, so bitwise across sizes is not a
    meaningful target — fixed 2-dev vs fixed 4-dev already differ at 1-2
    ULP).
  * The re-shard seam itself is value-preserving: a same-size "resize"
    (full canonical round trip + re-placement + recompiled step) is
    BITWISE identical to never resizing, and a run killed mid-re-shard
    (`reshard_kill`) that auto-resumes on the NEW world is BITWISE
    identical to the uninterrupted resized run.
  * Resize composes with --shard_update and steps_per_dispatch K>1.

Fleet half: the master's `_ResizeEpoch` state machine (announce → drain
barrier piggybacked on heartbeats → go → idle), barrier recomputation when a
member dies (lease eviction) or wedges (drain timeout — a wedged member's
daemon heartbeat thread keeps its lease alive, so the timeout is the
liveness guard), `ResizeClient` driving a real trainer end-to-end, and the
between-task drain of a registered `cluster_reader`.

The heavy multi-leg chaos_bench drill runs under the `nightly` marker
(nightly ⊆ slow, so tier-1 wall-clock stays within budget)."""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from paddle_tpu.core import faults, preempt, stats
from paddle_tpu.nn import costs as C
from paddle_tpu.nn import layers as L
from paddle_tpu.nn.graph import reset_name_scope
from paddle_tpu.optim import SGD
from paddle_tpu.parallel import DataParallel, make_mesh, resize_mesh
from paddle_tpu.trainer import SGDTrainer
from paddle_tpu.trainer import checkpoint as ckpt_mod
from paddle_tpu.trainer.events import EndIteration, EndPass

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DIM, CLASSES, BATCH, N = 12, 3, 24, 144


@pytest.fixture(autouse=True)
def _fresh():
    reset_name_scope()
    preempt.reset()
    stats.FT_EVENTS.reset()
    yield
    preempt.reset()


def _reader():
    rs = np.random.RandomState(0)
    xs = rs.randn(N, DIM).astype(np.float32)
    ys = (xs.sum(-1) > 0).astype(np.int32)

    def reader():
        for i in range(0, N, BATCH):
            yield {"x": xs[i:i + BATCH], "label": ys[i:i + BATCH]}

    return reader


def _build(world, shard=False):
    reset_name_scope()
    x = L.Data("x", shape=(DIM,))
    lbl = L.Data("label", shape=())
    h = L.Fc(x, 24, act="relu", name="h")
    logits = L.Fc(h, CLASSES, act=None, name="out")
    cost = C.ClassificationCost(logits, lbl, name="cost")
    dp = DataParallel(make_mesh({"data": world}))
    # power-of-two lr/momentum: scale products are FMA-proof, so bitwise
    # gates test the resize seam, not XLA contraction luck (PR 5 idiom)
    return SGDTrainer(
        cost, SGD(learning_rate=0.125, momentum=0.5), parallel=dp, seed=5,
        shard_update=shard,
    )


def _run(world, target=None, at_batch=1, shard=False, passes=1, **train_kw):
    preempt.reset()
    tr = _build(world, shard=shard)
    metrics = []

    def handler(ev):
        if (
            target is not None
            and isinstance(ev, EndIteration)
            and (ev.pass_id, ev.batch_id) == (0, at_batch)
        ):
            preempt.get().request_resize(target, reason="test resize")
        if isinstance(ev, EndPass):
            metrics.append(ev.metrics)

    tr.train(
        _reader(), num_passes=passes, event_handler=handler,
        log_period=10_000, **train_kw,
    )
    return tr, metrics


def _params(tr):
    return {k: np.asarray(v) for k, v in tr.state["params"].items()}


def _assert_bitwise(a, b, what=""):
    for k in a:
        assert np.array_equal(
            a[k].view(np.uint32), b[k].view(np.uint32)
        ), f"{what}: param {k} differs (max abs {np.abs(a[k] - b[k]).max()})"


def _assert_close(a, b, what=""):
    for k in a:
        np.testing.assert_allclose(
            a[k], b[k], rtol=1e-5, atol=1e-7, err_msg=f"{what}: param {k}"
        )


# -- mesh helper --------------------------------------------------------------


def test_resize_mesh_reshapes_data_axis():
    m = make_mesh({"data": 2})
    m4 = resize_mesh(m, "data", 4)
    assert int(m4.shape["data"]) == 4


def test_resize_mesh_accepts_non_dividing_world():
    """A world size that does not divide the host device count (3 trainers
    on an 8-chip host) must truncate the pool, not trip make_mesh's
    divisibility check — otherwise join-triggered epochs can announce a
    world the trainers can never build and the fleet wedges at the old
    size."""
    m = make_mesh({"data": 2})
    m3 = resize_mesh(m, "data", 3)
    assert int(m3.shape["data"]) == 3
    assert m3.devices.size == 3


def test_resize_mesh_rejects_unknown_axis_and_overflow():
    m = make_mesh({"data": 2})
    with pytest.raises(ValueError, match="no axis"):
        resize_mesh(m, "pipeline", 2)
    with pytest.raises(ValueError, match="device"):
        resize_mesh(m, "data", 4096)
    with pytest.raises(ValueError, match=">= 1"):
        resize_mesh(m, "data", 0)


# -- trainer-side equivalence -------------------------------------------------


def test_grow_mid_pass_matches_fixed_size_run():
    tr_fixed, m_fixed = _run(2)
    tr_rz, m_rz = _run(2, target=4)
    assert tr_rz.parallel.data_axis_size == 4
    _assert_close(_params(tr_fixed), _params(tr_rz), "grow 2->4")
    assert m_rz[0]["avg_cost"] == pytest.approx(
        m_fixed[0]["avg_cost"], rel=1e-6
    )
    assert m_rz[0]["batches"] == m_fixed[0]["batches"]
    # the latency split is part of the pass metrics contract
    assert m_rz[0]["resize_epochs"] == 1
    (split,) = m_rz[0]["resizes"]
    assert split["world"] == 4
    for leg in ("drain_s", "reshard_s", "resume_s"):
        assert split[leg] >= 0.0
    assert stats.FT_EVENTS.get("resize_epoch") == 1


def test_shrink_mid_pass_matches_fixed_size_run():
    tr_fixed, m_fixed = _run(4)
    tr_rz, m_rz = _run(4, target=2)
    assert tr_rz.parallel.data_axis_size == 2
    _assert_close(_params(tr_fixed), _params(tr_rz), "shrink 4->2")
    assert m_rz[0]["avg_cost"] == pytest.approx(
        m_fixed[0]["avg_cost"], rel=1e-6
    )


def test_same_size_resize_roundtrip_is_bitwise():
    """The seam itself is value-preserving: an explicit resize_to at the
    SAME world size (full canonical round trip + re-placement + recompile)
    changes nothing bitwise — and a drained epoch targeting the size the
    trainer already runs is a cheap drain-only epoch (no re-shard, no
    recompile) that leaves training bitwise-identical too."""
    tr_fixed, _ = _run(2)
    before = _params(tr_fixed)
    tr_fixed.resize_to(2)  # the full seam, exercised directly
    _assert_bitwise(before, _params(tr_fixed), "2->2 resize_to roundtrip")
    tr_rz, m_rz = _run(2, target=2)  # drain-only epoch inside train()
    assert m_rz[0]["resize_epochs"] == 1
    _assert_bitwise(before, _params(tr_rz), "2->2 drain-only epoch")


def test_resize_composes_with_shard_update():
    """ZeRO-1 flat slots re-flatten for the new shard count through the
    canonical seams; the grown run still matches the fixed-size one."""
    tr_fixed, m_fixed = _run(2, shard=True)
    tr_rz, m_rz = _run(2, target=4, shard=True)
    assert tr_rz.parallel.data_axis_size == 4
    assert tr_rz.updater.n == 4  # rebind really rebuilt the flat geometry
    _assert_close(_params(tr_fixed), _params(tr_rz), "shard_update grow")
    assert m_rz[0]["avg_cost"] == pytest.approx(
        m_fixed[0]["avg_cost"], rel=1e-6
    )
    # the same-size seam stays bitwise under shard_update too (explicit
    # resize_to: the drained path would early-out as a drain-only epoch)
    before = _params(tr_fixed)
    tr_fixed.resize_to(2)
    _assert_bitwise(before, _params(tr_fixed), "sharded 2->2 roundtrip")


def test_resize_with_prefetcher_stacked_straggler():
    """A DevicePrefetcher's in-flight stacked [K, B, ...] groups were
    prepared under the PRE-resize plan: committed to old-mesh devices and
    padded to the old shard multiple. The trainer must rebuild those
    stragglers for the current plan instead of feeding the new compiled
    program incompatible arrays — and then rebind the prefetcher so the
    rest of the run lands directly on the new mesh; the result still
    matches the fixed-size run."""
    from paddle_tpu.data.pipeline import DevicePrefetcher

    def pf(dp):
        return DevicePrefetcher(
            _reader(), feeder=None, parallel=dp, prefetch_depth=2, stack_k=2
        )

    preempt.reset()
    tr_fixed = _build(2)
    m_fixed = []
    tr_fixed.train(
        pf(tr_fixed.parallel), num_passes=1, steps_per_dispatch=2,
        log_period=10_000,
        event_handler=lambda e: m_fixed.append(e.metrics)
        if isinstance(e, EndPass) else None,
    )

    preempt.reset()
    tr = _build(2)
    metrics = []

    def handler(ev):
        if isinstance(ev, EndIteration) and (ev.pass_id, ev.batch_id) == (0, 1):
            preempt.get().request_resize(4, reason="test resize")
        if isinstance(ev, EndPass):
            metrics.append(ev.metrics)

    prefetcher = pf(tr.parallel)
    tr.train(
        prefetcher, num_passes=1, steps_per_dispatch=2,
        event_handler=handler, log_period=10_000,
    )
    assert tr.parallel.data_axis_size == 4
    # the drain rebound the prefetcher onto the post-resize plan, so only
    # the <= depth in-flight groups took the straggler rebuild path
    assert prefetcher.parallel is tr.parallel
    assert metrics[0]["batches"] == m_fixed[0]["batches"]
    _assert_close(_params(tr_fixed), _params(tr), "prefetched grow")
    assert metrics[0]["avg_cost"] == pytest.approx(
        m_fixed[0]["avg_cost"], rel=1e-6
    )


def test_prefetcher_rebind_parallel_switches_plan_mid_stream():
    """rebind_parallel points FUTURE batches at the new plan: batches the
    worker prepared before the swap stay consistent under the old plan
    (pad and shard together — never mixed), later ones arrive sharded for
    the new mesh with its shard multiple."""
    from paddle_tpu.data.pipeline import DevicePrefetcher

    dp2 = DataParallel(make_mesh({"data": 2}))
    dp4 = DataParallel(make_mesh({"data": 4}))
    pf = DevicePrefetcher(_reader(), parallel=dp2, prefetch_depth=1)
    it = iter(pf)
    first = next(it)
    assert dp2.is_sharded_batch(first)
    pf.rebind_parallel(dp4)
    rest = list(it)
    assert rest, "reader should have more batches after the first"
    # in-flight batches (<= depth + 1) may still carry the old plan; the
    # tail of the stream must be on the new one
    last = rest[-1]
    assert dp4.is_sharded_batch(last)
    for b in rest:
        # every batch is internally consistent: sharded for exactly one
        # of the two plans, never padded for one and placed for the other
        assert dp2.is_sharded_batch(b) or dp4.is_sharded_batch(b)


def test_oversize_resize_rejected_and_training_continues():
    """A bad announce (world larger than the host's devices) must reject the
    resize after the drain — not kill a checkpointed trainer mid-pass — and
    the pass finishes on the current mesh with untouched results."""
    tr_fixed, m_fixed = _run(2)
    tr, m = _run(2, target=4096)
    assert tr.parallel.data_axis_size == 2  # resize rejected, mesh unchanged
    assert m[0].get("resize_epochs", 0) == 0  # no completed epoch recorded
    assert stats.FT_EVENTS.get("resize_rejected") == 1
    _assert_bitwise(_params(tr_fixed), _params(tr), "rejected resize")
    assert m[0]["avg_cost"] == m_fixed[0]["avg_cost"]


def test_resize_composes_with_k_step_dispatch():
    tr_fixed, m_fixed = _run(2, steps_per_dispatch=2)
    tr_rz, m_rz = _run(2, target=4, steps_per_dispatch=2)
    assert tr_rz.parallel.data_axis_size == 4
    _assert_close(_params(tr_fixed), _params(tr_rz), "K=2 grow")
    assert m_rz[0]["batches"] == m_fixed[0]["batches"]
    assert m_rz[0]["avg_cost"] == pytest.approx(
        m_fixed[0]["avg_cost"], rel=1e-6
    )


@pytest.mark.chaos
def test_reshard_kill_auto_resume_bitwise(tmp_path):
    """Acceptance gate: bitwise resume across a resize boundary for SGD.
    The seeded `reshard_kill` dies AFTER the drain checkpoint, mid-re-shard;
    a fresh trainer at the TARGET world auto-resumes from the drained
    boundary and must land exactly on the uninterrupted resized run."""
    oracle, m_o = _run(2, target=4)
    with faults.inject("reshard_kill:step=0") as inj:
        with pytest.raises(faults.InjectedKill):
            _run(2, target=4, save_dir=str(tmp_path))
        assert inj.fired["reshard_kill"] == 1
    # the drain checkpoint is durable and marked mid-pass
    pid = ckpt_mod.find_latest_valid_pass(str(tmp_path))
    assert pid == 0
    extra = ckpt_mod.pass_manifest(str(tmp_path), 0)["extra"]
    assert extra["mid_pass"] and extra["batches_done"] == 2
    assert extra["world_size"] == 2  # saved on the OLD mesh
    resumed, m_r = _run(4, save_dir=str(tmp_path), auto_resume=True)
    # the bitwise params gate is the contract; the replayed pass's avg_cost
    # covers only the replayed batches (existing auto_resume semantics), so
    # it is deliberately not compared against the full-pass oracle
    _assert_bitwise(_params(oracle), _params(resumed), "reshard_kill resume")
    assert m_r[0]["batches"] == m_o[0]["batches"] - 2  # replayed from batch 2


@pytest.mark.chaos
def test_resize_drain_stall_site_fires_locally(monkeypatch):
    """The stall site wedges the trainer inside its own drain (deterministic,
    seeded); with a short stall the run still completes and resizes."""
    monkeypatch.setenv("PADDLE_TPU_RESIZE_STALL_S", "0.05")
    with faults.inject("resize_drain_stall:step=0") as inj:
        tr, m = _run(2, target=4)
        assert inj.fired["resize_drain_stall"] == 1
    assert tr.parallel.data_axis_size == 4
    assert m[0]["resize_epochs"] == 1


def test_checkpoint_records_world_size(tmp_path):
    tr, _ = _run(2, save_dir=str(tmp_path))
    extra = ckpt_mod.pass_manifest(str(tmp_path), 0)["extra"]
    assert extra["world_size"] == 2


def test_resize_without_mesh_is_ignored():
    """A resize order reaching a mesh-less trainer must be dropped with a
    warning, not crash or spin."""
    reset_name_scope()
    x = L.Data("x", shape=(DIM,))
    lbl = L.Data("label", shape=())
    logits = L.Fc(x, CLASSES, act=None)
    tr = SGDTrainer(C.ClassificationCost(logits, lbl), SGD(learning_rate=0.125))

    def handler(ev):
        if isinstance(ev, EndIteration) and ev.batch_id == 1:
            preempt.get().request_resize(4)

    tr.train(_reader(), num_passes=1, event_handler=handler, log_period=10_000)
    assert tr.parallel is None
    assert not preempt.resize_requested()  # claimed (and dropped), not stuck


# -- master resize-epoch state machine ---------------------------------------


def _native_available():
    from paddle_tpu.runtime import available

    return available()


needs_native = pytest.mark.skipif(
    not _native_available(), reason="native runtime unavailable"
)


@needs_native
@pytest.mark.timeout(60)
def test_epoch_barrier_all_members_ack():
    from paddle_tpu.runtime.master import MasterClient, MasterServer, TaskMaster

    srv = MasterServer(TaskMaster(), lease_s=5.0).start()
    try:
        c = MasterClient(srv.address)
        t1 = c.call("register")["trainer_id"]
        t2 = c.call("register")["trainer_id"]
        # malformed orders get an err REPLY on a surviving connection, not
        # a severed handler
        assert "err" in c.call("resize")
        assert "err" in c.call("resize", world="many")
        assert "err" in c.call("resize", world=0)
        ann = c.call("resize", world=4)
        assert ann["state"] == "draining" and ann["barrier"] == 2
        # a second announce while one is active is rejected with a reason
        assert "err" in c.call("resize", world=8)
        # a garbled epoch in the barrier RPCs replies status-only
        assert c.call("resize_drained", trainer_id=t1, epoch="x")["drained"] == 0
        # heartbeat piggybacks the drain signal, stamped with the resize
        # plane's instance token (epoch identity = instance + number)
        hb = c.call("heartbeat", trainer_id=t1)
        assert hb["resize"]["instance"]
        assert {
            k: hb["resize"][k] for k in ("state", "epoch", "world")
        } == {"state": "draining", "epoch": 1, "world": 4}
        mid = c.call("resize_drained", trainer_id=t1, epoch=1)
        assert mid["state"] == "draining" and mid["drained"] == 1
        go = c.call("resize_drained", trainer_id=t2, epoch=1)
        assert go["state"] == "go"
        # status polls double as resumed acks; epoch closes after both
        c.call("resize_status", trainer_id=t1, epoch=1)
        end = c.call("resize_status", trainer_id=t2, epoch=1)
        assert end["state"] == "idle" and end["completed"] == 1
        assert end["last"]["world"] == 4 and end["last"]["drain_s"] >= 0
        # idle → no piggyback
        assert "resize" not in c.call("heartbeat", trainer_id=t1)
        st = c.call("stats")
        assert st["resize"]["completed"] == 1
        c.close()
    finally:
        srv.stop()


@needs_native
@pytest.mark.timeout(60)
def test_epoch_completes_when_member_dies_in_barrier():
    """Lease eviction recomputes the drain barrier: a member killed mid-drain
    (no heartbeats) cannot wedge the epoch."""
    from paddle_tpu.runtime.master import MasterClient, MasterServer, TaskMaster

    srv = MasterServer(TaskMaster(), lease_s=0.6).start()
    try:
        c = MasterClient(srv.address)
        t1 = c.call("register")["trainer_id"]
        c.call("register")  # t2 registers then dies silently
        c.call("resize", world=2)
        info = c.call("resize_drained", trainer_id=t1, epoch=1)
        assert info["state"] == "draining"  # waiting on the dead member
        deadline = time.time() + 20
        while time.time() < deadline and info["state"] == "draining":
            time.sleep(0.1)
            info = c.call("resize_status", trainer_id=t1, epoch=1)
        assert info["state"] == "idle", info
        assert info["last"]["evicted_during"] >= 1
        assert stats.FT_EVENTS.get("resize_barrier_evicted") >= 1
        c.close()
    finally:
        srv.stop()


@needs_native
@pytest.mark.timeout(60)
def test_epoch_times_out_wedged_but_heartbeating_member():
    """A wedged member whose heartbeat thread is still alive holds its lease
    forever — the drain-barrier TIMEOUT is the liveness guard that drops it
    from the barrier so survivors proceed."""
    from paddle_tpu.runtime.master import MasterClient, MasterServer, TaskMaster

    srv = MasterServer(
        TaskMaster(), lease_s=5.0, resize_drain_timeout_s=0.8
    ).start()
    try:
        c = MasterClient(srv.address)
        t1 = c.call("register")["trainer_id"]
        t2 = c.call("register")["trainer_id"]
        c.call("resize", world=2)
        info = c.call("resize_drained", trainer_id=t1, epoch=1)
        assert info["state"] == "draining"
        deadline = time.time() + 20
        while time.time() < deadline and info["state"] == "draining":
            # t2 keeps heart-beating (wedged, not dead) yet never acks
            c.call("heartbeat", trainer_id=t2)
            time.sleep(0.1)
            info = c.call("resize_status", trainer_id=t1, epoch=1)
        assert info["state"] == "idle", info
        assert info["last"]["timed_out"] == 1
        # the woken straggler adopts the decided world from the idle epoch
        late = c.call("resize_drained", trainer_id=t2, epoch=1)
        assert late["state"] == "idle" and late["world"] == 2
        c.close()
    finally:
        srv.stop()


@needs_native
@pytest.mark.timeout(90)
def test_resize_client_drives_trainer_end_to_end():
    """The full tentpole path with a REAL master: announce over RPC →
    heartbeat watcher parks the request → trainer drains at a batch
    boundary, acks the barrier, re-shards, resumes — and the result matches
    the fixed-size run."""
    from paddle_tpu.runtime.master import (
        MasterClient, MasterServer, ResizeClient, TaskMaster,
    )

    srv = MasterServer(TaskMaster(), lease_s=0.45).start()
    rc = None
    try:
        rc = ResizeClient(srv.address, poll_s=0.05)
        boot = MasterClient(srv.address)
        tr_fixed, m_fixed = _run(2, passes=2)

        preempt.reset()
        tr = _build(2)
        metrics = []
        announced = []

        def handler(ev):
            if isinstance(ev, EndIteration):
                if ev.pass_id == 0 and ev.batch_id == 1 and not announced:
                    announced.append(boot.call("resize", world=4))
                time.sleep(0.05)  # stretch the pass past a heartbeat period
            if isinstance(ev, EndPass):
                metrics.append(ev.metrics)

        tr.train(
            _reader(), num_passes=2, event_handler=handler,
            resize_barrier=rc.barrier, log_period=10_000,
        )
        assert announced and announced[0]["state"] == "draining"
        assert tr.parallel.data_axis_size == 4
        _assert_close(_params(tr_fixed), _params(tr), "fleet grow")
        assert sum(m.get("resize_epochs", 0) for m in metrics) == 1
        st = boot.call("stats")["resize"]
        assert st["state"] == "idle" and st["completed"] == 1
        boot.close()
    finally:
        if rc is not None:
            rc.close()
        srv.stop()


@needs_native
@pytest.mark.timeout(90)
def test_cluster_reader_drains_between_tasks(tmp_path):
    """A registered cluster_reader is a drain-barrier member: it acks between
    task acks (holding no lease on any task) and resumes pulling afterwards —
    task accounting stays exactly-once across the epoch."""
    from paddle_tpu.runtime import recordio
    from paddle_tpu.runtime.master import (
        MasterClient, MasterServer, TaskMaster, cluster_reader,
    )

    shards = recordio.convert(
        str(tmp_path / "ds"), lambda: ({"sid": i} for i in range(24)),
        records_per_file=2,
    )
    srv = MasterServer(TaskMaster(timeout_s=30.0), lease_s=0.45).start()
    try:
        boot = MasterClient(srv.address)
        boot.call("set_dataset", shards=shards, chunks_per_task=1)
        got = []

        def consume():
            for s in cluster_reader(srv.address, poll_interval=0.05)():
                got.append(s["sid"])
                time.sleep(0.05)

        th = threading.Thread(target=consume, daemon=True)
        th.start()
        deadline = time.time() + 20
        while time.time() < deadline:
            if boot.call("stats").get("live_leases", 0) >= 1:
                break
            time.sleep(0.05)
        ann = boot.call("resize", world=2)
        assert ann["state"] == "draining"
        th.join(timeout=60)
        assert not th.is_alive()
        st = boot.call("stats")
        assert st["done"] == 12 and st["discarded"] == 0  # exactly-once
        assert sorted(got) == list(range(24))
        assert st["resize"]["completed"] == 1
        assert stats.FT_EVENTS.get("reader_resize_drain") == 1
        boot.close()
    finally:
        srv.stop()


@needs_native
@pytest.mark.timeout(90)
def test_two_lease_trainer_with_cluster_reader_no_deadlock(tmp_path):
    """The documented two-lease setup on ONE thread: a trainer whose data
    source is a registered cluster_reader. Whatever the ordering — the
    reader acks its drain without blocking for go, and when the resize
    lands mid-task the trainer's barrier acks the reader lease on its
    behalf — the epoch must complete with NO member timed out or evicted;
    the old circular wait could only be broken by the master timing out
    the healthy reader lease."""
    from paddle_tpu.runtime import recordio
    from paddle_tpu.runtime.master import (
        MasterClient, MasterServer, ResizeClient, TaskMaster, cluster_reader,
    )

    rs = np.random.RandomState(1)

    def batches():
        for _ in range(8):
            x = rs.randn(BATCH, DIM).astype(np.float32)
            yield {"x": x, "label": (x.sum(-1) > 0).astype(np.int32)}

    # ONE task holding every batch: the resize signal lands mid-task, so
    # the trainer reaches its dispatch-boundary drain while the reader can
    # never reach a between-task boundary — the barrier-services ordering
    shards = recordio.convert(
        str(tmp_path / "ds"), batches, records_per_file=8
    )
    srv = MasterServer(
        TaskMaster(timeout_s=60.0), lease_s=0.45, resize_drain_timeout_s=30.0,
    ).start()
    rc = None
    try:
        boot = MasterClient(srv.address)
        boot.call("set_dataset", shards=shards, chunks_per_task=1)
        rc = ResizeClient(srv.address, poll_s=0.05)
        tr = _build(2)
        announced = []

        def handler(ev):
            if isinstance(ev, EndIteration):
                time.sleep(0.2)  # let a heartbeat land inside the pass
                if ev.batch_id == 1 and not announced:
                    announced.append(boot.call("resize", world=4))

        t0 = time.time()
        tr.train(
            cluster_reader(srv.address, poll_interval=0.05), num_passes=1,
            event_handler=handler, resize_barrier=rc.barrier,
            log_period=10_000,
        )
        elapsed = time.time() - t0
        assert announced and announced[0]["state"] == "draining"
        assert tr.parallel.data_axis_size == 4
        st = boot.call("stats")["resize"]
        assert st["state"] == "idle" and st["completed"] == 1, st
        # the deadlock symptom: a healthy lease dropped by the drain timeout
        assert st["last"]["timed_out"] == 0, st
        assert st["last"]["evicted_during"] == 0, st
        assert elapsed < 25, f"epoch stalled ({elapsed:.1f}s): circular wait"
        assert boot.call("stats")["done"] == 1  # the single task, exactly once
        boot.close()
    finally:
        if rc is not None:
            rc.close()
        srv.stop()


@needs_native
@pytest.mark.timeout(60)
def test_resize_with_no_trainers_completes_immediately():
    """An announce with an empty live set must complete instantly, not wedge
    `draining` (and reject later resizes) until the drain timeout."""
    from paddle_tpu.runtime.master import MasterClient, MasterServer, TaskMaster

    srv = MasterServer(TaskMaster(), lease_s=5.0).start()
    try:
        c = MasterClient(srv.address)
        info = c.call("resize", world=4)
        assert info["state"] == "idle" and info["completed"] == 1, info
        # the control plane is immediately free for the next epoch
        assert c.call("resize", world=2)["state"] == "idle"
        c.close()
    finally:
        srv.stop()


@needs_native
@pytest.mark.timeout(60)
def test_resize_on_membership_announces_on_join():
    from paddle_tpu.runtime.master import MasterClient, MasterServer, TaskMaster

    srv = MasterServer(
        TaskMaster(), lease_s=5.0, resize_on_membership=True
    ).start()
    try:
        c = MasterClient(srv.address)
        c.call("register")  # first join: nothing to re-shape yet
        assert c.call("stats")["resize"]["state"] == "idle"
        c.call("register")  # second join announces world=2
        info = c.call("stats")["resize"]
        assert info["state"] == "draining" and info["world"] == 2
        c.close()
    finally:
        srv.stop()


# -- fleet metrics ------------------------------------------------------------


def test_observe_resize_lands_in_snapshot():
    from paddle_tpu.obs import metrics as obs_metrics

    before = obs_metrics.snapshot().get("paddle_tpu_resize_epochs_total", 0.0)
    obs_metrics.observe_resize(
        {"drain": 0.25, "reshard": 0.5, "resume": 0.125}
    )
    snap = obs_metrics.snapshot()
    assert snap["paddle_tpu_resize_epochs_total"] == before + 1
    assert (
        snap["paddle_tpu_resize_latency_seconds_total{phase=drain}"] >= 0.25
    )
    # counters sum exactly across fleet heartbeat snapshots
    agg = obs_metrics.aggregate_snapshots([snap, snap])
    assert agg["paddle_tpu_resize_epochs_total"] == 2 * (before + 1)


@needs_native
@pytest.mark.timeout(60)
def test_epoch_go_phase_times_out_wedged_resharder():
    """A member that acks the drain and then wedges INSIDE its re-shard —
    heartbeat thread still renewing the lease, never polling resize_status —
    must not pin the epoch in `go` forever (which would reject every future
    announce). The go phase carries the same timeout guard as the drain."""
    from paddle_tpu.runtime.master import MasterClient, MasterServer, TaskMaster

    srv = MasterServer(
        TaskMaster(), lease_s=5.0, resize_drain_timeout_s=0.8
    ).start()
    try:
        c = MasterClient(srv.address)
        t1 = c.call("register")["trainer_id"]
        t2 = c.call("register")["trainer_id"]
        c.call("resize", world=2)
        c.call("resize_drained", trainer_id=t1, epoch=1)
        go = c.call("resize_drained", trainer_id=t2, epoch=1)
        assert go["state"] == "go"
        # t1 resumes; t2 wedges mid-re-shard but keeps heart-beating
        info = c.call("resize_status", trainer_id=t1, epoch=1)
        deadline = time.time() + 20
        while time.time() < deadline and info["state"] == "go":
            c.call("heartbeat", trainer_id=t2)
            time.sleep(0.1)
            info = c.call("resize_status", trainer_id=t1, epoch=1)
        assert info["state"] == "idle", info
        assert info["completed"] == 1
        assert info["last"]["timed_out"] == 1
        # the epoch is not pinned: a new announce is accepted
        assert c.call("resize", world=2)["state"] == "draining"
        c.close()
    finally:
        srv.stop()


@needs_native
@pytest.mark.timeout(90)
def test_membership_churn_during_epoch_reannounces():
    """Churn that lands while an epoch is in flight must not be dropped:
    the rejected evict-triggered announce parks, and the reaper re-announces
    against the CURRENT membership once the epoch completes — the fleet
    never settles at a stale world size."""
    from paddle_tpu.runtime.master import MasterClient, MasterServer, TaskMaster

    srv = MasterServer(
        TaskMaster(), lease_s=0.6, resize_on_membership=True,
        resize_drain_timeout_s=30.0,
    ).start()
    try:
        c = MasterClient(srv.address)
        t1 = c.call("register")["trainer_id"]
        t2 = c.call("register")["trainer_id"]  # join-epoch 1: world=2
        c.call("resize_drained", trainer_id=t1, epoch=1)
        c.call("resize_drained", trainer_id=t2, epoch=1)
        c.call("resize_status", trainer_id=t1, epoch=1)
        info = c.call("resize_status", trainer_id=t2, epoch=1)
        assert info["state"] == "idle" and info["completed"] == 1

        t3 = c.call("register")["trainer_id"]  # join-epoch 2: world=3
        # t2 dies silently while epoch 2 drains; t1/t3 heartbeat but hold
        # their acks so the eviction lands mid-epoch
        info = c.call("resize_status", epoch=2)
        deadline = time.time() + 20
        while time.time() < deadline and info["barrier"] > 2:
            c.call("heartbeat", trainer_id=t1)
            c.call("heartbeat", trainer_id=t3)
            time.sleep(0.1)
            info = c.call("resize_status", epoch=2)
        assert info["barrier"] == 2, info  # t2 evicted from the barrier
        # epoch 2 completes at its (now stale) world=3
        c.call("resize_drained", trainer_id=t1, epoch=2)
        c.call("resize_drained", trainer_id=t3, epoch=2)
        c.call("resize_status", trainer_id=t1, epoch=2)
        c.call("resize_status", trainer_id=t3, epoch=2)
        # the parked churn re-announces epoch 3 with the live count (2)
        st = c.call("stats")["resize"]
        deadline = time.time() + 20
        while time.time() < deadline and st["epoch"] < 3:
            c.call("heartbeat", trainer_id=t1)
            c.call("heartbeat", trainer_id=t3)
            time.sleep(0.1)
            st = c.call("stats")["resize"]
        assert st["epoch"] == 3 and st["state"] == "draining", st
        assert st["world"] == 2, st
        c.call("resize_drained", trainer_id=t1, epoch=3)
        c.call("resize_drained", trainer_id=t3, epoch=3)
        c.call("resize_status", trainer_id=t1, epoch=3)
        end = c.call("resize_status", trainer_id=t3, epoch=3)
        assert end["state"] == "idle" and end["last"]["world"] == 2
        c.close()
    finally:
        srv.stop()


@needs_native
@pytest.mark.timeout(90)
def test_reader_leases_join_barrier_but_not_world():
    """A process may hold a reader lease besides its trainer lease. The
    announced WORLD counts trainer-role leases only (double-counting would
    shard the data axis to a size no real trainer backs) while the drain
    BARRIER spans every lease — and a reader joining/leaving triggers no
    membership epoch at all."""
    from paddle_tpu.runtime.master import MasterClient, MasterServer, TaskMaster

    srv = MasterServer(
        TaskMaster(), lease_s=5.0, resize_on_membership=True,
    ).start()
    try:
        c = MasterClient(srv.address)
        t1 = c.call("register")["trainer_id"]
        r1 = c.call("register", role="reader")["trainer_id"]
        # a reader lease joining changes no world size: still idle
        assert c.call("stats")["resize"]["state"] == "idle"
        t2 = c.call("register")["trainer_id"]  # join-epoch: world=2, not 3
        st = c.call("stats")["resize"]
        assert st["state"] == "draining" and st["world"] == 2, st
        assert st["barrier"] == 3, st  # ...but ALL three leases must drain
        for tid in (t1, r1, t2):
            c.call("resize_drained", trainer_id=tid, epoch=st["epoch"])
        for tid in (t1, r1, t2):
            end = c.call("resize_status", trainer_id=tid, epoch=st["epoch"])
        assert end["state"] == "idle" and end["last"]["world"] == 2
        c.close()
    finally:
        srv.stop()


@needs_native
@pytest.mark.timeout(60)
def test_watcher_claims_colliding_epoch_from_restarted_master():
    """Epoch numbers are per-master-instance counters: a promoted standby
    counts from 1 again, so its first epoch can COLLIDE with (or sit below)
    a number this trainer already claimed from the dead primary. The
    watcher's replay guard keys on (instance, epoch), so the new master's
    epoch still drains this trainer — a bare-number guard would silently
    exempt it from every resize the new master runs."""
    from paddle_tpu.runtime.master import MasterClient, MasterServer, ResizeClient, TaskMaster

    srv = MasterServer(TaskMaster(), lease_s=0.6).start()
    rc = None
    try:
        rc = ResizeClient(srv.address)
        # as if epoch 1 (and a later epoch 7) were claimed pre-failover
        # from a master instance that no longer exists
        rc._seen = ("dead-primary", 1)
        # ...with the primary's epoch-7 order still parked, unclaimed
        assert preempt.get().request_resize(
            8, epoch=7, instance="dead-primary", reason="stale primary"
        )
        c = MasterClient(srv.address)
        ann = c.call("resize", world=2)
        assert ann["epoch"] == 1  # fresh master numbering restarts
        deadline = time.time() + 15
        req = None
        while time.time() < deadline:
            req = preempt.get().resize_request()
            if req is not None and req.epoch == 1:
                break
            time.sleep(0.05)
        req = preempt.get().take_resize()
        assert req is not None, "watcher never parked the epoch-1 order"
        # the live master's epoch 1 SUPERSEDED the dead primary's parked 7:
        # different instance outranks a higher stale number
        assert req.world == 2 and req.epoch == 1
        assert req.instance == ann["instance"] != "dead-primary"
        c.close()
    finally:
        if rc is not None:
            rc.close()
        srv.stop()


@needs_native
def test_resurrected_reader_lease_keeps_its_role():
    """An evicted reader whose next get_task/task_done resurrects the lease
    (note_seen carries no role) must keep its reader role — defaulting back
    to "trainer" would inflate the next membership-triggered world size."""
    from paddle_tpu.runtime.master import _Membership

    m = _Membership(lease_s=0.01)
    m.register("trainer")
    rid = m.register("reader")
    assert m.live_trainers == 1
    m.drop(rid)  # eviction path
    assert m.live_trainers == 1
    m.note_seen(rid)  # role-less RPC resurrects the lease
    assert m.live == 2
    assert m.live_trainers == 1  # still a reader, not a default trainer
    assert m.role(rid) == "reader"


def test_request_resize_instance_supersede_rules():
    """The parked-order channel: local epoch-0 never clobbers anything
    parked, same-instance duplicates/stale epochs are ignored, a later
    same-instance epoch and ANY different-instance epoch supersede."""
    g = preempt.get()
    assert g.request_resize(2)  # local order parks
    assert not g.request_resize(4)  # second local order ignored
    assert g.request_resize(4, epoch=3, instance="m1")  # master beats local
    assert not g.request_resize(8, epoch=3, instance="m1")  # duplicate
    assert not g.request_resize(8, epoch=2, instance="m1")  # stale
    assert not g.request_resize(8)  # local never clobbers a parked master's
    assert g.request_resize(8, epoch=4, instance="m1")  # later epoch wins
    assert g.request_resize(2, epoch=1, instance="m2")  # failover wins
    req = g.take_resize()
    assert (req.world, req.epoch, req.instance) == (2, 1, "m2")


@needs_native
@pytest.mark.timeout(60)
def test_drain_barrier_proceeds_alone_when_master_dies():
    """A dead master mid-epoch must trigger the documented proceed-alone
    fallback (announced world), not crash the training pass with an
    unhandled ConnectionError from the barrier polls."""
    import socket as socket_mod

    from paddle_tpu.runtime.master import MasterClient, _drain_barrier

    s = socket_mod.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()  # nothing listens here: every call exhausts retries
    c = MasterClient(
        ("127.0.0.1", port), timeout=2.0, retries=2, backoff_base=0.01
    )
    world = _drain_barrier(
        c, "t-gone", epoch=3, fallback_world=4, poll_s=0.01, max_wait_s=10.0
    )
    assert world == 4
    assert stats.FT_EVENTS.get("resize_barrier_master_lost") >= 1
    c.close()


# -- nightly: the full chaos_bench drill --------------------------------------


@pytest.mark.nightly
@pytest.mark.chaos
@pytest.mark.timeout(560)
def test_chaos_bench_resize_all_gates():
    """Heavy real-subprocess drill: every --mode resize gate (grow, shrink,
    reshard_kill resume, drain-barrier kill with exactly-once accounting)
    must pass in a fresh interpreter."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("XLA_FLAGS", None)  # the bench forces its own device count
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "chaos_bench.py"),
         "--mode", "resize", "--batches", "8"],
        env=env, capture_output=True, text=True, timeout=540,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout)
    assert result["all_gates_pass"], json.dumps(result, indent=1)
    assert result["grow"]["pass_avg_match"]
    assert result["shrink"]["pass_avg_match"]
    assert result["reshard_kill"]["resume_bitwise_vs_uninterrupted"]
    fleet = result["drain_barrier_kill"]
    assert fleet["exactly_once_tasks"] and fleet["coverage_complete"]
    assert fleet["barrier_exercised"]
