"""Observability-plane tests (ISSUE 7): span tracing + Chrome export,
RPC trace-context propagation through a REAL MasterServer process,
heartbeat-aggregated fleet metrics, Prometheus export, serving request
correlation, HLO cost reporting, and the profiler-idempotence satellite."""

import json
import os
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from paddle_tpu.core import stats
from paddle_tpu.obs import metrics as obs_metrics
from paddle_tpu.obs import trace

pytestmark = [pytest.mark.timeout(150)]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _native_available() -> bool:
    from paddle_tpu.runtime import available

    return available()


needs_native = pytest.mark.skipif(
    not _native_available(), reason="native runtime unavailable"
)


@pytest.fixture(autouse=True)
def _fresh_tracer():
    was = trace.TRACER.enabled
    trace.reset()
    trace.enable_tracing(True)
    yield
    trace.enable_tracing(was)
    trace.reset()


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _wait_port(port: int, deadline_s: float = 60.0) -> None:
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        try:
            socket.create_connection(("127.0.0.1", port), 0.5).close()
            return
        except OSError:
            time.sleep(0.1)
    raise TimeoutError(f"port {port} never came up")


def _child_env() -> dict:
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    return env


# -- span API + Chrome export -------------------------------------------------


def test_chrome_export_golden_format():
    """The export is loadable trace-event JSON: every event carries
    ph/ts/pid/tid/name (the Perfetto-required keys), complete-event phase,
    and parent/trace ids that reflect span nesting."""
    with trace.span("outer", role="test"):
        with trace.span("inner"):
            time.sleep(0.001)
    trace.record_span("external", 1_000, 2_000)
    out = trace.export_chrome()
    assert trace.validate_chrome(out) == []
    events = out["traceEvents"]
    assert {e["name"] for e in events} == {"outer", "inner", "external"}
    for ev in events:
        for key in ("ph", "ts", "pid", "tid", "name"):
            assert key in ev, f"event missing {key}: {ev}"
        assert ev["ph"] == "X" and ev["dur"] >= 0
    # survives a JSON round-trip byte-for-byte (what a file load sees)
    assert json.loads(json.dumps(out)) == out
    inner = next(e for e in events if e["name"] == "inner")
    outer = next(e for e in events if e["name"] == "outer")
    assert inner["args"]["trace_id"] == outer["args"]["trace_id"]
    assert inner["args"]["parent_id"] == outer["args"]["span_id"]
    assert outer["args"]["role"] == "test"
    assert inner["ts"] >= outer["ts"]


def test_ring_buffer_bounded_and_counts_drops():
    t = trace.Tracer(capacity=4)
    t.enabled = True
    for i in range(10):
        t.record("s", i, 1, "tid", f"sp{i}", None, None)
    rows = t.snapshot()
    assert len(rows) == 4
    assert [r[1] for r in rows] == [6, 7, 8, 9]  # oldest dropped, order kept
    assert t.dropped == 6 and t.recorded == 10


def test_disabled_tracing_records_nothing():
    trace.enable_tracing(False)
    before = trace.TRACER.recorded
    with trace.span("nope", x=1):
        trace.record_span("also_nope", 0, 1)
    assert trace.TRACER.recorded == before
    assert trace.wire_context() is None


def test_activate_foreign_context_stitches_trace():
    wire = {"t": "cafe" * 4, "s": "dead.1"}
    with trace.activate(wire):
        with trace.span("child"):
            pass
    ev = trace.export_chrome()["traceEvents"][0]
    assert ev["args"]["trace_id"] == wire["t"]
    assert ev["args"]["parent_id"] == wire["s"]


def test_span_stack_survives_exceptions():
    with pytest.raises(RuntimeError):
        with trace.span("outer"):
            raise RuntimeError("boom")
    assert trace.TRACER.current() is None  # stack fully unwound
    with trace.span("after"):
        assert trace.TRACER.current() is not None


# -- metrics registry + Prometheus -------------------------------------------


def test_metrics_registry_absorbs_event_counters():
    stats.FT_EVENTS.incr("obs_test_marker", 3)
    snap = obs_metrics.snapshot()
    key = "paddle_tpu_events_total{event=obs_test_marker,group=ft}"
    assert snap[key] == 3.0
    text = obs_metrics.to_prometheus_text()
    assert "# TYPE paddle_tpu_events_total counter" in text
    assert 'paddle_tpu_events_total{event="obs_test_marker",group="ft"} 3' in text


def test_histogram_and_prometheus_shape():
    reg = obs_metrics.MetricsRegistry()
    h = reg.histogram("t_seconds", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = obs_metrics.to_prometheus_text(reg)
    assert 't_seconds_bucket{le="0.1"} 1' in text
    assert 't_seconds_bucket{le="1.0"} 2' in text
    assert 't_seconds_bucket{le="+Inf"} 3' in text
    assert "t_seconds_count 3" in text
    c = reg.counter("reqs_total")
    c.inc(2, tenant="a")
    assert 'reqs_total{tenant="a"} 2' in obs_metrics.to_prometheus_text(reg)


def test_aggregate_snapshots_sums_and_skips_garbage():
    agg = obs_metrics.aggregate_snapshots(
        [{"a": 1, "b": 2}, {"a": 4, "c": "garbage"}]
    )
    assert agg == {"a": 5.0, "b": 2.0}


def test_fleet_metrics_ttl_and_drop():
    fm = obs_metrics.FleetMetrics(ttl_s=60)
    fm.update("tr-1", {"a": 1.0})
    fm.update("tr-2", {"a": 2.0, "b": 1.0})
    agg = fm.aggregate()
    assert agg["reporting_trainers"] == 2
    assert agg["counters"] == {"a": 3.0, "b": 1.0}
    fm.drop("tr-1")
    assert fm.aggregate()["reporting_trainers"] == 1


def test_obs_export_cli_local(tmp_path):
    """`python -m paddle_tpu.obs export` without an endpoint prints this
    process's registry as Prometheus text; `... trace` emits loadable JSON."""
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.obs", "export"],
        env=_child_env(), capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 0, r.stderr
    assert "# TYPE paddle_tpu_shape_signatures gauge" in r.stdout
    out = tmp_path / "t.json"
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.obs", "trace", "--out", str(out)],
        env=_child_env(), capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 0, r.stderr
    loaded = json.loads(out.read_text())
    assert "traceEvents" in loaded


# -- RPC propagation + fleet aggregation (master plane) -----------------------


@needs_native
def test_rpc_trace_roundtrips_through_real_master_process(tmp_path):
    """Acceptance: the trace context piggybacked on the line-JSON frames
    round-trips through a REAL `python -m paddle_tpu.runtime.master serve`
    process — the server's handler spans (fetched over the `trace_export`
    RPC) stitch into the client span's trace id, and the merged trace is
    Perfetto-loadable."""
    from paddle_tpu.runtime.master import MasterClient

    port = _free_port()
    proc = subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.runtime.master", "serve",
         "--port", str(port), "--trace", "1"],
        env=_child_env(), stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )
    try:
        _wait_port(port)
        client = MasterClient(("127.0.0.1", port))
        client.call("set_dataset", shards=["a", "b"])
        got = client.call("get_task")
        assert "task_id" in got
        remote = client.call("trace_export")["chrome_trace"]
        client.close()
        local = trace.export_chrome()

        def events(tr, name, side):
            return [
                e for e in tr["traceEvents"]
                if e["name"] == name and e["args"].get("side") == side
            ]

        cl = events(local, "rpc.get_task", "client")
        sv = events(remote, "rpc.get_task", "server")
        assert len(cl) == 1 and len(sv) == 1
        # one trace id across the process boundary; the server span is the
        # client span's child; distinct processes (pid rows) in the merge
        assert sv[0]["args"]["trace_id"] == cl[0]["args"]["trace_id"]
        assert sv[0]["args"]["parent_id"] == cl[0]["args"]["span_id"]
        assert sv[0]["pid"] != cl[0]["pid"]
        merged = trace.merge_chrome([local, remote])
        assert trace.validate_chrome(merged) == []
    finally:
        proc.terminate()
        proc.wait(timeout=15)


@needs_native
def test_master_stats_aggregates_heartbeat_metrics():
    """Heartbeats carrying metric snapshots land in stats()["fleet"]:
    counters sum across trainers, deregister drops the contribution."""
    from paddle_tpu.runtime.master import MasterClient, MasterServer, TaskMaster

    server = MasterServer(TaskMaster(), lease_s=30.0).start()
    try:
        c = MasterClient(server.address)
        t1 = c.call("register")["trainer_id"]
        t2 = c.call("register")["trainer_id"]
        c.call("heartbeat", trainer_id=t1, metrics={"steps": 5, "x": 1})
        c.call("heartbeat", trainer_id=t2, metrics={"steps": 7})
        fleet = c.call("stats")["fleet"]
        assert fleet["reporting_trainers"] == 2
        assert fleet["counters"]["steps"] == 12.0
        assert fleet["counters"]["x"] == 1.0
        # a RE-heartbeat replaces (not doubles) that trainer's snapshot
        c.call("heartbeat", trainer_id=t2, metrics={"steps": 8})
        assert c.call("stats")["fleet"]["counters"]["steps"] == 13.0
        c.call("deregister", trainer_id=t2)
        fleet = c.call("stats")["fleet"]
        assert fleet["reporting_trainers"] == 1
        assert fleet["counters"]["steps"] == 5.0
        # the metrics RPC serves Prometheus text incl. the fleet aggregate
        text = c.call("metrics")["text"]
        assert "paddle_tpu_fleet_reporting_trainers 1" in text
        assert 'paddle_tpu_fleet{key="steps"} 5' in text
        c.close()
    finally:
        server.stop()


# -- serving correlation (client → server → session) --------------------------


@pytest.fixture(scope="module")
def tiny_session():
    from paddle_tpu.serving.session import make_demo_session

    return make_demo_session(
        vocab=64, n_layers=1, d_model=16, n_heads=2, seed=0,
        max_slots=2, page_size=8, prefill_buckets=(8,), max_new_limit=4,
    )


@pytest.mark.serving
@needs_native
def test_serving_request_spans_share_one_trace_id(tiny_session):
    """Acceptance: one serving request's spans — client RPC, server handler,
    and the engine's queue-wait/prefill/ttft — correlate under ONE trace id,
    and the server's buffer exports as loadable Chrome trace JSON."""
    from paddle_tpu.serving.server import ServingClient, ServingServer

    srv = ServingServer(session=tiny_session).start()
    try:
        c = ServingClient(srv.address)
        res = c.generate([1, 2, 3], max_new_tokens=3, timeout_s=60)
        assert res["done"]
        exported = c.trace_export()
        assert trace.validate_chrome(exported) == []
        c.close()
    finally:
        srv.stop()
    events = exported["traceEvents"]
    submit_client = [
        e for e in events
        if e["name"] == "rpc.submit" and e["args"].get("side") == "client"
    ]
    assert submit_client, [e["name"] for e in events]
    tid = submit_client[0]["args"]["trace_id"]
    by_trace = {
        e["name"] for e in events if e["args"].get("trace_id") == tid
    }
    assert {
        "rpc.submit", "serving.queue_wait", "serve.admit", "serving.ttft",
    } <= by_trace, by_trace
    # batch-level decode steps ran too (their own trace — they serve many
    # requests at once) and TTFT landed in the histogram
    assert any(e["name"] == "serve.decode" for e in events)
    from paddle_tpu.serving.session import TTFT_HISTOGRAM

    assert TTFT_HISTOGRAM._n > 0


@pytest.mark.serving
@needs_native
def test_serving_stats_forwards_master_health(tiny_session):
    """Satellite: stats() on a serving server wired to a routing master
    surfaces the control plane's snapshot_failures / lease evictions /
    live+evicted trainer counts — and reports unreachability as data."""
    from paddle_tpu.runtime.master import MasterClient, MasterServer, TaskMaster
    from paddle_tpu.serving.server import ServingClient, ServingServer

    master = MasterServer(TaskMaster(), lease_s=30.0).start()
    mc = MasterClient(master.address)
    tid = mc.call("register")["trainer_id"]
    srv = ServingServer(
        session=tiny_session, master_endpoints=master.address
    ).start()
    srv._master_health_ttl_s = 0.0  # probe every stats() — the test flips
    # the master down and must see the change immediately, not the cache
    try:
        c = ServingClient(srv.address)
        st = c.stats()
        assert st["master"]["reachable"] is True
        assert st["master"]["snapshot_failures"] == 0
        assert st["master"]["live_trainers"] == 1
        assert st["master"]["evicted_trainers"] == 0
        mc.close()
        master.stop()  # control plane dies; serving stats must say so
        st = c.stats()
        assert st["master"]["reachable"] is False and st["master"]["error"]
        c.close()
    finally:
        srv.stop()
        master.stop()


# -- profiling hooks ----------------------------------------------------------


def test_profiler_start_stop_idempotent(tmp_path):
    """Satellite: double start warns + no-ops (no jax RuntimeError), stop
    without start no-ops."""
    stats.profiler_stop()  # no active trace: must be a silent no-op
    stats.profiler_start(str(tmp_path / "p"))
    stats.profiler_start(str(tmp_path / "p"))  # second start: warn + no-op
    stats.profiler_stop()
    stats.profiler_stop()  # double stop: no-op


def test_profiler_start_passes_the_options_that_do_not_starve_the_host(
    tmp_path, monkeypatch
):
    """--profile pass:N goes through stats.profiler_start: Python tracer
    off, host tracer at user annotations only, no HLO copies (with jax's
    defaults the trace itself starved the chip: PERF.md, PR 25)."""
    import jax

    seen = {}
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda logdir, **kw: seen.update(logdir=logdir, **kw),
    )
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    stats.profiler_start(str(tmp_path / "p"))
    stats.profiler_stop()
    options = seen["profiler_options"]
    assert seen["logdir"] == str(tmp_path / "p")
    assert options.python_tracer_level == 0
    assert options.host_tracer_level == 1
    assert options.enable_hlo_proto is False


def _toy_trainer_and_batch():
    from paddle_tpu.nn import costs as C
    from paddle_tpu.nn import layers as L
    from paddle_tpu.nn.graph import reset_name_scope
    from paddle_tpu.optim import SGD
    from paddle_tpu.trainer import SGDTrainer

    reset_name_scope()
    x = L.Data("x", shape=(8,))
    lbl = L.Data("label", shape=())
    cost = C.ClassificationCost(L.Fc(L.Fc(x, 16, act="relu"), 3, act=None), lbl)
    trainer = SGDTrainer(cost, SGD(learning_rate=0.1), seed=0)
    rs = np.random.RandomState(0)
    batch = {
        "x": rs.randn(8, 8).astype(np.float32),
        "label": (np.arange(8) % 3).astype(np.int32),
    }
    return trainer, batch


def test_trainer_cost_report_top_k_buckets():
    from paddle_tpu.obs import profile as obs_profile

    trainer, batch = _toy_trainer_and_batch()
    trainer.init_state(batch)
    report = obs_profile.trainer_cost_report(trainer, batch, top_k=3)
    step = report["executables"]["train_step"]
    assert step["flops"] > 0
    assert step["bytes_accessed"] > 0
    assert 0 < len(step["top_buckets"]) <= 3
    # ranked descending, deterministically
    vals = [b["value"] for b in step["top_buckets"]]
    assert vals == sorted(vals, reverse=True)


def test_pass_profiler_captures_one_pass(tmp_path):
    from paddle_tpu.obs import profile as obs_profile

    trainer, batch = _toy_trainer_and_batch()
    profiler = obs_profile.PassProfiler.from_spec(
        "pass:1", logdir=str(tmp_path / "trace")
    )
    seen = []
    handler = profiler.wrap(lambda e: seen.append(type(e).__name__))
    trainer.train(
        lambda: iter([batch] * 4), num_passes=2, event_handler=handler,
        log_period=100,
    )
    assert profiler.captured
    assert not profiler._active
    assert (tmp_path / "trace").is_dir()
    assert "EndPass" in seen  # the wrapped handler still ran


def test_parse_profile_spec_rejects_bad_forms():
    from paddle_tpu.obs.profile import parse_profile_spec

    assert parse_profile_spec("pass:0") == ("pass", 0)
    for bad in ("", "pass", "pass:x", "pass:-1", "step:3"):
        with pytest.raises(ValueError):
            parse_profile_spec(bad)


# -- trainer spans: the flight recorder -----------------------------------------


def test_trainer_emits_pass_dispatch_checkpoint_spans(tmp_path):
    trainer, batch = _toy_trainer_and_batch()
    trainer.train(
        lambda: iter([batch] * 3), num_passes=1, log_period=100,
        save_dir=str(tmp_path / "ckpt"),
    )
    names = [r[0] for r in trace.TRACER.snapshot()]
    assert names.count("train.dispatch") == 3
    assert names.count("train.input_wait") == 4  # three items and the end
    assert "train.pass" in names
    assert "train.checkpoint" in names


NAME, START, DUR, TRACE, SPAN, PARENT, ATTRS, THREAD = range(8)


def _train_over_prefetcher(stack_k, n=8, k=2, sleep_s=0.0):
    """One train() of n batches, k steps a dispatch, fed by a
    DevicePrefetcher; returns the ring's rows and the pass's row."""
    from paddle_tpu.data.pipeline import DevicePrefetcher

    trainer, batch = _toy_trainer_and_batch()

    def reader():
        for _ in range(n):
            if sleep_s:
                time.sleep(sleep_s)
            yield batch

    seen = []
    trainer.train(
        DevicePrefetcher(reader, stack_k=stack_k), num_passes=1,
        steps_per_dispatch=k, log_period=10 ** 9,
        event_handler=lambda ev: seen.append(type(ev).__name__),
    )
    rows = trace.TRACER.snapshot()
    passes = [r for r in rows if r[NAME] == "train.pass"]
    assert len(passes) == 1
    return rows, passes[0], seen


@pytest.mark.parametrize("tracing", [False, True], ids=["trace_off", "trace_on"])
@pytest.mark.parametrize("stack_k", [1, 2])
def test_train_pass_is_a_span_tree_over_the_prefetcher(stack_k, tracing):
    """Recorded whether or not PADDLE_TPU_TRACE is set: every train.* and
    pipeline.* span carries the pass's trace id and a parent; the train
    thread's children of the pass do not overlap and fit inside it."""
    trace.enable_tracing(tracing)
    rows, tp, seen = _train_over_prefetcher(stack_k)
    ours = [r for r in rows if r[NAME].startswith(("train.", "pipeline."))]
    assert {r[TRACE] for r in ours} == {tp[TRACE]}
    assert all(r[PARENT] for r in ours if r is not tp) and tp[PARENT] is None
    assert (tp[ATTRS]["pass_id"], tp[ATTRS]["batches"]) == (0, 8)
    by_name = {}
    for r in ours:
        by_name.setdefault(r[NAME], []).append(r)
    assert len(by_name["train.dispatch"]) == 4
    assert {r[ATTRS]["k"] for r in by_name["train.dispatch"]} == {2}
    # one pull per item and one that finds the end
    assert len(by_name["train.input_wait"]) == (8 if stack_k == 1 else 4) + 1
    # the caller's handler: BeginPass, EndPass, Begin- and EndIteration
    assert len(by_name["train.handler"]) == len(seen) == 2 + 2 * 4
    assert len(by_name["pipeline.hostFeed"]) == 8
    assert len(by_name["pipeline.h2d"]) == (8 if stack_k == 1 else 4)
    assert len(by_name.get("pipeline.stack", ())) == (0 if stack_k == 1 else 4)
    # the worker's spans sit on another thread, under the pass
    worker = {r[THREAD] for r in ours if r[NAME].startswith("pipeline.")}
    assert len(worker) == 1 and tp[THREAD] not in worker
    assert all(r[PARENT] == tp[SPAN] for r in by_name["pipeline.hostFeed"])
    children = sorted(
        (r for r in ours if r[PARENT] == tp[SPAN] and r[THREAD] == tp[THREAD]),
        key=lambda r: r[START],
    )
    assert {r[NAME] for r in children} == {
        "train.input_wait", "train.handler", "train.dispatch", "train.cost_fetch"}
    for before, after in zip(children, children[1:]):
        assert before[START] + before[DUR] <= after[START], (before, after)
    assert children[0][START] >= tp[START]
    assert children[-1][START] + children[-1][DUR] <= tp[START] + tp[DUR]
    assert sum(r[DUR] for r in children) <= tp[DUR]


def test_one_batch_reads_feed_put_queue_wait_dispatch():
    """The worker's spans and the train thread's carry the same batch
    index, so one batch can be followed across the two threads."""
    rows, tp, _ = _train_over_prefetcher(stack_k=1, n=4, k=2)

    def batch_indices(name, key="batch"):
        return sorted(r[ATTRS][key] for r in rows if r[NAME] == name)

    assert batch_indices("pipeline.hostFeed") == [0, 1, 2, 3]
    assert batch_indices("pipeline.h2d") == [0, 1, 2, 3]
    assert batch_indices("pipeline.queue_full", "item") == [0, 1, 2, 3, 4]  # and the stop mark
    assert batch_indices("train.input_wait") == [0, 1, 2, 3, 4]
    assert batch_indices("train.dispatch", "first") == [0, 2]
    # the feed of a batch ends before the wait that received it ends
    feeds = {r[ATTRS]["batch"]: r for r in rows if r[NAME] == "pipeline.hostFeed"}
    waits = {r[ATTRS]["batch"]: r for r in rows if r[NAME] == "train.input_wait"}
    for i, feed in feeds.items():
        assert feed[START] + feed[DUR] <= waits[i][START] + waits[i][DUR]


def test_a_double_buffer_records_one_queue_full_span_an_item_and_nothing_else():
    """DoubleBuffer shares the prefetcher's producer loop (iter_async), so
    its worker writes `pipeline.queue_full` per queue item (and the stop
    mark), inside the trace of whoever iterates it."""
    from paddle_tpu.data.provider import DoubleBuffer

    with trace.flight("train.pass") as tp:
        assert list(DoubleBuffer(lambda: iter([1, 2, 3]), capacity=2)) == [1, 2, 3]
    rows = [r for r in trace.TRACER.snapshot() if r[NAME] != "train.pass"]
    assert [r[NAME] for r in rows] == ["pipeline.queue_full"] * 4
    assert sorted(r[ATTRS]["item"] for r in rows) == [0, 1, 2, 3]
    assert {(r[TRACE], r[PARENT]) for r in rows} == {(tp.trace_id, tp.span_id)}


def test_counters_equal_the_sums_of_the_spans_they_sit_beside():
    def read():
        return obs_metrics.snapshot()

    before = read()
    rows, tp, _ = _train_over_prefetcher(stack_k=1, sleep_s=0.002)
    after = read()

    def delta(key):
        return after.get(key, 0.0) - before.get(key, 0.0)

    def total_s(name):
        return sum(r[DUR] for r in rows if r[NAME] == name) * 1e-9

    def count(name):
        return sum(1 for r in rows if r[NAME] == name)

    assert delta("paddle_tpu_train_input_wait_seconds_total") == pytest.approx(
        total_s("train.input_wait"), rel=1e-9)
    assert total_s("train.input_wait") > 0.002  # the feed sleeps: the loop waited
    assert delta("paddle_tpu_train_dispatches_total") == count("train.dispatch") == 4
    assert delta("paddle_tpu_pipeline_batches_total") == count("pipeline.hostFeed") == 8
    for phase in ("trace", "lower", "backend"):
        key = "paddle_tpu_compile_seconds_total{phase=%s}" % phase
        assert delta(key) == pytest.approx(total_s("compile." + phase), rel=1e-9, abs=1e-12)
    text = obs_metrics.to_prometheus_text()
    assert "paddle_tpu_train_input_wait_seconds_total" in text
    assert "paddle_tpu_timer_ms_total" not in text  # the timers are gone


def test_a_new_batch_shape_inside_a_pass_leaves_a_compile_span_naming_the_step():
    """Which step recompiled, when, for how long: a compile.backend span
    with the jitted step's name, inside the dispatch that paid for it."""
    trainer, batch = _toy_trainer_and_batch()
    small = {k: v[:5] for k, v in batch.items()}
    trainer.train(lambda: iter([batch, batch]), num_passes=1, log_period=10 ** 9)
    trace.reset()
    trainer.train(lambda: iter([batch, small, batch]), num_passes=1, log_period=10 ** 9)
    rows = trace.TRACER.snapshot()
    tp = next(r for r in rows if r[NAME] == "train.pass")
    dispatches = {r[SPAN]: r for r in rows if r[NAME] == "train.dispatch"}
    step_name = trainer._step_fn.__name__
    backend = [r for r in rows if r[NAME] == "compile.backend"
               and r[PARENT] in dispatches]
    assert len(backend) == 1, [(r[NAME], r[ATTRS]) for r in rows if r[NAME].startswith("compile.")]
    assert step_name in backend[0][ATTRS]["fun_name"]
    assert dispatches[backend[0][PARENT]][ATTRS]["first"] == 1  # the small batch
    assert backend[0][TRACE] == tp[TRACE]
    phases = {r[NAME] for r in rows if r[PARENT] == backend[0][PARENT]}
    assert phases >= {"compile.trace", "compile.lower", "compile.backend"}


def test_what_compiles_inside_anothers_trace_leaves_no_span_of_its_own():
    """Every jnp ufunc is a jit: traced inside a model they would be
    thousands of microsecond spans and seconds counted twice; an op run
    eagerly on a concrete value there is lowered and compiled inside the
    outer trace too. The outer function's trace span covers them all, so a
    thread's compile spans never overlap; on their own they have theirs."""
    import jax
    import jax.numpy as jnp

    def outer_fn(x):
        with jax.ensure_compile_time_eval():  # compiled and run during the trace
            table = jnp.sin(np.ones((3, 11), np.float32))
        return jnp.multiply(jnp.add(x, table[0, 0]), jax.jit(lambda y: y * 3.0)(x))

    jax.jit(outer_fn)(np.ones((3, 5), np.float32)).block_until_ready()
    rows = [r for r in trace.TRACER.snapshot() if r[NAME].startswith("compile.")]
    assert [r[NAME] for r in rows] == ["compile.trace", "compile.lower", "compile.backend"]
    assert all("outer_fn" in r[ATTRS]["fun_name"] for r in rows)  # "jit(outer_fn)" once lowered
    for a, b in zip(rows, rows[1:]):
        assert a[START] + a[DUR] <= b[START]
    trace.reset()
    jnp.sin(np.ones((3, 7), np.float32)).block_until_ready()
    names = [(r[NAME], r[ATTRS]["fun_name"]) for r in trace.TRACER.snapshot()]
    assert {("compile.trace", "sin"), ("compile.backend", "jit(sin)")} <= set(names)


def test_tracing_off_records_no_gated_span_and_sends_no_wire_context():
    """PADDLE_TPU_TRACE unset: the flight recorder runs, and still no RPC,
    serving or router span is recorded and no `_trace` key rides a frame,
    not even from inside an open train span."""
    trace.enable_tracing(False)
    with trace.flight("train.pass"):
        assert trace.current_context() is not None
        assert trace.wire_context() is None
        with trace.span("rpc.get_task"):
            trace.record_span("serving.ttft", 0, 1)
            trace.span_from_monotonic("serving.queue_wait", time.monotonic())
        with trace.server_span("rpc.submit", {"t": "ab" * 8, "s": "1.1"}):
            pass
        with trace.activate({"t": "ab" * 8, "s": "1.1"}):  # a wire context
            assert trace.current_context()[0] != "ab" * 8
    assert [r[NAME] for r in trace.TRACER.snapshot()] == ["train.pass"]


def test_a_thread_adopts_a_pass_context_whether_or_not_tracing_is_on():
    trace.enable_tracing(False)
    got = {}

    def worker(ctx):
        with trace.activate(ctx):
            with trace.flight("pipeline.hostFeed") as sp:
                pass
        got["span"] = sp

    with trace.flight("train.pass") as tp:
        t = threading.Thread(target=worker, args=(trace.current_context(),))
        t.start()
        t.join(10)
        assert not t.is_alive()
    assert (got["span"].trace_id, got["span"].parent_id) == (tp.trace_id, tp.span_id)


def test_the_ring_keeps_nanoseconds_and_the_chrome_export_microseconds():
    t0 = time.time_ns()
    with trace.flight("train.dispatch", k=1) as sp:
        time.sleep(0.001)
    t1 = time.time_ns()
    row = trace.TRACER.snapshot()[-1]
    assert t0 <= row[START] <= t1 and row[DUR] == sp.dur_ns
    assert 1_000_000 <= row[DUR] <= t1 - t0
    ev = trace.export_chrome()["traceEvents"][-1]
    assert ev["ts"] == row[START] / 1000 and ev["dur"] == row[DUR] / 1000
