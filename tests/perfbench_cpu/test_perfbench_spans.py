"""The four per-layer metrics that read the program's span ring
(perfbench/spans.py and its readers): on hand-made rings for the
arithmetic, and on the tiny training cell, run on the CPU, for what the
program really records. Counts, shares of a host clock and structure only:
nothing here is a device number."""

import time

import pytest

from perfbench_testlib import (CPU_DEVICE, V5E_PEAKS, extended_base,
                               extended_benchmark, run_cell)

METRICS = ("input_wait_share.train", "host_ms_per_dispatch",
           "setup_trace_lower_s", "setup_backend_s")
CELL = "mlp_tiny.train"
MS = 1_000_000  # ns


def later_benchmark() -> dict:
    """What the PR that adds the tiny cell would do to the four entries:
    append its cell to their `workloads`."""
    bench = extended_benchmark()
    for entry in bench["per_layer"]:
        if entry["name"] in METRICS:
            entry["workloads"] = entry["workloads"] + [CELL]
    return bench


@pytest.fixture(scope="module")
def base(tmp_path_factory):
    return extended_base(tmp_path_factory.mktemp("pbspans"))


def traced_run(base, tmp, monkeypatch, seconds=0.5):
    """One traced run of the tiny cell with an empty ring to start from (a
    run of the benchmark is a process of its own)."""
    from paddle_tpu.obs import trace
    from perfbench import harness

    # the CPU's trace holds no TPU plane: the trace-fed readers are left out
    monkeypatch.setattr(harness.Profiler, "summary", lambda self, chips: None)
    monkeypatch.setattr("perfbench_testlib.extended_benchmark", later_benchmark)
    trace.reset()
    return run_cell(base, CELL, seconds=seconds, trace=True, tmp=tmp)


def shaped_run(base, tmp, monkeypatch, feed_sleep_s, handler_sleep_s):
    """A traced run with the cell's feed and the caller's handler slowed:
    the sleeps stand for a host-bound feed and for a handler blocked on a
    device-bound step."""
    from paddle_tpu.trainer.events import EndIteration
    from perfbench.training import TrainSystem

    plain = TrainSystem._train

    def shaped(self, batches, handler):
        def slow_batches():
            for b in batches:
                time.sleep(feed_sleep_s)
                yield b

        def slow_handler(ev):
            if isinstance(ev, EndIteration):
                time.sleep(handler_sleep_s)
            handler(ev)

        return plain(self, slow_batches(), slow_handler)

    with monkeypatch.context() as patch:
        patch.setattr(TrainSystem, "_train", shaped)
        return traced_run(base, tmp, patch)["metrics"]


# -- on the tiny training cell --------------------------------------------------


def test_the_four_readers_report_on_the_tiny_training_cell(base, tmp_path, monkeypatch):
    from paddle_tpu.obs import trace

    r = traced_run(base, tmp_path, monkeypatch)
    assert r["correct"] is True, r["checks"]
    assert set(r["metrics"]) == {"compile_s", "mfu.train", "steps_per_s", *METRICS}
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert {r["metrics"][k]["unit"] for k in METRICS} == {"%", "ms", "s"}
    assert 0.0 <= m["input_wait_share.train"] <= 100.0
    assert m["host_ms_per_dispatch"] > 0.0
    assert m["setup_trace_lower_s"] > 0.0 and m["setup_backend_s"] > 0.0
    # both are inside warm-up, which compile_s times with one host clock
    assert m["setup_trace_lower_s"] + m["setup_backend_s"] <= m["compile_s"]
    assert trace.TRACER.dropped == 0
    # the window's pass counts the steps the harness counted
    passes = [row for row in trace.TRACER.snapshot() if row[0] == "train.pass"]
    assert passes[-1][6]["batches"] == r["attempted"]


def test_input_wait_share_answers_to_the_feed_and_host_ms_leaves_the_handler_out(
    base, tmp_path, monkeypatch
):
    """A feed that sleeps 20 ms a batch (40 ms a dispatch of 2) against a
    handler that takes 20 ms: the loop waits for about half of every cycle,
    stated here as more than 30%. An instant feed under the same handler
    runs ahead: under 15%, with room for a loaded test machine. Neither
    sleep is the loop's own work."""
    slow = shaped_run(base, tmp_path / "slow", monkeypatch, 0.02, 0.02)
    fast = shaped_run(base, tmp_path / "fast", monkeypatch, 0.0, 0.02)
    assert slow["input_wait_share.train"]["value"] > 30.0
    assert fast["input_wait_share.train"]["value"] < 15.0
    for run in (slow, fast):
        assert run["host_ms_per_dispatch"]["value"] < 10.0


# -- on hand-made rings -----------------------------------------------------------


def row(name, start_ms, dur_ms, span, parent=None, thread=1, trace="t", **attrs):
    return (name, start_ms * MS, dur_ms * MS, trace, span, parent, attrs or None, thread)


T0 = 100_000  # ms on the ring's clock at which the window's pass starts

RING = [
    # before the process began (this run's setup_s is 5 s): another run's
    row("compile.backend", T0 - 9000, 500, "old"),
    # set-up, thread 1: an outer trace holding an inner one, then its lower
    row("compile.trace", T0 - 4000, 100, "c1"),
    row("compile.trace", T0 - 3990, 20, "c2"),
    row("compile.lower", T0 - 3900, 30, "c3"),
    row("compile.backend", T0 - 3870, 400, "c4"),
    # set-up, thread 2 at the same time: summed, not merged with thread 1
    row("compile.trace", T0 - 3950, 70, "c5", thread=2),
    row("compile.backend", T0 - 3880, 100, "c6", thread=2),
    # an earlier pass (the check drive), with waits of its own
    row("train.input_wait", T0 - 2000, 300, "e1", parent="early"),
    row("train.pass", T0 - 2100, 500, "early", batches=2),
    # the window's pass: 1000 ms
    row("train.input_wait", T0 + 0, 100, "w1", parent="win", batch=0),
    row("train.handler", T0 + 100, 200, "h1", parent="win"),
    row("train.dispatch", T0 + 300, 150, "d1", parent="win", first=0, k=2),
    row("compile.backend", T0 + 310, 90, "c7", parent="d1"),       # inside the window
    row("train.input_wait", T0 + 450, 50, "w2", parent="win", batch=2),
    row("train.dispatch", T0 + 500, 150, "d2", parent="win", first=2, k=2),
    row("train.guard_poll", T0 + 650, 10, "g1", parent="win"),
    row("train.checkpoint", T0 + 660, 40, "k1", parent="win"),
    row("train.cost_fetch", T0 + 900, 90, "s1", parent="win"),
    row("train.checkpoint", T0 + 110, 50, "k2", parent="h1"),      # the handler's own save
    row("pipeline.hostFeed", T0 + 5, 400, "f1", parent="win", thread=3, batch=0),
    row("train.pass", T0, 1000, "win", pass_id=0, batches=4),
    # after the window: the reference's compiles
    row("compile.backend", T0 + 1500, 700, "c8"),
]
FACTS = {"steps": 4, "window_s": 1.2}
EXPECTED = {
    "input_wait_share.train": 15.0,                    # (100 + 50) / 1000
    "host_ms_per_dispatch": (1000 - 150 - 200 - 10 - 40 - 90) / 2,
    "setup_trace_lower_s": (100 + 30 + 70) / 1e3,      # unions per thread
    "setup_backend_s": (400 + 100) / 1e3,
}


@pytest.fixture
def ring(monkeypatch):
    """A tracer of its own in the program's place, filled by the test."""
    from paddle_tpu.obs import trace

    def fill(rows, dropped=0):
        # rows carry their own thread ids, which record() would overwrite
        # with the caller's: a full ring that wrapped over `dropped` rows
        tracer = trace.Tracer(capacity=len(rows))
        tracer._ring[:] = rows
        tracer._recorded = len(rows) + dropped
        monkeypatch.setattr(trace, "TRACER", tracer)
        return tracer

    return fill


def read(name, facts=FACTS, setup_s=5.0):
    from perfbench import harness, registry

    cell = harness.load_cell("resnet50.train")
    ctx = harness.ReadContext(cell, dict(facts), {"throughput": 1.0, "setup_s": setup_s},
                              None, V5E_PEAKS, CPU_DEVICE)
    meta = cell.per_layer[name]
    return registry.load_module("readers", meta["reader"]).read(ctx, meta)


@pytest.mark.parametrize("name", METRICS)
def test_reader_arithmetic_on_a_hand_made_ring(ring, name):
    ring(RING)
    assert read(name) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", METRICS)
def test_a_ring_that_dropped_spans_reads_as_nothing(ring, name, capsys):
    tracer = ring(RING, dropped=1)
    assert tracer.dropped == 1 and len(tracer.snapshot()) == len(RING)
    assert read(name) is None
    assert "dropped 1 of" in capsys.readouterr().out


@pytest.mark.parametrize("name", METRICS)
@pytest.mark.parametrize("why", ["served_cell", "no_pass", "another_pass", "longer_than_window"])
def test_without_the_windows_pass_a_reader_reads_nothing(ring, name, why, capsys):
    """Never a number from the wrong interval: a cell that runs no train()
    (its facts count no steps), a ring without a pass, a last pass that is
    not the one the harness measured."""
    rows, facts = RING, FACTS
    if why == "served_cell":
        facts = {"serve_s": 1.0}
    elif why == "no_pass":
        rows = [r for r in RING if r[0] != "train.pass"]
    elif why == "another_pass":
        facts = {"steps": 40, "window_s": 2.0}
    elif why == "longer_than_window":
        facts = {"steps": 4, "window_s": 0.9}
    ring(rows)
    assert read(name, facts) is None
    assert "no span read" in capsys.readouterr().out


@pytest.mark.parametrize("name", METRICS)
def test_a_program_without_the_flight_recorder_reads_as_nothing(ring, name, monkeypatch):
    """The parent of the PR that added the spans: the reader returns nothing
    and does not raise."""
    from paddle_tpu.obs import trace

    ring(RING)
    monkeypatch.delattr(trace, "flight")
    assert read(name) is None


def test_the_four_entries_name_their_layer_their_source_and_the_accepted_cells():
    """Only what has to stay true: a later cell appends its name to these
    lists and a later metric its entry to `per_layer`, with no edit here."""
    from perfbench import registry

    bench = registry.load_benchmark()
    entries = {e["name"]: e for e in bench["per_layer"]}
    assert set(METRICS) <= set(entries)
    for name in METRICS:
        e = entries[name]
        assert e["source"] == "program_span" and e["better"] == "lower"
        assert {"resnet50.train", "seq2seq_nmt.train"} <= set(e["workloads"])
    assert {entries[n]["layer"] for n in METRICS[:2]} == {"Train loop"}
    assert {entries[n]["moves"] for n in METRICS[:2]} == {"throughput"}
    assert {entries[n]["layer"] for n in METRICS[2:]} == {"Entry points"}
    assert {entries[n]["moves"] for n in METRICS[2:]} == {"setup_s"}
