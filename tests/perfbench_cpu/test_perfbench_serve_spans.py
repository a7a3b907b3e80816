"""The three readers of the engine step's own account (perfbench/serve_spans.py,
readers/engine_host_ms_per_step.py, prefill_step_time_share.py,
replayed_lane_share.py) on hand-made rings: the arithmetic, the window's
edges, and every case that has to read as nothing. Then the tiny looped
cell, run on the CPU, for what the program really records: counts, shares of
a host clock and structure only, nothing here is a device number."""

import pytest

from perfbench_testlib import CPU_DEVICE, V5E_PEAKS

READERS = ("engine_host_ms_per_step", "prefill_step_time_share", "replayed_lane_share")
CELL = "ouro_2_6b.worked_answers_saturated"
MS = 1_000_000  # ns
T0 = 500_000    # ms on the ring's clock at which the window opens
WINDOW = (T0 * MS, (T0 + 1000) * MS)


def row(name, start_ms, dur_ms, span, parent=None, **attrs):
    return (name, int(start_ms * MS), int(dur_ms * MS), "t", span, parent, attrs or None, 1)


def step(start_ms, dur_ms, span, wait_ms=0.0, **did):
    did = dict(dict(admitted=0, chunks=0, decoded=1, slots=4, preempted=0), **did)
    return row("serve.step", start_ms, dur_ms, span, wait_ns=int(wait_ms * MS), **did)


def decode(start_ms, dur_ms, parent, slots=4, replaying=0, wait_ms=0.0):
    return row("serve.decode", start_ms, dur_ms, "d" + parent, parent=parent, slots=slots,
               layer_passes=2, replaying=replaying, wait_ns=int(wait_ms * MS))


RING = [
    row("compile.backend", T0 - 9000, 500, "c0"),
    # the lead-in's: ended before the window opened
    step(T0 - 100, 40, "a", wait_ms=30), decode(T0 - 99, 38, "a", replaying=4),
    # straddles the opening edge: ENDED inside, so the window's, whole
    step(T0 - 10, 30, "b", wait_ms=26), decode(T0 - 9, 28, "b"),
    # decode-only steps: host's own 4, 2 and 6 ms
    step(T0 + 100, 34, "c", wait_ms=30), decode(T0 + 101, 32, "c", replaying=1, wait_ms=30),
    step(T0 + 200, 32, "d", wait_ms=30), decode(T0 + 201, 30, "d", slots=3, wait_ms=30),
    step(T0 + 300, 36, "e", wait_ms=30), decode(T0 + 301, 33, "e", slots=3, replaying=1),
    # an admission's step and a chunk's: prefill time, not decode-only
    step(T0 + 400, 90, "f", wait_ms=70, admitted=1),
    row("serve.admit", T0 + 401, 50, "af", parent="f", bucket=64, prompt=50, replay=0,
        queued_ms=120, wait_ns=40 * MS),
    decode(T0 + 452, 37, "f", wait_ms=30),
    step(T0 + 500, 60, "g", wait_ms=40, chunks=1), decode(T0 + 530, 29, "g"),
    # a step that only drained (nothing dispatched) and one that preempted
    step(T0 + 600, 20, "h", wait_ms=19, decoded=0, slots=0),
    step(T0 + 700, 35, "i", wait_ms=30, preempted=1, slots=3), decode(T0 + 702, 32, "i", slots=3),
    # a faulted step closed without attrs: counted as no kind of step
    ("serve.step", (T0 + 800) * MS, 5 * MS, "t", "j", None, None, 1),
    # straddles the close: ended after it, so not the window's
    step(T0 + 990, 30, "k", wait_ms=10, admitted=1), decode(T0 + 991, 28, "k", replaying=4),
    # the drain's
    step(T0 + 1100, 30, "l"), decode(T0 + 1101, 28, "l", replaying=4),
]
EXPECTED = {
    # decode-only: b (30-26), c, d, e and i (35-30): the median of 4, 4, 2, 6, 5
    "engine_host_ms_per_step": 4.0,
    # f and g over the window's 1000 ms
    "prefill_step_time_share": 100.0 * (90 + 60) / 1000,
    # the window's serve.decode rows: b c d e f g i
    "replayed_lane_share": 100.0 * 2 / (4 + 4 + 3 + 3 + 4 + 4 + 3),
}


@pytest.fixture
def ring(monkeypatch):
    """A tracer of its own in the program's place, filled by the test."""
    from paddle_tpu.obs import trace

    def fill(rows, dropped=0):
        tracer = trace.Tracer(capacity=len(rows))
        tracer._ring[:] = rows
        tracer._recorded = len(rows) + dropped
        monkeypatch.setattr(trace, "TRACER", tracer)
        return tracer

    return fill


def read(name, facts):
    from perfbench import harness, registry

    ctx = harness.ReadContext(harness.load_cell(CELL), dict(facts), {"serve_throughput": 1.0},
                              None, V5E_PEAKS, CPU_DEVICE)
    return registry.load_module("readers", name).read(ctx, {"name": name})


FACTS = {"decode_window_ns": WINDOW, "decode_only_step_s": [0.034, 0.032, 0.036]}


@pytest.mark.parametrize("name", READERS)
def test_reader_arithmetic_on_a_hand_made_ring(ring, name, capsys):
    ring(RING)
    assert read(name, FACTS) == pytest.approx(EXPECTED[name])
    said = capsys.readouterr().out
    assert said.count("info: serve spans:") == 1
    assert "9 serve.step ended in the window, 5 decode-only, 2 with a prefill (1 with an " \
           "admission), 1 with a preemption" in said
    assert "median decode-only serve.step 34.000 ms beside the benchmark's decode_step_ms " \
           "34.000 ms" in said
    assert "median admission step 90.000 ms" in said
    assert f"{100.0 * (26 + 30 * 4 + 70 + 40 + 19) / 1000:.2f}% of the window" in said
    assert "median queued_ms of 1 first admissions 120" in said


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("why", ["no_window", "dropped", "no_serve_step", "none_ended_inside",
                                 "no_flight_recorder"])
def test_what_must_read_as_nothing(ring, name, why, capsys, monkeypatch):
    """Never a number from another interval: a builder that gives no window
    (`servable_lm_2048`'s), a ring that dropped, a program without
    `serve.step` (this PR's parent, whose `serve.decode` spans are there all
    the same), a window no step ended in, a program without the recorder."""
    from paddle_tpu.obs import trace

    rows, facts, dropped = RING, FACTS, 0
    if why == "no_window":
        facts = {"decode_only_step_s": [0.03]}
    elif why == "dropped":
        dropped = 3
    elif why == "no_serve_step":
        rows = [r for r in RING if r[0] != "serve.step"]
    elif why == "none_ended_inside":
        facts = dict(FACTS, decode_window_ns=((T0 + 2000) * MS, (T0 + 3000) * MS))
    ring(rows, dropped)
    if why == "no_flight_recorder":
        monkeypatch.delattr(trace, "flight")
    assert read(name, facts) is None
    said = capsys.readouterr().out
    assert "serve spans:" not in said
    want = {"dropped": "dropped 3 of", "no_serve_step": "records no serve.step span",
            "none_ended_inside": "no serve.step span ended in the window",
            "no_flight_recorder": "no flight recorder"}.get(why)
    assert want is None or want in said


def test_a_window_with_no_decode_only_step_or_no_lane_gives_those_two_nothing(ring):
    rows = [r for r in RING if r[4] in ("f", "af", "g", "h")]   # prefills and a drain only
    ring(rows)
    assert read("engine_host_ms_per_step", FACTS) is None
    assert read("prefill_step_time_share", FACTS) == pytest.approx(15.0)
    assert read("replayed_lane_share", FACTS) is None


def test_the_readers_share_one_snapshot_a_run(ring, capsys):
    from perfbench import harness, registry

    ring(RING)
    ctx = harness.ReadContext(harness.load_cell(CELL), dict(FACTS), {}, None, V5E_PEAKS, CPU_DEVICE)
    for name in READERS:
        assert registry.load_module("readers", name).read(ctx, {}) == pytest.approx(EXPECTED[name])
    assert capsys.readouterr().out.count("serve spans:") == 1


# -- on the tiny looped cell ----------------------------------------------------


def test_the_tiny_looped_cell_reads_its_own_steps(tmp_path_factory):
    """What the program really records, through the builder that gives the
    window's edges: the window's `serve.step` spans are the engine steps the
    harness counted, and the three readers give numbers of the right kind."""
    import time

    from paddle_tpu.obs import trace
    from perfbench import harness, registry, serve_spans
    from perfbench.builders import looped_lm
    from test_perfbench_looped import base, load

    cell = load(base.__wrapped__(tmp_path_factory))
    system = looped_lm.build(cell, 3000000019)
    system.setup(say=lambda *_: None)
    trace.reset()
    out = system.window(1.0, None, time.perf_counter())
    system.release()
    assert trace.TRACER.dropped == 0
    ctx = harness.ReadContext(cell, out["facts"], out["end_to_end"], None, V5E_PEAKS, CPU_DEVICE)
    win = serve_spans.window(ctx)
    # the harness stamps a step after the span closed: an edge may fall between
    assert abs(len(win.steps) - out["facts"]["steps"]) <= 2
    assert abs(len(win.prefilling) - out["facts"]["prefill_steps"]) <= 2
    got = {n: registry.load_module("readers", n).read(ctx, {}) for n in READERS}
    assert 0.0 < got["engine_host_ms_per_step"] < 1e3 * max(out["facts"]["decode_only_step_s"])
    assert 0.0 < got["prefill_step_time_share"] < 100.0
    assert 0.0 <= got["replayed_lane_share"] < 100.0
    inside = [r[serve_spans.DUR] * 1e-9 for r in win.decode_only]
    outside = out["facts"]["decode_only_step_s"]
    assert sorted(inside)[len(inside) // 2] <= sorted(outside)[len(outside) // 2]
