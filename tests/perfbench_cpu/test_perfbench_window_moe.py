"""The window-and-experts decoder's cell through the harness at a tiny size on
the CPU (data/window_moe: trinity_tiny, the builder, reference, counts and
readers being perfbench's own): the real configuration against the catalog
row and its arithmetic, the real cell letter for letter, `correct`
and what has to fail it (the fp8 control, a program that ignores the window,
an altered token), and what each new reader gives where its source exists
and where it does not."""

import copy
import json
import os
import shutil
import time

import numpy as np
import pytest

from perfbench_testlib import CPU_DEVICE, HERE, V5E_PEAKS
from perfbench import harness, registry, window_moe_counts

CELL = "trinity_tiny.rag_mixed_saturated"
REAL = "trinity_mini.rag_mixed_saturated"
NEW_METRICS = {"window_attention_decode_roofline", "kv_bytes_per_context_token",
               "mfu.serve_window_moe"}
# The published configuration's row, as the model catalog gives it.
CATALOG_ROW = os.path.join(HERE, "data", "window_moe", "trinity_mini_catalog_row.json")


@pytest.fixture(scope="module")
def base(tmp_path_factory):
    """perfbench's data with the tiny configuration and cell added."""
    out = str(tmp_path_factory.mktemp("pbwindow") / "pb")
    for kind in ("configs", "workloads", "metrics"):
        shutil.copytree(os.path.join(registry.HERE, kind), os.path.join(out, kind))
        extra = os.path.join(HERE, "data", "window_moe", kind)
        for f in os.listdir(extra) if os.path.isdir(extra) else ():
            assert not os.path.exists(os.path.join(out, kind, f)), "may only ADD"
            shutil.copy(os.path.join(extra, f), os.path.join(out, kind, f))
    return out


def benchmark():
    """BENCHMARK.json with the tiny cell wherever the real one is listed."""
    bench = copy.deepcopy(registry.load_benchmark())
    bench["configs"].append({"name": "trinity_tiny", "source": "tests", "file": "x", "reduced": [], "why": "t"})
    bench["workloads"].append({"name": CELL, "config": "trinity_tiny", "traffic": "rag_mixed_saturated",
                               "chips": 1, "why": "t"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if REAL in m.get("workloads", ()):
            m["workloads"] = m["workloads"] + [CELL]
    return bench


def load(base):
    return harness.load_cell(CELL, base=base, benchmark=benchmark())


def run(base, tmp, trace=False, seconds=1.0, say=lambda *_: None, seed=3000000019):
    from paddle_tpu.core.init_ctx import enable_compilation_cache

    enable_compilation_cache()
    return harness.run_cell(load(base), seed, seconds, trace, time.perf_counter(), CPU_DEVICE,
                            V5E_PEAKS, scratch=os.path.join(str(tmp), "scratch"), say=say)


def test_the_real_cell_is_letter_for_letter_as_specified():
    cell = harness.load_cell(REAL)
    assert set(cell.end_to_end) == {"serve_throughput", "setup_s"} and cell.chips == 1
    assert set(cell.per_layer) == NEW_METRICS | {
        "compile_s", "decode_step_ms", "prefill_step_share", "queue_wait_p95_ms",
        "device_idle_share.serve", "decode_live_slots", "moe_expert_load_max_over_mean"}
    p, c = cell.workload["params"], cell.config
    assert cell.workload["generator"] == "closed_loop"
    assert (p["clients"], p["plan_requests"], p["sizes_seed"], p["lead_in_finished"], p["temperature"]) == (
        96, 2048, 20261018, 96, 0.0)
    assert p["prompt_len"] == {"median": 2048, "sigma": 1.2, "min": 32, "max": 16384}
    assert p["output_len"] == {"median": 384, "sigma": 0.6, "min": 32, "max": 1536}
    check = cell.workload["check"]
    assert check["control"] == "fp8" and check["sample_requests"] == 8
    s = c["session"]
    assert (s["max_slots"], s["page_size"], s["prefill_chunk"], s["max_new_limit"]) == (64, 16, 2048, 1536)
    assert p["clients"] > s["max_slots"] and "matmul_precision" not in c
    # slots bind, pages never do: the full layer's pool holds every slot's
    # longest request
    assert (s["num_pages"] - 1) * s["page_size"] >= s["max_slots"] * (16384 + s["max_new_limit"])
    # what the sizes' seed draws
    from perfbench.traffic import closed_loop

    plan = closed_loop.make_schedule(p, 50.0, 1, 200192, 1)
    prompts = sorted(len(r["prompt"]) for r in plan)
    assert [round(float(q)) for q in np.percentile(prompts, [10, 50, 90, 99])] == [444, 2076, 9245, 16384]
    assert sum(prompts) / len(prompts) == pytest.approx(3668.5, abs=0.1)
    assert sum(n > 2048 for n in prompts) / len(prompts) == pytest.approx(0.504, abs=1e-3)
    assert sum(n == 16384 for n in prompts) == 82
    assert sum(r["max_new"] for r in plan) / len(plan) == pytest.approx(454.5, abs=0.1)


def test_the_configuration_holds_every_number_of_the_catalog_row():
    with open(CATALOG_ROW) as f:
        row = json.load(f)
    assert row["name"] == "Trinity-Mini"
    c = registry.load_config("trinity_mini")
    entry = next(e for e in registry.load_benchmark()["configs"] if e["name"] == "trinity_mini")
    assert entry["source"] == c["source"] == row["source_url"]
    assert entry["reduced"] == ["num_hidden_layers", "layer_types"]
    for key, value in row["config"].items():
        if key in entry["reduced"]:
            assert c["published"][key] == value, key
        else:
            assert c[key] == value, key
    assert c["num_hidden_layers"] == 6 and c["layer_types"] == row["config"]["layer_types"][:6]
    assert c["layer_types"] == ["sliding_attention"] * 3 + ["full_attention"] + ["sliding_attention"] * 2
    assert (c["weights_dtype"], c["pool_dtype"]) == ("bfloat16", "bfloat16")
    assert "first stage" in c["deployment"] and "7 v5e chips" in c["deployment"]


def test_the_counts_are_the_configurations_arithmetic():
    import importlib

    c = registry.load_config("trinity_mini")
    attention = 2048 * 4096 * 3 + 2048 * 512 * 2          # q, gate, o; k, v
    assert attention == pytest.approx(27.26e6, rel=1e-3)
    dense = attention + 3 * 2048 * 6144
    expert_layer = attention + 2048 * 128 + 129 * 3 * 2048 * 1024
    assert dense == pytest.approx(65.0e6, rel=1e-3) and expert_layer == pytest.approx(839.1e6, rel=1e-3)
    held = window_moe_counts.parameters(c)
    assert held == pytest.approx(2 * 200192 * 2048 + 2 * dense + 4 * expert_layer, rel=1e-4)
    assert held * 2 == pytest.approx(8.61e9, rel=2e-3)
    spec = c["flops"]
    per_token = getattr(importlib.import_module(spec["module"]), spec["function"])(**spec["args"])
    assert per_token == 2 * (6 * attention + 2 * 3 * 2048 * 6144
                             + 4 * (2048 * 128 + 9 * 3 * 2048 * 1024) + 2048 * 200192)
    # one decode step's attention: a full layer reads the whole context, a
    # window layer its last 2048, K and V of 4 heads of 128 at 2 bytes
    work = window_moe_counts.decode_attention_work(c, [100, 5000])["calls"]
    assert [w["bytes"] for w in work] == [2 * 2 * 512 * 2148] * 3 + [2 * 2 * 512 * 5100] + [
        2 * 2 * 512 * 2148] * 2
    # pages of 16 positions, K and V of 4 heads of 128: 32 KB a page a layer
    assert window_moe_counts.kv_bytes_held(c, 10, 4) == 32768 * (10 + 5 * 4)


@pytest.fixture(scope="module")
def traced(base, tmp_path_factory):
    lines = []
    with pytest.MonkeyPatch.context() as mp:
        # the CPU's trace holds no TPU plane: the trace-fed readers get none
        mp.setattr(harness.Profiler, "summary", lambda self, chips: None)
        return run(base, tmp_path_factory.mktemp("traced"), trace=True, say=lines.append), lines


def test_the_tiny_cell_is_correct_and_reads_its_spans_and_its_counters(traced):
    r, lines = traced
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 6
    assert r["checks"]["never_answered"] == {"value": 0.0, "limit": 0.0}
    assert r["checks"]["window_compiles"]["value"] == 0.0
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert set(m) >= {"kv_bytes_per_context_token", "mfu.serve_window_moe", "decode_step_ms",
                      "prefill_step_share", "queue_wait_p95_ms", "decode_live_slots",
                      "moe_expert_load_max_over_mean"}
    assert "window_attention_decode_roofline" not in m          # no TPU plane on the CPU
    assert 1.0 <= m["decode_live_slots"] <= 4.0
    assert 0 < m["mfu.serve_window_moe"] < 100
    # pages of 4 positions of K and V over 2 heads of 16 in float32... no:
    # bfloat16, 256 bytes a page a layer; at most a whole page over a token
    # more than 6 layers' 128 bytes a token
    assert 0 < m["kv_bytes_per_context_token"] < 6 * 128 * 2
    assert 1.0 <= m["moe_expert_load_max_over_mean"] < 4.0
    assert any("repeat the token before them" in line for line in lines)


def test_an_untraced_run_reports_the_end_to_end_metrics(base, tmp_path):
    r = run(base, tmp_path, seed=2147483659)
    assert r["correct"] is True, r["checks"]
    assert set(r["metrics"]) == {"serve_throughput", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())


def test_the_control_a_window_ignored_and_an_altered_token_are_not_correct(base):
    from perfbench.builders import window_moe_lm

    system = window_moe_lm.build(load(base), 2147483659)
    rows = {row["who"]: row for row in system.calibrate(window_s=0.5, control=True, faults=True)}
    assert harness.decide(system.judge(rows["program"]["numbers"]))
    assert rows["program"]["numbers"]["past_window"] > 0, "nothing compared where the window cuts"
    assert not harness.decide(system.judge(rows["control:fp8"]["numbers"]))
    assert not harness.decide(system.judge(rows["fault:window_ignored"]["numbers"]))
    assert not harness.decide(system.judge(rows["fault:token_altered"]["numbers"]))
    assert rows["program"]["numbers"]["repeat_share"] < 0.5, "a model that repeats compares nothing"


def test_a_program_that_ignores_the_window_is_not_correct(base, tmp_path, monkeypatch):
    """The served program with its window layers attending to the whole
    context (rotary positions kept, one pool of full pages): the cell's own
    comparison refuses it."""
    from perfbench.builders import window_moe_lm

    built = window_moe_lm.WindowMoEServeSystem._model

    def unwindowed(self):
        model = built(self)
        model._windows = (0,) * len(model._windows)
        return model

    monkeypatch.setattr(window_moe_lm.WindowMoEServeSystem, "_model", unwindowed)
    r = run(base, tmp_path, seed=2147483659)
    assert r["correct"] is False
    checks = r["checks"]
    assert checks["token_logit_gap_mean"]["value"] > 3 * checks["token_logit_gap_mean"]["limit"]
    assert checks["never_answered"]["value"] == 0 and checks["window_compiles"]["value"] == 0


def ctx_for(base, facts, summary=None):
    return harness.ReadContext(load(base), facts, {"serve_throughput": 1.0}, summary, V5E_PEAKS, CPU_DEVICE)


def test_each_new_reader_gives_nothing_where_its_source_is_missing(base):
    cell = load(base)
    readers = {n: registry.load_module("readers", cell.per_layer[n]["reader"]) for n in NEW_METRICS}
    empty = ctx_for(base, {})
    assert all(r.read(empty, cell.per_layer[n]) is None for n, r in readers.items())
    # a window but no serve.decode span with the pages in it (a program
    # without window pages reads the same), no trace: nothing, not 0
    nothing = ctx_for(base, {"decode_window_ns": (1, 2), "traced_contexts": [[3, 4]]})
    for n in NEW_METRICS - {"mfu.serve_window_moe"}:
        assert readers[n].read(nothing, cell.per_layer[n]) is None, n
    other = harness.ReadContext(harness.load_cell("ouro_2_6b.worked_answers_saturated"),
                                {"traced_contexts": [[3]]}, {}, None, V5E_PEAKS, CPU_DEVICE)
    assert readers["window_attention_decode_roofline"].read(other, {}) is None


def test_the_roofline_reader_counts_the_window_not_the_context(base):
    from perfbench import trace as trace_mod
    from perfbench.readers import window_attention_decode_roofline

    class Summary:
        def __init__(self, seconds):
            self.seconds = seconds

        def ops(self):
            return [(0, int(self.seconds * 1e9), "paged_attention_decode.3")]

    c = load(base).config
    contexts = [[40, 7], [41, 8]]
    least = sum(window_moe_counts.decode_attention_least_time(c, [n + 1 for n in s], V5E_PEAKS)
                for s in contexts)
    ctx = ctx_for(base, {"traced_contexts": contexts}, Summary(4 * least))
    assert window_attention_decode_roofline.read(ctx, {}) == pytest.approx(25.0, rel=0.01)
    # a window layer's least time stops growing at its window: 16 here
    far = window_moe_counts.decode_attention_work(c, [1000])["calls"]
    assert far[0]["bytes"] == window_moe_counts.decode_attention_work(c, [16])["calls"][0]["bytes"]
    assert far[3]["bytes"] > far[0]["bytes"]
    assert trace_mod.time_by_substring(Summary(1.0).ops(), ("paged_attention_decode",))[1] == 1
