"""The hybrid decoder's cell through the harness at a tiny size on the CPU
(data/hybrid_moe: granite_tiny, the builder, reference, counts and readers
being perfbench's own): the real configuration against the catalog row and
the issue's arithmetic, `correct` and its two controls, the counts, and what
each new reader gives where its source exists and where it does not."""

import copy
import json
import os
import shutil
import time

import pytest

from perfbench_testlib import CPU_DEVICE, HERE, V5E_PEAKS
from perfbench import harness, hybrid_moe_counts, registry

CELL = "granite_tiny.chat_saturated"
REAL = "granite_4_0_h_small.chat_saturated"
NEW_METRICS = {"mfu.serve_hybrid_moe", "decode_bytes_roofline", "moe_local_assignments_per_token",
               "moe_expert_load_max_over_mean"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def base(tmp_path_factory):
    """perfbench's data with the tiny configuration and cell added."""
    out = str(tmp_path_factory.mktemp("pbhybrid") / "pb")
    for kind in ("configs", "workloads", "metrics"):
        shutil.copytree(os.path.join(registry.HERE, kind), os.path.join(out, kind))
        extra = os.path.join(HERE, "data", "hybrid_moe", kind)
        for f in os.listdir(extra) if os.path.isdir(extra) else ():
            assert not os.path.exists(os.path.join(out, kind, f)), "may only ADD"
            shutil.copy(os.path.join(extra, f), os.path.join(out, kind, f))
    return out


def benchmark():
    """BENCHMARK.json with the tiny cell wherever the real one is listed."""
    bench = copy.deepcopy(registry.load_benchmark())
    bench["configs"].append({"name": "granite_tiny", "source": "tests", "file": "x", "reduced": [], "why": "t"})
    bench["workloads"].append({"name": CELL, "config": "granite_tiny", "traffic": "chat_saturated",
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


def test_the_real_cell_is_letter_for_letter_the_issues():
    cell = harness.load_cell(REAL)
    assert set(cell.end_to_end) == {"serve_throughput", "setup_s"} and cell.chips == 1
    assert set(cell.per_layer) == NEW_METRICS | {
        "compile_s", "decode_step_ms", "prefill_step_share", "queue_wait_p95_ms",
        "device_idle_share.serve", "decode_live_slots"}
    p, c = cell.workload["params"], cell.config
    assert cell.workload["generator"] == "closed_loop"
    assert (p["clients"], p["plan_requests"], p["sizes_seed"], p["lead_in_finished"], p["temperature"]) == (
        96, 4096, 20261004, 96, 0.0)
    assert p["prompt_len"] == {"median": 117, "sigma": 0.8, "min": 16, "max": 1024}
    assert p["output_len"] == {"median": 245, "sigma": 0.8, "min": 16, "max": 1024}
    same = registry.load_workload("servable_lm_2048.chat_saturated")["params"]
    assert (p["prompt_len"], p["output_len"]) == (same["prompt_len"], same["output_len"])
    s = c["session"]
    assert (s["page_size"], s["num_pages"], s["prefill_buckets"], s["max_new_limit"]) == (
        16, 8193, [64, 128, 256, 512, 1024], 1024)
    assert s["max_slots"] <= 64 and p["clients"] > s["max_slots"] and "matmul_precision" not in c
    # slots bind, pages never do
    assert (s["num_pages"] - 1) * s["page_size"] >= s["max_slots"] * (1024 + s["max_new_limit"])
    assert cell.workload["check"]["control"] == "fp8" and cell.workload["check"]["sample_requests"] == 8


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="the catalog is not on this machine")
def test_the_configuration_holds_every_number_of_the_catalog_row():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "granite-4.0-h-small")
    c = registry.load_config("granite_4_0_h_small")
    entry = next(e for e in registry.load_benchmark()["configs"] if e["name"] == "granite_4_0_h_small")
    assert entry["source"] == c["source"] == row["source_url"]
    assert entry["reduced"] == ["num_hidden_layers", "num_local_experts", "vocab_size"]
    for key, value in row["config"].items():
        if key in entry["reduced"]:
            assert c["published"][key] == value and c[key] < value, key
        elif key == "layer_types":
            assert c[key] == value[:10] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
        else:
            assert c[key] == value, key
    assert (c["num_hidden_layers"], c["num_local_experts"], c["vocab_size"]) == (10, 36, 50176)
    assert c["num_experts_routed"] == 72 and c["experts_held"] == list(range(36))
    assert c["num_experts_per_tok"] == 10 and c["intermediate_size"] == 768
    assert (c["weights_dtype"], c["pool_dtype"], c["state_dtype"]) == ("bfloat16", "bfloat16", "float32")
    assert "2 chips share each layer" in c["deployment"] and "rank 0" in c["deployment"]


def test_the_counts_are_the_issues_arithmetic():
    import importlib

    c = registry.load_config("granite_4_0_h_small")
    assert hybrid_moe_counts.parameters_held(c) == pytest.approx(4.757e9, rel=2e-4)
    # by hand: a Mamba mixer 68.68 M in, 33.55 M out, 0.05 M of convolution, scalars and norm
    mixer = 4096 * 16768 + 8192 * 4096 + 8448 * 5 + 3 * 128 + 8192
    layer = 2 * 4096 + 4096 * 72 + 36 * 3 * 4096 * 768 + 3 * 4096 * 1536
    attention = 4096 * 128 * (2 * 32 + 2 * 8)
    assert hybrid_moe_counts.parameters_held(c) == 9 * mixer + attention + 10 * layer + 50176 * 4096 + 4096
    assert hybrid_moe_counts.state_bytes_per_slot(c) == 9 * (128 * 64 * 128 * 4 + 3 * 8448 * 2)
    assert hybrid_moe_counts.kv_bytes_per_token(c) == 4096            # 4 KB: ONE layer in ten leaves K/V
    step = hybrid_moe_counts.decode_step_bytes(c, 64)
    assert step == pytest.approx(14.3e9, rel=0.01)                    # 17.5 ms at 819 GB/s
    experts = 10 * 36 * 3 * 4096 * 768 * 2
    state = 2 * 64 * 9 * 128 * 64 * 128 * 4
    assert experts / step == pytest.approx(0.47, abs=0.01) and state / step == pytest.approx(0.34, abs=0.01)
    assert hybrid_moe_counts.decode_step_bytes(c, 64, 64 * 400) - step == 64 * 400 * 4096
    spec = c["flops"]
    per_token = getattr(importlib.import_module(spec["module"]), spec["function"])(**spec["args"])
    matrices = 9 * (4096 * 16768 + 8192 * 4096) + attention + 10 * (
        4096 * 72 + 3 * 4096 * 1536 + 5 * 3 * 4096 * 768) + 4096 * 50176
    assert 2 * matrices == pytest.approx(3.66e9, rel=2e-3)
    assert per_token == 2 * matrices + 2 * 9 * 4 * 8448 + 5 * 9 * 128 * 64 * 128
    a = spec["args"]
    assert (a["hidden_size"], a["experts_held"], a["num_experts_routed"], a["vocab_size"]) == (
        c["hidden_size"], len(c["experts_held"]), c["num_experts_routed"], c["vocab_size"])


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
    assert set(r["metrics"]) >= NEW_METRICS | {"compile_s", "decode_step_ms", "prefill_step_share",
                                               "queue_wait_p95_ms", "decode_live_slots"}
    assert "mfu.serve" not in r["metrics"] and "mfu.serve_looped" not in r["metrics"]
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert 1.0 <= m["decode_live_slots"] <= 4.0
    assert 0 < m["mfu.serve_hybrid_moe"] < 100 and 0 < m["decode_bytes_roofline"] < 100
    # top-3 of 8 with 4 held: 1.5 in expectation
    assert 1.0 < m["moe_local_assignments_per_token"] < 2.0
    assert 1.0 <= m["moe_expert_load_max_over_mean"] < 2.5
    assert any("repeat the token before them" in line for line in lines)


def test_an_untraced_run_reports_the_end_to_end_metrics(base, tmp_path):
    r = run(base, tmp_path, seed=2147483659)
    assert r["correct"] is True, r["checks"]
    assert set(r["metrics"]) == {"serve_throughput", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())


def test_the_fp8_control_and_an_altered_token_are_not_correct(base):
    from perfbench.builders import hybrid_moe_lm

    system = hybrid_moe_lm.build(load(base), 2147483659)
    rows = {row["who"]: row for row in system.calibrate(window_s=0.5, control=True, faults=True)}
    assert harness.decide(system.judge(rows["program"]["numbers"]))
    assert not harness.decide(system.judge(rows["control:fp8"]["numbers"]))
    assert not harness.decide(system.judge(rows["fault:token_altered"]["numbers"]))
    assert rows["program"]["numbers"]["repeat_share"] < 0.5, "a model that repeats compares nothing"


def ctx_for(base, facts, summary=None):
    return harness.ReadContext(load(base), facts, {"serve_throughput": 1.0}, summary, V5E_PEAKS, CPU_DEVICE)


def test_each_new_reader_gives_nothing_where_its_source_is_missing(base):
    cell = load(base)
    readers = {n: registry.load_module("readers", cell.per_layer[n]["reader"]) for n in NEW_METRICS}
    empty = ctx_for(base, {})
    assert all(r.read(empty, cell.per_layer[n]) is None for n, r in readers.items())
    # step times but no serve.decode span in the window (a program without
    # the span reads the same), counters that counted nothing: nothing, not 0
    nothing = ctx_for(base, {"decode_only_step_s": [0.01], "decode_window_ns": (1, 2),
                             "moe_counted": {"moe_assignments": [[0, 0]], "moe_expert_tokens": [[0, 0]]}})
    for n in NEW_METRICS - {"mfu.serve_hybrid_moe"}:
        assert readers[n].read(nothing, cell.per_layer[n]) is None, n
    # and a configuration that is no such model
    other = harness.ReadContext(harness.load_cell("ouro_2_6b.worked_answers_saturated"),
                                {"decode_only_step_s": [0.01]}, {}, None, V5E_PEAKS, CPU_DEVICE)
    assert readers["decode_bytes_roofline"].read(other, {}) is None


def test_the_counter_readers_arithmetic(base):
    from perfbench.readers import moe_expert_load_max_over_mean, moe_local_assignments_per_token

    facts = {"moe_counted": {"moe_assignments": [[30, 30], [45, 15]],
                             "moe_expert_tokens": [[10, 10, 5, 5], [15, 15, 15, 0]]}}
    ctx = ctx_for(base, facts)
    assert moe_local_assignments_per_token.read(ctx, {}) == pytest.approx(3 * 75 / 120)
    assert moe_expert_load_max_over_mean.read(ctx, {}) == pytest.approx((10 / 7.5 + 15 / 11.25) / 2)
