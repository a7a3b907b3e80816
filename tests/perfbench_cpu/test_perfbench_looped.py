"""The looped decoder's cell through the harness at a tiny size on the CPU
(data/looped: ouro_tiny, the builder, reference, counts and readers being
perfbench's own): `correct`, its two controls, and what each new reader
gives where its source exists and where it does not."""

import copy
import os
import shutil
import time

import pytest

from perfbench_testlib import CPU_DEVICE, HERE, V5E_PEAKS
from perfbench import harness, registry, trace as trace_mod

CELL = "ouro_tiny.worked_answers_saturated"
REAL = "ouro_2_6b.worked_answers_saturated"
NEW_METRICS = {"mfu.serve_looped", "paged_attention_looped_roofline", "decode_live_slots"}


@pytest.fixture(scope="module")
def base(tmp_path_factory):
    """perfbench's data with the tiny configuration and cell added."""
    out = str(tmp_path_factory.mktemp("pblooped") / "pb")
    for kind in ("configs", "workloads", "metrics"):
        shutil.copytree(os.path.join(registry.HERE, kind), os.path.join(out, kind))
        extra = os.path.join(HERE, "data", "looped", kind)
        for f in os.listdir(extra) if os.path.isdir(extra) else ():
            assert not os.path.exists(os.path.join(out, kind, f)), "may only ADD"
            shutil.copy(os.path.join(extra, f), os.path.join(out, kind, f))
    return out


def benchmark():
    """BENCHMARK.json with the tiny cell wherever the real one is listed."""
    bench = copy.deepcopy(registry.load_benchmark())
    bench["configs"].append({"name": "ouro_tiny", "source": "tests", "file": "x", "reduced": [], "why": "t"})
    bench["workloads"].append({"name": CELL, "config": "ouro_tiny", "traffic": "worked_answers_saturated",
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


def test_the_real_cell_reports_what_the_issue_lists_and_not_servable_lms_counts():
    cell = harness.load_cell(REAL)
    assert set(cell.end_to_end) == {"serve_throughput", "setup_s"} and cell.chips == 1
    assert set(cell.per_layer) == NEW_METRICS | {
        "compile_s", "decode_step_ms", "prefill_step_share", "queue_wait_p95_ms",
        "device_idle_share.serve"}
    p, c = cell.workload["params"], cell.config
    assert (p["clients"], p["plan_requests"], p["sizes_seed"], p["lead_in_finished"], p["temperature"]) == (
        24, 384, 20261003, 12, 0.0)
    assert p["prompt_len"] == {"median": 96, "sigma": 0.6, "min": 16, "max": 512}
    assert p["output_len"] == {"median": 256, "sigma": 0.6, "min": 32, "max": 768}
    s = c["session"]
    assert (s["page_size"], s["max_slots"], s["prefill_buckets"], s["max_new_limit"]) == (
        16, 16, [64, 128, 256, 512], 768)
    assert s["num_pages"] >= 256 and "matmul_precision" not in c
    assert p["prompt_len"]["max"] <= s["prefill_buckets"][-1] and p["output_len"]["max"] <= s["max_new_limit"]
    assert cell.workload["check"]["control"] == "fp8"


def test_the_configuration_holds_every_number_of_the_catalog_row_and_counts_as_the_issue_says():
    import importlib

    c = registry.load_config("ouro_2_6b")
    published = {"hidden_size": 2048, "num_attention_heads": 16, "num_key_value_heads": 16, "head_dim": 128,
                 "intermediate_size": 5632, "num_hidden_layers": 48, "total_ut_steps": 4,
                 "early_exit_threshold": 1, "vocab_size": 49152, "rope_theta": 1000000,
                 "rms_norm_eps": 1e-6, "max_position_embeddings": 65536, "max_window_layers": 48}
    assert {k: c[k] for k in published} == published and c["tie_word_embeddings"] is False
    assert c["weights_dtype"] == c["pool_dtype"] == "bfloat16"
    entry = next(e for e in registry.load_benchmark()["configs"] if e["name"] == "ouro_2_6b")
    assert entry["reduced"] == [] and entry["source"] == c["source"]
    spec = c["flops"]
    per_token = getattr(importlib.import_module(spec["module"]), spec["function"])(**spec["args"])
    assert per_token == pytest.approx(19.93e9, rel=1e-3)
    from perfbench import looped_counts

    held = looped_counts.looped_lm_params_touched_per_token(**dict(spec["args"], total_ut_steps=1))
    assert 2 * (held + 49152 * 2048) == pytest.approx(5.34e9, rel=0.01)   # bfloat16, with the embedding
    token = 2 * 2 * c["total_ut_steps"] * c["num_hidden_layers"] * 2048
    assert token == 1.5 * 2 ** 20
    s = c["session"]
    assert 7.0e9 <= s["num_pages"] * s["page_size"] * token <= 8.6e9


@pytest.fixture(scope="module")
def traced(base, tmp_path_factory):
    lines = []
    with pytest.MonkeyPatch.context() as mp:
        # the CPU's trace holds no TPU plane: the trace-fed readers get none
        mp.setattr(harness.Profiler, "summary", lambda self, chips: None)
        return run(base, tmp_path_factory.mktemp("traced"), trace=True, say=lines.append), lines


def test_the_tiny_cell_is_correct_and_reads_its_span_and_its_counts(traced):
    r, lines = traced
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 6
    assert r["checks"]["never_answered"] == {"value": 0.0, "limit": 0.0}
    assert r["checks"]["window_compiles"]["value"] == 0.0
    # no device plane: the kernel's share has no source here and is left
    # out; the span- and clock-fed ones report
    assert set(r["metrics"]) >= {"compile_s", "decode_step_ms", "prefill_step_share",
                                 "queue_wait_p95_ms", "mfu.serve_looped", "decode_live_slots"}
    assert "paged_attention_looped_roofline" not in r["metrics"]
    assert "mfu.serve" not in r["metrics"] and "paged_attention_roofline" not in r["metrics"]
    assert 1.0 <= r["metrics"]["decode_live_slots"]["value"] <= 4.0
    assert 0 < r["metrics"]["mfu.serve_looped"]["value"] < 100


def test_an_untraced_run_reports_the_end_to_end_metrics(base, tmp_path):
    r = run(base, tmp_path, seed=2147483659)
    assert r["correct"] is True, r["checks"]
    assert set(r["metrics"]) == {"serve_throughput", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())


def test_the_fp8_control_and_an_altered_token_are_not_correct(base):
    from perfbench.builders import looped_lm

    system = looped_lm.build(load(base), 2147483659)
    rows = {row["who"]: row for row in system.calibrate(window_s=0.5, control=True, faults=True)}
    assert harness.decide(system.judge(rows["program"]["numbers"]))
    assert not harness.decide(system.judge(rows["control:fp8"]["numbers"]))
    assert not harness.decide(system.judge(rows["fault:token_altered"]["numbers"]))


def ctx_for(base, facts, summary=None):
    return harness.ReadContext(load(base), facts, {"serve_throughput": 1.0}, summary, V5E_PEAKS, CPU_DEVICE)


def test_each_new_reader_gives_nothing_where_its_source_is_missing(base):
    cell = load(base)
    readers = {n: registry.load_module("readers", cell.per_layer[n]["reader"]) for n in NEW_METRICS}
    empty = ctx_for(base, {})
    assert all(r.read(empty, cell.per_layer[n]) is None for n, r in readers.items())
    # a window in which no serve.decode span ended (a program without the
    # span reads the same): nothing, not 0
    nothing = ctx_for(base, {"decode_window_ns": (1, 2)})
    assert readers["decode_live_slots"].read(nothing, cell.per_layer["decode_live_slots"]) is None


def test_the_kernels_share_counts_a_call_a_cache_layer_at_the_pools_bytes(base):
    from perfbench import rooflines
    from perfbench.readers import paged_attention_looped_roofline as reader

    contexts = [[40, 7, 100], [41, 8, 101]]
    ops = [(i * 1000, i * 1000 + 400, "paged_attention_decode.6") for i in range(24)] + [(0, 90000, "fusion.1")]
    summary = trace_mod.TraceSummary({"/device:TPU:0": ops}, 1e-4, 1e-4, "/device:TPU:0", [], [])
    got = reader.read(ctx_for(base, {"traced_contexts": contexts}, summary), {})
    least = 0.0
    for c in contexts:
        work = rooflines.paged_attention_decode_work([n + 1 for n in c], 2, 32, 8, dtype_bytes=2)
        least += 12 * rooflines.least_time(work["flops"], work["bytes"], V5E_PEAKS)[0]
    assert got == pytest.approx(100.0 * least / (24 * 400e-9))
    four = rooflines.paged_attention_decode_work([41, 8, 101], 2, 32, 8, dtype_bytes=4)["bytes"]
    two = rooflines.paged_attention_decode_work([41, 8, 101], 2, 32, 8, dtype_bytes=2)["bytes"]
    assert four == 2 * two
