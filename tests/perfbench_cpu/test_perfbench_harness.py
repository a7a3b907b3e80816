"""The harness's load, warm-up, window, verify and report functions, called
directly at a tiny size on the CPU, with the files of a would-be later PR
added beside the benchmark's own (no existing file edited). The command
itself has no CPU switch: run without a TPU it exits non-zero."""

import json
import os
import subprocess
import sys

import pytest

from perfbench_testlib import ROOT, extended_base, run_cell


@pytest.fixture(scope="module")
def base(tmp_path_factory):
    return extended_base(tmp_path_factory.mktemp("pbdata"))


@pytest.fixture(scope="module")
def train_result(base, tmp_path_factory):
    return run_cell(base, "mlp_tiny.train", tmp=tmp_path_factory.mktemp("t"))


def test_added_cell_runs_and_is_correct(train_result):
    r = train_result
    assert r["correct"] is True, r["checks"]
    assert set(r) >= {"correct", "attempted", "failed", "metrics", "device", "checks"}
    assert list(r)[-1] == "checks", "the numbers compared come last in the line"
    assert set(r["metrics"]) == {"throughput", "setup_s"}
    assert r["metrics"]["throughput"]["unit"] == "items/s/chip"
    assert r["attempted"] > 0 and r["attempted"] % 2 == 0 and r["failed"] == 0
    assert r["checks"]["window_compiles"] == {"value": 0.0, "limit": 0.0}
    for name in ("loss_gap", "grad_gap", "update_gap", "fused_loss_gap", "fused_update_gap"):
        assert r["checks"][name]["value"] <= r["checks"][name]["limit"]
    json.dumps(r)


def test_the_engagement_counter_is_on_an_info_line_after_set_up(base, tmp_path):
    from perfbench import harness

    lines = []
    run_cell(base, "mlp_tiny.train", tmp=tmp_path, say=lines.append)
    told = [ln for ln in lines if ln.startswith("info: fused projection xent")]
    assert len(told) == 1 and lines.index(told[0]) < next(
        i for i, ln in enumerate(lines) if ln.startswith("info: set-up"))
    counted = harness.engagement()
    assert counted and set(counted) <= {"path=fused", "path=unfused"}
    assert str(counted) in told[0]


def test_throughput_counts_all_steps_over_the_whole_window(base, tmp_path):
    from perfbench import harness
    from perfbench_testlib import extended_benchmark

    cell = harness.load_cell("mlp_tiny.train", base=base, benchmark=extended_benchmark())
    # its own metric, and every metric of the benchmark that lists no cells
    # and moves an end-to-end metric this cell reports
    assert cell.chips == 1 and set(cell.per_layer) == {
        "steps_per_s", "compile_s", "device_idle_share.train", "mfu.train"}
    assert set(cell.end_to_end) == {"throughput", "setup_s"}


def test_the_set_of_list_less_metrics_is_pinned():
    """A metric without `workloads` is reported by every cell that reports
    what it moves, later cells too: so the set grows only by a `benchmark`
    PR's decision."""
    from perfbench import registry

    bench = registry.load_benchmark()
    assert {m["name"] for m in bench["per_layer"] if "workloads" not in m} == {
        "compile_s", "device_idle_share.train", "mfu.train"}
    assert {m["name"] for m in bench["end_to_end"] if "workloads" not in m} == {"setup_s"}


@pytest.mark.parametrize("cell,e2e", [
    ("resnet50.train_cli_feed", {"throughput", "setup_s"}),
    ("servable_lm_2048.chat_steady", {"serve_throughput", "setup_s"}),
])
def test_the_cells_that_wait_load_by_name_once_a_benchmark_lists_them(base, cell, e2e):
    """PR 28 measured both and admitted neither (PERF.md section 7): their
    files load by name, and report what they would once BENCHMARK.json
    lists them (the served one still waits for a latency metric)."""
    from perfbench import harness, registry
    from perfbench_testlib import extended_benchmark

    assert cell not in {w["name"] for w in registry.load_benchmark()["workloads"]}
    loaded = harness.load_cell(cell, base=base, benchmark=extended_benchmark())
    assert set(loaded.end_to_end) == e2e and loaded.chips == 1
    if cell.startswith("resnet50"):
        # a data file beside resnet50.train's, differing in stack_k alone
        assert harness.load_cell(cell).workload == loaded.workload
        assert loaded.workload["params"]["stack_k"] == 8 == loaded.config["steps_per_dispatch"]
        plain = registry.load_workload("resnet50.train")
        assert loaded.workload["check"] == plain["check"]
        assert dict(loaded.workload["params"], stack_k=1) == plain["params"]
        assert set(loaded.per_layer) == {"compile_s", "device_idle_share.train", "mfu.train"}
    else:
        p = loaded.workload["params"]
        assert (p["prompt_len"], p["output_len"], p["sizes_seed"], p["temperature"]) == (
            {"median": 117, "sigma": 0.8, "min": 16, "max": 1024},
            {"median": 245, "sigma": 0.8, "min": 16, "max": 1024}, 20260930, 0.0)
        s = loaded.config["session"]
        assert p["prompt_len"]["max"] + p["output_len"]["max"] <= loaded.config["max_position_embeddings"]
        assert p["output_len"]["max"] <= s["max_new_limit"] and p["prompt_len"]["max"] <= s["prefill_buckets"][-1]
        assert set(loaded.per_layer) == {
            "compile_s", "mfu.serve", "paged_attention_roofline", "decode_step_ms",
            "prefill_step_share", "device_idle_share.serve", "queue_wait_p95_ms"}


def test_added_metric_is_read_by_its_own_reader(base):
    from perfbench import harness
    from perfbench_testlib import CPU_DEVICE, V5E_PEAKS, extended_benchmark

    cell = harness.load_cell("mlp_tiny.train", base=base, benchmark=extended_benchmark())
    ctx = harness.ReadContext(cell, {"steps": 40, "window_s": 2.0}, {"throughput": 320.0},
                              None, V5E_PEAKS, CPU_DEVICE)
    got = harness.read_per_layer(ctx, say=lambda *_: None)
    assert got["steps_per_s"] == {"value": 20.0, "unit": "steps/s"}
    assert set(got) == {"steps_per_s", "mfu.train"}   # no trace, no warm-up in ctx


def test_a_reader_with_nothing_to_read_is_left_out(base):
    from perfbench import harness
    from perfbench_testlib import CPU_DEVICE, V5E_PEAKS

    cell = harness.load_cell("resnet50.train")
    ctx = harness.ReadContext(cell, {"warm_s": 3.5}, {"throughput": 2690.0}, None,
                              V5E_PEAKS, CPU_DEVICE)
    got = harness.read_per_layer(ctx, say=lambda *_: None)
    assert "device_idle_share.train" not in got      # no trace: nothing, never 0
    assert got["compile_s"]["value"] == 3.5
    assert got["mfu.train"]["value"] == pytest.approx(100 * 3 * 8.18e9 * 2690 / 197e12)


def test_decide_needs_every_number_under_its_limit():
    from perfbench import harness

    assert harness.decide({"a": (0.1, 0.2), "b": (0.0, 0.0)})
    assert not harness.decide({"a": (0.3, 0.2), "b": (0.0, 0.0)})
    assert not harness.decide({"a": (float("nan"), 0.2)})
    assert not harness.decide({})


def test_serving_cell_runs_and_is_correct(base, tmp_path):
    lines = []
    r = run_cell(base, "servable_lm_tiny.chat_steady", seconds=1.5, tmp=tmp_path, say=lines.append)
    assert r["correct"] is True, r["checks"]
    assert set(r["metrics"]) == {"serve_throughput", "setup_s"}
    assert r["metrics"]["serve_throughput"]["unit"] == "tokens/s/chip"
    assert all(m["value"] > 0 for m in r["metrics"].values()), r["metrics"]
    assert r["attempted"] == 150 and r["failed"] == 0
    assert r["checks"]["never_answered"]["value"] == 0.0
    # the tails left the end-to-end metrics for the info line, numbers still:
    # enough requests and gaps for both to be tails (arith.tail_mean)
    told = next(ln for ln in lines if "gap tail mean" in ln)
    assert "tail mean nan" not in told


def test_the_closed_loop_cell_runs_end_to_end_and_is_correct(base, tmp_path):
    lines = []
    r = run_cell(base, "servable_lm_tiny.chat_saturated", seconds=1.0, tmp=tmp_path, say=lines.append)
    assert r["correct"] is True, r["checks"]
    assert set(r["metrics"]) == {"serve_throughput", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values()), r["metrics"]
    # six clients: six at the start, one more for each that finished by the close
    assert r["attempted"] > 6 and r["failed"] == 0
    assert r["checks"]["never_answered"] == {"value": 0.0, "limit": 0.0}
    told = next(ln for ln in lines if ln.startswith("info: serve_throughput"))
    assert "mean gap between tokens in it (tpot)" in told and "nan" not in told
    finished = int(told.split(" requests finished in it")[0].split()[-1])
    # the lead-in's six and the window's finished were each replaced while the
    # loop was open; at the close those with no first token were cancelled
    # (six clients on four slots: at least two) and are not waited for
    assert 6 + finished - 4 <= r["attempted"] <= 6 + 6 + 4 + finished
    gave_up = next(ln for ln in lines if "cancelled at the close" in ln)
    assert int(gave_up.split(" requests with no first token")[0].split()[-1]) >= 2
    assert r["metrics"]["setup_s"]["value"] > float(gave_up.split("lead-in ")[1].split(" s")[0]) > 0


def test_the_benchmarks_cells_report_what_benchmark_json_gives_them():
    from perfbench import harness

    served = harness.load_cell("servable_lm_2048.chat_saturated")
    assert set(served.end_to_end) == {"serve_throughput", "setup_s"} and served.chips == 1
    assert set(served.per_layer) == {
        "compile_s", "decode_step_ms", "prefill_step_share", "queue_wait_p95_ms", "mfu.serve",
        "paged_attention_roofline", "device_idle_share.serve"}
    assert all(m["moves"] == "serve_throughput" for n, m in served.per_layer.items()
               if n != "compile_s")
    p = served.workload["params"]
    assert (p["clients"], p["plan_requests"], p["sizes_seed"]) == (48, 512, 20260930)
    assert served.config["session"]["max_slots"] == 32 < p["clients"]
    for name, kernel in (("resnet50.train", set()), ("seq2seq_nmt.train", {"gru_seq_roofline"})):
        cell = harness.load_cell(name)
        assert set(cell.end_to_end) == {"throughput", "setup_s"}
        assert set(cell.per_layer) == kernel | {
            "compile_s", "device_idle_share.train", "mfu.train", "input_wait_share.train",
            "host_ms_per_dispatch", "setup_trace_lower_s", "setup_backend_s"}


def test_traced_serving_run_reports_the_per_layer_metrics_it_can_read(base, tmp_path, monkeypatch):
    from perfbench import harness

    # the CPU's trace holds no TPU plane: the trace-fed readers find nothing
    # and are left out; the span- and counter-fed ones report
    monkeypatch.setattr(harness.Profiler, "summary", lambda self, chips: None)
    r = run_cell(base, "servable_lm_tiny.chat_steady", seconds=1.5, trace=True, tmp=tmp_path)
    assert r["correct"] is True, r["checks"]
    assert set(r["metrics"]) == {"compile_s", "mfu.serve", "decode_step_ms",
                                 "queue_wait_p95_ms", "prefill_step_share"}
    assert 0 < r["metrics"]["prefill_step_share"]["value"] < 100
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert "busy_s" not in r["device"] and "breakdown" not in r


def test_command_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", "resnet50.train",
         "--seed", "3000000019", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout and '"metrics"' not in proc.stdout
    assert "TPU" in proc.stderr


def test_print_result_puts_checks_last_on_stderr_and_the_line_last_on_stdout(capsys):
    from perfbench import harness

    harness.print_result({"correct": True, "attempted": 1, "failed": 0, "metrics": {},
                          "device": {}, "checks": {"gap": {"value": 0.5, "limit": 1.0}}})
    out, err = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1])["correct"] is True
    assert err.strip().splitlines()[-2:] == ["check gap: 0.5 limit 1.0", "correct: True"]
