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


def test_throughput_counts_all_steps_over_the_whole_window(base, tmp_path):
    from perfbench import harness
    from perfbench_testlib import extended_benchmark

    cell = harness.load_cell("mlp_tiny.train", base=base, benchmark=extended_benchmark())
    # its own metric, and every metric of the benchmark that lists no cells
    # and moves an end-to-end metric this cell reports
    assert cell.chips == 1 and set(cell.per_layer) == {
        "steps_per_s", "compile_s", "device_idle_share.train", "mfu.train"}
    assert set(cell.end_to_end) == {"throughput", "setup_s"}


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
    r = run_cell(base, "servable_lm_tiny.chat_steady", seconds=1.5, tmp=tmp_path)
    assert r["correct"] is True, r["checks"]
    assert set(r["metrics"]) == {"ttft_p95_ms", "itl_p99_ms", "setup_s"}
    assert r["attempted"] == 12 and r["failed"] == 0
    assert r["checks"]["never_answered"]["value"] == 0.0


def test_traced_serving_run_reports_the_per_layer_metrics_it_can_read(base, tmp_path, monkeypatch):
    from perfbench import harness

    # the CPU's trace holds no TPU plane: the trace-fed readers find nothing
    # and are left out; the span- and counter-fed ones report
    monkeypatch.setattr(harness.Profiler, "summary", lambda self, chips: None)
    r = run_cell(base, "servable_lm_tiny.chat_steady", seconds=1.5, trace=True, tmp=tmp_path)
    assert r["correct"] is True, r["checks"]
    assert set(r["metrics"]) == {"compile_s", "mfu.serve", "decode_step_ms",
                                 "queue_wait_p95_ms"}
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
