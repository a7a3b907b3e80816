"""Shared by the perfbench CPU tests: a copy of the benchmark's data
directories in a temporary directory with the files of a would-be later PR
(tests/perfbench_cpu/data/extra) ADDED beside them: new files and entries
only, no file that exists edited."""

import copy
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
EXTRA = os.path.join(HERE, "data", "extra")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CPU_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
SERVING_CELL = "servable_lm_tiny.chat_steady"
SATURATED_CELL = "servable_lm_tiny.chat_saturated"
SERVING_E2E = ("serve_throughput",)
SERVING_METRICS = ("decode_step_ms", "queue_wait_p95_ms", "mfu.serve", "prefill_step_share",
                   "device_idle_share.serve", "paged_attention_roofline")
V5E_PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def extended_base(tmp_path) -> str:
    """perfbench's data + the extra files; code modules of the extra PR
    become importable as perfbench.<kind>.<name> by extending the packages'
    search paths (what adding the files to the directories would do)."""
    from perfbench import registry

    base = str(tmp_path / "pb")
    for kind in ("configs", "workloads", "metrics"):
        shutil.copytree(os.path.join(registry.HERE, kind), os.path.join(base, kind))
        for f in os.listdir(os.path.join(EXTRA, kind)):
            assert not os.path.exists(os.path.join(base, kind, f)), "extra may only ADD"
            shutil.copy(os.path.join(EXTRA, kind, f), os.path.join(base, kind, f))
    import perfbench.builders, perfbench.readers, perfbench.reference  # noqa: E401

    for pkg, kind in ((perfbench.builders, "builders"), (perfbench.readers, "readers"),
                      (perfbench.reference, "reference")):
        path = os.path.join(EXTRA, kind)
        if path not in pkg.__path__:
            pkg.__path__.append(path)
    return base


def extended_benchmark() -> dict:
    """BENCHMARK.json with the extra PR's entries appended."""
    from perfbench import registry

    bench = copy.deepcopy(registry.load_benchmark())
    for name in ("mlp_tiny", "servable_lm_tiny"):
        bench["configs"].append({"name": name, "source": "tests", "file": "x", "reduced": [], "why": "t"})
    bench["workloads"].append({"name": "mlp_tiny.train", "config": "mlp_tiny", "traffic": "train",
                               "chips": 1, "why": "t"})
    # later serving cells: the tiny ones of the tests, and the open-loop cell
    # that waits for a latency metric (PERF.md section 7)
    cells = [SERVING_CELL, SATURATED_CELL, "servable_lm_2048.chat_steady"]
    for name in cells:
        config, traffic = name.split(".")
        bench["workloads"].append({"name": name, "config": config, "traffic": traffic,
                                   "chips": 1, "why": "t"})
    # the cell whose data file waits in perfbench/workloads (PERF.md section 7)
    bench["workloads"].append({"name": "resnet50.train_cli_feed", "config": "resnet50",
                               "traffic": "train_cli_feed", "chips": 1, "why": "t"})
    bench["per_layer"].append({"name": "steps_per_s", "unit": "steps/s", "better": "higher",
                               "source": "host_clock", "layer": "Train loop",
                               "moves": "throughput", "workloads": ["mlp_tiny.train"]})
    for m in bench["end_to_end"]:
        if m["name"] == "throughput":
            m["workloads"] = m["workloads"] + ["mlp_tiny.train", "resnet50.train_cli_feed"]
    # a later serving cell appends its name to serve_throughput's list and to
    # the serving per-layer metrics' lists
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in SERVING_E2E + SERVING_METRICS:
            m["workloads"] = m["workloads"] + cells
    return bench


def run_cell(base, name, seed=3000000019, seconds=0.5, trace=False, tmp=".", say=lambda *_: None):
    from paddle_tpu.core.init_ctx import enable_compilation_cache
    from perfbench import harness

    enable_compilation_cache()
    cell = harness.load_cell(name, base=base, benchmark=extended_benchmark())
    return harness.run_cell(
        cell, seed, seconds, trace, time.perf_counter(), CPU_DEVICE, V5E_PEAKS,
        scratch=os.path.join(str(tmp), "scratch"), say=say,
    )
