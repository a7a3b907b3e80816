"""python3 -m perfbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell once on the machine it is started on: fails without a TPU (or
with fewer chips than the cell asks for), sets up, warms only that cell's
shapes, measures for --seconds, checks the timed path's outputs against the
plain reference, and prints the contract's one result line last."""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from perfbench import harness
    from perfbench.peaks import peaks_for

    cell = harness.load_cell(args.workload)
    # libtpu logs to /tmp/tpu_logs unless told otherwise: keep what a run
    # writes inside its checkout
    scratch = os.path.join(ROOT, ".perfbench_tmp")
    os.makedirs(os.path.join(scratch, "tpu_logs"), exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(scratch, "tpu_logs"))
    # the program and its compile cache (JAX_COMPILATION_CACHE_DIR, else
    # .jax_cache/ inside the checkout): an import error here means the
    # directory holds the benchmark without the program, and ends the run
    import paddle_tpu  # noqa: F401
    from paddle_tpu.core.init_ctx import enable_compilation_cache

    device = harness.device_info()
    if device["platform"] != "tpu" or device["count"] < cell.chips:
        print(
            f"perfbench: cell {cell.name} needs {cell.chips} TPU chip(s); jax "
            f"reports platform {device['platform']!r} with {device['count']} "
            "device(s). No result.", file=sys.stderr,
        )
        return 2
    peaks = peaks_for(device["kind"])
    cache_dir = enable_compilation_cache()
    print(f"info: device {device}; compile cache {cache_dir}", flush=True)
    result = harness.run_cell(
        cell, args.seed, args.seconds, bool(args.trace), T_PROCESS_START,
        device, peaks, scratch=scratch,
    )
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
