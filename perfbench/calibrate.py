"""Readings for the limits behind `correct` (not run by the benchmark).

    python3 -m perfbench.calibrate --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--fault-seeds 1,2,3] [--window 0] [--rates 4,6,8]

One process, a dozen seeds: for each seed the cell's system is set up as a
run sets it up (the check drive through the window's own call, or a short
window at the cell's own load), the plain reference is read beside it, and
the numbers compared are printed with the verdict the run's own
harness.decide() gives on them against the cell's limits (`correct`, and
`over`: the numbers past their limit). On the control seeds the reference,
computed in the nearest precision below the configuration's, takes the
program's place; on the fault seeds each planted fault does: both have to
read `correct` false. One JSON line per reading; the same lines go to
chiprun_out/calibrate/."""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def ints(text):
    return [int(x) for x in text.split(",") if x.strip()]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=ints, default=[])
    ap.add_argument("--control-seeds", type=ints, default=[])
    ap.add_argument("--fault-seeds", type=ints, default=[])
    ap.add_argument("--window", type=float, default=0.0,
                    help="seconds of window for cells whose check needs one")
    ap.add_argument("--rates", type=lambda t: [float(x) for x in t.split(",")], default=[],
                    help="serving: sweep these arrival rates, one window each, "
                         "in one session (the knee is found once, here)")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsal only: readings from a CPU are not readings")
    args = ap.parse_args(argv)

    from perfbench import harness, registry
    import paddle_tpu  # noqa: F401
    from paddle_tpu.core.init_ctx import enable_compilation_cache

    cell = harness.load_cell(args.workload)
    device = harness.device_info()
    if device["platform"] != "tpu" and not args.allow_cpu:
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    enable_compilation_cache()
    out_dir = os.path.join(ROOT, "chiprun_out", "calibrate")
    os.makedirs(out_dir, exist_ok=True)
    out = open(os.path.join(out_dir, f"{cell.name}.jsonl"), "a")
    builder = registry.load_module("builders", cell.config["builder"])

    def emit(**row):
        row.update(cell=cell.name, device=device["kind"])
        line = json.dumps(row)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    if args.rates:
        system = builder.build(cell, (args.seeds or [1])[0])
        for row in system.sweep(args.rates, args.window or 30.0):
            emit(who="sweep", **row)
        return 0
    seeds = list(dict.fromkeys(args.seeds + args.control_seeds + args.fault_seeds))
    for seed in seeds:
        t0 = time.perf_counter()
        system = builder.build(cell, seed)
        for row in system.calibrate(
            window_s=args.window,
            program=seed in args.seeds,
            control=seed in args.control_seeds,
            faults=seed in args.fault_seeds,
        ):
            # the verdict a run would give on these numbers, by the run's own
            # decide(): the program's has to read true, a control's or a
            # fault's false
            checks = system.judge(row["numbers"])
            emit(seed=seed, seconds=round(time.perf_counter() - t0, 1),
                 correct=harness.decide(checks),
                 over=sorted(k for k, (v, lim) in checks.items() if not v <= lim), **row)
        del system
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
