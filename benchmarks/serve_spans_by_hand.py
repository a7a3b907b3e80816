"""One traced run of a served cell with the engine step's own account read
by hand: the three readers of perfbench/serve_spans.py beside the cell's
accepted metrics, and the ring's `recorded` and `dropped`.

BENCHMARK.json does not list `engine_host_ms_per_step`,
`prefill_step_time_share` and `replayed_lane_share` yet (ROADMAP.md S9(c)
says which `benchmark` PR edit that waits for), so the harness does not call
their readers. This calls perfbench.run as the driver does, in this process,
with `harness.read_per_layer` followed by the three, and gives a builder
that gives no `decode_window_ns` (`servable_lm_2048`'s) the window's edges
on the ring's clock the way builders/looped_lm.py takes them. Chip only, as
perfbench.run is; the result line is last, the three metrics in it.

    python3 benchmarks/serve_spans_by_hand.py --workload <cell> --seed <n> --seconds 50
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

READERS = {"engine_host_ms_per_step": "ms", "prefill_step_time_share": "%",
           "replayed_lane_share": "%"}


def attach() -> None:
    from perfbench import harness, registry, serving

    edges = []
    plain_drive, plain_read = serving.ServeSystem.drive, harness.read_per_layer

    def drive(self, schedule, seconds, profiler=None, clock=time.monotonic):
        wall_ns, at = time.time_ns(), clock()   # one pair: the ring's clock and the drive's
        out = plain_drive(self, schedule, seconds, profiler, clock)
        edges[:] = [wall_ns + int((out[k] - at) * 1e9) for k in ("t0", "t_end")]
        return out

    def read_per_layer(ctx, say=print):
        from paddle_tpu.obs import trace

        out = plain_read(ctx, say)
        ctx.facts.setdefault("decode_window_ns", tuple(edges))
        say(f"info: span ring after the run: {trace.TRACER.recorded} recorded, "
            f"{trace.TRACER.dropped} dropped of a capacity of {trace.TRACER.capacity}")
        for name, unit in READERS.items():
            value = registry.load_module("readers", name).read(ctx, {"name": name})
            if value is None:
                say(f"info: per-layer metric {name}: nothing to read in this run")
            else:
                out[name] = {"value": float(value), "unit": unit}
        return out

    serving.ServeSystem.drive = drive
    harness.read_per_layer = read_per_layer


def main(argv=None) -> int:
    from perfbench import run

    attach()
    return run.main(list(argv if argv is not None else sys.argv[1:]) + ["--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
