"""The parts of one run: load the cell, set the system up, measure the
window, read memory, verify against the plain reference, reduce the trace,
report. run.py strings them together behind the look for a chip; the CPU
tests call them directly at a tiny size."""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, Optional, Tuple

from perfbench import registry
from perfbench import trace as trace_mod


@dataclasses.dataclass
class Cell:
    name: str
    workload: dict
    config: dict
    chips: int
    end_to_end: Dict[str, dict]   # the metrics of BENCHMARK.json this cell reports
    per_layer: Dict[str, dict]    # BENCHMARK.json's entry + metrics/<name>.json


def load_cell(name: str, base: str = registry.HERE, benchmark: Optional[dict] = None) -> Cell:
    workload = registry.load_workload(name, base)
    config = registry.load_config(workload["config"], base)
    bench = benchmark if benchmark is not None else registry.load_benchmark()
    e2e = {
        m["name"]: m for m in bench["end_to_end"]
        if "workloads" not in m or name in m["workloads"]
    }
    chips = int(workload.get("chips", 1))
    for entry in bench.get("workloads", ()):
        if entry["name"] == name:
            chips = int(entry["chips"])
    return Cell(name, workload, config, chips, e2e, registry.metrics_for(name, bench, base))


def trace_options():
    """The device planes are all the reduction reads. With jax's defaults
    (Python and host tracers on, every program's HLO copied into the trace)
    starting the profiler stalled the host for 2.3-4.1 s under ResNet-50's
    step and starved the chip for as long as the trace ran (my chip runs,
    PR 25: 27-65% idle); with them off the same cell traces at 0.006% idle."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    options.enable_hlo_proto = False
    return options


class Profiler:
    """jax's profiler around the end of the window, into a directory inside
    the checkout that is removed once the trace is reduced. It starts
    LEAD_S before the part that is kept, so that whatever its start costs
    the host is not read as the program's idle time, and the reduction keeps
    the last KEEP_S seconds of device activity."""

    LEAD_S = 2.0
    KEEP_S = 3.0

    def __init__(self, trace_dir: Optional[str]):
        self.dir = trace_dir
        self.running = False
        self.t_start = None

    @property
    def wanted(self) -> bool:
        return self.dir is not None and self.t_start is None

    def warm(self) -> None:
        """The profiler's first start in a process is its slowest: spend it
        in set-up, on a trace that is thrown away."""
        import jax

        if self.dir is None:
            return
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir, profiler_options=trace_options())
        jax.profiler.stop_trace()
        shutil.rmtree(self.dir, ignore_errors=True)

    def due(self, now: float, t_end: float) -> bool:
        """Time to start: LEAD_S before the last KEEP_S of the window."""
        return self.wanted and now > t_end - self.KEEP_S - self.LEAD_S

    def start(self) -> None:
        import jax

        if self.dir is None or self.running:
            return
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir, profiler_options=trace_options())
        self.running, self.t_start = True, time.perf_counter()

    def stop(self) -> None:
        import jax

        if self.running:
            jax.profiler.stop_trace()
            self.running = False

    def summary(self, chips: int) -> Optional[trace_mod.TraceSummary]:
        if self.dir is None or self.t_start is None:
            return None
        try:
            planes = trace_mod.load_device_ops(trace_mod.find_xplane(self.dir))
            planes = {k: trace_mod.last_seconds(v, self.KEEP_S) for k, v in planes.items()}
            return trace_mod.summarize(planes, chips)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


@dataclasses.dataclass
class ReadContext:
    """What a per-layer reader may read."""

    cell: Cell
    facts: Dict[str, Any]           # the system's spans and counters of the window
    e2e: Dict[str, float]           # this run's end-to-end values
    trace: Optional[trace_mod.TraceSummary]
    peaks: dict
    device: dict


def compile_count() -> int:
    """Every XLA compile asks the persistent cache first, so hits + misses
    counts compiles (core/stats.RECOMPILES listens to jax.monitoring)."""
    from paddle_tpu.core import stats

    return stats.RECOMPILES.cache_hits + stats.RECOMPILES.cache_misses


def engagement() -> Dict[str, float]:
    """The program's engagement counter after set-up, by label: how many
    classification costs were traced through the fused projection and how
    many beside it. Empty where nothing has moved it: a program without the
    counter (a parent of PR 27), a cell that traces no such cost."""
    from paddle_tpu.obs.metrics import REGISTRY

    counter = REGISTRY.counter("paddle_tpu_fused_projection_xent_total")
    return {
        ",".join(f"{k}={v}" for k, v in s.labels): s.value
        for s in counter.samples() if s.labels
    }


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def memory_peak_bytes(chips: int) -> int:
    import jax

    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def read_per_layer(ctx: ReadContext, say=print) -> Dict[str, dict]:
    out = {}
    for name, meta in sorted(ctx.cell.per_layer.items()):
        reader = registry.load_module("readers", meta["reader"])
        value = reader.read(ctx, meta)
        if value is None:
            say(f"info: per-layer metric {name}: nothing to read in this run")
            continue
        out[name] = {"value": float(value), "unit": meta["unit"]}
    return out


def decide(checks: Dict[str, Tuple[float, float]]) -> bool:
    """`correct`: every number compared is at or under its limit (and is a
    number: a NaN fails)."""
    return bool(checks) and all(
        v == v and v <= limit for v, limit in checks.values()
    )


def format_checks(checks: Dict[str, Tuple[float, float]]) -> Dict[str, dict]:
    return {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}


def run_cell(
    cell: Cell,
    seed: int,
    seconds: float,
    trace: bool,
    t_process_start: float,
    device: dict,
    peaks: dict,
    scratch: str,
    say=print,
) -> dict:
    """Set up, measure, verify, report: returns the result line's object."""
    builder = registry.load_module("builders", cell.config["builder"])
    system = builder.build(cell, seed)
    compiles0 = compile_count()
    t0 = time.perf_counter()
    system.setup(say)
    warm_s = time.perf_counter() - t0
    compiles_setup = compile_count() - compiles0
    say(f"info: fused projection xent, costs traced by path: {engagement() or 'none'}")
    profiler = Profiler(os.path.join(scratch, "trace") if trace else None)
    profiler.warm()
    compiles1 = compile_count()
    window = system.window(seconds, profiler, t_process_start)
    profiler.stop()
    compiles_window = compile_count() - compiles1
    mem_peak = memory_peak_bytes(cell.chips)
    facts = dict(window["facts"])
    facts.update(
        warm_s=warm_s, compiles_setup=compiles_setup,
        compiles_window=compiles_window,
    )
    e2e = dict(window["end_to_end"])
    say(f"info: set-up {e2e['setup_s']:.2f} s (warm-up and first steps {warm_s:.2f} s, "
        f"{compiles_setup} programs asked of the compile cache); "
        f"{compiles_window} compiles inside the window")
    for line in window.get("info", ()):
        say("info: " + line)
    summary = profiler.summary(cell.chips)
    system.release()
    t_verify = time.perf_counter()
    checks = dict(system.verify(say))
    say(f"info: the reference and the comparison took {time.perf_counter() - t_verify:.1f} s")
    checks["window_compiles"] = (float(compiles_window), 0.0)
    correct = decide(checks)
    result: Dict[str, Any] = {
        "correct": correct,
        "attempted": int(window["attempted"]),
        "failed": int(window["failed"]),
    }
    dev = dict(device, memory_peak_bytes=mem_peak)
    if trace:
        ctx = ReadContext(cell, facts, e2e, summary, peaks, dev)
        result["metrics"] = read_per_layer(ctx, say)
        if summary is not None:
            dev["busy_s"] = summary.busy_s
            dev["window_s"] = summary.window_s
            result["breakdown"] = {
                "device_ops": [[n, s] for n, s in summary.device_ops],
                "idle_gaps": [[n, s] for n, s in summary.gaps],
            }
    else:
        result["metrics"] = {
            name: {"value": float(e2e[name]), "unit": meta["unit"]}
            for name, meta in cell.end_to_end.items()
        }
    result["device"] = dev
    result["checks"] = format_checks(checks)
    return result


def print_result(result: dict) -> None:
    """Numbers compared, each beside its limit, as the last lines of stderr;
    the result as the last line of stdout, `checks` last in it."""
    sys.stdout.flush()
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
