"""Skew between the program's span clock and a device trace's clock.

obs/trace.py stamps spans with time.time_ns(); a device trace's events are
`profile_start_time + start_ns` (the xplane's `Task Environment` plane holds
`profile_start_time` in unix ns). This drives a small trainer through
synchronous single-step dispatches (the handler fetches each cost, so the
device is idle when the next dispatch is enqueued) under jax's profiler with
the options the benchmark's harness uses, keeps the trace, and bounds the
skew s (device trace's clock minus the span clock) from both sides, each
dispatch giving one reading of each edge:

    s <= (start of the dispatch's program on the device) - (start of its
         `train.dispatch` span): the program cannot start before the host
         asked for it, and the reading is the skew plus the enqueue latency;
    s >= (end of that program on the device) - (the host's time when the
         handler's fetch of its cost returned): the fetch cannot return
         before the program ended, and the reading is the skew less the
         fetch's latency.

The least of the first and the greatest of the second over all dispatches
are the interval the skew lies in; its width is the two latencies, and an
idle gap shorter than the interval's farther edge from zero cannot be
attributed to a program span. The skew is a property of the two clocks, not
of the model, so a small trainer reads it as well as a cell does. Chip only;
one JSON line.

    python3 benchmarks/clock_skew.py [--dispatches 80] [--out DIR]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def module_starts(xplane_path: str):
    """(profile_start_time, [(unix start ns, unix end ns, program name)]) of
    the first device plane's "XLA Modules" line."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    start = next(
        int(v) for p in data.planes if p.name == "Task Environment"
        for k, v in p.stats if k == "profile_start_time"
    )
    plane = next(p for p in data.planes if p.name.startswith("/device:TPU:"))
    line = next(ln for ln in plane.lines if ln.name == "XLA Modules")
    return start, sorted(
        (start + int(ev.start_ns), start + int(ev.start_ns + ev.duration_ns), ev.name)
        for ev in line.events
    )


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dispatches", type=int, default=80)
    ap.add_argument("--out", default=None, help="keep the trace here")
    args = ap.parse_args()

    import jax
    import numpy as np

    from paddle_tpu.nn import costs as C
    from paddle_tpu.nn import layers as L
    from paddle_tpu.obs import trace
    from paddle_tpu.optim import SGD
    from paddle_tpu.trainer import SGDTrainer
    from paddle_tpu.trainer.events import EndIteration
    from perfbench.harness import trace_options
    from perfbench.trace import find_xplane

    if jax.devices()[0].platform != "tpu":
        print("clock_skew: needs a TPU (a CPU trace has no device plane)", file=sys.stderr)
        return 2
    x = L.Data("x", shape=(1024,))
    cost = C.ClassificationCost(
        L.Fc(L.Fc(x, 4096, act="relu"), 16, act=None), L.Data("label", shape=()))
    trainer = SGDTrainer(cost, SGD(learning_rate=0.01), seed=0)
    rs = np.random.RandomState(0)
    batch = {"x": rs.randn(512, 1024).astype(np.float32),
             "label": (np.arange(512) % 16).astype(np.int32)}

    fetched = []  # the host's clock when each dispatch's cost had arrived

    def fetch(ev):
        if isinstance(ev, EndIteration):
            float(ev.cost)  # the device is idle when the next dispatch is enqueued
            fetched.append(time.time_ns())

    trainer.train(lambda: iter([batch] * 4), event_handler=fetch, log_period=10 ** 9)
    logdir = args.out or tempfile.mkdtemp(prefix="clock_skew_")
    trace.reset()
    del fetched[:]
    jax.profiler.start_trace(logdir, profiler_options=trace_options())
    trainer.train(lambda: iter([batch] * args.dispatches), event_handler=fetch,
                  log_period=10 ** 9)
    jax.profiler.stop_trace()

    spans = sorted(r[1] for r in trace.TRACER.snapshot() if r[0] == "train.dispatch")
    path = find_xplane(logdir)
    start, modules = module_starts(path)
    step = trainer._step_fn.__name__
    steps = [(t0, t1) for t0, t1, name in modules if step in name]
    if not len(steps) == len(spans) == len(fetched):
        print(f"clock_skew: {len(steps)} device programs named {step!r} for "
              f"{len(spans)} dispatch spans and {len(fetched)} fetches; programs seen: "
              f"{sorted({n.split('(')[0] for _, _, n in modules})}", file=sys.stderr)
        return 1
    above = [dev[0] - host for dev, host in zip(steps, spans)]    # skew + enqueue latency
    below = [dev[1] - host for dev, host in zip(steps, fetched)]  # skew - fetch latency

    def spread(v):
        return {"least": min(v), "median": statistics.median(v), "greatest": max(v)}

    print(json.dumps({
        "dispatches": len(above), "device": jax.devices()[0].device_kind,
        "skew_at_most_ns": min(above), "skew_at_least_ns": max(below),
        "start_less_span_ns": spread(above), "end_less_fetch_ns": spread(below),
        "profile_start_time": start, "trace": path,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
