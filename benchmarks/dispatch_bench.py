"""Dispatch-overhead benchmark for the async execution runtime.

Measures steps/sec of a synthetic FAST train step (a tiny FC classifier whose
compiled step costs tens of microseconds, so per-step host work — not kernel
time — dominates) across the three levers this runtime added:

  * divergence guard: off / device-resident with guard_check_every=1 (the old
    react-at-every-batch latency, one host sync per step) / guard_check_every=16
    (bounded-window reaction, one sync per 16 steps);
  * steps_per_dispatch K ∈ {1, 4, 16}: batches fused per compiled lax.scan
    dispatch;
  * checkpointing: synchronous pass-boundary saves on the training thread vs
    the zero-stall async writer (non-blocking D2H fetch + background npz/CRC/
    v1/retention), every pass, keep_last_n=2.

Timing includes the end-of-run checkpoint_wait() flush, so async mode is
charged for its durability barrier. The headline `value` is the speedup of
(guard_check_every=16, K=16, async) over yesterday's defaults
(guard every step, K=1, sync) — the ISSUE 4 acceptance gate is >= 1.3x.

The headline config's host time is split by span name (`span_split`), read
from the train loop's always-recorded spans (obs/trace.py's flight recorder:
train.input_wait / train.dispatch / train.handler / train.guard_poll /
train.checkpoint under train.pass) — no second instrumented run, no sync.

ISSUE 9 adds a precision × remat grid leg (`precision_remat` in the JSON):
f32/bf16 × none/dots (plus "full" with --full), each entry platform-tagged,
with the compiled step's top-3 HLO cost buckets before (f32/none) and after
(bf16/dots). The heavy version of this drill runs in the nightly pytest tier
(tests/test_precision.py::test_nightly_precision_grid_drill).

Usage:
  JAX_PLATFORMS=cpu python benchmarks/dispatch_bench.py [--batches N]
      [--passes N] [--batch_size N] [--dim N] [--hidden N] [--full]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_trainer(args, guard, precision=None, remat=None):
    from paddle_tpu.nn import costs as C
    from paddle_tpu.nn import layers as L
    from paddle_tpu.nn.graph import reset_name_scope
    from paddle_tpu.optim import SGD
    from paddle_tpu.trainer import SGDTrainer

    reset_name_scope()
    x = L.Data("x", shape=(args.dim,))
    lbl = L.Data("label", shape=())
    logits = L.Fc(L.Fc(x, args.hidden, act="relu"), args.classes, act=None)
    cost = C.ClassificationCost(logits, lbl)
    policy = None if guard == "off" else "skip_batch"
    return SGDTrainer(
        cost, SGD(learning_rate=0.01), seed=0,
        divergence_policy=policy,
        guard_check_every=1 if guard == "off" else int(guard),
        precision=precision, remat=remat,
    )


def make_batches(args):
    import numpy as np

    rs = np.random.RandomState(0)
    return [
        {
            "x": rs.randn(args.batch_size, args.dim).astype(np.float32),
            "label": (np.arange(args.batch_size) % args.classes).astype(
                np.int64
            ),
        }
        for _ in range(args.batches)
    ]


def run_config(args, batches, guard: str, k: int, async_ckpt: bool,
               precision=None, remat=None, cost_report=False) -> dict:
    """steps/sec over the timed passes (pass 0 compiles and is excluded);
    the clock stops only after train() returns, i.e. after the async
    writer's durability barrier. `cost_report=True` attaches the compiled
    step's top-3 HLO cost buckets (obs.profile.trainer_cost_report on the
    trainer this run already built — no rebuild) as `hlo_cost`."""
    from paddle_tpu.trainer import EndPass

    trainer = build_trainer(args, guard, precision=precision, remat=remat)
    save_dir = tempfile.mkdtemp(prefix="dispatch_bench_")
    marks = []

    def handler(e):
        if isinstance(e, EndPass):
            marks.append(time.perf_counter())

    try:
        trainer.train(
            lambda: iter(batches),
            num_passes=1 + args.passes,
            event_handler=handler,
            save_dir=save_dir,
            keep_last_n=2,
            log_period=args.batches // 2 or 1,
            steps_per_dispatch=k,
            async_checkpoint=async_ckpt,
        )
        t_end = time.perf_counter()  # after the checkpoint_wait() barrier
    finally:
        shutil.rmtree(save_dir, ignore_errors=True)
    steps = args.batches * args.passes
    dt = t_end - marks[0]  # timed window starts when the warmup pass ended
    out = {
        "guard": guard,
        "steps_per_dispatch": k,
        "checkpoint": "async" if async_ckpt else "sync",
        "steps_per_sec": round(steps / dt, 1),
        "ms_per_step": round(1e3 * dt / steps, 4),
    }
    if cost_report:
        from paddle_tpu.obs.profile import trainer_cost_report

        try:
            out["hlo_cost"] = trainer_cost_report(
                trainer, batches[0], top_k=3
            )["executables"]["train_step"]
        except Exception as exc:  # noqa: BLE001 — report must not kill bench
            out["hlo_cost"] = {"error": repr(exc)[-200:]}
    return out


def run_span_split(args, batches) -> dict:
    """Where the fully-async config's host time goes, by span name, from the
    ring the train loop always writes (spans of this one run only)."""
    from paddle_tpu.obs import trace as obs_trace

    obs_trace.reset()
    run_config(args, batches, guard="16", k=16, async_ckpt=True)
    split: dict = {}
    for name, _t0, dur_ns, *_ in obs_trace.TRACER.snapshot():
        entry = split.setdefault(name, {"total_ms": 0.0, "count": 0})
        entry["total_ms"] += dur_ns * 1e-6
        entry["count"] += 1
    for entry in split.values():
        entry["total_ms"] = round(entry["total_ms"], 2)
    split["dropped_spans"] = obs_trace.TRACER.dropped
    return split


def run_precision_grid(args, batches, full: bool) -> dict:
    """ISSUE 9 grid leg: precision × remat over the same reader, measured
    through the full train loop (run_config), every entry platform-tagged so
    trajectory tooling can exclude CPU rounds per entry (bf16 dots are
    EMULATED on the CPU backend — expect the bf16 legs to lose there; the
    grid exists to show the MXU-path levers and their composition cost).
    `hlo_cost` records the compiled step's top-3 FLOP/byte buckets before
    (f32, no remat) and after (bf16, dots) — the profile-driven-pass
    bookkeeping of ROADMAP item 2."""
    import jax

    platform = jax.default_backend()
    remats = ("none", "dots", "full") if full else ("none", "dots")
    # the before/after of the profile-driven pass: cost reports come off the
    # trainers these two grid legs already built (run_config cost_report=)
    report_legs = {("f32", "none"): "before_f32_none",
                   ("bf16", "dots"): "after_bf16_dots"}
    grid, costs = [], {}
    for precision in ("f32", "bf16"):
        for remat in remats:
            leg = report_legs.get((precision, remat))
            r = run_config(
                args, batches, guard="off", k=1, async_ckpt=True,
                precision=precision, remat=remat, cost_report=bool(leg),
            )
            if leg:
                costs[leg] = r["hlo_cost"]
            grid.append({
                "precision": precision,
                "remat": remat,
                "steps_per_sec": r["steps_per_sec"],
                "ms_per_step": r["ms_per_step"],
                "platform": platform,
            })

    return {"grid": grid, "hlo_cost": costs, "platform": platform}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", type=int, default=192, help="batches per pass")
    ap.add_argument("--passes", type=int, default=2, help="timed passes")
    ap.add_argument("--batch_size", type=int, default=64)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--classes", type=int, default=8)
    ap.add_argument(
        "--full", action="store_true",
        help="run the full guard x K x checkpoint grid (18 configs); the "
             "default runs the 8 configs that bracket the answer",
    )
    args = ap.parse_args()

    import jax

    batches = make_batches(args)
    if args.full:
        grid = [
            (g, k, a)
            for g in ("off", "1", "16")
            for k in (1, 4, 16)
            for a in (False, True)
        ]
    else:
        grid = [
            ("1", 1, False),    # yesterday's defaults: per-step sync + sync ckpt
            ("off", 1, False),  # what the guard alone used to cost
            ("16", 1, False),   # device-resident guard, everything else old
            ("1", 16, False),   # fused dispatch, old guard cadence
            ("16", 16, False),  # guard + fusion, sync ckpt
            ("16", 1, True),    # guard + async ckpt, unfused
            ("off", 16, True),  # no guard at all, fully async
            ("16", 16, True),   # the new runtime defaults at K=16
        ]
    results = [run_config(args, batches, g, k, a) for g, k, a in grid]

    def sps(g, k, a):
        for r in results:
            if (
                r["guard"] == g
                and r["steps_per_dispatch"] == k
                and r["checkpoint"] == ("async" if a else "sync")
            ):
                return r["steps_per_sec"]
        return None

    baseline = sps("1", 1, False)
    best = sps("16", 16, True)

    out = {
        "metric": "dispatch_runtime_speedup",
        "value": round(best / baseline, 3) if baseline and best else 0.0,
        "unit": "x",
        "baseline": {
            "config": "guard_check_every=1, K=1, sync ckpt",
            "steps_per_sec": baseline,
        },
        "async_runtime": {
            "config": "guard_check_every=16, K=16, async ckpt",
            "steps_per_sec": best,
        },
        "grid": results,
        "precision_remat": run_precision_grid(args, batches, args.full),
        "span_split": run_span_split(args, batches),
        "batches_per_pass": args.batches,
        "timed_passes": args.passes,
        "batch_size": args.batch_size,
        "backend": jax.default_backend(),
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
