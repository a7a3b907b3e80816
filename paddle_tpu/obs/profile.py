"""Profile-driven HLO cost reporting (`--profile pass:N`).

ROADMAP item 2 asks for "a profile-driven pass over the top-3 HLO cost
buckets" — which first needs the buckets. Two hooks deliver them:

  * `PassProfiler` — watches the trainer's event stream and captures a
    `jax.profiler` trace of exactly one pass (start at BeginPass N, stop at
    EndPass N) into `logdir`, via the idempotent `stats.profiler_start/stop`
    so a crashed pass or a double-wrapped handler cannot wedge the tracer.
  * `compiled_cost_report` / `trainer_cost_report` — lower+compile the step
    program(s) and rank XLA's `cost_analysis()` entries into top-k FLOP/byte
    buckets, the machine-readable target list that lands in the JSON of
    `--job=time --profile` and in the `--profile` report file.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

__all__ = [
    "PassProfiler",
    "compiled_cost_report",
    "parse_profile_spec",
    "trainer_cost_report",
]


def parse_profile_spec(spec: str) -> Tuple[str, int]:
    """'pass:N' → ("pass", N). The shape is extensible ('step:N' later);
    anything else is a ValueError naming the accepted form."""
    kind, sep, arg = (spec or "").partition(":")
    if kind != "pass" or not sep:
        raise ValueError(
            f"bad --profile spec {spec!r}: expected 'pass:N' "
            f"(capture a jax.profiler trace of pass N)"
        )
    try:
        n = int(arg)
    except ValueError:
        raise ValueError(f"bad --profile spec {spec!r}: N must be an integer")
    if n < 0:
        raise ValueError(f"bad --profile spec {spec!r}: N must be >= 0")
    return kind, n


class PassProfiler:
    """Wraps a trainer event handler; profiles exactly one pass."""

    def __init__(self, pass_id: int, logdir: str):
        self.pass_id = int(pass_id)
        self.logdir = logdir
        self.captured = False
        self._active = False

    @classmethod
    def from_spec(cls, spec: str, logdir: str) -> "PassProfiler":
        _, n = parse_profile_spec(spec)
        return cls(n, logdir)

    def wrap(self, handler: Callable) -> Callable:
        from paddle_tpu.trainer.events import BeginPass, EndPass

        def wrapped(event):
            if isinstance(event, BeginPass) and event.pass_id == self.pass_id:
                self.start()
            try:
                handler(event)
            finally:
                if isinstance(event, EndPass) and self._active:
                    self.stop()

        return wrapped

    def start(self) -> None:
        from paddle_tpu.core import stats

        os.makedirs(self.logdir, exist_ok=True)
        stats.profiler_start(self.logdir)
        self._active = True

    def stop(self) -> None:
        from paddle_tpu.core import stats

        stats.profiler_stop()
        self._active = False
        self.captured = True


# -- HLO cost buckets --------------------------------------------------------


def _normalize_cost(ca: Any) -> Dict[str, float]:
    """cost_analysis() returns a dict on recent jax, a [dict] on older ones
    (one entry per module); normalize to one flat {key: number} dict."""
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    out: Dict[str, float] = {}
    for k, v in (ca or {}).items():
        if isinstance(v, (int, float)):
            out[str(k)] = float(v)
    return out


def compiled_cost_report(compiled: Any, top_k: int = 3) -> Dict[str, Any]:
    """One executable's cost analysis, ranked: headline flops / bytes
    accessed, plus the top-k remaining buckets (per-operand bytes,
    utilization entries — whatever the backend reports) by magnitude."""
    cost = _normalize_cost(compiled.cost_analysis())
    headline_keys = ("flops", "bytes accessed")
    buckets = sorted(
        (
            {"bucket": k, "value": v}
            for k, v in cost.items()
            if k not in headline_keys and v > 0
        ),
        key=lambda b: (-b["value"], b["bucket"]),
    )[: max(0, int(top_k))]
    return {
        "flops": cost.get("flops", 0.0),
        "bytes_accessed": cost.get("bytes accessed", 0.0),
        "top_buckets": buckets,
    }


def trainer_cost_report(
    trainer: Any, batch: Dict[str, Any], top_k: int = 3
) -> Dict[str, Any]:
    """Per-executable HLO cost buckets for a trainer's compiled step
    program(s) against `batch` (a feed-ready batch of the trained shape).
    Lowering + AOT compile only — nothing executes, state is not donated."""
    assert trainer.state is not None, "init_state()/train() first"
    reports: Dict[str, Any] = {}
    step_fn = trainer._step_fn
    if step_fn is None:
        step_fn = trainer._step_fn = trainer._make_step()
    reports["train_step"] = compiled_cost_report(
        step_fn.lower(trainer.state, batch).compile(), top_k
    )
    if trainer._eval_fn is not None:
        reports["eval_step"] = compiled_cost_report(
            trainer._eval_fn.lower(trainer.state, batch).compile(), top_k
        )
    return {
        "top_k": top_k,
        "generated_unix_s": int(time.time()),
        "executables": reports,
    }


def write_report(report: Dict[str, Any], path: str) -> str:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = f"{path}.tmp{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    os.replace(tmp, path)
    return path
