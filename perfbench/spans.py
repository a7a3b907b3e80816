"""The program's own account of its host time, read from its span ring
(paddle_tpu/obs/trace.py) after the window.

The ring is process-global and outlives `system.release()`, so a reader
reaches it in-process, as harness.compile_count() reaches the compile
counters. A row is (name, start_ns, duration_ns, trace id, span id, parent
id, attrs, thread): wall-clock nanoseconds, the clock of a device trace's
`profile_start_time + start_ns`.

The window is the LAST `train.pass` span: the harness's window is one
`SGDTrainer.train()` call, which is one pass. It is taken for the window
only if it counts the steps the harness counted and is no longer than the
harness's window; a cell that never calls `train()`, a program without the
flight recorder (the parent of the PR that added it), and a ring that
dropped spans all read as nothing, never as a number from another interval."""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from perfbench.trace import union_ns

NAME, START, DUR, TRACE, SPAN, PARENT, ATTRS, THREAD = range(8)

# children of the pass that are NOT the loop's own work: waiting for data,
# the caller's handler, the host blocked on the device (a guard poll, the
# pass-end fetch of the cost sum, which waits out the last dispatch) or on a
# save
NOT_THE_LOOPS_OWN = ("train.input_wait", "train.handler", "train.guard_poll",
                     "train.cost_fetch", "train.checkpoint")


def say(line: str) -> None:
    print("info: " + line, flush=True)


def ring_rows() -> Optional[List[tuple]]:
    """The ring's rows, oldest first; None (with an info line) where the
    program records no flight spans or the ring overflowed."""
    try:
        from paddle_tpu.obs import trace
    except ImportError:
        return None
    if not hasattr(trace, "flight"):
        say("the program has no flight recorder (obs/trace.py::flight): no span to read")
        return None
    tracer = trace.TRACER
    if tracer.dropped:
        say(f"the span ring dropped {tracer.dropped} of {tracer.recorded} spans "
            f"(capacity {tracer.capacity}): its account is incomplete, not read")
        return None
    return tracer.snapshot()


@dataclasses.dataclass
class Window:
    rows: List[tuple]          # the whole ring: the one snapshot the readers share
    train_pass: tuple          # the window's train.pass row
    children: List[tuple]      # its direct children, every thread

    @property
    def duration_ns(self) -> int:
        return self.train_pass[DUR]

    def child_ns(self, names: Sequence[str]) -> int:
        return sum(r[DUR] for r in self.children if r[NAME] in names)

    def count(self, name: str) -> int:
        return sum(1 for r in self.children if r[NAME] == name)


_found: Optional[Tuple[object, Optional[Window]]] = None  # (a run's ctx, its window)


def window(ctx) -> Optional[Window]:
    """The measured window as the program recorded it, or None. Found once a
    run and kept here beside the run's context (not in the harness's
    facts): the four readers share one snapshot of the ring, and its info
    lines are said once."""
    global _found
    if _found is None or _found[0] is not ctx:
        _found = (ctx, _find_window(ctx.facts))
    return _found[1]


def _find_window(facts: dict) -> Optional[Window]:
    rows = ring_rows()
    if rows is None:
        return None
    passes = [r for r in rows if r[NAME] == "train.pass"]
    if not passes or "steps" not in facts:
        say("no train.pass span marks this cell's window (a cell that never calls "
            "SGDTrainer.train brings its own marker): no span read")
        return None
    last = passes[-1]
    batches = (last[ATTRS] or {}).get("batches")
    if batches != facts["steps"] or last[DUR] > facts.get("window_s", 0.0) * 1e9:
        say(f"the last train.pass span ({batches} batches, {last[DUR] * 1e-9:.3f} s) is not "
            f"the window ({facts['steps']} steps, {facts.get('window_s', 0.0):.3f} s): no span read")
        return None
    children = [r for r in rows if r[PARENT] == last[SPAN] and r[TRACE] == last[TRACE]]
    before = sum(1 for r in rows if r[START] + r[DUR] <= last[START])
    inside = sum(1 for r in rows if r[TRACE] == last[TRACE])
    say(f"span ring: {len(rows)} spans held and none dropped; {before} ended before the "
        f"window's pass began, {inside} belong to it, {len(rows) - before - inside} came after")
    return Window(rows, last, children)


def setup_seconds(ctx, names: Sequence[str]) -> Optional[float]:
    """Seconds of set-up covered by spans of these names: those that began
    after the process did (the window's start less this run's `setup_s`) and
    ended before the window began, as a union per thread (an outer jit's
    trace contains its inner ones), summed over threads."""
    win = window(ctx)
    setup_s = ctx.e2e.get("setup_s")
    if win is None or setup_s is None:
        return None
    window_began = win.train_pass[START]
    begin = window_began - int(setup_s * 1e9)
    by_thread: Dict[int, List[Tuple[int, int]]] = {}
    for r in win.rows:
        end = r[START] + r[DUR]
        if r[NAME] in names and r[START] >= begin and end <= window_began:
            by_thread.setdefault(r[THREAD], []).append((r[START], end))
    return sum(union_ns(v) for v in by_thread.values()) * 1e-9
