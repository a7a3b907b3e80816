"""The served cells' perfbench/spans.py: the engine step's own account of
its time, read from the program's span ring after the window.

`ServingSession.step()` leaves one `serve.step` flight span a call that did
work: int attrs `admitted`, `chunks`, `decoded`, `slots`, `preempted` and
`wait_ns`, the nanoseconds the host spent blocked on a fetch from the device
(the rest of the span is the host's own work); `serve.decode`, its child
around a decode dispatch and the fetch of the step before, carries `slots`
and `replaying` (lanes that only rebuild a preempted request's K/V);
`serve.admit` carries `queued_ms`. The window is the builder's
`decode_window_ns`, its two edges on the ring's clock, and a span belongs to
the window in which it ENDED, as everything in the served cells does.

Nothing is read, and every reader gives nothing, where the builder gave no
window, the ring dropped spans, the program records no `serve.step` (the
parent of the PR that added it), or no step ended in the window: never a
number from another interval. One snapshot a run, shared by the readers,
and its info lines said once."""

from __future__ import annotations

import dataclasses
import statistics
from typing import List, Optional, Tuple

from perfbench import spans
from perfbench.spans import ATTRS, DUR, NAME, START, say

STEP, DECODE, ADMIT = "serve.step", "serve.decode", "serve.admit"


def attr(row: tuple, key: str) -> int:
    return (row[ATTRS] or {}).get(key, 0)


@dataclasses.dataclass
class Window:
    t0_ns: int
    t_end_ns: int
    steps: List[tuple]      # the serve.step rows that ended in (t0, t_end]
    decodes: List[tuple]    # the serve.decode rows, likewise
    admits: List[tuple]     # the serve.admit rows, likewise

    @property
    def duration_ns(self) -> int:
        return self.t_end_ns - self.t0_ns

    @property
    def decode_only(self) -> List[tuple]:
        """Steps that dispatched a decode step and ran no prefill."""
        return [r for r in self.steps if attr(r, "decoded")
                and not attr(r, "admitted") and not attr(r, "chunks")]

    @property
    def prefilling(self) -> List[tuple]:
        return [r for r in self.steps if attr(r, "admitted") + attr(r, "chunks")]


_found: Optional[Tuple[object, Optional[Window]]] = None  # (a run's ctx, its window)


def window(ctx) -> Optional[Window]:
    global _found
    if _found is None or _found[0] is not ctx:
        _found = (ctx, _find_window(ctx.facts))
    return _found[1]


def _find_window(facts: dict) -> Optional[Window]:
    edges = facts.get("decode_window_ns")
    if not edges:
        return None
    rows = spans.ring_rows()
    if rows is None:
        return None
    if not any(r[NAME] == STEP for r in rows):
        say("the program records no serve.step span: the engine step's account is not read")
        return None
    t0, t_end = edges

    def ended_inside(name):
        return [r for r in rows if r[NAME] == name and t0 < r[START] + r[DUR] <= t_end]

    win = Window(t0, t_end, ended_inside(STEP), ended_inside(DECODE), ended_inside(ADMIT))
    if not win.steps:
        say("no serve.step span ended in the window: the engine step's account is not read")
        return None
    _say_account(win, len(rows), facts)
    return win


def _median_ms(values) -> str:
    values = list(values)
    return f"{1e-6 * statistics.median(values):.3f} ms" if values else "none"


def _say_account(win: Window, held: int, facts: dict) -> None:
    outside = facts.get("decode_only_step_s")
    admission_steps = [r[DUR] for r in win.steps if attr(r, "admitted")]
    waited = sum(attr(r, "wait_ns") for r in win.steps)
    queued = [attr(r, "queued_ms") for r in win.admits if not attr(r, "replay")]
    say(f"serve spans: {held} spans held and none dropped; {len(win.steps)} serve.step ended in "
        f"the window, {len(win.decode_only)} decode-only, {len(win.prefilling)} with a prefill "
        f"({len(admission_steps)} with an admission), "
        f"{sum(1 for r in win.steps if attr(r, 'preempted'))} with a preemption; median "
        f"decode-only serve.step {_median_ms(r[DUR] for r in win.decode_only)} beside the "
        f"benchmark's decode_step_ms "
        f"{f'{1e3 * statistics.median(outside):.3f} ms' if outside else 'none'} around the "
        f"same call; median admission step {_median_ms(admission_steps)}; the host waited for "
        f"the device {100.0 * waited / win.duration_ns:.2f}% of the window; median queued_ms "
        f"of {len(queued)} first admissions "
        f"{f'{statistics.median(queued):.0f}' if queued else 'none'}")
