"""Reduction from a profiler trace (.xplane.pb) to device metrics.

Source (as benchmarks/profile_resnet.py::parse_xplane found it): the device
planes are named "/device:TPU:<n>", and the line "XLA Ops" of each holds one
event per executed HLO op with its start and duration. Unlike that script,
which summed durations, busy time here is the UNION of the intervals, so
nested events (a while loop and the ops of its body) and overlapping ones
count once, and idle share is 1 - busy / window. Read with
jax.profiler.ProfileData: nothing but jax is needed."""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float, str]  # start_ns, end_ns, name

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"

def short_op_name(name: str, type_chars: int = 48) -> str:
    """The trace names an op by its whole HLO line; the breakdown keeps the
    instruction's name, its opcode and the start of its output type:
    "%fusion.7 = bf16[8,128]{...} fusion(...), kind=kOutput, calls=..." ->
    "fusion.7 fusion bf16[8,128]{...}"."""
    lhs, eq, rest = name.partition(" = ")
    if not eq:
        return name[:120]
    depth, end = 0, len(rest)
    for i, ch in enumerate(rest):           # the output type ends at the first
        if ch in "([{":                     # space outside brackets
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            end = i
            break
    out_type, tail = rest[:end], rest[end + 1:]
    opcode = tail.split("(", 1)[0].strip()
    return f"{lhs.lstrip('%')} {opcode} {out_type[:type_chars]}".strip()


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load_device_ops(path: str) -> Dict[str, List[Interval]]:
    """{plane name: [(start_ns, end_ns, op name)]} for every device plane."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: Dict[str, List[Interval]] = {}
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        ops: List[Interval] = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                start = float(ev.start_ns)
                ops.append((start, start + float(ev.duration_ns), ev.name))
        out[plane.name] = sorted(ops)
    return out


def last_seconds(ops: Sequence[Interval], seconds: float) -> List[Interval]:
    """The ops of the last `seconds` of device activity, those that straddle
    the cut clipped to it."""
    if not ops:
        return []
    cut = max(hi for _, hi, _ in ops) - seconds * 1e9
    return [(max(lo, cut), hi, name) for lo, hi, name in ops if hi > cut]


def union_ns(intervals: Iterable[Tuple[float, float]]) -> float:
    """Total length covered by the intervals, overlaps counted once."""
    total, cur_lo, cur_hi = 0.0, None, None
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        else:
            cur_hi = max(cur_hi, hi)
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


def idle_gaps(ops: Sequence[Interval], top: int = 10) -> List[Tuple[str, float]]:
    """The longest gaps in which no op ran, named by the ops on either side
    ("after <op> before <op>"): what the host was doing then needs spans
    inside the program, which is the tracing issue's."""
    gaps, hi, last = [], None, ""
    for lo, end, name in sorted(ops):
        if hi is not None and lo > hi:
            gaps.append(
                (f"after {short_op_name(last, 0)} before {short_op_name(name, 0)}", (lo - hi) * 1e-9)
            )
        if hi is None or end > hi:
            hi, last = end, name
    gaps.sort(key=lambda g: -g[1])
    return gaps[:top]


def self_times(ops: Sequence[Interval]) -> Dict[str, float]:
    """Seconds by op name, each instant attributed to the innermost op
    running then (a while loop's own time excludes its body's ops)."""
    out: Dict[str, float] = {}
    stack: List[List] = []  # [end, name, self_ns]

    def close(until: float) -> None:
        while stack and stack[-1][0] <= until:
            end, name, own = stack.pop()
            out[name] = out.get(name, 0.0) + own * 1e-9

    for lo, hi, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        close(lo)
        if stack:
            # a child: take its time (clipped to the parent) off the parent
            stack[-1][2] -= max(0.0, min(hi, stack[-1][0]) - lo)
        stack.append([hi, name, hi - lo])
    close(float("inf"))
    return out


def time_by_substring(ops: Sequence[Interval], marks: Sequence[str]) -> Tuple[float, int]:
    """(seconds, events) of the union of every op whose name holds one of
    `marks`; the union, so a kernel's wrapper and its body count once."""
    hit = [(lo, hi) for lo, hi, name in ops if any(m in name for m in marks)]
    return union_ns(hit) * 1e-9, len(hit)


@dataclasses.dataclass
class TraceSummary:
    planes: Dict[str, List[Interval]]
    window_s: float          # extent of device ops, the widest over planes
    busy_s: float            # union busy, averaged over the planes used
    busiest_plane: str       # the plane with the most busy time
    device_ops: List[Tuple[str, float]]   # top self times on busiest plane
    gaps: List[Tuple[str, float]]

    def ops(self, plane: Optional[str] = None) -> List[Interval]:
        return self.planes[plane or self.busiest_plane]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def summarize(planes: Dict[str, List[Interval]], chips: int, top: int = 10) -> TraceSummary:
    used = {k: v for k, v in planes.items() if v}
    if not used:
        raise ValueError("the trace holds no device op: nothing ran on the chip")
    busy = {k: union_ns((lo, hi) for lo, hi, _ in v) * 1e-9 for k, v in used.items()}
    ranked = sorted(busy, key=lambda k: -busy[k])[:chips]
    window = max(
        (max(hi for _, hi, _ in used[k]) - min(lo for lo, _, _ in used[k])) * 1e-9
        for k in ranked
    )
    fullest = ranked[0]
    by_name: Dict[str, float] = {}
    for name, seconds in self_times(used[fullest]).items():
        short = short_op_name(name)
        # a kernel called once per layer is one line, not one per instance
        short = re.sub(r"\.\d+ custom-call ", " custom-call ", short)
        by_name[short] = by_name.get(short, 0.0) + seconds
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return TraceSummary(
        planes=used,
        window_s=window,
        busy_s=sum(busy[k] for k in ranked) / len(ranked),
        busiest_plane=fullest,
        device_ops=[(n, s) for n, s in ops],
        gaps=idle_gaps(used[fullest], top),
    )
