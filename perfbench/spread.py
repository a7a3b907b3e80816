"""Spread of every metric over sets of runs, the way the bounds are derived
(not run by the benchmark):

    python3 -m perfbench.spread <prefix> [<prefix> ...]

Each prefix names one set: the files <prefix>*.out, each holding one run's
standard output. For every metric the median and the spread (distance
between the first and third quartile over the median, arith.iqr_share); for
every number compared the largest reading beside its limit. Given two sets
(the same seeds, run twice), for every metric but setup_s (0.1 by the
contract) ISSUE 29's rule: the bound is the least of five times the wider
spread (the contract's), six times the narrower (so that a quieter machine's
eight times the widest still covers it) and 0.1, rounded up to two
significant digits, never under 0.01; the metric is ADMITTED only if that
bound is at least twice the wider spread and the two medians differ by less
than half of it. Beside it the window the driver's check leaves a bound: too
tight under twice the mean of the two sets' spreads with each set's farthest
run left out, too loose (unless it is 0.01) over eight times the wider
spread of all runs."""

from __future__ import annotations

import glob
import json
import statistics
import sys

from perfbench import arith


def last_line(path: str):
    for line in reversed(open(path).read().strip().splitlines()):
        if line.startswith("{"):
            return json.loads(line)
    return None


def rule(first, second) -> str:
    """(median, spread) of two sets of the same seeds -> the rule's terms,
    the bound it gives and its verdict, in one line."""
    (m1, s1), (m2, s2) = first, second
    wider, narrower = max(s1, s2), min(s1, s2)
    bound = max(0.01, arith.ceil_sig(min(5 * wider, 6 * narrower, 0.1), 2))
    moved = m2 / m1 - 1
    admit = bound >= 2 * wider and abs(moved) < bound / 2
    return (f"five times the wider spread {5 * wider:.4f}, six times the narrower "
            f"{6 * narrower:.4f}, twice the wider {2 * wider:.4f}; second median "
            f"{100 * moved:+.3f}% of the first; bound {bound:g}: "
            + ("admit" if admit else "refuse"))


def drivers_window(trimmed, wider: float) -> str:
    """What the driver's check would accept on these two sets: a bound from
    twice the mean trimmed spread up to eight times the wider spread."""
    return (f"on these runs the check takes a bound from {2 * statistics.fmean(trimmed):.4f} "
            f"(twice the mean trimmed spread) to {max(0.01, 8 * wider):.4f} (eight times the wider)")


def main(prefixes) -> int:
    sets, trims = {}, {}
    for prefix in prefixes:
        rows = [r for r in map(last_line, sorted(glob.glob(prefix + "*.out"))) if r]
        print(f"{prefix}: {len(rows)} runs; correct {[r['correct'] for r in rows]}")
        for name in sorted({k for r in rows for k in r["metrics"]}):
            vals = [r["metrics"][name]["value"] for r in rows if name in r["metrics"]]
            if len(vals) >= 2:
                sets.setdefault(name, []).append(
                    (statistics.median(vals), arith.iqr_share(vals)))
                trimmed = arith.trimmed_iqr_share(vals) if len(vals) >= 3 else float("nan")
                trims.setdefault(name, []).append(trimmed)
                print(f"  {name}: median {statistics.median(vals):.6g} spread "
                      f"{100 * arith.iqr_share(vals):.3f}% (farthest run left out "
                      f"{100 * trimmed:.3f}%) min {min(vals):.6g} "
                      f"max {max(vals):.6g} first {vals[0]:.6g}")
        for name in sorted({k for r in rows for k in r["checks"]}):
            vals = [r["checks"][name]["value"] for r in rows if name in r["checks"]]
            print(f"  check {name}: max {max(vals):.6g} limit {rows[0]['checks'][name]['limit']}")
        print("  memory_peak_bytes", sorted({r["device"]["memory_peak_bytes"] for r in rows}))
    for name, pair in sorted(sets.items()):
        if len(pair) != 2:
            continue
        if name == "setup_s":
            print(f"setup_s: second median {100 * (pair[1][0] / pair[0][0] - 1):+.3f}% of the "
                  "first; bound 0.1 by the contract")
        else:
            print(f"{name}: " + rule(*pair))
            print(f"{name}: " + drivers_window(trims[name], max(s for _, s in pair)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
