"""Spread of every metric over sets of runs, the way the bounds are derived
(not run by the benchmark):

    python3 -m perfbench.spread <prefix> [<prefix> ...]

Each prefix names one set: the files <prefix>*.out, each holding one run's
standard output. For every metric the median and the spread (distance
between the first and third quartile over the median, arith.iqr_share); for
every number compared the largest reading beside its limit. Given two sets
(the same seeds, run twice), for every metric the bound the contract's rule
gives: five times the wider spread, never under 1%, beside the share by
which the second set's median differs from the first's."""

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


def main(prefixes) -> int:
    sets = {}
    for prefix in prefixes:
        rows = [r for r in map(last_line, sorted(glob.glob(prefix + "*.out"))) if r]
        print(f"{prefix}: {len(rows)} runs; correct {[r['correct'] for r in rows]}")
        for name in sorted({k for r in rows for k in r["metrics"]}):
            vals = [r["metrics"][name]["value"] for r in rows if name in r["metrics"]]
            if len(vals) >= 2:
                sets.setdefault(name, []).append(
                    (statistics.median(vals), arith.iqr_share(vals)))
                print(f"  {name}: median {statistics.median(vals):.6g} spread "
                      f"{100 * arith.iqr_share(vals):.3f}% min {min(vals):.6g} "
                      f"max {max(vals):.6g} first {vals[0]:.6g}")
        for name in sorted({k for r in rows for k in r["checks"]}):
            vals = [r["checks"][name]["value"] for r in rows if name in r["checks"]]
            print(f"  check {name}: max {max(vals):.6g} limit {rows[0]['checks'][name]['limit']}")
        print("  memory_peak_bytes", sorted({r["device"]["memory_peak_bytes"] for r in rows}))
    for name, pair in sorted(sets.items()):
        if len(pair) == 2:
            (m1, s1), (m2, s2) = pair
            wider = max(s1, s2)
            print(f"{name}: wider spread {100 * wider:.3f}%, five times it "
                  f"{max(0.01, 5 * wider):.4f}, eight times {max(0.01, 8 * wider):.4f}; "
                  f"second median {100 * (m2 / m1 - 1):+.3f}% of the first")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
