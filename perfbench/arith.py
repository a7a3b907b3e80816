"""Metric arithmetic on plain numbers: percentiles, tail means, rates, spreads. No jax."""

from __future__ import annotations

import math
import statistics
from typing import Iterable, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (q in 0..100) of a non-empty sample: the
    smallest value with at least q% of the sample at or below it. No
    interpolation, so a reported tail is a latency some request really had."""
    if not values:
        raise ValueError("percentile of an empty sample")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return float(ordered[rank - 1])


TAIL_MIN_SAMPLES = 10


def tail_mean(values: Sequence[float], share: float) -> float:
    """Mean of the largest `share` (0..1) of a sample: the ceil(share * n)
    largest values. Where a percentile reads ONE order statistic and jumps
    when a plateau's share crosses the cut, this moves by that one value's
    weight in the tail. A tail of fewer than TAIL_MIN_SAMPLES values is no
    tail: NaN, never a number."""
    k = math.ceil(share * len(values))
    if k < TAIL_MIN_SAMPLES:
        return float("nan")
    return float(statistics.fmean(sorted(values)[-k:]))


def rate(items: float, seconds: float, chips: int = 1) -> float:
    """Items of ALL work completed in the window over the WHOLE window."""
    if seconds <= 0:
        raise ValueError("a rate needs a window longer than 0 s")
    return items / seconds / chips


def count_until(stamps: Sequence[float], t_end: float) -> int:
    """How many of the stamps lie at or before t_end: the work a window that
    closes at t_end completed."""
    return sum(1 for s in stamps if s <= t_end)


def token_gaps(stamps: Sequence[float]) -> List[float]:
    """Gaps between consecutive token times of one request."""
    return [b - a for a, b in zip(stamps, stamps[1:])]


def ttft_samples(
    due: Sequence[float], first: Sequence[Optional[float]], worst: float
) -> List[float]:
    """First-token time minus DUE time per request; a request with no first
    token (failed, shed, unfinished) counts as `worst`."""
    return [worst if f is None else f - d for d, f in zip(due, first)]


def ceil_sig(value: float, digits: int) -> float:
    """`value` (above 0) rounded UP to `digits` significant digits."""
    unit = 10.0 ** (math.floor(math.log10(value)) - digits + 1)
    return round(math.ceil(value / unit - 1e-9) * unit, 12)


def iqr_share(values: Iterable[float]) -> float:
    """The contract's spread: distance between the first and third quartile
    (statistics.quantiles, n=4) as a share of the median."""
    vals = list(values)
    q1, _, q3 = statistics.quantiles(vals, n=4)
    return (q3 - q1) / statistics.median(vals)


def trimmed_iqr_share(values: Iterable[float]) -> float:
    """The spread with the run farthest from the median left out: what the
    driver's check reads for tightness (a bound is too tight where the mean
    of its two sets' trimmed spreads is over half of it)."""
    vals = list(values)
    mid = statistics.median(vals)
    vals.remove(max(vals, key=lambda v: abs(v - mid)))
    return iqr_share(vals)
