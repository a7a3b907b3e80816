"""Open-loop request schedule: independent users.

ONE multiset of (gap before the arrival, prompt length, output length) is
drawn from the cell's own `sizes_seed`, so every run of the cell carries the
same work at the same rate; the run's --seed gives a free permutation of it
(the same set, another order) and the prompts' token ids. Which long
requests happen to land together therefore changes from seed to seed, and
moved PR 25's tails by several percent: that spread is what the serving
bounds stand on, and a check pairs parent and change on the same seed. Gaps
are exponential at the cell's fixed rate, rescaled (by the same factor under
every seed: the sum of a permutation is the sum) so that the last request is
due inside the window; lengths are log-normal with the given medians and
sigma, clipped. (A copy of serving/workload.py's idea of a schedule, with
seeded gaps in place of its even spacing.)"""

from __future__ import annotations

import math
from typing import List

import numpy as np


def _lognormal(rs, n, median, sigma, lo, hi):
    return np.clip(np.rint(rs.lognormal(math.log(median), sigma, n)), lo, hi).astype(int)


def make_schedule(params: dict, seconds: float, seed: int, vocab: int, bos_id: int) -> List[dict]:
    rate = float(params["rate_per_s"])
    n = max(1, int(rate * seconds))
    sizes = np.random.default_rng(int(params["sizes_seed"]))
    gaps = sizes.exponential(1.0 / rate, n)
    p = params["prompt_len"]
    o = params["output_len"]
    prompt_lens = _lognormal(sizes, n, p["median"], p["sigma"], p["min"], p["max"])
    output_lens = _lognormal(sizes, n, o["median"], o["sigma"], o["min"], o["max"])
    rs = np.random.default_rng(seed)
    order = rs.permutation(n)
    due = np.cumsum(gaps[order])
    due = due * (seconds * n / (n + 1.0)) / due[-1]
    out = []
    for i in range(n):
        plen = int(prompt_lens[order[i]])
        body = rs.integers(3, vocab, plen - 1)
        out.append({
            "due": float(due[i]),
            "prompt": [bos_id] + [int(t) for t in body],
            "max_new": int(output_lens[order[i]]),
        })
    return out
