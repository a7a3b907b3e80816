"""Closed-loop plan: a fixed number of clients, each with one request
outstanding and no think time.

ONE sequence of `plan_requests` (prompt length, output length) pairs is drawn
from the cell's own `sizes_seed`; its ORDER is the same under every --seed,
and the run's --seed draws the prompts' token ids only. The plan carries no
due times: ServeSystem.drive submits `clients` requests at the start of the
window and the next of the plan, from one cursor, whenever a handle is done
and the close has not passed. Which requests share an engine step is then
decided by the plan and the engine alone, never by the host's clock; only
how many steps fit before the close varies from run to run. Lengths are
log-normal with the given medians and sigma, clipped (open_loop's)."""

from __future__ import annotations

from typing import List

import numpy as np

from perfbench.traffic.open_loop import _lognormal


def make_schedule(params: dict, seconds: float, seed: int, vocab: int, bos_id: int) -> List[dict]:
    n = int(params["plan_requests"])
    sizes = np.random.default_rng(int(params["sizes_seed"]))
    p = params["prompt_len"]
    o = params["output_len"]
    prompt_lens = _lognormal(sizes, n, p["median"], p["sigma"], p["min"], p["max"])
    output_lens = _lognormal(sizes, n, o["median"], o["sigma"], o["min"], o["max"])
    rs = np.random.default_rng(seed)
    return [
        {
            "prompt": [bos_id] + [int(t) for t in rs.integers(3, vocab, int(plen) - 1)],
            "max_new": int(olen),
        }
        for plen, olen in zip(prompt_lens, output_lens)
    ]
