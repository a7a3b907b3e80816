"""The generators: the same seed gives the same inputs, another seed the
same sizes in another order."""

import numpy as np

from perfbench_testlib import ROOT  # noqa: F401
from perfbench.traffic import batch_pool, open_loop

CHAT = {"rate_per_s": 3.0, "sizes_seed": 20260930,
        "prompt_len": {"median": 117, "sigma": 0.8, "min": 16, "max": 1024},
        "output_len": {"median": 245, "sigma": 0.8, "min": 16, "max": 1024}}
BIG = 3_000_000_019  # seeds run past 2**31


def sizes(schedule):
    return sorted((len(r["prompt"]), r["max_new"]) for r in schedule)


def test_open_loop_same_seed_same_schedule():
    a = open_loop.make_schedule(CHAT, 30.0, BIG, 50304, 1)
    b = open_loop.make_schedule(CHAT, 30.0, BIG, 50304, 1)
    assert a == b


def test_open_loop_other_seed_same_sizes_other_order():
    a = open_loop.make_schedule(CHAT, 30.0, BIG, 50304, 1)
    b = open_loop.make_schedule(CHAT, 30.0, BIG + 1, 50304, 1)
    assert sizes(a) == sizes(b)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    assert a[0]["prompt"] != b[0]["prompt"]
    gaps = lambda s: np.round(np.diff([0.0] + [r["due"] for r in s]), 9)  # noqa: E731
    assert np.allclose(sorted(gaps(a)), sorted(gaps(b)))
    # ONE multiset of (gap, prompt, output) triples, freely permuted: the
    # same triples under both seeds, and neither order a rotation of the other
    seq = lambda s: [(len(r["prompt"]), r["max_new"], g) for r, g in zip(s, gaps(s))]  # noqa: E731
    sa, sb = seq(a), seq(b)
    assert sorted(sa) == sorted(sb)
    assert not any(sa[k:] + sa[:k] == sb for k in range(len(sa)))
    # the same work at the same rate: the last request is due at the same time
    assert abs(a[-1]["due"] - b[-1]["due"]) < 1e-9


def test_open_loop_keeps_to_the_cells_limits():
    s = open_loop.make_schedule(CHAT, 30.0, 5, 50304, 1)
    assert len(s) == int(CHAT["rate_per_s"] * 30.0)
    assert all(0.0 < r["due"] < 30.0 for r in s)
    assert [r["due"] for r in s] == sorted(r["due"] for r in s)
    p, o = CHAT["prompt_len"], CHAT["output_len"]
    assert all(p["min"] <= len(r["prompt"]) <= p["max"] for r in s)
    assert all(o["min"] <= r["max_new"] <= o["max"] for r in s)
    assert all(r["prompt"][0] == 1 and min(r["prompt"][1:], default=3) >= 3 for r in s)
    lens = sorted(len(r["prompt"]) for r in s)
    assert 80 <= lens[len(lens) // 2] <= 170   # median about 117


def test_batch_pool_is_seeded_and_rows_differ():
    params = {"pool_batches": 3, "slots": [
        {"name": "x", "kind": "normal", "shape": [5], "dtype": "float32"},
        {"name": "ids", "kind": "randint", "low": 2, "high": 99, "shape": [4], "dtype": "int32"},
        {"name": "ids.lengths", "kind": "full", "value": 4, "dtype": "int32"}]}
    a, b = batch_pool.make_pool(params, 6, BIG), batch_pool.make_pool(params, 6, BIG)
    c = batch_pool.make_pool(params, 6, BIG + 1)
    assert all(np.array_equal(x[k], y[k]) for x, y in zip(a, b) for k in x)
    assert not np.array_equal(a[0]["x"], c[0]["x"])
    assert a[0]["x"].shape == (6, 5) and a[0]["ids"].dtype == np.int32
    rows = np.concatenate([p["x"] for p in a])
    assert len({r.tobytes() for r in rows}) == len(rows)
    assert (a[0]["ids.lengths"] == 4).all()
    assert sorted(batch_pool.order(params, 1)) == [0, 1, 2]
