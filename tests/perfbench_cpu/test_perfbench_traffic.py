"""The generators: the same seed gives the same inputs, another seed the
same sizes in another order (open loop) or in the same order (closed loop);
and the loop that drives a closed-loop plan."""

import time

import numpy as np
import pytest

from perfbench_testlib import SATURATED_CELL, extended_base, extended_benchmark
from perfbench.traffic import batch_pool, closed_loop, open_loop

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


SATURATED = dict(CHAT, clients=48, plan_requests=512)


def test_closed_loop_plan_is_the_seeds_and_other_seeds_differ_in_token_ids_only():
    a = closed_loop.make_schedule(SATURATED, 50.0, BIG, 50304, 1)
    assert a == closed_loop.make_schedule(SATURATED, 50.0, BIG, 50304, 1)
    b = closed_loop.make_schedule(SATURATED, 50.0, BIG + 1, 50304, 1)
    lengths = lambda s: [(len(r["prompt"]), r["max_new"]) for r in s]  # noqa: E731
    assert len(a) == 512 and lengths(a) == lengths(b)        # lengths AND order
    assert all(x["prompt"] != y["prompt"] for x, y in zip(a, b))
    assert all("due" not in r and r["prompt"][0] == 1 and min(r["prompt"][1:]) >= 3 for r in a)
    # the plan does not depend on the window's length either
    assert a == closed_loop.make_schedule(SATURATED, 5.0, BIG, 50304, 1)
    p, o = CHAT["prompt_len"], CHAT["output_len"]
    assert all(p["min"] <= n <= p["max"] and o["min"] <= m <= o["max"] for n, m in lengths(a))
    # the means the cell's `why` gives: 161 in, 338 out (Kwon et al. 2023)
    assert 140 <= np.mean([n for n, _ in lengths(a)]) <= 180
    assert 300 <= np.mean([m for _, m in lengths(a)]) <= 370


@pytest.fixture(scope="module")
def saturated(tmp_path_factory):
    """The tiny closed-loop cell's system, set up once; each drive leaves
    the session idle."""
    from perfbench import harness, registry

    base = extended_base(tmp_path_factory.mktemp("pbdata"))
    cell = harness.load_cell(SATURATED_CELL, base=base, benchmark=extended_benchmark())
    system = registry.load_module("builders", cell.config["builder"]).build(cell, BIG)
    system.setup(say=lambda *_: None)
    plan = closed_loop.make_schedule(
        cell.workload["params"], 1.0, BIG, int(cell.config["vocab_size"]), system.session.cfg.bos_id)
    return system, plan


def outstanding(recs, step):
    """Requests submitted before engine step `step` ran and not finished by
    an earlier one."""
    return sum(1 for r in recs
               if r["step"] <= step and (r["done_step"] is None or r["done_step"] >= step))


def test_drive_keeps_the_clients_outstanding_until_the_close_and_submits_none_after(saturated):
    system, plan = saturated
    clients = system.wl["params"]["clients"]
    run = system.drive(plan, 0.6)
    recs = run["recs"]
    assert clients < len(recs) < len(plan), "as much of the plan as lead-in and window took"
    assert [r["prompt"] for r in recs] == [r["prompt"] for r in plan[: len(recs)]]
    assert [r["step"] for r in recs[:clients]] == [0] * clients
    assert all(run["origin"] + r["due"] < run["t_end"] for r in recs), "none after the close"
    last = max(r["step"] for r in recs)
    assert all(outstanding(recs, k) == clients for k in range(last + 1))
    # in flight at the close: the clients, less what the last step before it finished
    assert clients - system.cfg["session"]["max_slots"] <= run["backlog_end"] <= clients
    assert run["waiting_end"] > 0, "more clients than slots: a queue remains"
    # time to first token and the queue's wait count from the submit
    assert all(r["stamps"][0] >= run["origin"] + r["due"] for r in recs if r["stamps"])
    m = system.reduce(run)
    assert m["failed"] == 0
    assert 0 < m["window_tokens"] < m["output_tokens"]        # the drain's tokens are not counted
    assert m["serve_throughput"] == pytest.approx(m["window_tokens"] / 0.6)
    assert m["tpot_mean_ms"] > 0 and m["ttft_p50_ms"] > 0


def test_at_the_close_requests_with_no_first_token_are_cancelled_and_the_rest_drained(saturated):
    system, plan = saturated
    run = system.drive(plan, 0.5)
    recs = run["recs"]
    gone = [r for r in recs if r["cancelled"]]
    kept = [r for r in recs if not r["cancelled"]]
    # (a first token that came in the step that straddled the close is kept)
    assert 0 < len(gone) <= run["waiting_end"]
    assert all(not r["stamps"] and r["handle"].done and not r["handle"].tokens for r in gone)
    # every other request is answered: what was decoding at the close is drained
    assert all(r["handle"].done and r["handle"].tokens for r in kept)
    assert any(r["stamps"][0] <= run["t_end"] < r["stamps"][-1] for r in kept), "a drained one"
    m = system.reduce(run)
    assert m["attempted"] == len(kept) and m["cancelled_at_close"] == len(gone) and m["failed"] == 0
    # a request cancelled by its client is not one the system never answered,
    # and is not in the sample
    system.release()
    assert system.verify(say=lambda *_: None)["never_answered"] == (0.0, 0.0)
    assert all(not r["cancelled"] for r in system.sample())
    system.setup(say=lambda *_: None)  # the fixture's next test drives again


def test_the_window_opens_behind_the_lead_in_and_counts_nothing_of_it(saturated):
    system, plan = saturated
    lead_in = system.wl["params"]["lead_in_finished"]
    run = system.drive(plan, 0.5)
    t0, t_end, recs = run["t0"], run["t_end"], run["recs"]
    assert t_end - t0 == pytest.approx(0.5) and t0 > run["origin"]
    # the window opens at the end of the step in which the lead_in-th request
    # finished: at least that many are done by it, fewer than a step's more
    done_by = sum(1 for r in recs if r["stamps"] and r["handle"].done and r["stamps"][-1] <= t0)
    assert lead_in <= done_by < lead_in + system.cfg["session"]["max_slots"]
    opening = max(k for k, s in enumerate(run["step_spans"]) if s[3] <= t0)
    assert run["step_spans"][opening][3] == t0
    assert sum(1 for r in recs if r["done_step"] is not None and r["done_step"] < opening) < lead_in
    m = system.reduce(run)
    assert m["lead_in_s"] == pytest.approx(t0 - run["origin"])
    assert m["steps"] == sum(1 for s in run["step_spans"] if t0 < s[3] <= t_end) < len(run["step_spans"])
    assert m["window_tokens"] == sum(1 for r in recs for s in r["stamps"] if t0 < s <= t_end)
    assert sum(m["tokens_by_fifth"]) == m["window_tokens"]
    # the lead-in's requests are not the window's: none of them is sampled
    assert all(r["stamps"][-1] > t0 for r in system.sample())


def test_the_sequence_of_submissions_does_not_depend_on_the_clock(saturated):
    """Two drives of one plan, the second on a clock that runs at a third of
    the speed (so its window holds three times the steps): the same prompts
    in the same order, each behind the same engine step, and the window
    opens behind the same step on the same engine state."""
    system, plan = saturated
    quick, slowed = system.drive(plan, 0.4), system.drive(plan, 0.4, clock=lambda: time.monotonic() / 3.0)
    fast, slow = quick["recs"], slowed["recs"]
    behind = lambda recs: [(r["step"], r["prompt"], r["max_new"]) for r in recs]  # noqa: E731
    assert len(slow) > len(fast) > system.wl["params"]["clients"]
    assert behind(slow)[: len(fast)] == behind(fast)
    served = lambda recs: [r["handle"].tokens for r in recs if not r["cancelled"]]  # noqa: E731
    both = [i for i, r in enumerate(fast) if not r["cancelled"]]
    assert [slow[i]["handle"].tokens for i in both] == served(fast)
    opening = lambda run: sum(1 for s in run["step_spans"] if s[3] <= run["t0"])  # noqa: E731
    assert opening(quick) == opening(slowed) > 0


def test_a_plan_that_runs_out_before_the_close_ends_the_run(saturated):
    system, plan = saturated
    with pytest.raises(RuntimeError, match="ran out"):
        system.drive(plan[:8], 30.0)
    system.session.run_until_idle()


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
