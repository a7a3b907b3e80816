"""Percentile and rate arithmetic on hand-made samples."""

import pytest

from perfbench_testlib import ROOT  # noqa: F401
from perfbench import arith


def test_percentile_is_nearest_rank():
    sample = list(range(1, 101))
    assert arith.percentile(sample, 95) == 95
    assert arith.percentile(sample, 99) == 99
    assert arith.percentile(sample, 50) == 50
    assert arith.percentile([7.0], 99) == 7.0
    assert arith.percentile([3, 1, 2], 100) == 3
    with pytest.raises(ValueError):
        arith.percentile([], 50)


def test_rate_is_over_all_work_and_the_whole_window():
    assert arith.rate(1000, 10.0) == 100.0
    assert arith.rate(4000, 10.0, chips=4) == 100.0
    with pytest.raises(ValueError):
        arith.rate(1, 0.0)


def test_a_stall_in_the_window_moves_throughput_and_the_tails():
    steady = [0.1 * i for i in range(1, 101)]            # a step every 100 ms
    stalled = [t if t < 5.0 else t + 2.0 for t in steady]  # a 2 s stall at 5 s
    assert arith.rate(100 * 256, stalled[-1]) < 0.85 * arith.rate(100 * 256, steady[-1])
    gaps_steady, gaps_stalled = arith.token_gaps(steady), arith.token_gaps(stalled)
    assert arith.percentile(gaps_steady, 99) == pytest.approx(0.1)
    assert arith.percentile(gaps_stalled, 100) == pytest.approx(2.1)
    # requests due during the stall wait for its end: timed from DUE time
    due = [0.1 * i for i in range(100)]
    first_steady = [d + 0.05 for d in due]
    first_stalled = [max(f, 7.0) if 5.0 <= d < 7.0 else f for d, f in zip(due, first_steady)]
    p95 = arith.percentile(arith.ttft_samples(due, first_stalled, 60.0), 95)
    assert p95 > 1.0 > arith.percentile(arith.ttft_samples(due, first_steady, 60.0), 95)


def test_a_request_with_no_first_token_counts_as_the_worst():
    samples = arith.ttft_samples([0.0, 1.0, 2.0], [0.5, None, 2.25], worst=90.0)
    assert samples == [0.5, 90.0, 0.25]
    assert arith.percentile(samples, 95) == 90.0


def two_plateaus(n_decode, n_behind_512, n_behind_1024):
    """Gaps as the served decoder makes them: a decode step, a step behind a
    512-bucket prefill, a step behind a 1024-bucket one (seconds)."""
    return [0.019] * n_decode + [0.068] * n_behind_512 + [0.106] * n_behind_1024


def test_tail_mean_is_the_mean_of_the_largest_share():
    sample = [float(v) for v in range(1, 201)]
    assert arith.tail_mean(sample, 0.10) == pytest.approx(sum(range(181, 201)) / 20)
    assert arith.tail_mean(sample[::-1], 0.10) == arith.tail_mean(sample, 0.10)
    # ceil(share * n) values: 10% of 101 is the 11 largest
    assert arith.tail_mean([float(v) for v in range(101)], 0.10) == pytest.approx(95.0)


def test_one_gap_moving_flips_the_percentile_and_barely_moves_the_tail_mean():
    # 10,000 gaps: the nearest-rank 99th percentile is the 101st-largest. With
    # 101 gaps on the 106 ms plateau it reads 106; move ONE down and it reads 68.
    before = two_plateaus(9699, 200, 101)
    after = two_plateaus(9699, 201, 100)
    p_before, p_after = arith.percentile(before, 99), arith.percentile(after, 99)
    assert (p_before, p_after) == (0.106, 0.068)
    assert abs(p_after - p_before) / p_before > 0.35
    t_before, t_after = arith.tail_mean(before, 0.01), arith.tail_mean(after, 0.01)
    assert t_before == pytest.approx(0.106)
    assert abs(t_after - t_before) / t_before < 0.01
    # and each further gap that leaves the plateau moves it by that gap's
    # weight in the tail, 0.36% here: smoothly, where the percentile jumped
    moved = [arith.tail_mean(two_plateaus(9699, 201 + k, 100 - k), 0.01) for k in range(1, 4)]
    steps = [a - b for a, b in zip([t_after] + moved, moved)]
    assert all(s == pytest.approx((0.106 - 0.068) / 100) for s in steps)
    assert steps[0] / t_before < 0.004


def test_failed_requests_count_at_the_worst_in_the_ttft_tail():
    due = [0.1 * i for i in range(100)]
    first = [d + 0.05 for d in due]
    served = arith.tail_mean(arith.ttft_samples(due, first, worst=90.0), 0.10)
    assert served == pytest.approx(0.05)
    first[40] = None   # shed, failed or never answered
    one_lost = arith.tail_mean(arith.ttft_samples(due, first, worst=90.0), 0.10)
    assert one_lost == pytest.approx((90.0 + 9 * 0.05) / 10)


@pytest.mark.parametrize("n,share,is_number", [
    (99, 0.10, True), (91, 0.10, True), (90, 0.10, False), (12, 0.10, False),
    (0, 0.10, False), (901, 0.01, True), (900, 0.01, False),
])
def test_a_tail_of_fewer_than_ten_samples_is_not_a_number(n, share, is_number):
    value = arith.tail_mean([1.0] * n, share)
    assert (value == value) is is_number
    if is_number:
        assert value == 1.0


def test_spread_is_the_contracts_quartile_distance_over_the_median():
    vals = [100.0, 101.0, 99.0, 100.5, 99.5, 100.0]
    assert arith.iqr_share(vals) == pytest.approx(
        (100.625 - 99.375) / 100.0, rel=1e-6)


def test_gradient_difference_sees_zero_mean_noise_that_the_gap_of_norms_cannot():
    import numpy as np

    from perfbench.reference import compare

    rs = np.random.default_rng(0)
    g = {"big": rs.standard_normal((256, 256)).astype(np.float32),
         "small": 1e-3 * rs.standard_normal(16).astype(np.float32)}
    noisy = {k: v * (1 + 0.1 * rs.standard_normal(v.shape).astype(np.float32)) for k, v in g.items()}
    norms = lambda t: compare.to_floats(compare.leaf_norms(t))  # noqa: E731
    ref = {"grad": norms(g), "grad_full": g}
    prog = {"grad": norms(noisy), "grad_full": noisy}
    gap, _ = compare.worst_leaf_gap(prog["grad"], ref["grad"])
    diff, leaf = compare.grad_diff(prog, ref)
    assert gap < 0.01                       # 10% zero-mean noise cancels in the norm
    assert diff == pytest.approx(0.1, rel=0.1) and leaf == "big"
    assert compare.grad_diff(ref, ref)[0] == 0.0


def test_profiler_starts_its_lead_before_the_part_that_is_kept():
    from perfbench import harness

    p = harness.Profiler("/nonexistent/trace")
    assert not p.due(now=44.9, t_end=50.0)
    assert p.due(now=45.1, t_end=50.0)        # 50 - KEEP_S - LEAD_S
    assert not harness.Profiler(None).due(now=49.0, t_end=50.0)


@pytest.mark.parametrize("second,verdict", [
    # the same spread 1% higher: five times 1.25% is 0.0625, rounded up to 0.063
    ([101.0, 102.0, 100.0, 101.5, 100.5, 101.0],
     "five times the wider spread 0.0625, six times the narrower 0.0743, twice the wider "
     "0.0250; second median +1.000% of the first; bound 0.063: admit"),
    # a narrower second set: six times ITS spread is the bound, under twice the wider
    ([100.0, 100.1, 99.9, 100.05, 99.95, 100.0],
     "five times the wider spread 0.0625, six times the narrower 0.0075, twice the wider "
     "0.0250; second median +0.000% of the first; bound 0.01: refuse"),
    # the medians apart by more than half the bound
    ([104.0, 105.0, 103.0, 104.5, 103.5, 104.0],
     "five times the wider spread 0.0625, six times the narrower 0.0721, twice the wider "
     "0.0250; second median +4.000% of the first; bound 0.063: refuse"),
])
def test_spread_tool_gives_the_rule_two_sets_of_the_same_seeds_stand_on(tmp_path, capsys, second, verdict):
    import json

    from perfbench import spread

    def write(prefix, values):
        for i, v in enumerate(values):
            line = {"correct": True, "metrics": {"serve_throughput": {"value": v, "unit": "tokens/s/chip"},
                                                 "setup_s": {"value": 20.0 + i, "unit": "s"}},
                    "checks": {"token_logit_gap": {"value": 0.5 + 0.01 * i, "limit": 3.0}},
                    "device": {"memory_peak_bytes": 7}}
            (tmp_path / f"{prefix}{i}.out").write_text("info: x\n" + json.dumps(line) + "\n")

    write("a.", [100.0, 101.0, 99.0, 100.5, 99.5, 100.0])      # spread 1.25%
    write("b.", second)
    assert spread.main([str(tmp_path / "a."), str(tmp_path / "b.")]) == 0
    out = capsys.readouterr().out
    assert "serve_throughput: median 100 spread 1.250%" in out
    assert "check token_logit_gap: max 0.55 limit 3.0" in out
    assert "serve_throughput: " + verdict in out.splitlines()
    # the window the driver's check leaves: set a's trimmed spread is 1% (the
    # run at 101 or 99 left out), and eight times the wider spread is 0.1
    window = next(ln for ln in out.splitlines() if "the check takes a bound from" in ln)
    assert window.startswith("serve_throughput: on these runs") and "to 0.1000 (eight" in window
    assert "setup_s: second median +0.000% of the first; bound 0.1 by the contract" in out.splitlines()


@pytest.mark.parametrize("value,up", [(0.0625, 0.063), (0.0234, 0.024), (0.05, 0.05),
                                      (0.00123, 0.0013), (0.1, 0.1), (0.0301, 0.031)])
def test_a_bound_is_rounded_up_to_two_significant_digits(value, up):
    assert arith.ceil_sig(value, 2) == pytest.approx(up, rel=1e-9)


@pytest.mark.parametrize("stamps,counted", [
    ([9.0, 9.5, 10.0], 3),            # a token stamped AT the close counts
    ([9.0, 9.5, 10.0, 10.001, 11.0], 3),   # a request that straddles it: the tokens it had
    ([10.5, 11.0], 0),                # first token after the close: none
    ([], 0),
])
def test_serve_throughput_counts_tokens_stamped_by_the_close_and_none_after(stamps, counted):
    from types import SimpleNamespace

    from perfbench.serving import ServeSystem

    assert arith.count_until(stamps, 10.0) == counted
    # and through the reduction: one request beside one that finished early
    cell = SimpleNamespace(config={}, workload={}, chips=1)
    done = SimpleNamespace(done=True, tokens=[5, 6])
    recs = [{"due": 0.0, "prompt": [1, 2, 3], "handle": done, "stamps": [1.0, 2.0]},
            {"due": 0.0, "prompt": [1, 2], "handle": SimpleNamespace(done=True, tokens=stamps),
             "stamps": stamps}]
    run = {"recs": recs, "t0": 0.0, "t1": 12.0, "t_end": 10.0, "step_spans": [], "lateness": [],
           "backlog_mid": 0, "backlog_end": 0, "waiting_mid": 0, "waiting_end": 0,
           "traced_contexts": []}
    m = ServeSystem(cell, 1).reduce(run)
    assert m["window_tokens"] == 2 + counted
    assert m["serve_throughput"] == pytest.approx((2 + counted) / 10.0)
    assert m["finished_in_window"] == 1 + (bool(stamps) and stamps[-1] <= 10.0)


@pytest.mark.parametrize("values,trimmed", [
    ([100.0, 101.0, 99.0, 100.5, 99.5, 100.0], arith.iqr_share([100.0, 99.0, 100.5, 99.5, 100.0])),
    ([100.0, 100.0, 100.0, 100.0, 100.0, 140.0], 0.0),     # one far-off run does no harm
    ([100.0, 100.0, 100.0, 100.0, 140.0, 140.0], arith.iqr_share([100.0, 100.0, 100.0, 100.0, 140.0])),
])
def test_the_trimmed_spread_leaves_out_the_run_farthest_from_the_median(values, trimmed):
    assert arith.trimmed_iqr_share(values) == pytest.approx(trimmed)
    assert arith.trimmed_iqr_share(values) <= arith.iqr_share(values)
