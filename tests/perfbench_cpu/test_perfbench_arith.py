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
