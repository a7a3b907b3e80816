"""The reduction from a profiler trace to busy time, idle share and kernel
time by name: on a hand-made trace whose answers are known, and on a small trace recorded on the chip
(data/tiny_tpu.xplane.pb; data/record_tiny_trace.py says how)."""

import os

import pytest

from perfbench_testlib import HERE
from perfbench import trace as tr

RECORDED = os.path.join(HERE, "data", "tiny_tpu.xplane.pb")

# start_ns, end_ns, name: a while loop holding two fusions, a kernel twice, an
# all-reduce half hidden under a fusion, and idle gaps
OPS = [
    (0.0, 100.0, "while.1"),
    (10.0, 40.0, "fusion.1"),
    (50.0, 90.0, "fusion.2"),
    (150.0, 170.0, "paged_attention_decode"),
    (200.0, 220.0, "paged_attention_decode"),
    (300.0, 340.0, "all-reduce.7"),
    (320.0, 360.0, "fusion.3"),
]


def test_union_counts_overlaps_once():
    assert tr.union_ns([(0, 10), (5, 20), (30, 40), (40, 45), (50, 50)]) == 35


def test_last_seconds_keeps_the_end_and_clips_what_straddles_the_cut():
    kept = tr.last_seconds(sorted(OPS), 160e-9)          # the cut falls at 200
    assert kept == [(200.0, 220.0, "paged_attention_decode"),
                    (300.0, 340.0, "all-reduce.7"), (320.0, 360.0, "fusion.3")]
    kept = tr.last_seconds(sorted(OPS), 280e-9)          # at 80: inside while.1 and fusion.2
    assert kept[:2] == [(80.0, 100.0, "while.1"), (80.0, 90.0, "fusion.2")]
    assert tr.last_seconds([], 1.0) == []
    # a profiler's start-up stall before the kept part does not count as idle
    stalled = [(0.0, 10.0, "a"), (5e9, 5e9 + 100.0, "a"), (5e9 + 100.0, 5e9 + 200.0, "a")]
    s = tr.summarize({"/device:TPU:0": tr.last_seconds(stalled, 200e-9)}, chips=1)
    assert s.idle_share == pytest.approx(0.0)


def test_summary_of_a_hand_made_trace():
    s = tr.summarize({"/device:TPU:0": sorted(OPS)}, chips=1)
    assert s.window_s == pytest.approx(360e-9)
    assert s.busy_s == pytest.approx((100 + 20 + 20 + 60) * 1e-9)
    assert s.idle_share == pytest.approx(1 - 200 / 360)
    own = dict(s.device_ops)
    assert own["while.1"] == pytest.approx(30e-9)      # its body's ops taken off
    assert own["fusion.2"] == pytest.approx(40e-9)
    assert own["paged_attention_decode"] == pytest.approx(40e-9)
    assert s.gaps[0][1] == pytest.approx(80e-9) and "all-reduce.7" in s.gaps[0][0]


def test_breakdown_names_are_short_and_kernels_merge_over_layers():
    line = ("%fusion.7 = (f32[256]{0:T(256)S(1)}, bf16[8,128]{1,0:T(8,128)(2,1)}) fusion(bf16[8,128]"
            "{1,0:T(8,128)(2,1)} %p.1), kind=kOutput, calls=%fused_computation.69.clone")
    assert tr.short_op_name(line) == "fusion.7 fusion (f32[256]{0:T(256)S(1)}, bf16[8,128]{1,0:T(8,128"
    assert tr.short_op_name("while.1") == "while.1"
    call = '%paged_attention_decode.{} = f32[32,1,2048]{{2,1,0}} custom-call(f32[32,16,2048]{{2,1,0}} %q), custom_call_target="tpu_custom_call"'
    ops = [(0.0, 10.0, call.format(3)), (20.0, 30.0, call.format(4)), (40.0, 45.0, "%copy.1 = f32[8]{0} copy(f32[8]{0} %x)")]
    s = tr.summarize({"/device:TPU:0": ops}, chips=1)
    assert s.device_ops[0] == ("paged_attention_decode custom-call f32[32,1,2048]{2,1,0}", pytest.approx(20e-9))
    assert s.gaps[0][0] == "after paged_attention_decode.3 custom-call before paged_attention_decode.4 custom-call"


def test_kernel_time_by_name():
    seconds, events = tr.time_by_substring(OPS, ("paged_attention_decode",))
    assert (seconds, events) == (pytest.approx(40e-9), 2)
    assert tr.time_by_substring(OPS, ("gru_seq_fwd",)) == (0.0, 0)
    # a kernel's wrapper and its body count once
    assert tr.time_by_substring(OPS, ("while.1", "fusion.1")) == (pytest.approx(100e-9), 2)


def test_four_chips_average_busy_over_the_chips_used():
    planes = {"/device:TPU:0": [(0.0, 100.0, "a")], "/device:TPU:1": [(0.0, 50.0, "a")],
              "/device:TPU:2": []}
    s = tr.summarize(planes, chips=2)
    assert s.busy_s == pytest.approx(75e-9) and s.busiest_plane == "/device:TPU:0"
    with pytest.raises(ValueError):
        tr.summarize({"/device:TPU:0": []}, chips=1)


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_chip_trace_reduces_to_sane_numbers():
    planes = tr.load_device_ops(RECORDED)
    assert list(planes) and all(k.startswith("/device:TPU:") for k in planes)
    s = tr.summarize(planes, chips=1)
    assert 0.0 < s.busy_s < s.window_s
    assert 0.0 < s.idle_share < 1.0          # the loop sleeps between calls
    assert s.device_ops and s.device_ops[0][1] > 0
    assert sum(t for _, t in tr.self_times(s.ops()).items()) == pytest.approx(s.busy_s, rel=1e-6)
    assert len(s.gaps) >= 4                   # five calls, four sleeps between
