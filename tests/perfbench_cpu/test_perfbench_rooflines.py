"""Operation and byte counts on shapes worked out by hand."""

import pytest

from perfbench_testlib import ROOT  # noqa: F401
from perfbench import rooflines
from perfbench.peaks import peaks_for

V5E = peaks_for("TPU v5 lite")


def test_resnet50_is_three_forward_passes():
    assert rooflines.resnet50_train_flops_per_image(224) == pytest.approx(3 * 8.18e9)
    assert rooflines.resnet50_train_flops_per_image(112) == pytest.approx(3 * 8.18e9 / 4)


def test_seq2seq_matches_bench_py_formula():
    e = h = 512
    enc = 2 * 3 * (e * h + h * h) * 2
    dec = 3 * ((e + 2 * h) * h + h * h) * 2
    attn = 50 * (2 * h) * 2
    out = h * 30000 * 2
    want = 3 * (enc + dec + attn + out)
    got = rooflines.seq2seq_train_flops_per_token(512, 512, 30000, 50, 50)
    assert got == pytest.approx(want)
    assert 0.6 < 3 * out / got < 0.8     # the projection is about 70% of it


def test_lm_flops_per_token():
    d, layers, vocab = 2048, 24, 50304
    assert rooflines.lm_params_touched_per_token(d, layers, vocab) == 12 * d * d * layers + d * vocab
    assert rooflines.lm_flops_per_token(d, layers, vocab) == pytest.approx(2.62e9, rel=0.01)


def test_paged_attention_reads_whole_pages_of_its_own_context():
    work = rooflines.paged_attention_decode_work([17, 16, 0], n_heads=2, head_dim=4, page_size=16)
    kd = 8
    assert work["bytes"] == 4 * (2 * 3 * 16 * kd + 2 * 3 * kd)   # 2 + 1 + 0 pages, K and V
    assert work["flops"] == 2 * 2 * (17 + 16) * kd
    t, bound = rooflines.least_time(work["flops"], work["bytes"], V5E)
    assert bound == "bytes" and t == pytest.approx(work["bytes"] / 819e9)


def test_gru_seq_work_and_least_time():
    fwd = rooflines.gru_seq_work(t=50, b=512, h=512, backward=False, dtype_bytes=2)
    assert fwd["flops"] == 2 * 512 * (512 * 1024 + 512 * 512) * 50
    bwd = rooflines.gru_seq_work(t=50, b=512, h=512, backward=True, dtype_bytes=2)
    assert bwd["flops"] == 3 * fwd["flops"] and bwd["bytes"] > fwd["bytes"]
    assert rooflines.least_time(197e12, 1.0, V5E) == (1.0, "flops")
