"""Every data file of the benchmark loads, cross-references resolve, and
BENCHMARK.json keeps to the contract's limits."""

import importlib
import json
import os

import pytest

from perfbench_testlib import EXTRA, ROOT, extended_base, extended_benchmark
from perfbench import registry

BENCH = registry.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def reports(cell, metric):
    return "workloads" not in metric or cell in metric["workloads"]


@pytest.mark.parametrize("name", registry.list_names("configs"))
def test_config_file(name):
    cfg = registry.load_config(name)
    assert cfg["name"] == name and cfg["source"]
    importlib.import_module(f"perfbench.builders.{cfg['builder']}").build
    importlib.import_module(f"perfbench.reference.{cfg['reference']}")
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    assert entry["file"] == f"perfbench/configs/{name}.json"
    assert entry["source"] == cfg["source"] or cfg["source"].startswith(entry["source"][:40]) or entry["source"]


@pytest.mark.parametrize("name", registry.list_names("workloads"))
def test_workload_file(name):
    wl = registry.load_workload(name)
    assert name == f"{wl['config']}.{wl['traffic']}"
    registry.load_config(wl["config"])
    importlib.import_module(f"perfbench.traffic.{wl['generator']}")
    assert wl["chips"] in (1, 4)
    assert set(wl["check"]) >= {"control", "limits"}
    if name in CELLS:
        entry = next(w for w in BENCH["workloads"] if w["name"] == name)
        assert (entry["config"], entry["traffic"], entry["chips"]) == (
            wl["config"], wl["traffic"], wl["chips"])
        assert wl["check"]["limits"], "a cell in the benchmark compares something"


@pytest.mark.parametrize("name", registry.list_names("metrics"))
def test_metric_file(name):
    m = registry.load_metric(name)
    registry.check_name(name)
    assert registry.UNIT_RE.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    importlib.import_module(f"perfbench.readers.{m['reader']}").read
    assert "workloads" not in m, "BENCHMARK.json says which cells report a metric"
    entry = next(p for p in BENCH["per_layer"] if p["name"] == name)
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == m[key], key
    moved = E2E[m["moves"]]
    for cell in entry.get("workloads", CELLS):
        if "workloads" in entry:
            assert reports(cell, moved), f"{cell} does not report {m['moves']}"


def test_every_per_layer_entry_has_its_file():
    assert sorted(p["name"] for p in BENCH["per_layer"]) == registry.list_names("metrics")


def test_benchmark_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert len(json.dumps(BENCH)) < 64 * 1024
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert 2 + 14 * 24 >= 0 and (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert all(os.path.isdir(os.path.join(ROOT, p)) for p in BENCH["paths"])
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.1
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
        assert registry.UNIT_RE.match(m["unit"])
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert "mfu" not in m["name"] or m["unit"] == "%"
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    for entry in BENCH["configs"] + BENCH["workloads"]:
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_what_the_contract_asks(cell):
    from perfbench import harness

    loaded = harness.load_cell(cell)
    assert "setup_s" in loaded.end_to_end and len(loaded.end_to_end) >= 2
    assert loaded.per_layer, "at least one per-layer metric"
    used = {w["config"] for w in BENCH["workloads"]}
    assert {c["name"] for c in BENCH["configs"]} == used
    # beside a kernel's roofline, the whole step's share of the peak
    for m in loaded.per_layer.values():
        if m["name"].endswith("_roofline"):
            assert any("mfu" in o["name"] and o["moves"] == m["moves"]
                       for o in loaded.per_layer.values())


@pytest.mark.parametrize("bad", ["", "a b", "a,b", "a/b", "x" * 65, "-lead", "grü"])
def test_names_refuse_what_the_contract_refuses(bad):
    with pytest.raises(ValueError):
        registry.check_name(bad)


def test_peak_table_has_the_published_numbers_and_no_default():
    from perfbench.peaks import peaks_for

    v5e = peaks_for("TPU v5 lite")
    assert v5e["flops_bf16"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")


# -- the files a later PR would add (data/extra): the serving cell's among them


@pytest.fixture(scope="module")
def later(tmp_path_factory):
    return extended_base(tmp_path_factory.mktemp("pbfiles")), extended_benchmark()


@pytest.mark.parametrize("name", registry.list_names("metrics", EXTRA))
def test_a_later_prs_metric_file_resolves_beside_the_benchmarks_own(later, name):
    base, bench = later
    m = registry.load_metric(name, base)
    assert registry.UNIT_RE.match(m["unit"]) and m["source"] in SOURCES
    importlib.import_module(f"perfbench.readers.{m['reader']}").read
    entry = next(p for p in bench["per_layer"] if p["name"] == name)
    moved = next(e for e in bench["end_to_end"] if e["name"] == m["moves"])
    assert all(reports(cell, moved) for cell in entry["workloads"])


@pytest.mark.parametrize("name", registry.list_names("workloads", EXTRA))
def test_a_later_prs_cell_loads_with_every_metric_it_reports(later, name):
    from perfbench import harness

    base, bench = later
    cell = harness.load_cell(name, base=base, benchmark=bench)
    assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
    assert {"compile_s"} < set(cell.per_layer)
    for m in cell.per_layer.values():
        if m["name"].endswith("_roofline"):
            assert any("mfu" in o["name"] and o["moves"] == m["moves"]
                       for o in cell.per_layer.values())


def test_the_served_decoders_full_size_configuration_counts_as_perf_md_says(later):
    from perfbench import rooflines

    cfg = registry.load_config("servable_lm_2048", later[0])
    d, layers, vocab = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    assert (d, layers, vocab, cfg["head_dim"] * cfg["num_attention_heads"]) == (2048, 24, 50304, 2048)
    weights = 4 * (rooflines.lm_params_touched_per_token(d, layers, vocab) + vocab * d)
    assert weights == pytest.approx(5.67e9, rel=0.01)           # float32, with the embedding
    s = cfg["session"]
    pool = 2 * 4 * layers * s["num_pages"] * s["page_size"] * d  # K and V
    assert pool == pytest.approx(5.24e9, rel=0.01)
