"""Finds a cell's files by name. A cell is workloads/<cell>.json, which names
its configuration (configs/<config>.json) and its traffic generator (a module
under traffic/); BENCHMARK.json says which per-layer metrics it reports, and
metrics/<metric>.json names each one's reader (a module under readers/). Adding a cell, a
configuration or a metric is adding files and BENCHMARK.json entries only."""

from __future__ import annotations

import importlib
import json
import os
import re
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def check_name(name: str) -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise ValueError(f"not a valid benchmark name: {name!r}")
    return name


def _load(kind: str, name: str, base: str = HERE) -> dict:
    path = os.path.join(base, kind, check_name(name) + ".json")
    with open(path) as f:
        data = json.load(f)
    if data.get("name", name) != name:
        raise ValueError(f"{path} names itself {data.get('name')!r}")
    return data


def load_config(name: str, base: str = HERE) -> dict:
    return _load("configs", name, base)


def load_workload(name: str, base: str = HERE) -> dict:
    return _load("workloads", name, base)


def load_metric(name: str, base: str = HERE) -> dict:
    return _load("metrics", name, base)


def list_names(kind: str, base: str = HERE) -> List[str]:
    return sorted(
        f[:-5] for f in os.listdir(os.path.join(base, kind)) if f.endswith(".json")
    )


def metrics_for(cell: str, bench: dict, base: str = HERE) -> Dict[str, dict]:
    """Every per-layer metric of BENCHMARK.json that this cell reports: the
    entry lists the cell, or lists none and the cell reports the end-to-end
    metric it moves. The metric's own file adds its reader."""
    reported = {
        m["name"] for m in bench["end_to_end"]
        if "workloads" not in m or cell in m["workloads"]
    }
    out = {}
    for entry in bench["per_layer"]:
        cells = entry.get("workloads")
        if cell in cells if cells is not None else entry["moves"] in reported:
            out[entry["name"]] = dict(load_metric(entry["name"], base), **entry)
    return out


def load_module(kind: str, name: str, package: str = "perfbench"):
    """perfbench/<kind>/<name>.py: a builder, a traffic generator, a reader
    or a plain reference."""
    check_name(name)
    return importlib.import_module(f"{package}.{kind}.{name}")


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)
