"""Find a cell and everything it names, by name, in the benchmark's data files.

``BENCHMARK.json`` (repo root) lists cells, configurations and metrics; what
belongs to one of them sits in a file of its own under ``benchmark/``:

- ``configs/<config>.json``       the model as it is run, and its deployment
- ``traffic/<mix>.json``          parameters of one traffic mix
- ``workloads/<cell>.json``       loop, rate or clients, reported metrics
- ``layer_metrics/<metric>.json`` one per-layer metric: reader kind + parameters

Nothing here knows a cell, a mix, a configuration or a metric by name, so a
later PR adds one by adding a file and an entry.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_DIR = os.path.dirname(BENCH_DIR)


class CatalogError(ValueError):
    """A data file is missing, or names something that does not exist."""


def _load(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise CatalogError(f"missing benchmark file {path}") from None


@dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with every file it names, loaded."""

    name: str
    chips: int
    config_name: str
    config: dict  # configs/<config>.json
    traffic_name: str
    traffic: dict  # traffic/<mix>.json
    spec: dict  # workloads/<cell>.json
    end_to_end: List[dict]  # BENCHMARK.json entries this cell reports
    per_layer: List[dict]  # BENCHMARK.json entries + their layer_metrics file


def _reported_in(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = REPO_DIR, bench_dir: str = None) -> Cell:
    bench_dir = bench_dir or os.path.join(root, "benchmark")
    bench = _load(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        known = [w["name"] for w in bench["workloads"]]
        raise CatalogError(f"no workload {name!r} in BENCHMARK.json; known: {known}")
    cfg_entry = next(
        (c for c in bench["configs"] if c["name"] == entry["config"]), None
    )
    if cfg_entry is None:
        raise CatalogError(f"cell {name!r} names unknown config {entry['config']!r}")
    config = _load(os.path.join(root, cfg_entry["file"]))
    traffic = _load(os.path.join(bench_dir, "traffic", entry["traffic"] + ".json"))
    spec = _load(os.path.join(bench_dir, "workloads", name + ".json"))
    e2e = [m for m in bench["end_to_end"] if _reported_in(m, name)]
    per_layer = []
    for m in bench["per_layer"]:
        if _reported_in(m, name):
            reader = _load(os.path.join(bench_dir, "layer_metrics", m["name"] + ".json"))
            per_layer.append({**m, "reader": reader})
    return Cell(
        name=name, chips=int(entry["chips"]), config_name=entry["config"],
        config=config, traffic_name=entry["traffic"], traffic=traffic, spec=spec,
        end_to_end=e2e, per_layer=per_layer,
    )


def check_catalog(root: str = REPO_DIR, bench_dir: str = None) -> Dict[str, Cell]:
    """Load every cell and hold the catalog to its own rules: every file
    resolves, every ``moves`` names an end-to-end metric each of the metric's
    cells reports, every cell reports ``setup_s``, one more end-to-end metric
    and one per-layer metric, and the cell file agrees with BENCHMARK.json."""
    bench = _load(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: load_cell(w["name"], root, bench_dir) for w in bench["workloads"]}
    for cell in cells.values():
        e2e = {m["name"] for m in cell.end_to_end}
        if "setup_s" not in e2e or len(e2e) < 2:
            raise CatalogError(f"{cell.name}: needs setup_s and one more end-to-end metric")
        if not cell.per_layer:
            raise CatalogError(f"{cell.name}: reports no per-layer metric")
        for m in cell.per_layer:
            if m["moves"] not in e2e:
                raise CatalogError(
                    f"{cell.name}: {m['name']} moves {m['moves']!r}, which the cell does not report"
                )
            for key in ("layer", "unit", "moves", "source"):
                if m["reader"].get(key) != m[key]:
                    raise CatalogError(
                        f"layer_metrics/{m['name']}.json disagrees with BENCHMARK.json on {key!r}"
                    )
        for key, want in (("config", cell.config_name), ("traffic", cell.traffic_name),
                          ("chips", cell.chips)):
            if cell.spec.get(key) != want:
                raise CatalogError(f"workloads/{cell.name}.json disagrees with BENCHMARK.json on {key!r}")
    used = {c.config_name for c in cells.values()}
    for c in bench["configs"]:
        if c["name"] not in used:
            raise CatalogError(f"config {c['name']!r} is used by no cell")
    return cells
