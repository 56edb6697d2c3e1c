"""Finding a cell's files by the names in ``BENCHMARK.json``.

- the configuration: the ``file`` of its ``configs`` entry;
- the traffic mix: ``bench/traffic/<traffic>.json``;
- the comparison limits: ``bench/checks/<workload>.json``;
- each metric: a reader ``bench/metrics/<metric>.py`` with ``read(run)``.

Adding a cell, a mix or a metric adds files and entries; no existing file
needs an edit.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    limits: dict
    end_to_end: list          # metric entries of BENCHMARK.json that apply
    per_layer: list


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def applies(metric: dict, cell: str, e2e_names: set) -> bool:
    """Does ``metric`` report in ``cell``?  Listed cells when the entry
    has ``workloads``; otherwise every cell that reports the end-to-end
    metric it moves (or, for an end-to-end metric, every cell)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def load_cell(name: str, root: str = ROOT) -> Cell:
    spec = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    e2e = [m for m in spec["end_to_end"] if applies(m, name, set())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if applies(m, name, names)]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_load_json(os.path.join(root, conf["file"])),
        mix=_load_json(os.path.join(BENCH, "traffic",
                                    w["traffic"] + ".json")),
        limits=_load_json(os.path.join(BENCH, "checks", name + ".json")),
        end_to_end=e2e, per_layer=per_layer)


def reader(metric: str):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    path = os.path.join(BENCH, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
