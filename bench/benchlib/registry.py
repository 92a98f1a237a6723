"""Find everything a cell needs by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix.  The configuration's file
is the one its ``BENCHMARK.json`` entry gives; its ``"model"`` key names
the plain reference ``bench/refs/<model>.py``.  The traffic mix is
``bench/traffic/<traffic>.json``; its ``"job"`` key names the job
``bench/jobs/<job>.py``.  Each per-layer metric is read by
``bench/metrics/<metric>.py``.  Adding a cell, configuration, mix or
metric therefore means adding files and entries, never editing one.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class SpecError(Exception):
    """BENCHMARK.json or a file it names is missing or inconsistent."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict            # the configuration file's contents
    traffic: dict           # the traffic mix's contents
    end_to_end: tuple       # BENCHMARK.json entries this cell reports
    per_layer: tuple


def load_json(path: pathlib.Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


def load_module(path: pathlib.Path, name: str):
    """Import one file by path (file names follow the cells' names and may
    hold '-' or '.', so they are not importable by module name)."""
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name} names unknown config {w['config']}")
    return Cell(
        name=name, config_name=w["config"], traffic_name=w["traffic"],
        chips=int(w["chips"]),
        config=load_json(root / configs[w["config"]]["file"]),
        traffic=load_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        end_to_end=tuple(m for m in spec["end_to_end"] if _reports(m, name)),
        per_layer=tuple(m for m in spec["per_layer"] if _reports(m, name)))


def job(kind: str, root: pathlib.Path = ROOT):
    return load_module(root / "bench" / "jobs" / f"{kind}.py",
                       f"bench_job_{kind}")


def reference(model: str, root: pathlib.Path = ROOT):
    return load_module(root / "bench" / "refs" / f"{model}.py",
                       f"bench_ref_{model}")


def metric_reader(metric: str, root: pathlib.Path = ROOT):
    mod = load_module(root / "bench" / "metrics" / f"{metric}.py",
                      "bench_metric_" + metric.replace(".", "_"))
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"bench/metrics/{metric}.py defines no read(run)")
    return mod
