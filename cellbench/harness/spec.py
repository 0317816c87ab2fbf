"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration (``configs/<name>.json``, the file its
``configs`` entry gives) and a traffic mix (``traffic/<name>.json``); each
per-layer metric is a reader ``metrics/<metric name>.py``.  Nothing here
knows any particular cell: adding one is adding files and entries.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class SpecError(ValueError):
    """The benchmark description is missing a name or a file."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file, as run
    traffic: dict           # the traffic file
    end_to_end: tuple       # metric entries of BENCHMARK.json for this cell
    per_layer: tuple


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names unknown config "
                        f"{w['config']!r}")
    cfg_path = root / configs[w["config"]]["file"]
    traffic_path = BENCH_DIR / "traffic" / f"{w['traffic']}.json"
    for p in (cfg_path, traffic_path):
        if not p.is_file():
            raise SpecError(f"workload {name!r}: missing file {p}")
    return Cell(
        name=name, chips=int(w["chips"]),
        config=json.loads(cfg_path.read_text()),
        traffic=json.loads(traffic_path.read_text()),
        end_to_end=tuple(m for m in bench["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, name)))


def metric_reader(name: str):
    """The ``read(ctx)`` function of ``metrics/<name>.py``."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no reader for metric {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        "cellbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
