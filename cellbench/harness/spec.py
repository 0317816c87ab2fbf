"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration (``configs/<name>.json``, the file its
``configs`` entry gives) and a traffic mix (``traffic/<name>.json``); each
per-layer metric is a reader ``metrics/<metric name>.py``.  Nothing here
knows any particular cell: adding one is adding files and entries.

A configuration states its deployment in a ``deployment`` group, as
``{"mesh": [2, 2], "axes": ["data", "model"]}``: a mesh of the cell's
chips, the docs split over every axis.  A configuration without one is a
one-chip deployment.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import pathlib

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class SpecError(ValueError):
    """The benchmark description is missing a name or a file."""


@dataclasses.dataclass(frozen=True)
class Deployment:
    """How a configuration lays its corpus over the cell's chips."""
    mesh: tuple = (1,)        # chips along each mesh axis
    axes: tuple = ("docs",)   # the mesh's axis names

    @property
    def chips(self) -> int:
        return math.prod(self.mesh)


DEPLOYMENT_KEYS = {f.name for f in dataclasses.fields(Deployment)}


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file, as run
    traffic: dict           # the traffic file
    end_to_end: tuple       # metric entries of BENCHMARK.json for this cell
    per_layer: tuple
    deployment: Deployment  # the configuration's, on the cell's chips


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def deployment_of(config: dict, cell: str, chips: int) -> Deployment:
    """The configuration's ``deployment`` group, held to the cell's chips."""
    g = config.get("deployment")
    if g is None:
        dep = Deployment()
    elif set(g) != DEPLOYMENT_KEYS:
        raise SpecError(f"workload {cell!r}: a deployment group has exactly "
                        f"the keys {sorted(DEPLOYMENT_KEYS)}, not {sorted(g)}")
    else:
        dep = Deployment(mesh=tuple(int(n) for n in g["mesh"]),
                         axes=tuple(str(a) for a in g["axes"]))
    if len(dep.mesh) != len(dep.axes) or len(set(dep.axes)) != len(dep.axes):
        raise SpecError(f"workload {cell!r}: mesh {list(dep.mesh)} needs as "
                        f"many distinct axis names, got {list(dep.axes)}")
    if dep.chips != chips:
        raise SpecError(f"workload {cell!r} asks for {chips} chip(s); its "
                        f"configuration's mesh {list(dep.mesh)} holds "
                        f"{dep.chips}")
    return dep


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
    config = json.loads(cfg_path.read_text())
    return Cell(
        name=name, chips=int(w["chips"]), config=config,
        traffic=json.loads(traffic_path.read_text()),
        end_to_end=tuple(m for m in bench["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, name)),
        deployment=deployment_of(config, name, int(w["chips"])))


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
