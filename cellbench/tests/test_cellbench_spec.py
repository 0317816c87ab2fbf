"""Every name in BENCHMARK.json finds its files; unknown names fail."""
import json

import pytest

import _paths  # noqa: F401
from harness import spec
from harness.peaks import peaks


def test_every_cell_loads_its_config_and_traffic_by_name():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["loop"] in ("open", "closed")
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer


def test_every_metric_has_a_reader():
    bench = spec.load_benchmark()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))


def test_unknown_names_are_errors():
    with pytest.raises(spec.SpecError):
        spec.load_cell("no.such-cell")
    with pytest.raises(spec.SpecError):
        spec.metric_reader("no.such_metric")
    with pytest.raises(KeyError):
        peaks("cpu")


def test_configs_hold_their_reductions_and_check_limits():
    bench = spec.load_benchmark()
    for c in bench["configs"]:
        cfg = json.loads((spec.ROOT / c["file"]).read_text())
        assert set(c["reduced"]) <= set(cfg["reduced"]) | {"ncent"}
        assert set(cfg["check"]["limits"]) == {
            "score_gap", "selection_miss", "malformed", "unanswered"}
        assert set(cfg["check"]) == {"sample", "limits"}


def test_metric_cell_lists_name_real_cells():
    bench = spec.load_benchmark()
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells


def _root_with(tmp_path, deployment, chips):
    """A checkout whose one cell runs the sq8 configuration with
    ``deployment`` (None: no group) on ``chips`` chips."""
    bench = spec.load_benchmark()
    c = bench["configs"][0]
    cfg = json.loads((spec.ROOT / c["file"]).read_text())
    cfg.pop("deployment", None)
    if deployment is not None:
        cfg["deployment"] = deployment
    (tmp_path / "cfg.json").write_text(json.dumps(cfg))
    w = dict(bench["workloads"][0], chips=chips)
    bench.update(configs=[dict(c, file="cfg.json")], workloads=[w])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return w["name"]


TWO_BY_TWO = {"mesh": [2, 2], "axes": ["data", "model"]}


def test_a_config_without_deployment_is_one_chip(tmp_path):
    assert spec.load_cell("sq8.closed-64").deployment == spec.Deployment()
    name = _root_with(tmp_path, None, 1)
    assert spec.load_cell(name, tmp_path).deployment.chips == 1


def test_a_deployment_over_a_mesh_loads(tmp_path):
    name = _root_with(tmp_path, TWO_BY_TWO, 4)
    dep = spec.load_cell(name, tmp_path).deployment
    assert dep == spec.Deployment(mesh=(2, 2), axes=("data", "model"))
    assert dep.chips == 4


@pytest.mark.parametrize("deployment, chips", [
    (TWO_BY_TWO, 1),                              # the cell's chips differ
    (None, 4),                                    # no group: one chip
    (dict(TWO_BY_TWO, mesh=[2, 1]), 4),           # the mesh holds 2
    (dict(TWO_BY_TWO, mesh=[2, 2]), 2),           # the mesh holds 4
    (dict(TWO_BY_TWO, chips=4), 4),               # a key the group has not
    (dict(TWO_BY_TWO, axes=["data"]), 4),         # an axis per mesh dim
    (dict(TWO_BY_TWO, axes=["data", "data"]), 4), # the axes are distinct
    ({"mesh": [4]}, 4),                           # keys missing
], ids=["chips", "no_group_on_4", "mesh_short", "mesh_long", "unknown_key",
        "axes", "axes_repeated", "keys"])
def test_a_deployment_that_does_not_fit_its_cell_is_an_error(
        tmp_path, deployment, chips):
    name = _root_with(tmp_path, deployment, chips)
    with pytest.raises(spec.SpecError):
        spec.load_cell(name, tmp_path)
