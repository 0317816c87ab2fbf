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
