import json
import os
import re

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_names_and_units(spec):
    names = ([c["name"] for c in spec["configs"]]
             + [w["name"] for w in spec["workloads"]]
             + [w["config"] for w in spec["workloads"]]
             + [w["traffic"] for w in spec["workloads"]]
             + [m["name"] for m in spec["end_to_end"] + spec["per_layer"]])
    for n in names:
        assert NAME.match(n), n
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    metric_names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    for w in spec["workloads"]:
        assert 1 <= len(w["why"]) <= 200 and w["chips"] in (1, 4)


def test_every_cell_finds_its_files(spec):
    for w in spec["workloads"]:
        for kind, name in (("configs", w["config"]), ("traffic", w["traffic"]),
                           ("limits", w["name"])):
            assert os.path.isfile(os.path.join(BENCH, kind, name + ".json")), (kind, name)
        with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        assert os.path.isfile(os.path.join(BENCH, "drivers", traffic["driver"] + ".py"))
    for m in spec["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics", m["name"] + ".py")), m["name"]


def test_every_per_layer_metric_moves_a_metric_its_cells_report(spec):
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    cells = [w["name"] for w in spec["workloads"]]
    for m in spec["per_layer"]:
        target = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in target.get("workloads", cells), (m["name"], cell)
    for cell in cells:
        assert any(cell in m.get("workloads", cells) for m in spec["per_layer"])
        assert sum(cell in m.get("workloads", cells) for m in spec["end_to_end"]) >= 2
