"""BENCHMARK.json is well formed and the harness is driven by its data."""
import glob
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def metrics(bench):
    return bench["end_to_end"] + bench["per_layer"]


def test_names_units_and_references(bench):
    cells = {w["name"] for w in bench["workloads"]}
    configs = {c["name"] for c in bench["configs"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for n in cells | configs | {m["name"] for m in metrics(bench)}:
        assert NAME.match(n), n
    for m in metrics(bench):
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells, m
    for m in bench["per_layer"]:
        assert m["moves"] in e2e, m
    for w in bench["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"]) and len(w["why"]) <= 200
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m


def test_every_name_has_its_file(bench):
    for m in metrics(bench):
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py")), m["name"]
    for w in bench["workloads"]:
        assert os.path.isfile(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"] == []
        assert cfg["assumed"] and cfg["source"] and cfg["guarantees"]
        assert cfg["check"]["score_gap"] > 0


def test_every_cell_reports_setup_another_metric_and_a_layer(bench):
    for w in bench["workloads"]:
        e2e = [m for m in bench["end_to_end"]
               if w["name"] in m.get("workloads", [w["name"]])]
        layers = [m for m in bench["per_layer"]
                  if w["name"] in m.get("workloads", [])]
        assert "setup_s" in {m["name"] for m in e2e}
        assert len(e2e) >= 2 and layers, w["name"]


def test_the_harness_names_no_cell_config_or_metric(bench):
    names = ({w["name"] for w in bench["workloads"]}
             | {c["name"] for c in bench["configs"]}
             | {w["traffic"] for w in bench["workloads"]}
             | {m["name"] for m in metrics(bench)})
    code = [p for p in glob.glob(os.path.join(BENCH, "*.py"))
            + glob.glob(os.path.join(BENCH, "reference", "*.py"))]
    for path in code:
        with open(path) as f:
            text = f.read()
        for n in names:
            assert not re.search(r"(?<![\w.])" + re.escape(n) + r"(?![\w])",
                                 text), (path, n)
