"""BENCHMARK.json against the benchmark's contract: names, units, the
references between entries, and the files the harness finds by name."""
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TEXT = re.compile(r"^[^\t\n]{1,200}$")
CELLS = {w["name"]: w for w in BENCH["workloads"]}
E2E = {m["name"]: m for m in BENCH["end_to_end"]}


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p)
        assert os.path.isdir(os.path.join(ROOT, p))
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


@pytest.mark.parametrize("section", ["configs", "workloads", "end_to_end",
                                     "per_layer"])
def test_names_and_units(section):
    entries = BENCH[section]
    names = [e["name"] for e in entries]
    assert len(names) == len(set(names))
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for key in ("why", "layer", "source"):
            if key in e:
                assert TEXT.match(e[key]), (e["name"], key)


def test_configs_and_cells():
    configs = {c["name"]: c for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        path = os.path.join(ROOT, c["file"])
        assert c["file"].startswith("bench/") and os.path.isfile(path)
        cfg = json.load(open(path))
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert all(NAME.match(k) for k in c["reduced"])
    used = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"])
        traffic = os.path.join(ROOT, "bench", "traffic",
                               w["traffic"] + ".json")
        kind = json.load(open(traffic))["kind"]
        assert os.path.isfile(os.path.join(ROOT, "bench", "kinds",
                                           kind + ".py"))
        used.add(w["config"])
    assert used == set(configs)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


def test_metrics_reference_what_exists():
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", [])) <= set(CELLS)
    for m in BENCH["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in E2E
        assert os.path.isfile(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))
        moved = E2E[m["moves"]].get("workloads", list(CELLS))
        assert m["workloads"] and set(m["workloads"]) <= set(moved)
        if m["unit"] == "%":
            assert m["name"].endswith("_roofline") or "idle" in m["name"]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_every_cell_reports_enough(cell):
    e2e = [m for m in BENCH["end_to_end"]
           if cell in m.get("workloads", [cell])]
    assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
    assert any(cell in m["workloads"] for m in BENCH["per_layer"])


def test_a_full_check_fits_its_budget():
    cells, rs = 24, BENCH["run_seconds"]
    assert (2 + 14 * cells) * (rs + 60) + cells * 2 * 90 + 1200 <= 43200


PARKED = sorted(f[:-5] for f in os.listdir(os.path.join(ROOT, "bench",
                                                          "parked")))


@pytest.mark.parametrize("cell", PARKED)
def test_parked_cell_would_fit(cell):
    """A parked cell's entries, added to BENCHMARK.json, name a config,
    a traffic file and metric readers that exist, and report enough."""
    parked = json.load(open(os.path.join(ROOT, "bench", "parked",
                                         cell + ".json")))
    assert set(parked) <= {"workloads", "end_to_end", "per_layer"}
    assert [w["name"] for w in parked["workloads"]] == [cell]
    assert cell not in CELLS
    w = parked["workloads"][0]
    assert w["config"] in {c["name"] for c in BENCH["configs"]}
    assert os.path.isfile(os.path.join(ROOT, "bench", "traffic",
                                       w["traffic"] + ".json"))
    e2e = {**E2E, **{m["name"]: m for m in parked.get("end_to_end", [])}}
    mine = [m for m in e2e.values() if cell in m.get("workloads", [cell])]
    assert "setup_s" in [m["name"] for m in mine] and len(mine) >= 2
    for m in parked.get("end_to_end", []) + parked.get("per_layer", []):
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["name"] not in E2E
    for m in parked.get("per_layer", []):
        assert m["moves"] in e2e and m["workloads"] == [cell]
        assert TEXT.match(m["layer"])
        assert os.path.isfile(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))
