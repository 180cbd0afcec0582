"""Each cell's window and its check, called as functions at a size a
CPU holds; the control (the reference one precision lower) fails the
check; the command itself refuses a machine without a TPU."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from bench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELLS = ["amazon_ic.sample", "amazon_lt.sample", "amazon_ic.select",
         "amazon_ic.serve"]
SEED = 2**31 + 12345


def run(cell, tiny, **kw):
    c = harness.load_cell(cell, overrides={**tiny, **kw})
    return harness.run(c, seed=SEED, seconds=0.5, trace=False,
                       t_start=time.perf_counter(), control=True)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_is_correct_and_its_control_is_not(cell, tiny):
    extra = ({"theta_per_arena": 4096, "k": 20} if "select" in cell else
             {"graph": {**tiny["graph"], "undirected_edges": 15000}})
    r = run(cell, tiny, **extra)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in r["checks"].values())
    assert any(c["value"] > c["limit"] for c in r["control"].values())
    e2e = {"sample": "rrr_sets_per_s", "select": "select_s",
           "serve": "query_p95_ms"}[cell.split(".")[1]]
    assert set(r["metrics"]) == {"setup_s", e2e}
    assert r["metrics"][e2e]["value"] > 0


def test_command_refuses_a_cpu(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "amazon_ic.sample",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout and "no TPU" in p.stderr


def test_command_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "amazon_ic.sample",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and "{" not in p.stdout


def test_harness_finds_new_files_by_name(tmp_path):
    """A configuration, a traffic mix and a per-layer metric added as
    new files plus entries, with no existing file edited."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cfg = json.load(open(tmp_path / "bench/configs/amazon_ic.json"))
    cfg["k"] = 7
    json.dump(cfg, open(tmp_path / "bench/configs/new_cfg.json", "w"))
    json.dump({"kind": "select", "trace_seconds": 1.0},
              open(tmp_path / "bench/traffic/new_mix.json", "w"))
    (tmp_path / "bench/metrics/new_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    bench["configs"].append({"name": "new_cfg", "source": "x",
                             "file": "bench/configs/new_cfg.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "new_cfg.new_mix",
                               "config": "new_cfg", "traffic": "new_mix",
                               "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "new_metric", "unit": "ms",
                               "better": "lower", "source": "device_trace",
                               "layer": "selection", "moves": "select_s",
                               "workloads": ["new_cfg.new_mix"]})
    bench["end_to_end"][2]["workloads"].append("new_cfg.new_mix")
    json.dump(bench, open(tmp_path / "BENCHMARK.json", "w"))
    c = harness.load_cell("new_cfg.new_mix", root=str(tmp_path))
    assert c.config["k"] == 7 and c.traffic["kind"] == "select"
    assert c.kind.__name__.endswith("kind_select")
    assert [m["name"] for m in c.per_layer] == ["new_metric"]
    assert c.readers["new_metric"].read(None) == 42.0
    assert {m["name"] for m in c.end_to_end} == {"setup_s", "select_s"}
