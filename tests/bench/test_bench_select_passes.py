"""``select_passes``: the arena rows a greedy selection reads over the
arena's height, from the ``select`` spans' ``rows`` and ``theta``, on
hand-made spans and through a traced run of the select cell at a size a
CPU holds."""
import os
import time
import types

import pytest

from bench import harness
from repro import obs

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2**31 + 4242


@pytest.fixture(autouse=True)
def _isolated_obs():
    obs.reset()
    yield
    obs.reset()


def reader():
    return harness.load_module(
        os.path.join(ROOT, "bench", "metrics", "select_passes.py"),
        "select_passes")


def span(name, **args):
    return {"name": name, "ph": "X", "ts": 0.0, "dur": 1.0,
            "args": {"depth": 0, "parent": "", **args}}


def test_reader_sums_rows_over_theta():
    run = types.SimpleNamespace(spans=[
        span("select", k=50, rows=20_000, theta=16_384),
        span("select", k=50, rows=24_000, theta=16_384),
        span("extend", rows=10**9, theta=1),
        span("select", k=50)])
    assert reader().read(run) == pytest.approx(44_000 / 32_768)


@pytest.mark.parametrize("evs", [[], [span("select", k=50)],
                                 [span("extend", batches=3)]])
def test_reader_finds_nothing_without_rows(evs):
    assert reader().read(types.SimpleNamespace(spans=evs)) is None


def test_a_traced_select_run_reads_the_arena_about_once(tiny):
    c = harness.load_cell("amazon_ic.select",
                          overrides={**tiny, "k": 20})
    r = harness.run(c, seed=SEED, seconds=0.5, trace=True,
                    t_start=time.perf_counter())
    assert r["correct"] is True
    passes = r["metrics"]["select_passes"]
    assert passes["unit"] == "passes"
    assert 1.0 <= passes["value"] <= 2.5
