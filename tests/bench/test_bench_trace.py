"""The trace reduction: busy union, idle share, gap attribution and
per-program device time, on a small hand-made trace and on a trace
recorded on one TPU v5e."""
import os

import pytest

from bench.trace import Trace, _program_of

HERE = os.path.dirname(os.path.abspath(__file__))


def small() -> Trace:
    modules = [["jit_chain(1)", 10, 30], ["jit__commit_write(2)", 60, 10],
               ["jit_chain(1)", 95, 20]]
    ops = [["fusion.1", 10, 10], ["%arena_commit.1", 20, 20],
           ["copy.2", 60, 10], ["fusion.1", 95, 10]]
    host = [["extend", 0, 100], ["sample", 40, 20], ["bench.idle", 80, 5]]
    return Trace(0, 100, {"/device:TPU:0": modules},
                 {"/device:TPU:0": _program_of(ops, modules)}, host)


def test_busy_is_the_union_of_op_intervals():
    t = small()
    # [10, 40) + [60, 70) + [95, 100) clipped to the window
    assert t.busy_s() == pytest.approx(45e-9)
    assert t.window_s() == pytest.approx(100e-9)
    assert t.idle_frac() == pytest.approx(0.55)


def test_gaps_are_named_by_the_innermost_host_span():
    t = small()
    assert t.gaps() == [(0, 10), (40, 60), (70, 95)]
    assert t.idle_by_host() == pytest.approx(
        {"extend": 10e-9, "sample": 20e-9, "bench.idle": 25e-9})


def test_program_time_counts_executions_inside_the_window():
    t = small()
    assert t.runs(r"jit_chain") == 1          # the second ends outside
    assert t.program_seconds(r"jit_chain") == pytest.approx(30e-9)
    assert t.op_seconds(r"jit_chain", r"arena_commit") == pytest.approx(
        20e-9)
    assert t.program_seconds(r"jit__commit_write") == pytest.approx(10e-9)


def test_an_op_that_holds_others_is_not_a_leaf():
    ops = _program_of([["%while.1", 0, 50], ["%fusion.2", 5, 10],
                       ["%fusion.3", 20, 10]], [["jit_f", 0, 50]])
    assert [o[4] for o in ops] == [0, 1, 1]


def test_breakdown_lists_ops_and_gaps():
    b = small().breakdown()
    assert b["device_ops"][0] == ["jit_chain(1)/%arena_commit.1",
                                  pytest.approx(20e-9)]
    assert b["device_ops"][1] == ["jit_chain(1)/fusion.1",
                                  pytest.approx(15e-9)]
    assert b["idle_gaps"][0] == ["bench.idle", pytest.approx(25e-9)]


def test_recorded_trace_of_a_select_window():
    """Three select(50) calls over the 16,384 x 334,863 arena, traced
    on one TPU v5e (the select cell's traced window)."""
    from bench.trace import load
    import shutil
    import tempfile
    d = tempfile.mkdtemp()
    try:
        shutil.copy(os.path.join(HERE, "fixtures", "select_v5e.xplane.pb"), d)
        t = load(d)
    finally:
        shutil.rmtree(d)
    assert list(t.ops) == ["/device:TPU:0"]
    assert t.window_s() == pytest.approx(2.736434584)
    assert t.busy_s() == pytest.approx(2.724205354)
    assert t.runs(r"^jit_select_dense$") == 3
    assert t.program_seconds(r"^jit_select_dense$") == pytest.approx(
        2.724204976)
    # the coverage_matvec kernel, 50 per call, is a leaf inside the while
    assert t.op_seconds(r"^jit_select_dense$", r"^%coverage_matvec") == \
        pytest.approx(2.658639265)
    b = t.breakdown()
    assert b["device_ops"][0][0] == "jit_select_dense/%coverage_matvec.7"
    assert {k for k, _ in b["idle_gaps"]} <= {"none", "select"}
