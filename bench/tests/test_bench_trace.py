"""The trace reduction and the roofline formula on hand-made inputs."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import roofline, trace  # noqa: E402


def _plane(name, lines):
    return {"name": name, "lines": [{"name": k, "events": v}
                                    for k, v in lines.items()]}


# one device: ops at [0,10) [5,20) [30,40) [100,110) ns; the solver's
# executable spans [0,40), another module [100,110); the host runs a
# pass over [0,120) and a dispatch over [45,95)
PLANES = [
    _plane("/device:TPU:0", {
        "XLA Ops": [("scatter", 0, 10), ("gather", 5, 15),
                    ("scatter", 30, 10), ("copy", 100, 10)],
        "XLA Modules": [("jit__simulate(7)", 0, 40),
                        ("jit_other(3)", 100, 10)]}),
    _plane("/host:CPU", {"python": [("bench.pass", 0, 120),
                                    ("PjitFunction(_simulate)", 45, 50)]}),
]


def test_busy_is_the_union_of_op_intervals():
    assert trace.union([(5, 20), (0, 10), (30, 40)]) == [(0, 20), (30, 40)]
    assert trace.busy_ns(PLANES[0]) == 20 + 10 + 10


def test_control_flow_around_ops_is_not_busy():
    # a ``while`` spans two ops and the gap between them; its event
    # would cover the gap, its leaves do not
    plane = _plane("/device:TPU:0", {"XLA Ops": [
        ("%while.74 = (s32[]) while(...)", 0, 100),
        ("%fusion.1 = f32[8]", 10, 20), ("%fusion.2 = f32[8]", 60, 30),
        ("%copy.3 = f32[8]", 100, 5)]})
    assert [e[0] for e in trace.leaf_ops(plane)] == [
        "%fusion.1 = f32[8]", "%fusion.2 = f32[8]", "%copy.3 = f32[8]"]
    assert trace.busy_ns(plane) == 20 + 30 + 5
    assert trace.idle_share([plane], 200) == pytest.approx(1 - 55 / 200)
    assert [g[1] for g in trace.idle_gaps([plane])] == [
        pytest.approx(30e-9), pytest.approx(10e-9)]
    assert [n for n, _ in trace.top_ops([plane])] == [
        "%fusion.2 = f32[8]", "%fusion.1 = f32[8]", "%copy.3 = f32[8]"]


def test_solver_time_sums_its_executables_only():
    assert trace.solver_ns(PLANES[0], trace.SOLVER_MODULES) == 40
    assert trace.solver_seconds(PLANES) == pytest.approx(40e-9)
    assert trace.solver_seconds(PLANES[1:]) is None


def test_idle_share_and_gaps():
    assert trace.idle_share(PLANES, 200) == pytest.approx(1 - 40 / 200)
    gaps = trace.idle_gaps(PLANES)
    assert gaps[0][0] == "PjitFunction(_simulate)"    # [40, 100)
    assert gaps[0][1] == pytest.approx(60e-9)
    assert gaps[1] == ["bench.pass", pytest.approx(10e-9)]   # [20, 30)
    top = trace.top_ops(PLANES)
    assert top[0] == ["scatter", pytest.approx(20e-9)]


def test_roofline_on_a_hand_sized_problem():
    # two solves: 6 non-zeros over 3 flows, then 2 over 1; 10 links
    work = [(6, 3), (2, 1)]
    assert roofline.min_bytes(work, 10) == 4 * 10 + (24 + 24) + (8 + 8)
    assert roofline.min_ops(work) == 16
    peak = {"hbm_bytes_per_s": 1e3, "bf16_flops_per_s": 1e6}
    t, bound = roofline.least_seconds(work, 10, peak)
    assert bound == "bytes" and t == pytest.approx(104 / 1e3)
    t, bound = roofline.least_seconds(work, 10, {"hbm_bytes_per_s": 1e9,
                                                 "bf16_flops_per_s": 1.0})
    assert bound == "ops" and t == pytest.approx(16.0)
