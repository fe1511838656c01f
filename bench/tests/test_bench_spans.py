"""The program's host spans and solver counters, and the readers of the
per-layer metrics built on them.

- a traced ``run_workloads`` on a tiny fabric yields each ``flow.*``
  span it should, every one nested under ``flow.run_workloads``, and
  the leaf spans cover nearly all of the root;
- ``spans.py``'s self time and each reader, on hand-made planes and
  counters, including no reading from a program without them;
- a traced run of each tiny cell reads every new metric.
"""
import os
import shutil
import sys
import tempfile

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import check, gen, run, spans, sut, trace  # noqa: E402
from bench.tests import _tiny  # noqa: E402

CELLS = ["hpl16k.decay", "loss4k.fresh"]
SEED = 2 ** 31 + 77
LEAVES = ("flow.stage", "flow.derive", "flow.warm", "flow.flows",
          "flow.pack", "flow.dispatch", "flow.finish", "flow.segments",
          "flow.fill")
NEW_METRICS = ("stage_ms_per_pass", "derive_ms_per_pass",
               "pack_ms_per_pass", "fill_ms_per_pass",
               "solver_epochs_per_pass", "maxmin_rounds_per_pass",
               "vmap_lane_use_pct", "maxmin_rounds_per_lane")


def _traced(fn):
    """Planes of a profiler trace of ``fn()``."""
    import jax
    tdir = tempfile.mkdtemp(prefix="bench-spans-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            fn()
        finally:
            jax.profiler.stop_trace()
        return trace.load(tdir)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)


def _flow_lines(planes):
    return [[e for e in line["events"] if e[0].startswith("flow.")]
            for p in planes if p["name"].startswith("/host")
            for line in p["lines"]]


def _check_nesting(planes):
    """Every span lies inside a root on its line; the names seen."""
    names = set()
    for evs in _flow_lines(planes):
        roots = [(s, s + d) for n, s, d in evs if n == spans.ROOT]
        for n, s, d in evs:
            names.add(n)
            assert any(r0 <= s and s + d <= r1 for r0, r1 in roots), n
    return names


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_pass_names_its_phases(cell):
    sut.import_program()
    cfg, mix = _tiny.cell(cell)
    topo = sut.build_fabric(cfg["fabric"])
    trs = [gen.pass_traffic(cfg, mix, SEED, i) for i in range(4)]
    wls = [sut.workloads(tr) for tr in trs]

    def passes():       # the first pass on the fabric is cold
        for tr, wl in zip(trs, wls):
            sut.run_pass(topo, wl, tr["loss_rate"])

    planes = _traced(passes)
    names = _check_nesting(planes)
    assert names >= {spans.ROOT, "flow.warm", "flow.derive", "flow.stage",
                     "flow.flows", "flow.pack", "flow.dispatch",
                     "flow.finish", "flow.fill"}
    own = spans.self_ns(planes)
    root = sum(d for evs in _flow_lines(planes) for n, _, d in evs
               if n == spans.ROOT)
    assert sum(own.get(n, 0) for n in LEAVES) >= 0.9 * root
    assert sum(own.values()) == root


def test_dynamic_segments_have_their_span():
    sut.import_program()
    from repro.core import fattree
    from repro.core.engine import make_engine
    from repro.core.workload import GroupOp, MemberEvent, Workload
    topo = fattree.testbed(n_hosts=8)
    wl = Workload("dyn")
    wl.add(GroupOp("bcast", ("h0", "h1", "h2", "h3"), 1 << 18,
                   events=(MemberEvent("leave", "h3", 2e-5),)))
    wl.add(GroupOp("bcast", ("h4", "h1", "h2"), 1 << 18))
    planes = _traced(lambda: make_engine("flow", topo).run_workloads([wl]))
    names = _check_nesting(planes)
    assert {"flow.segments", "flow.derive", "flow.dispatch"} <= names


def _plane(name, lines):
    return {"name": name, "lines": [{"name": k, "events": v}
                                    for k, v in lines.items()]}


# two passes on one host line: a root [0,100) holding stage [10,50)
# (with a derive [20,30) and a nested derive [22,25) in it), pack
# [50,60), dispatch [60,90) around JAX's own event, fill [90,98); the
# second root [200,300) with a stage [200,290) and no derive.  Another
# line holds a stray span of another thread [0,40)
PLANES = [
    _plane("/host:CPU", {
        "python": [("bench.pass", 0, 100), ("flow.run_workloads", 0, 100),
                   ("flow.stage", 10, 40), ("flow.derive", 20, 10),
                   ("flow.derive", 22, 3), ("flow.pack", 50, 10),
                   ("flow.dispatch", 60, 30),
                   ("PjitFunction(_simulate)", 65, 20),
                   ("flow.fill", 90, 8),
                   ("flow.run_workloads", 200, 100),
                   ("flow.stage", 200, 90)],
        "other": [("flow.flows", 0, 40)]}),
    _plane("/device:TPU:0", {"XLA Ops": [("flow.stage", 0, 1000)]}),
]


def test_self_time_takes_out_nested_spans_only():
    own = spans.self_ns(PLANES)
    assert own == {"flow.run_workloads": 12 + 10, "flow.stage": 30 + 90,
                   "flow.derive": 7 + 3, "flow.pack": 10,
                   "flow.dispatch": 30, "flow.fill": 8, "flow.flows": 40}
    assert spans.self_ns(PLANES[1:]) == {}


def test_span_readers():
    from bench.metrics import (derive_ms_per_pass, fill_ms_per_pass,
                               pack_ms_per_pass, stage_ms_per_pass)
    ctx = {"planes": PLANES, "trace_passes": 2}
    assert stage_ms_per_pass.read(ctx) == pytest.approx(120e-6 / 2)
    assert derive_ms_per_pass.read(ctx) == pytest.approx(10e-6 / 2)
    assert pack_ms_per_pass.read(ctx) == pytest.approx(50e-6 / 2)
    assert fill_ms_per_pass.read(ctx) == pytest.approx(8e-6 / 2)
    # a root with no derive in it reads 0; no root at all reads nothing
    second = [_plane("/host:CPU", {"python": PLANES[0]["lines"][0][
        "events"][-2:]})]
    assert derive_ms_per_pass.read({"planes": second,
                                    "trace_passes": 1}) == 0.0
    for reader in (derive_ms_per_pass, fill_ms_per_pass, pack_ms_per_pass,
                   stage_ms_per_pass):
        assert reader.read({"planes": PLANES[1:], "trace_passes": 2}) \
            is None


def test_counter_readers(monkeypatch):
    from bench.metrics import (maxmin_rounds_per_lane,
                               maxmin_rounds_per_pass,
                               solver_epochs_per_pass, vmap_lane_use_pct)
    ctx = {"pass_s": [0.1] * 8, "trace_passes": 2}
    stats = {"solve_s": 1.0, "calls": 10, "shapes": set(), "lanes": 50,
             "epochs": 60, "rounds": 300, "lane_rounds_run": 400}
    monkeypatch.setattr(sut, "solve_stats", lambda: stats)
    assert solver_epochs_per_pass.read(ctx) == pytest.approx(6.0)
    assert maxmin_rounds_per_pass.read(ctx) == pytest.approx(30.0)
    assert vmap_lane_use_pct.read(ctx) == pytest.approx(75.0)
    assert maxmin_rounds_per_lane.read(ctx) == pytest.approx(6.0)
    # the parent program's stats have none of the counters
    monkeypatch.setattr(sut, "solve_stats",
                        lambda: {"solve_s": 1.0, "calls": 10, "shapes": []})
    for reader in (maxmin_rounds_per_lane, maxmin_rounds_per_pass,
                   solver_epochs_per_pass, vmap_lane_use_pct):
        assert reader.read(ctx) is None


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_run_reads_every_new_metric(cell, monkeypatch):
    sut.import_program()
    monkeypatch.setattr(run, "use_compile_cache", lambda: None)
    monkeypatch.setattr(run, "TRACE_SECONDS", 0.005)
    monkeypatch.setattr(run.roofline, "peaks", lambda kind: {
        "hbm_bytes_per_s": 1e12, "bf16_flops_per_s": 1e14})
    cfg, mix = _tiny.cell(cell)
    names = run.metric_names(_tiny.bench_json(), cell, True)
    res = run.run_cell(cfg, mix, check.limits(cell), SEED, 0.3, True,
                       names, {"platform": "cpu", "kind": "cpu",
                               "count": 1})
    got = res["metrics"]
    assert set(NEW_METRICS) <= set(got)
    assert res["correct"]
    if cell == "hpl16k.decay":          # trees replay from the cache
        assert got["derive_ms_per_pass"]["value"] == 0.0
    else:                               # fresh members every pass
        assert got["derive_ms_per_pass"]["value"] > 0.0
    assert got["solver_epochs_per_pass"]["value"] > 0
    assert got["maxmin_rounds_per_pass"]["value"] \
        >= got["solver_epochs_per_pass"]["value"]
    assert 0 < got["vmap_lane_use_pct"]["value"] <= 100
    assert got["maxmin_rounds_per_lane"]["value"] >= 1
