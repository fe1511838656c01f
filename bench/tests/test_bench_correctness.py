"""``correct`` at a size a test run holds: the program agrees with the
plain reference, the reference in the next precision down (the
control) does not, and a run whose timed path is broken underneath
comes out not correct."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import check, control, run, sut  # noqa: E402
from bench.tests import _tiny  # noqa: E402

CELLS = ["hpl16k.decay", "loss4k.fresh"]
SEED = 2 ** 31 + 77


@pytest.mark.parametrize("cell", CELLS)
def test_control_reads_above_the_limit(cell):
    cfg, mix = _tiny.cell(cell)
    limit = check.limits(cell)["max_rel_gap"]["limit"]
    assert control.reading(cfg, mix, SEED, 6, 4) > limit


def _drawn(seed, n_passes, n_classes, size):
    picked = check.Sample(seed, n_classes, size)
    for i in range(n_passes):
        picked.offer(i % n_classes, i)
    return picked.items()


def test_sample_covers_every_class_and_follows_the_seed():
    got = _drawn(SEED, 500, 5, 8)
    assert len(got) == 10 and len(set(got)) == 10
    assert sorted(i % 5 for i in got) == sorted(list(range(5)) * 2)
    assert got == _drawn(SEED, 500, 5, 8)
    assert got != _drawn(SEED + 1, 500, 5, 8)
    assert max(got) >= 50              # not only the window's start
    assert _drawn(SEED, 3, 8, 8) == [0, 1, 2]


def _run(cell, monkeypatch):
    sut.import_program()
    monkeypatch.setattr(run, "use_compile_cache", lambda: None)
    cfg, mix = _tiny.cell(cell)
    b = _tiny.bench_json()
    return run.run_cell(cfg, mix, check.limits(cell), SEED, 0.3, False,
                        run.metric_names(b, cell, False),
                        {"platform": "cpu", "kind": "cpu", "count": 1})


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, monkeypatch):
    res = _run(cell, monkeypatch)
    assert res["correct"] and res["failed"] == 0
    assert res["checks"]["max_rel_gap"]["value"] < 1e-6
    assert set(res["metrics"]) >= {"ops_per_s", "setup_s"}


def _altered_answer(monkeypatch):
    """One flow's completion altered where the solver produces it."""
    from repro.core import flowsim_jax
    orig = flowsim_jax.JaxFlowSim._finish

    def finish(self, flows, done):
        done = np.array(done, copy=True)
        done[0] *= 1.01
        return orig(self, flows, done)
    monkeypatch.setattr(flowsim_jax.JaxFlowSim, "_finish", finish)


def _half_left_out(monkeypatch):
    """Every other flow of the solver's batch left out of the solve;
    the left-out flows take the mean completion of the rest."""
    from repro.core import flowsim_jax
    orig = flowsim_jax.JaxFlowSim.solve_many

    def solve_many(self, epochs):
        flat = [f for ep in epochs for f in ep]
        left = {id(f) for f in flat[1::2]}
        out = orig(self, [[f for f in ep if id(f) not in left]
                          for ep in epochs])
        kept = [f.done_t for f in flat[::2]]
        for f in flat[1::2]:
            f.done_t = sum(kept) / len(kept)
            f.remaining = 0.0
        return out
    monkeypatch.setattr(flowsim_jax.JaxFlowSim, "solve_many", solve_many)


def _state_unchanged(monkeypatch):
    """Every pass returns the first pass's records."""
    from repro.core import engine
    orig = engine.FlowEngine.run_workloads
    first = []

    def run_workloads(self, workloads, *a, **kw):
        recs = orig(self, workloads, *a, **kw)
        if not first:
            first.append(recs)
        return first[0]
    monkeypatch.setattr(engine.FlowEngine, "run_workloads", run_workloads)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [_altered_answer, _half_left_out,
                                   _state_unchanged])
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    sut.import_program()
    fault(monkeypatch)
    res = _run(cell, monkeypatch)
    assert not res["correct"]


def test_no_accelerator_exits_non_zero_with_no_result(capsys):
    # the tests run with JAX on the CPU
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", CELLS[0], "--seed", str(SEED),
                  "--seconds", "1"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""
