"""Batched dynamic-segment solver regressions (PR 10).

Deterministic fixed-seed halves of the invariants driven by
``_segment_props`` (the hypothesis wrappers live in
``test_protocol_properties``), plus the dynamic-op registry
token regression.
"""
import math

import numpy as np

from repro.core import fattree
from repro.core.engine import make_engine
from repro.core.flowsim import static_maxmin, static_maxmin_loops
from repro.core.workload import GroupOp, MemberEvent


# ------------------------------------------------- vectorized filling

def test_static_maxmin_bit_identity():
    from _segment_props import run_solver_identity_case
    for seed in range(8):
        run_solver_identity_case(seed)
    run_solver_identity_case(99, n_flows=1, n_links=3)
    run_solver_identity_case(100, n_flows=40, n_links=8)


def test_static_maxmin_edge_cases():
    assert static_maxmin(np.array([1e9]), []).shape == (0,)
    # single flow, and everyone contending for one shared link
    cap = np.array([1e9, 2e9])
    for sets in ([(0, 1)], [(0,), (0,), (0, 1)]):
        vec = static_maxmin(cap, sets)
        ref = static_maxmin_loops(cap, sets)
        assert (vec == ref).all()


# -------------------------------------------- dynamic-op registry keys

def test_dynamic_registry_tokens_never_reused():
    """Allocate/free dynamic ops in a loop: the old ``id(hidden)`` keys
    could collide once records were garbage-collected; monotonic tokens
    must never repeat, registries must drain after every run, and the
    workload must stay deterministic across iterations."""
    eng = make_engine("flow-np", fattree.testbed(n_hosts=8))
    events = (MemberEvent("leave", "h3", 2e-5),)
    seen, jcts = set(), []
    for _ in range(6):
        t0 = eng.now
        rec = eng.stage(GroupOp("bcast", ["h0", "h1", "h2", "h3"],
                                1 << 18, events=events))
        toks = set(eng._dyn_links)
        assert toks and not (toks & seen)
        seen |= toks
        eng.run()
        assert not eng._dyn_links and not eng._dyn_meta \
            and not eng._seg_fair
        jcts.append(rec.t_sender_cqe - t0)
    assert eng._dyn_seq == 6
    # absolute-time offsets cost a last-place ulp per iteration, no more
    assert all(math.isclose(j, jcts[0], rel_tol=1e-9) for j in jcts)


# ------------------------------------------------ batched == oracle

def test_batched_matches_legacy_numpy():
    from _segment_props import run_engine_timeline_case
    for seed in range(3):
        run_engine_timeline_case(seed, n_ops=3, engine="flow-np")


def test_lone_dynamic_op_scenarios_numpy():
    from _segment_props import run_engine_timeline_case
    run_engine_timeline_case(3, n_ops=2, engine="flow-np",
                             scenarios=True)


def test_batched_matches_legacy_jax():
    from _segment_props import run_engine_timeline_case
    run_engine_timeline_case(0, n_ops=3, engine="flow")


def test_segment_rates_many_parity():
    from _segment_props import run_segment_rates_parity_case
    for seed in range(4):
        run_segment_rates_parity_case(seed)
    run_segment_rates_parity_case(7, with_loss=False)


# ------------------------------------------------ zero-event identity

def _static_records(engine, mode):
    eng = make_engine(engine, fattree.testbed(n_hosts=10),
                      segment_solver=mode)
    ops = [GroupOp("bcast", [f"h{i}" for i in range(5)], 1 << 18),
           GroupOp("bcast", ["h5", "h6", "h7"], 1 << 16)]
    recs = [eng.stage(op) for op in ops]
    eng.run()
    return [(r.t_sender_cqe, sorted(r.t_deliver.items()))
            for r in recs]


def test_zero_event_bit_identity_numpy():
    assert _static_records("flow-np", "batched") == \
        _static_records("flow-np", "legacy")


def test_zero_event_bit_identity_jax():
    assert _static_records("flow", "batched") == \
        _static_records("flow", "legacy")


# ------------------------------------------------ memoized warm starts

def test_segment_memo_stable_across_runs():
    """Identical workloads re-run on one engine hit the segment-rate
    memo (warm start) and must reproduce the first run exactly."""
    eng = make_engine("flow-np", fattree.testbed(n_hosts=8))

    def go():
        t0 = eng.now
        recs = [eng.stage(GroupOp("bcast", ["h0", "h1", "h2", "h3"],
                                  1 << 18,
                                  events=(MemberEvent("join", "h5",
                                                      1.5e-5),))),
                eng.stage(GroupOp("bcast", ["h4", "h6", "h7"],
                                  1 << 18))]
        eng.run()
        return [r.t_sender_cqe - t0 for r in recs]

    first = go()
    memo = eng._sim.cache.sync().misc.get("segrates")
    assert memo                      # batched solves were memoized
    assert go() == first
