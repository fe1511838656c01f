"""Shared drivers for the dynamic-segment solver invariants (ISSUE 10),
used by BOTH the hypothesis property tests
(``test_protocol_properties``) and the deterministic fixed-seed cases
in ``test_segments`` (run everywhere — hypothesis is optional).

Three acceptance properties, as executable drivers:

- **vectorized filling bit-identity** — the CSR/np.add.at
  ``static_maxmin`` reproduces the original per-flow-loop
  implementation bit for bit on arbitrary problems;
- **batched == per-segment oracle** — the batched segment solver
  (numpy and device paths) matches the legacy per-segment
  ``static_maxmin`` closures: bit-identical on the numpy backend,
  <= 1e-6 relative on the JAX backend (float64, same tol, same round
  cap — only reduction-order rounding differs);
- **zero-event bit-identity** — workloads with no events/faults never
  touch the segment machinery: batched and legacy modes produce
  bit-identical records on both flow backends.
"""
from __future__ import annotations

import numpy as np

from repro.core import fattree
from repro.core.engine import make_engine
from repro.core.flowsim import (FlowSim, LossParams, static_maxmin,
                                static_maxmin_loops)
from repro.core.workload import GroupOp, MemberEvent, Workload

NBYTES = 1 << 18
SEG_TOL = 1e-6              # jax-vs-oracle acceptance bound


def random_problem(rng, n_links: int, n_flows: int):
    """(cap, link_sets): random capacities and duplicate-free sets."""
    cap = rng.uniform(1e8, 4e9, n_links)
    hi = min(7, n_links + 1)
    sets = [tuple(int(x) for x in
                  rng.choice(n_links, size=int(rng.integers(1, hi)),
                             replace=False))
            for _ in range(n_flows)]
    return cap, sets


def run_solver_identity_case(seed: int, n_flows: int = 12,
                             n_links: int = 24) -> None:
    """Vectorized ``static_maxmin`` == loop oracle, bit for bit."""
    rng = np.random.default_rng(seed)
    cap, sets = random_problem(rng, n_links, n_flows)
    vec = static_maxmin(cap, sets)
    ref = static_maxmin_loops(cap, sets)
    assert vec.shape == ref.shape
    assert (vec == ref).all(), (vec, ref)


def random_dynamic_ops(rng, n_ops: int, pool: int = 12):
    """Random bcast ops with valid join/leave/fail timelines."""
    hosts = [f"h{i}" for i in range(pool)]
    ops = []
    for _ in range(n_ops):
        size = int(rng.integers(3, 7))
        members = [hosts[i] for i in
                   rng.choice(pool, size=size, replace=False)]
        spare = [h for h in hosts if h not in members]
        present = set(members)
        events = []
        t = 0.0
        for _ in range(int(rng.integers(0, 4))):
            t += float(rng.uniform(5e-6, 4e-5))
            if spare and rng.random() < 0.5:
                m = spare.pop(int(rng.integers(len(spare))))
                events.append(MemberEvent("join", m, t))
                present.add(m)
            else:
                cands = sorted(m for m in present if m != members[0])
                if not cands:
                    continue
                m = cands[int(rng.integers(len(cands)))]
                kind = "leave" if rng.random() < 0.5 else "fail"
                events.append(MemberEvent(kind, m, t))
                present.remove(m)
        ops.append(GroupOp("bcast", members, NBYTES,
                           events=tuple(events)))
    return ops


def _records(engine: str, mode: str, ops, loss_rate: float = 0.0,
             scenarios: bool = False):
    """Run ops on one engine/segment-solver mode; full record rows."""
    kw = {"loss_rate": loss_rate} if loss_rate else {}
    eng = make_engine(engine, fattree.testbed(n_hosts=14),
                      segment_solver=mode, **kw)
    if scenarios:                       # one op per isolated scenario
        recs = [r for (r,) in eng.run_workloads(
            [Workload("seg", [op]) for op in ops], timeout=60.0)]
    else:                               # all ops contend in one fabric
        recs = [eng.stage(op) for op in ops]
        eng.run()
    return [(r.t_sender_cqe, sorted(r.t_deliver.items())) for r in recs]


def run_engine_timeline_case(seed: int, n_ops: int = 3,
                             engine: str = "flow-np",
                             scenarios: bool = False) -> None:
    """Batched vs legacy on a random event timeline: bit-identical on
    the numpy backend (same solver, same problems), <= 1e-6 on JAX."""
    rng = np.random.default_rng(seed)
    ops = random_dynamic_ops(rng, n_ops)
    got = _records(engine, "batched", ops, scenarios=scenarios)
    want = _records(engine, "legacy", ops, scenarios=scenarios)
    if engine == "flow-np":
        assert got == want, (got, want)
        return
    for (gc, gd), (wc, wd) in zip(got, want):
        assert abs(gc - wc) <= SEG_TOL * wc, (gc, wc)
        for (m, gt), (_, wt) in zip(gd, wd):
            assert abs(gt - wt) <= SEG_TOL * wt, (m, gt, wt)


def random_loss_params(rng) -> LossParams:
    """Plausible pre-folded loss-model inputs (see LossParams)."""
    return LossParams(q=float(rng.uniform(0.0, 0.05)),
                      wsq=float(rng.uniform(0.0, 1e-4)),
                      wnd=float(rng.choice([64.0, 256.0, 512.0])),
                      tail=0.0, ecn=bool(rng.random() < 0.5))


def run_segment_rates_parity_case(seed: int, n_problems: int = 6,
                                  with_loss: bool = True) -> None:
    """JAX ``segment_rates_many`` vs the numpy oracle, <= 1e-6."""
    from repro.core.flowsim_jax import JaxFlowSim
    topo = fattree.testbed(n_hosts=12)
    np_sim = FlowSim(topo)
    jx_sim = JaxFlowSim(topo)
    rng = np.random.default_rng(seed)
    n_links = len(np_sim.cap)
    problems = []
    for _ in range(n_problems):
        _, sets = random_problem(rng, n_links,
                                 int(rng.integers(2, 9)))
        lp = random_loss_params(rng) \
            if with_loss and rng.random() < 0.7 else None
        problems.append((tuple(sets), lp))
    want = np_sim.segment_rates_many(problems)
    got = jx_sim.segment_rates_many(problems)
    for g, w in zip(got, want):
        assert abs(g - w) <= SEG_TOL * w, (g, w)
