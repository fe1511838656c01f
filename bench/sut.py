"""The system under test: the flow engine, driven as a sweep drives it.

Every pass builds a fresh engine over the one fabric the run set up
(``make_engine("flow", topo)``; the staging cache lives on the
``Topology``) and calls ``run_workloads`` with the pass's scenarios,
one ``Workload`` each.  This module is the only part of the benchmark
that imports the program.
"""
from __future__ import annotations

import os
import sys
from typing import Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def import_program():
    """Put the checkout's ``src/`` on the path; fail if it is absent."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"bench: the program is not in this checkout "
                         f"({src} has no repro package)")
    if src not in sys.path:
        sys.path.insert(0, src)


def build_fabric(fabric: dict):
    from repro.core import fattree
    return fattree.fat_tree(
        n_pods=fabric["n_pods"], leaves_per_pod=fabric["leaves_per_pod"],
        hosts_per_leaf=fabric["hosts_per_leaf"],
        aggs_per_pod=fabric["aggs_per_pod"],
        bw=fabric["host_gbps"] * fattree.GBPS,
        delay=fabric["link_delay_s"])


def _group_op(d: dict):
    from repro.core.workload import GroupOp
    return GroupOp(d["op"], tuple(d["members"]), d["nbytes"], key=d["key"],
                   phase=d["phase"])


def workloads(traffic: dict) -> List:
    """One ``Workload`` of fresh op objects per scenario."""
    from repro.core.workload import Workload
    out = []
    for i, ops in enumerate(traffic["scenarios"]):
        wl = Workload(f"bench/{i}")
        for d in ops:
            wl.add(_group_op(d))
        out.append(wl)
    return out


def run_pass(topo, wls, loss_rate: float):
    """One sweep pass through the entry users call; the records, and
    the engine (for its staging counters)."""
    from repro.core.engine import make_engine
    kw = {"loss_rate": loss_rate} if loss_rate else {}
    eng = make_engine("flow", topo, **kw)
    if eng.name != "flow":
        raise SystemExit(f"bench: make_engine('flow') built {eng.name!r}")
    recs = eng.run_workloads(wls, timeout=600.0)
    return recs, eng


def answers(recs) -> List[List[Dict]]:
    """Per scenario, per op: delivery and sender-CQE times relative to
    the op's submission, as plain data."""
    out = []
    for scen in recs:
        rows = []
        for r in scen:
            rows.append({
                "deliver": {m: t - r.t_submit
                            for m, t in r.t_deliver.items()},
                "cqe": (r.t_sender_cqe - r.t_submit
                        if r.t_sender_cqe >= 0.0 else None),
                "error": r.error})
        out.append(rows)
    return out


def staging_counts(topo):
    """(hits, misses) of the fabric's shared staging cache so far."""
    from repro.core.staging import StagingCache
    cache = StagingCache.of(topo)
    return cache.hits, cache.misses


def solve_stats():
    from repro.core import flowsim_jax
    return flowsim_jax.SOLVE_STATS


def reset_solve_stats():
    from repro.core import flowsim_jax
    flowsim_jax.reset_solve_stats()
