#!/usr/bin/env python3
"""Churn-scenario smoke gate (wired into CI).

Runs one dynamic-membership sweep (join / leave / fail / master-switch
mid-stream) and asserts the membership-control-plane invariants:

1. **packet + flow** — every scenario completes on BOTH engines and
   their JCTs agree within 10% (the ISSUE-5 acceptance bound);
2. **serial == workers=2** — the packet engine's scenario-parallel path
   reproduces the serial records bit for bit with dynamic events in
   flight (quiesce/fork machinery intact).

Exit code 0 = clean; 1 = divergence (details on stderr).

    PYTHONPATH=src python tools/check_churn.py
"""
from __future__ import annotations

import sys

from repro.core import fattree
from repro.core.engine import make_engine
from repro.core.workload import GroupOp, MemberEvent, Workload

MEMBERS = [f"h{i}" for i in range(8)]
NBYTES = 1 << 20
TOL = 0.10

SCENARIOS = [
    ("static", ()),
    ("join", (MemberEvent("join", "h8", 30e-6),)),
    ("leave", (MemberEvent("leave", "h7", 30e-6),)),
    ("fail", (MemberEvent("fail", "h7", 30e-6),)),
    ("mix", (MemberEvent("master-switch", "h1", 10e-6),
             MemberEvent("leave", "h6", 20e-6),
             MemberEvent("join", "h8", 40e-6),
             MemberEvent("fail", "h5", 60e-6))),
]


def run_engine(engine: str, workers):
    eng = make_engine(engine, fattree.testbed(n_hosts=10), **(
        {"loss_rate": 1e-5, "seed": 11} if engine == "packet" else {}))
    ops = [GroupOp("bcast", MEMBERS, NBYTES, events=ev)
           for _, ev in SCENARIOS]
    kw = {"workers": workers} if engine == "packet" else {}
    recs = [op_recs[0] for op_recs in eng.run_workloads(
        [Workload(ops=[op]) for op in ops], timeout=60.0, **kw)]
    return [(r.msg_id, r.t_submit, r.t_sender_cqe,
             sorted(r.t_deliver.items())) for r in recs], \
           [r.jct(len(op.surviving_receivers()))
            for r, op in zip(recs, ops)]


def main() -> int:
    problems = []
    serial, jct_p = run_engine("packet", None)
    parallel, _ = run_engine("packet", 2)
    if serial != parallel:
        problems.append("packet serial vs workers=2 records diverge")
    _, jct_f = run_engine("flow", None)
    for (name, _), jp, jf in zip(SCENARIOS, jct_p, jct_f):
        if jp == float("inf") or jf == float("inf"):
            problems.append(f"{name}: incomplete (packet={jp}, flow={jf})")
            continue
        div = abs(jp - jf) / jp
        print(f"check_churn: {name:7s} packet={jp * 1e3:.4f}ms "
              f"flow={jf * 1e3:.4f}ms div={100 * div:.1f}%")
        if div > TOL:
            problems.append(
                f"{name}: packet-vs-flow divergence {100 * div:.1f}% "
                f"> {100 * TOL:.0f}%")
    if problems:
        for p in problems:
            print(f"check_churn: {p}", file=sys.stderr)
        return 1
    print("check_churn: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
