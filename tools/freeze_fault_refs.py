"""Freeze the zero-fault reference records for tools/check_faults.py.

Run ONCE against the pre-fault-plane tree (PR-6) to capture fixed-seed
ground truth; ``check_faults.py`` then asserts that zero-fault scenarios
stay bit-identical after the fault subsystem landed.  Keep the scenarios
expressible in the PR-6 Workload IR (no ``faults=`` field) so the frozen
file never needs regenerating.

    PYTHONPATH=src python tools/freeze_fault_refs.py
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core import fattree, workload as wl          # noqa: E402
from repro.core.engine import make_engine               # noqa: E402

OUT = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                   "ref_faults_zero.json")

NBYTES = 1 << 18
SEED = 7


def scenarios():
    """(name, op) pairs — PR-6 IR only (no faults)."""
    return [
        ("static-g8", wl.GroupOp("bcast", [f"h{i}" for i in range(8)],
                                 NBYTES)),
        ("churn-g6", wl.GroupOp(
            "bcast", [f"h{i}" for i in range(6)], NBYTES,
            events=(wl.MemberEvent("join", "h7", 4e-5),
                    wl.MemberEvent("leave", "h3", 8e-5)))),
        ("ring-g6", wl.GroupOp("bcast", [f"h{i}" for i in range(6)],
                               NBYTES, transport="ring")),
    ]


def record_rows(engine_name):
    topo = fattree.testbed(n_hosts=10)
    kw = {"seed": SEED} if engine_name == "packet" else {}
    eng = make_engine(engine_name, topo, **kw)
    ops = [op for _, op in scenarios()]
    recs = [op_recs[0] for op_recs in eng.run_workloads(
        [wl.Workload(ops=[op]) for op in ops], timeout=60.0)]
    rows = {}
    for (name, op), r in zip(scenarios(), recs):
        rows[name] = {
            "t_submit": repr(float(r.t_submit)),
            "t_sender_cqe": repr(float(r.t_sender_cqe)),
            "t_deliver": sorted((m, repr(float(t)))
                                for m, t in r.t_deliver.items()),
            "jct": repr(float(r.jct(len(op.surviving_receivers())))),
        }
    return rows


def main():
    ref = {"nbytes": NBYTES, "seed": SEED,
           "engines": {name: record_rows(name)
                       for name in ("packet", "flow-np")}}
    with open(OUT, "w") as fh:
        json.dump(ref, fh, indent=1, sort_keys=True)
        fh.write("\n")
    print(f"wrote {os.path.normpath(OUT)}")


if __name__ == "__main__":
    main()
