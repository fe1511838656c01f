"""Matrix sweep — churn x loss x faults cross-product at Fig. 14 scale
(the headline for the ISSUE-10 batched dynamic-segment solver; no
counterpart figure in the paper, which evaluates each axis alone).

Every cell of the grid stages ``N_GROUPS`` contending bcasts on ONE
fabric with all three planes riding the same ops:

- **churn** — alternating ``leave``/``join`` ``MemberEvent``s at
  interval ``1/rate`` (tail members leave, per-group spares join);
- **loss** — the engine-level calibrated loss/DCQCN model
  (``loss_rate=``), folded into the SAME per-segment solves by the
  batched solver (churn-under-loss is native, not a post-hoc scale);
- **faults** — ``link_flap`` ``FaultEvent``s on member racks' plane-0
  uplinks (plane 1 keeps every member routable).

The full grid runs the flow engine on a 4096-host 3-layer fat-tree —
8 groups x 32 members per cell, every dynamic op cut into piecewise
segments.  Before the batched solver each segment cost one serial
``static_maxmin`` call from inside the staging loop; now per-scenario
timelines are bucketed by padded shape and solved device-resident in a
handful of vmapped calls (see docs/ARCHITECTURE.md "Dynamic-segment
solver"), which is what makes this cross-product tractable.

A small-scale twin of the same grid (16-host, 2 agg planes) runs on
BOTH engines and reports the packet-vs-flow JCT divergence per cell —
the acceptance gate is <= 15% (tools/check_matrix.py).

Standalone:

    PYTHONPATH=src python benchmarks/fig_matrix.py --engine flow
    PYTHONPATH=src python benchmarks/fig_matrix.py --smoke
"""
from __future__ import annotations

import argparse
import os
import sys
import time

if __package__ in (None, ""):      # `python benchmarks/fig_matrix.py`
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "src"))

from repro.core import fattree
from repro.core.engine import make_engine
from repro.core.workload import FaultEvent, GroupOp, MemberEvent, Workload

NBYTES = 1 << 20
CHURN_RATES = (0.0, 5e4)                # membership events / second
LOSS_RATES = (0.0, 1e-3)               # per-packet loss probability
FLAPS = (0, 2)                         # link flaps riding each op
N_EVENTS = 4                           # alternating leave / join
SPARES = N_EVENTS                      # joinable hosts per group
N_GROUPS, GROUP = 8, 32                # full-scale cell shape
FAULT_AT = 3e-6
FAULT_GAP = 5e-6
FLAP_DURATION = 20e-6
NBYTES_SMALL = 1 << 19                 # packet-vs-flow parity twin
N_GROUPS_SMALL, GROUP_SMALL = 2, 4


def build_topo(smoke: bool = False):
    if smoke:
        # fig_faults' 16-host twin: 2 agg planes keep every leaf a
        # surviving uplink under any single flap
        return fattree.fat_tree(n_pods=2, leaves_per_pod=2,
                                hosts_per_leaf=4, aggs_per_pod=2)
    # Fig. 14's size class: 16 pods x 16 leaves x 16 hosts = 4096
    return fattree.fat_tree(n_pods=16, leaves_per_pod=16,
                            hosts_per_leaf=16, aggs_per_pod=4)


def _leaf_agg(host: str):
    """(leaf, plane-0 agg) of ``h{pod}.{leaf}.{idx}``."""
    pod, leaf, _ = host[1:].split(".")
    return f"L{pod}.{leaf}", f"A{pod}.0"


def cell_ops(hosts, n_groups, group, churn_rate, n_flaps,
             nbytes=NBYTES, spares=SPARES):
    """One matrix cell: ``n_groups`` contending bcasts over disjoint
    host blocks, each op carrying its cell's churn schedule and link
    flaps."""
    stride = group + spares
    assert n_groups * stride <= len(hosts), (n_groups, stride, len(hosts))
    ops = []
    for g in range(n_groups):
        block = hosts[g * stride:(g + 1) * stride]
        members, spare = block[:group], block[group:]
        events = []
        if churn_rate > 0:
            dt = 1.0 / churn_rate
            for i in range(N_EVENTS):
                if i % 2 == 0:
                    events.append(MemberEvent(
                        "leave", members[-1 - i // 2], (i + 1) * dt))
                else:
                    events.append(MemberEvent(
                        "join", spare[i // 2], (i + 1) * dt))
        leaves = []
        for m in members[1:]:           # distinct member racks
            la = _leaf_agg(m)
            if la not in leaves:
                leaves.append(la)
        faults = tuple(
            FaultEvent("link_flap", FAULT_AT + i * FAULT_GAP,
                       node=leaves[i % len(leaves)][0],
                       peer=leaves[i % len(leaves)][1],
                       duration=FLAP_DURATION)
            for i in range(n_flaps))
        ops.append(GroupOp("bcast", members, nbytes,
                           events=tuple(events), faults=faults))
    return ops


def _cells():
    return [(churn, flaps) for churn in CHURN_RATES for flaps in FLAPS]


def sweep_grid(engine_name, topo, n_groups, group, nbytes,
               workers=None, timeout=120.0, seeds=1, engine_kw=None):
    """The full (churn x flaps) grid for each loss level, one
    ``run_workloads`` batch per engine pass; {(churn, loss, flaps): jct}.

    Lossy packet points average ``seeds`` independent repetitions —
    the packet engine SAMPLES drops and RTO stalls while the flow
    model charges their expectation, so a single draw can sit a whole
    stall tail away from the mean (the fig15 convention)."""
    out = {}
    cells = _cells()
    for loss in LOSS_RATES:
        reps = seeds if (loss and engine_name == "packet") else 1
        kw = {"loss_rate": loss} if loss else {}
        kw.update(engine_kw or {})
        eng = make_engine(engine_name, topo, **kw)
        all_ops = [cell_ops(topo.hosts, n_groups, group, churn, flaps,
                            nbytes=nbytes)
                   for churn, flaps in cells]
        run_kw = {"workers": workers} if workers is not None else {}
        recss = eng.run_workloads([Workload(ops=ops) for ops in all_ops]
                                  * reps, timeout=timeout, **run_kw)
        for i, (cell, ops) in enumerate(zip(cells, all_ops)):
            # cell metric: MEAN over the cell's group JCTs — linear in
            # the per-op values, so the sampled packet mean and the
            # flow engine's expected values are directly comparable
            # (max-over-groups would bias the sampled side up:
            # E[max] > max(E))
            js = [sum(rec.jct(len(op.surviving_receivers()))
                      for op, rec in zip(ops,
                                         recss[r * len(cells) + i]))
                  / len(ops) for r in range(reps)]
            out[(cell[0], loss, cell[1])] = sum(js) / reps
    return out


def run(rows, engine="flow", workers=0, smoke=False):
    flow_engine = engine if engine.startswith("flow") else "flow"
    # 1) full-scale grid, flow engine (4096 hosts; smoke: 16)
    topo = build_topo(smoke)
    n_groups, group = (N_GROUPS_SMALL, GROUP_SMALL) if smoke \
        else (N_GROUPS, GROUP)
    jct = sweep_grid(flow_engine, topo, n_groups, group, NBYTES)
    for (churn, loss, flaps), j in sorted(jct.items()):
        rows.append((
            f"figmatrix/jct_c{churn:g}_l{loss:g}_f{flaps}/flow_ms",
            j * 1e3,
            f"groups={n_groups}x{group} hosts={len(topo.hosts)} "
            f"events={N_EVENTS if churn else 0} flaps={flaps}"))
    # 2) small-scale packet-vs-flow parity twin (every cell, both
    # engines; the <= 15% gate lives in tools/check_matrix.py)
    small = build_topo(smoke=True)
    jp = sweep_grid("packet", small, N_GROUPS_SMALL, GROUP_SMALL,
                    NBYTES_SMALL, workers=workers, seeds=16)
    jf = sweep_grid(flow_engine, small, N_GROUPS_SMALL, GROUP_SMALL,
                    NBYTES_SMALL)
    for cell in sorted(jp):
        churn, loss, flaps = cell
        div = abs(jp[cell] - jf[cell]) / jp[cell] if jp[cell] else 0.0
        rows.append((
            f"figmatrix/parity_c{churn:g}_l{loss:g}_f{flaps}/packet_ms",
            jp[cell] * 1e3,
            f"flow={jf[cell] * 1e3:.4f}ms div={100 * div:.1f}% "
            f"(16-seed mean; the CI gate compares against the frozen "
            f"64-seed GT, tools/check_matrix.py)"))
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--engine", default="flow",
                    choices=("packet", "flow", "flow-np"),
                    help="flow backend for the grid (packet always "
                         "runs the small parity twin)")
    ap.add_argument("--smoke", action="store_true",
                    help="16-host grid instead of 4096 (CI smoke)")
    ap.add_argument("--workers", type=int, default=0,
                    help="packet-engine scenario workers (0 = per CPU)")
    args = ap.parse_args(argv)
    rows: list = []
    t0 = time.time()
    run(rows, engine=args.engine, workers=args.workers, smoke=args.smoke)
    print("name,value,derived")
    for n, v, d in rows:
        print(f"{n},{v:.3f},{d}")
    print(f"# fig_matrix done in {time.time() - t0:.1f}s",
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
