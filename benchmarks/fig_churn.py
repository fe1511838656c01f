"""Membership churn — JCT and failure-recovery time vs churn rate and
group size, on BOTH engines (the headline for the §3.4 membership
control plane; no counterpart figure in the paper, which evaluates a
static world).

Scenario: one 1MB Gleam bcast per point, with timed membership events
riding the op (Workload-IR ``MemberEvent``s):

- the **churn axis** alternates graceful ``leave``s and ``join``s at
  interval ``1/rate`` — at low rates the events land after the message
  completes (churn is invisible to JCT, as it should be), at high rates
  the tree is rebuilt mid-stream;
- the **recovery axis** crashes one receiver (``fail``) mid-stream: the
  dead port freezes the aggregated-ACK minimum, the sender wedges once
  its go-back-N window drains, and the master's isolation envelope
  (+``fail_detect``) un-wedges it.  Recovery time is reported as the
  JCT penalty over the same point without the failure.

Every point runs on the packet engine (per-packet control plane: real
MFT-update envelopes, QP re-arm, isolation) AND the flow engine
(piecewise-membership segments), and the derived column carries the
packet-vs-flow divergence — the acceptance gate is <= 10%.  Packet
points of one group size run as a single ``run_workloads`` batch
(``--workers`` aware).
"""
from __future__ import annotations

from repro.core import fattree
from repro.core.engine import make_engine
from repro.core.workload import GroupOp, MemberEvent, Workload

NBYTES = 1 << 20
SIZES = (16, 64)
CHURN_RATES = (0.0, 2e3, 1e4, 5e4)      # membership events / second
N_EVENTS = 4                            # alternating leave / join
FAIL_AT = 30e-6                         # crash 30us into the stream
SPARES = N_EVENTS                       # joinable hosts beyond the group


def churn_events(group: int, rate: float):
    """Alternating leave/join schedule at interval ``1/rate``: members
    leave from the tail, spare hosts h{group}.. join in their stead."""
    if rate <= 0:
        return ()
    dt = 1.0 / rate
    evs = []
    for i in range(N_EVENTS):
        at = (i + 1) * dt
        if i % 2 == 0:
            evs.append(MemberEvent("leave", f"h{group - 1 - i // 2}", at))
        else:
            evs.append(MemberEvent("join", f"h{group + i // 2}", at))
    return tuple(evs)


def _points(group):
    members = [f"h{i}" for i in range(group)]
    pts = [(f"r{rate:g}", GroupOp("bcast", members, NBYTES,
                                  events=churn_events(group, rate)))
           for rate in CHURN_RATES]
    pts.append(("fail", GroupOp(
        "bcast", members, NBYTES,
        events=(MemberEvent("fail", f"h{group - 1}", FAIL_AT),))))
    return pts


def _sweep(engine_name, group, workers, timeout=120.0):
    """All of one group size's points as one independent-scenario batch;
    returns {label: jct_seconds}."""
    topo = fattree.testbed(n_hosts=group + SPARES)
    eng = make_engine(engine_name, topo)
    pts = _points(group)
    recss = eng.run_workloads([Workload(ops=[op]) for _, op in pts],
                              timeout=timeout, workers=workers)
    return {label: recs[0].jct(len(op.surviving_receivers()))
            for (label, op), recs in zip(pts, recss)}


def run(rows, engine="packet", workers=0, sizes=SIZES):
    # both engines always run — the packet-vs-flow divergence IS the
    # result; --engine only picks which flow solver to compare against
    flow_engine = engine if engine.startswith("flow") else "flow"
    for group in sizes:
        jct_p = _sweep("packet", group, workers)
        jct_f = _sweep(flow_engine, group, None)
        for rate in CHURN_RATES:
            label = f"r{rate:g}"
            jp, jf = jct_p[label], jct_f[label]
            div = abs(jp - jf) / jp if jp > 0 else 0.0
            rows.append((f"figchurn/jct_g{group}_{label}/packet_ms",
                         jp * 1e3,
                         f"events={len(churn_events(group, rate))} "
                         f"flow={jf * 1e3:.4f}ms div={100 * div:.1f}%"))
        # recovery: the fail point's JCT penalty over the static point
        rp = jct_p["fail"] - jct_p["r0"]
        rf = jct_f["fail"] - jct_f["r0"]
        div = abs(jct_p["fail"] - jct_f["fail"]) / jct_p["fail"]
        rows.append((f"figchurn/recovery_g{group}/packet_ms", rp * 1e3,
                     f"flow={rf * 1e3:.4f}ms div={100 * div:.1f}% "
                     f"(fail@{FAIL_AT * 1e6:.0f}us, detect=1ms)"))
    return rows
