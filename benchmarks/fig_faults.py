"""Fault injection — recovery latency and JCT vs fault rate, group size
and scheme, on BOTH engines (the headline for the ISSUE-7 fault plane;
the paper's failure evaluation stops at a single silent receiver crash,
Appendix B).

Scenario: one 1MB bcast per point on a 2-pod fat-tree with two agg
planes (every leaf keeps a surviving uplink under any single fault),
with timed faults riding the op (Workload-IR ``FaultEvent``s):

- the **fault-rate axis** injects ``link_flap``s at interval ``1/rate``
  on the member leaves' plane-0 uplinks — at low rates the flap lands
  after the message completes (invisible to JCT, as it should be), at
  high rates the stream takes real RTO stalls and the tree is repaired
  mid-flight;
- the **recovery axis** runs one scenario per fault class
  (link_down / switch_fail / host_gone_dark / master_crash) with the
  fault 3us into the stream.  Recovery is reported as the JCT penalty
  over the same point without the fault: RTO-bounded for fabric
  faults, ``link_detect``-bounded for a dark host (switch-originated
  teardown confirm, no master round trip), ``fail_detect``-bounded for
  a master crash (member-driven re-election).

Two correlated multi-fault rows ride each group size (``storm_cases``):
a plane-wide link storm and a whole-rack blast — several
``FaultEvent``s in ONE scenario, stressing concurrent repair instead
of the one-fault-at-a-time recovery axis.

Every point runs on the packet engine (real repair envelopes, bounded
retry, re-election) AND the flow engine (piecewise stall/dark
segments); the derived column carries the packet-vs-flow divergence —
the acceptance gate is <= 15% (tools/check_faults.py).  The overlay
row (``ring-dark``) exercises the relay-schedule repair path in
baselines.py: a mid-ring relay goes dark and its children are spliced
onto the dead relay's parent.

Each point runs on a FRESH engine (no shared ``run_workloads`` fabric):
Algorithm 4 balances tree edges across the agg planes by accumulated
port utilization, so a point's tree — and therefore whether a given
fault even touches it — would otherwise depend on its batch position.
On a fresh fabric both engines deterministically root the tree on
plane 0, which is where the fault targets aim.
"""
from __future__ import annotations

from repro.core import fattree
from repro.core.engine import make_engine
from repro.core.workload import FaultEvent, GroupOp

NBYTES = 1 << 20
SIZES = (4, 8)
FAULT_RATES = (0.0, 2e3, 1e4, 5e4)      # fault events / second
N_FAULTS = 2                            # flaps along the rate axis
FLAP_DURATION = 20e-6
FAULT_AT = 3e-6                         # recovery-axis fault, 3us in


def build_topo():
    # 2 pods x 2 leaves x 4 hosts, two agg planes: any single link or
    # agg-switch fault leaves every leaf a surviving path
    return fattree.fat_tree(n_pods=2, leaves_per_pod=2, hosts_per_leaf=4,
                            aggs_per_pod=2)


def members_for(group: int):
    """Members interleaved across leaves so faults hit real tree edges:
    h0.0.0, h0.1.0, h1.0.0, h1.1.0, then the .1 hosts, ..."""
    hosts = [f"h{p}.{l}.{h}" for h in range(4)
             for p in range(2) for l in range(2)]
    return hosts[:group]


def flap_events(members, rate: float):
    """``link_flap``s at interval ``1/rate`` cycling over non-source
    member leaves' plane-0 uplinks (the fresh-fabric tree's plane;
    never both uplinks of one leaf at once — the plan must keep every
    member routable)."""
    if rate <= 0:
        return ()
    leaves = []
    for m in members[1:]:                       # skip the source's leaf
        leaf = f"L{m[1]}.{m[3]}"
        if leaf not in leaves:
            leaves.append(leaf)
    return tuple(
        FaultEvent("link_flap", (i + 1) / rate,
                   node=leaves[i % len(leaves)],
                   peer=f"A{leaves[i % len(leaves)][1]}.0",
                   duration=FLAP_DURATION)
        for i in range(N_FAULTS))


def recovery_cases(members):
    """(label, faults) per fault class, targeting the last member's
    plane-0 branch of the tree."""
    last = members[-1]
    leaf = f"L{last[1]}.{last[3]}"
    agg = f"A{last[1]}.0"
    return [
        ("link_down", (FaultEvent("link_down", FAULT_AT, node=leaf,
                                  peer=agg),)),
        ("switch_fail", (FaultEvent("switch_fail", FAULT_AT, node=agg),)),
        ("host_dark", (FaultEvent("host_gone_dark", FAULT_AT,
                                  node=last),)),
        ("master_crash", (FaultEvent("master_crash", FAULT_AT),)),
    ]


def storm_cases(members):
    """Correlated multi-``FaultEvent`` scenarios (blast radius > 1).

    - ``storm``: a correlated link storm — plane 0 drops across EVERY
      member rack within a microsecond, so the repair fan-out to
      plane 1 runs for all branches concurrently instead of one at a
      time (the fault plan is validated cumulatively: plane 1 keeps
      every member routable throughout);
    - ``rack-blast``: the last member's whole rack dies in one blast —
      every non-source member on that leaf goes dark back-to-back
      while the leaf's plane-0 uplink drops, exercising teardown
      cascades racing a link repair on the same branch.
    """
    leaves = []
    for m in members[1:]:                       # skip the source's leaf
        leaf = f"L{m[1]}.{m[3]}"
        if leaf not in leaves:
            leaves.append(leaf)
    storm = tuple(FaultEvent("link_down", FAULT_AT + i * 1e-7, node=lf,
                             peer=f"A{lf[1]}.0")
                  for i, lf in enumerate(leaves))
    last = members[-1]
    rack_leaf = f"L{last[1]}.{last[3]}"
    rack = [m for m in members[1:] if f"L{m[1]}.{m[3]}" == rack_leaf]
    # the servers die first, then the ToR uplink drops — by the time
    # the link fault lands no live receiver sits behind it, so neither
    # engine should charge a repair stall to the survivors
    blast = tuple(FaultEvent("host_gone_dark", FAULT_AT + i * 1e-7,
                             node=m)
                  for i, m in enumerate(rack))
    blast += (FaultEvent("link_down", FAULT_AT + len(rack) * 1e-7,
                         node=rack_leaf, peer=f"A{last[1]}.0"),)
    return [("storm", storm), ("rack-blast", blast)]


def _points(group):
    members = members_for(group)
    pts = [(f"r{rate:g}", GroupOp("bcast", members, NBYTES,
                                  faults=flap_events(members, rate)))
           for rate in FAULT_RATES]
    pts += [(label, GroupOp("bcast", members, NBYTES, faults=faults))
            for label, faults in recovery_cases(members)]
    pts += [(label, GroupOp("bcast", members, NBYTES, faults=faults))
            for label, faults in storm_cases(members)]
    # overlay relay repair: a mid-ring relay goes dark
    pts.append(("ring-dark", GroupOp(
        "bcast", members, NBYTES, transport="ring",
        faults=(FaultEvent("host_gone_dark", FAULT_AT,
                           node=members[len(members) // 2]),))))
    pts.append(("ring-r0", GroupOp("bcast", members, NBYTES,
                                   transport="ring")))
    return pts


def _sweep(engine_name, group, timeout=60.0):
    """One fresh engine per point (see module docstring); returns
    {label: (jct_seconds, error)}."""
    out = {}
    for label, op in _points(group):
        eng = make_engine(engine_name, build_topo())
        rec = eng.stage(op)
        eng.run(timeout=timeout)
        out[label] = (rec.jct(len(op.surviving_receivers())), rec.error)
    return out


def run(rows, engine="packet", sizes=SIZES):
    # both engines always run — the packet-vs-flow divergence IS the
    # result; --engine only picks which flow solver to compare against
    flow_engine = engine if engine.startswith("flow") else "flow"
    for group in sizes:
        jct_p = _sweep("packet", group)
        jct_f = _sweep(flow_engine, group)
        for rate in FAULT_RATES:
            label = f"r{rate:g}"
            (jp, ep), (jf, _) = jct_p[label], jct_f[label]
            div = abs(jp - jf) / jp if jp > 0 else 0.0
            n_ev = len(flap_events(members_for(group), rate))
            rows.append((f"figfaults/jct_g{group}_{label}/packet_ms",
                         jp * 1e3,
                         f"flaps={n_ev} flow={jf * 1e3:.4f}ms "
                         f"div={100 * div:.1f}%"
                         + (f" error={ep}" if ep else "")))
        # recovery: each fault class's JCT penalty over the clean point
        for label, _ in recovery_cases(members_for(group)):
            rp = jct_p[label][0] - jct_p["r0"][0]
            rf = jct_f[label][0] - jct_f["r0"][0]
            div = abs(jct_p[label][0] - jct_f[label][0]) / jct_p[label][0]
            rows.append((f"figfaults/recovery_g{group}_{label}/packet_us",
                         rp * 1e6,
                         f"flow={rf * 1e6:.2f}us div={100 * div:.1f}%"))
        # correlated storms: several faults riding ONE scenario
        for label, faults in storm_cases(members_for(group)):
            rp = jct_p[label][0] - jct_p["r0"][0]
            rf = jct_f[label][0] - jct_f["r0"][0]
            div = abs(jct_p[label][0] - jct_f[label][0]) / jct_p[label][0]
            rows.append((f"figfaults/recovery_g{group}_{label}/packet_us",
                         rp * 1e6,
                         f"flow={rf * 1e6:.2f}us div={100 * div:.1f}% "
                         f"({len(faults)} correlated faults)"))
        # overlay: dead mid-ring relay, children respliced
        rp = jct_p["ring-dark"][0] - jct_p["ring-r0"][0]
        rf = jct_f["ring-dark"][0] - jct_f["ring-r0"][0]
        div = (abs(jct_p["ring-dark"][0] - jct_f["ring-dark"][0])
               / jct_p["ring-dark"][0])
        rows.append((f"figfaults/recovery_g{group}_ring-dark/packet_us",
                     rp * 1e6,
                     f"flow={rf * 1e6:.2f}us div={100 * div:.1f}% "
                     f"(overlay relay resplice)"))
    return rows
