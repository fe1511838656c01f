"""Cross-validation of the SimEngine backends (core/engine.py).

The packet engine is the fidelity reference; the flow engines must agree
with it on topologies small enough for both to run.  ISSUE acceptance:
JCT within 10% on a small topology FOR EVERY TRANSPORT (gleam /
multiunicast / ring / binary-tree) — asserted here on the paper's
testbed across message sizes, plus the original gleam checks on a
2-pod fat tree.  The two flow solvers (numpy / JAX) must agree with
each other far tighter.
"""
from __future__ import annotations

import sys

import pytest

from repro.core import fattree
from repro.core.engine import (ENGINE_CHOICES, FlowEngine, PacketEngine,
                               SimEngine, make_engine, wire_bytes)
from repro.core.workload import TRANSPORT_CHOICES, GroupOp


def two_pod_fat_tree():
    """8 hosts, 2 pods x 2 leaves x 2 hosts, dual agg planes."""
    return fattree.fat_tree(n_pods=2, leaves_per_pod=2, hosts_per_leaf=2,
                            aggs_per_pod=2, bw=100 * fattree.GBPS)


def bcast_jct(engine_name, topo, members, nbytes):
    eng = make_engine(engine_name, topo)
    rec = eng.stage(GroupOp("bcast", members, nbytes))
    eng.run(timeout=60.0)
    jct = rec.jct(len(members) - 1)
    assert jct != float("inf"), f"{engine_name} bcast did not complete"
    return jct


# ============================================================== conformance

def test_all_engines_satisfy_protocol():
    for name in ENGINE_CHOICES:
        eng = make_engine(name, fattree.testbed())
        assert isinstance(eng, SimEngine)


def test_make_engine_rejects_unknown():
    with pytest.raises(ValueError):
        make_engine("ns3", fattree.testbed())


def test_wire_bytes_includes_per_segment_headers():
    from repro.core.packet import HDR, MTU
    assert wire_bytes(1) == 1 + HDR
    assert wire_bytes(MTU) == MTU + HDR
    assert wire_bytes(MTU + 1) == MTU + 1 + 2 * HDR


# ======================================================= packet-vs-flow JCT

@pytest.mark.parametrize("nbytes", [64 << 10, 1 << 20, 8 << 20])
def test_testbed_bcast_jct_agrees_within_10pct(nbytes):
    members = ["h0", "h1", "h2", "h3"]
    jp = bcast_jct("packet", fattree.testbed(), members, nbytes)
    jf = bcast_jct("flow", fattree.testbed(), members, nbytes)
    assert abs(jf - jp) / jp < 0.10, (jp, jf)


@pytest.mark.parametrize("nbytes", [256 << 10, 4 << 20])
def test_two_pod_fat_tree_bcast_jct_agrees_within_10pct(nbytes):
    """All 8 hosts of a 2-pod fat tree: a genuinely multi-hop tree
    (leaf -> agg -> core -> agg -> leaf)."""
    topo = two_pod_fat_tree()
    members = list(topo.hosts)
    jp = bcast_jct("packet", topo, members, nbytes)
    jf = bcast_jct("flow", two_pod_fat_tree(), members, nbytes)
    assert abs(jf - jp) / jp < 0.10, (jp, jf)


# =============================================== transport parity (ISSUE 3)

def transport_bcast_jct(engine_name, transport, nbytes, members=None):
    members = members or ["h0", "h1", "h2", "h3"]
    eng = make_engine(engine_name, fattree.testbed(n_hosts=len(members)))
    rec = eng.stage(GroupOp("bcast", members, nbytes, transport=transport))
    eng.run(timeout=120.0)
    jct = rec.jct(len(members) - 1)
    assert jct != float("inf"), (engine_name, transport)
    return jct


@pytest.mark.parametrize("transport", TRANSPORT_CHOICES)
@pytest.mark.parametrize("nbytes", [256 << 10, 1 << 20])
def test_transport_jct_parity_flow_vs_packet(transport, nbytes):
    """Every transport must agree between the packet lowering (the
    baselines.py relay machinery) and the flow lowering (relay edge
    flows + analytic pipeline) within the 10% acceptance bound."""
    jp = transport_bcast_jct("packet", transport, nbytes)
    jf = transport_bcast_jct("flow", transport, nbytes)
    assert abs(jf - jp) / jp < 0.10, (transport, jp, jf)


@pytest.mark.parametrize("transport", TRANSPORT_CHOICES)
def test_transport_flow_solvers_agree(transport):
    """numpy and JAX lower transports identically (same edge flows,
    same finalizers): JCTs must match to 0.1%."""
    j_np = transport_bcast_jct("flow-np", transport, 1 << 20)
    j_jx = transport_bcast_jct("flow", transport, 1 << 20)
    assert abs(j_np - j_jx) / j_np < 1e-3, (transport, j_np, j_jx)


@pytest.mark.parametrize("transport", TRANSPORT_CHOICES)
def test_allreduce_parity_flow_vs_packet(transport):
    """allreduce = fan-in reduce + transport bcast on BOTH engines.
    Bound is looser than bcast (20%): the fluid model solves both
    phases concurrently, so phases sharing a host uplink (e.g. the
    ring overlay's relay egress vs the member's reduce contribution)
    contend in the solve while the packet engine sequences them."""
    members = ["h0", "h1", "h2", "h3"]
    jcts = {}
    for name in ("packet", "flow"):
        eng = make_engine(name, fattree.testbed())
        rec = eng.stage(GroupOp("allreduce", members, 1 << 20,
                                transport=transport))
        eng.run(timeout=120.0)
        jcts[name] = rec.jct(len(members))      # every member delivers
        assert jcts[name] != float("inf"), name
    assert abs(jcts["flow"] - jcts["packet"]) / jcts["packet"] < 0.20, \
        (transport, jcts)


def test_overlay_transport_per_receiver_ordering():
    """Relay pipelines deliver in hop order: on a ring, receiver i+1
    cannot finish before receiver i (both engines)."""
    members = ["h0", "h1", "h2", "h3"]
    for name in ("packet", "flow"):
        eng = make_engine(name, fattree.testbed())
        rec = eng.stage(GroupOp("bcast", members, 1 << 20,
                                transport="ring"))
        eng.run(timeout=120.0)
        times = [rec.t_deliver[m] for m in members[1:]]
        assert times == sorted(times), (name, times)


def test_flow_solvers_agree_tightly():
    """numpy and JAX progressive filling are the same algorithm; on a
    contended fat tree their JCTs must match to 0.1%."""
    topo = two_pod_fat_tree()
    members = list(topo.hosts)
    j_np = bcast_jct("flow-np", topo, members, 1 << 20)
    j_jx = bcast_jct("flow", two_pod_fat_tree(), members, 1 << 20)
    assert abs(j_np - j_jx) / j_np < 1e-3, (j_np, j_jx)


# ================================================== multi-flow consistency

def test_concurrent_groups_share_fabric_consistently():
    """Two disjoint-receiver groups from the same sender link must each
    see roughly half the sender bandwidth in BOTH engines."""
    members_a = ["h0", "h1", "h2"]
    members_b = ["h0", "h3", "h4"]
    jcts = {}
    for name in ("packet", "flow"):
        eng = make_engine(name, fattree.testbed(n_hosts=5))
        ra = eng.stage(GroupOp("bcast", members_a, 1 << 20))
        rb = eng.stage(GroupOp("bcast", members_b, 1 << 20))
        eng.run(timeout=60.0)
        jcts[name] = (ra.jct(2), rb.jct(2))
    for name, (ja, jb) in jcts.items():
        assert ja != float("inf") and jb != float("inf"), name
    # sharing: each group's JCT is ~2x the solo JCT; engines within 15%
    solo = bcast_jct("flow", fattree.testbed(n_hosts=5), members_a, 1 << 20)
    for name, (ja, jb) in jcts.items():
        assert ja > 1.5 * solo, (name, ja, solo)
    assert abs(jcts["flow"][0] - jcts["packet"][0]) \
        / jcts["packet"][0] < 0.15


def test_unicast_and_write_complete_on_both_engines():
    for name in ("packet", "flow"):
        eng = make_engine(name, fattree.testbed())
        ru = eng.stage(GroupOp("unicast", ("h0", "h1"), 256 << 10))
        rw = eng.stage(GroupOp("write", ("h0", "h1", "h2", "h3"),
                               256 << 10))
        eng.run(timeout=60.0)
        assert ru.jct(1) != float("inf"), name
        assert rw.jct(3) != float("inf"), name
        assert ru.complete and rw.complete, name


def test_flow_engine_is_the_device_solver_or_raises(monkeypatch):
    """``flow`` is always the JAX solver; where that cannot be built it
    raises instead of quietly becoming the numpy ``flow-np``."""
    from repro.core.flowsim_jax import JaxFlowSim
    eng = make_engine("flow", fattree.testbed())
    assert eng.name == "flow" and isinstance(eng._sim, JaxFlowSim)
    monkeypatch.setitem(sys.modules, "repro.core.flowsim_jax", None)
    with pytest.raises(ImportError):
        make_engine("flow", fattree.testbed())
    assert make_engine("flow-np", fattree.testbed()).name == "flow-np"


def test_flow_engine_epochs_are_sequential():
    """Records of a second staged batch start no earlier than the first
    batch's completion (the engine's clock advances)."""
    eng = FlowEngine(fattree.testbed(), backend="auto")
    op = GroupOp("bcast", ("h0", "h1", "h2", "h3"), 1 << 20)
    r1 = eng.stage(op)
    eng.run()
    r2 = eng.stage(op)
    eng.run()
    assert r2.t_submit >= max(r1.t_deliver.values())
    assert r2.jct(3) == pytest.approx(r1.jct(3), rel=1e-6)


def test_packet_engine_source_rotation():
    """Appendix-B source switching through the engine API: rotating the
    source must not re-register and must still deliver."""
    eng = PacketEngine(fattree.testbed())
    members = ["h0", "h1", "h2", "h3"]
    r0 = eng.stage(GroupOp("bcast", members, 64 << 10))
    eng.run()
    r1 = eng.stage(GroupOp("bcast", members, 64 << 10, source="h2"))
    eng.run()
    assert r0.jct(3) != float("inf")
    assert r1.jct(3) != float("inf")
    assert len(eng._groups) == 1            # one registration, rotated
