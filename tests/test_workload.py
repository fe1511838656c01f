"""Workload IR (core/workload.py) + its engine integration.

Covers the ISSUE-3 satellite checklist: IR round-trip through dicts,
registry errors that NAME the valid choices (transports and engines),
``run_workloads`` scenario semantics (static and dynamic ops in one
workload), and the packet engine's between-scenario quiesce.
"""
from __future__ import annotations

import pytest

from repro.core import fattree
from repro.core.engine import make_engine
from repro.core.workload import (GroupOp, MemberEvent, TRANSPORT_CHOICES,
                                 Transport, Workload, get_transport,
                                 register_transport, relay_plan,
                                 transport_names)


# ================================================================ the IR

def test_groupop_roundtrip():
    op = GroupOp("bcast", ("h0", "h1", "h2"), 1 << 20, transport="ring",
                 source="h1", key=3, chunks=4, phase="weights")
    back = GroupOp.from_dict(op.to_dict())
    assert back == op and back.phase == "weights"


def test_workload_roundtrip():
    wl = Workload("fig09/1MB")
    wl.bcast(["h0", "h1", "h2", "h3"], 1 << 20)
    wl.unicast("h0", "h1", 4 << 10, key=7)
    wl.write(["h0", "h1"], 8 << 10, same_mr=True, transport="gleam")
    wl.allreduce(["h0", "h1", "h2"], 64 << 10, transport="binary-tree")
    back = Workload.from_dict(wl.to_dict())
    assert back.name == wl.name and back.ops == wl.ops


def test_workload_meta_roundtrip():
    """App-plane generator specs ride in ``meta`` (ISSUE-8): the tag
    survives the dict round-trip, and metaless dumps stay stable (no
    ``meta`` key) so old fixtures keep parsing."""
    wl = Workload("serve/w0",
                  meta={"kind": "serve", "window": 0,
                        "spec": {"kind": "poisson", "rate": 1e4,
                                 "n": 16, "seed": 3, "trace": []}})
    wl.allreduce(["h0", "h1"], 4 << 10, phase="prefill")
    d = wl.to_dict()
    assert d["meta"]["spec"]["seed"] == 3
    back = Workload.from_dict(d)
    assert back.meta == wl.meta
    assert back.ops[0].phase == "prefill"
    plain = Workload("x")
    plain.bcast(["h0", "h1"], 1024)
    assert "meta" not in plain.to_dict()
    assert Workload.from_dict(plain.to_dict()).meta == {}


def test_groupop_validation():
    members = ("h0", "h1")
    with pytest.raises(ValueError, match="unknown op"):
        GroupOp("scatter", members, 1024)
    with pytest.raises(ValueError, match="nbytes"):
        GroupOp("bcast", members, 0)
    with pytest.raises(ValueError, match="exactly"):
        GroupOp("unicast", ("h0", "h1", "h2"), 1024)
    with pytest.raises(ValueError, match=">= 2 members"):
        GroupOp("bcast", ("h0",), 1024)
    with pytest.raises(ValueError, match="not in members"):
        GroupOp("bcast", members, 1024, source="h9")
    with pytest.raises(ValueError, match="chunks"):
        GroupOp("bcast", members, 1024, chunks=0)


def test_groupop_normalizes_transport_aliases():
    op = GroupOp("bcast", ("h0", "h1"), 1024, transport="bintree")
    assert op.transport == "binary-tree"


def test_from_dict_rejects_unknown_fields():
    with pytest.raises(ValueError, match="unknown GroupOp fields"):
        GroupOp.from_dict({"op": "bcast", "members": ["h0", "h1"],
                           "nbytes": 1024, "fanout": 2})
    with pytest.raises(ValueError, match="unknown Workload fields"):
        Workload.from_dict({"name": "x", "ops": [], "extra": 1})


def test_ordered_members_rotates_source_first():
    op = GroupOp("bcast", ("h0", "h1", "h2", "h3"), 1024, source="h2")
    assert op.ordered_members() == ["h2", "h0", "h1", "h3"]


# =============================================================== registry

def test_unknown_transport_raises_valueerror_listing_names():
    with pytest.raises(ValueError) as ei:
        get_transport("carrier-pigeon")
    for name in TRANSPORT_CHOICES:
        assert name in str(ei.value)
    with pytest.raises(ValueError):
        GroupOp("bcast", ("h0", "h1"), 1024, transport="carrier-pigeon")


def test_unknown_engine_raises_valueerror_listing_names():
    with pytest.raises(ValueError) as ei:
        make_engine("ns3", fattree.testbed())
    for name in ("packet", "flow", "flow-np"):
        assert name in str(ei.value)


def test_builtin_transports_registered():
    assert set(TRANSPORT_CHOICES) <= set(transport_names())
    assert get_transport("gleam").native
    assert not get_transport("ring").native


def test_register_custom_transport_and_relay_plan():
    """Any edge-providing strategy slots in: a chain transport's hops
    fall out of the edge list (relay_plan walks parent pointers)."""
    register_transport(Transport(
        "test-chain",
        relay_edges=lambda m: [(m[i], m[i + 1])
                               for i in range(len(m) - 1)],
        chunked=True))
    try:
        plan = relay_plan(get_transport("test-chain"),
                          ["a", "b", "c", "d"])
        assert plan == [("a", "b", 1), ("b", "c", 2), ("c", "d", 3)]
        # flow engine lowers it like any built-in
        eng = make_engine("flow", fattree.testbed())
        rec = eng.stage(GroupOp("bcast", ("h0", "h1", "h2", "h3"),
                                256 << 10, transport="test-chain"))
        eng.run()
        assert rec.jct(3) != float("inf")
    finally:
        from repro.core import workload as wl
        wl._TRANSPORTS.pop("test-chain", None)


def test_relay_plan_deep_ring_no_recursion_limit():
    members = [f"h{i}" for i in range(3000)]
    plan = relay_plan(get_transport("ring"), members)
    assert plan[-1][2] == 2999


# ======================================================== run_workloads

@pytest.mark.parametrize("engine", ["packet", "flow"])
def test_run_workloads_returns_per_op_records(engine):
    members = ["h0", "h1", "h2", "h3"]
    wl_a = Workload("a")
    wl_a.bcast(members, 256 << 10)
    wl_a.unicast("h0", "h1", 64 << 10)
    wl_b = Workload("b")
    wl_b.bcast(members, 256 << 10, transport="multiunicast")
    eng = make_engine(engine, fattree.testbed())
    recss = eng.run_workloads([wl_a, wl_b], timeout=60.0)
    assert [len(r) for r in recss] == [2, 1]
    assert recss[0][0].jct(3) != float("inf")
    assert recss[0][1].jct(1) != float("inf")
    assert recss[1][0].jct(3) != float("inf")


def test_run_workloads_scenarios_are_independent():
    """Two identical workloads batched together must each match the
    solo run — scenarios never share bandwidth (flow engine)."""
    members = ["h0", "h1", "h2", "h3"]
    wl = Workload("solo")
    wl.bcast(members, 1 << 20)
    solo = make_engine("flow", fattree.testbed())
    ref = solo.run_workloads([wl])[0][0]
    eng = make_engine("flow", fattree.testbed())
    recss = eng.run_workloads([Workload("x", list(wl.ops)),
                               Workload("y", list(wl.ops))])
    for recs in recss:
        assert recs[0].jct(3) == pytest.approx(ref.jct(3), rel=1e-6)


def _rows(recs):
    return [(r.msg_id, r.t_submit, r.t_sender_cqe,
             sorted(r.t_deliver.items())) for r in recs]


@pytest.mark.parametrize("engine", ["flow", "flow-np"])
def test_run_workloads_mixed_static_and_dynamic(engine):
    """A workload holding a static op and a MemberEvent op, batched
    beside a second workload, gives the records those two ops give
    when staged and ``run()`` on a fresh engine: bit-identical on the
    numpy solver, within 1e-6 on JAX (a vmapped batch)."""
    ops = [GroupOp("bcast", ("h0", "h1", "h2", "h3"), 1 << 20),
           GroupOp("bcast", ("h0", "h4", "h5", "h6"), 1 << 20,
                   events=(MemberEvent("leave", "h6", 20e-6),
                           MemberEvent("join", "h7", 40e-6)))]
    other = Workload("other")
    other.bcast(["h1", "h2", "h3"], 512 << 10)
    eng = make_engine(engine, fattree.testbed(n_hosts=8))
    got = eng.run_workloads([Workload("mixed", list(ops)), other])[0]
    ref = make_engine(engine, fattree.testbed(n_hosts=8))
    want = [ref.stage(op) for op in ops]
    ref.run()
    if engine == "flow-np":
        assert _rows(got) == _rows(want)
        return
    for g, w in zip(_rows(got), _rows(want)):
        assert g[:2] == w[:2]
        assert g[2] == pytest.approx(w[2], rel=1e-6)
        assert [m for m, _ in g[3]] == [m for m, _ in w[3]]
        assert [t for _, t in g[3]] == pytest.approx(
            [t for _, t in w[3]], rel=1e-6)


def test_packet_run_workloads_quiesces_between_scenarios():
    """Satellite: the serial fallback must reset sim time and drain
    residual events so scenarios are independent experiments — the
    same heavy scenario twice must measure the same JCT, with the
    second starting on a fresh clock and an empty event queue."""
    members = ["h0", "h1", "h2", "h3"]
    wl = Workload("w")
    wl.bcast(members, 1 << 20)
    eng = make_engine("packet", fattree.testbed())
    recss = eng.run_workloads([Workload("a", list(wl.ops)),
                               Workload("b", list(wl.ops))])
    ja, jb = recss[0][0].jct(3), recss[1][0].jct(3)
    assert ja != float("inf") and jb != float("inf")
    assert jb == pytest.approx(ja, rel=1e-6)       # independent experiments
    assert recss[1][0].t_submit == 0.0             # clock was reset
    assert not eng.net.sim._q                      # events were drained


def test_packet_quiesce_resets_congestion_state():
    """DCQCN rate cuts from scenario A must not leak into scenario B."""
    members = ["h0", "h1", "h2", "h3"]
    eng = make_engine("packet", fattree.testbed())
    wl = Workload("w")
    wl.bcast(members, 4 << 20)
    eng.run_workloads([wl, wl])
    for host in eng.net.sim.hosts.values():
        for qp in host.qps.values():
            assert qp.rate.rate == qp.rate.peak


# ============================================================= allreduce

@pytest.mark.parametrize("engine", ["packet", "flow"])
def test_allreduce_root_delivers_at_reduce_completion(engine):
    """allreduce covers every member (root included): root's delivery
    is the reduce completion, receivers follow after the bcast."""
    members = ["h0", "h1", "h2", "h3"]
    eng = make_engine(engine, fattree.testbed())
    rec = eng.stage(GroupOp("allreduce", members, 256 << 10))
    eng.run(timeout=60.0)
    assert set(rec.t_deliver) == set(members)
    assert rec.t_deliver["h0"] <= min(rec.t_deliver[m]
                                      for m in members[1:])
    assert rec.complete
