"""Membership control plane (ISSUE 5): the MulticastGroup state
machine, in-band join/leave/fail/master-switch on the live fabric,
incremental forwarding-table maintenance under churn, and the
Workload-IR ``MemberEvent`` lowering on BOTH engines.

The acceptance gates live here too: dynamic scenarios agree between the
packet and flow engines within 10%, and a no-events GroupOp takes the
exact static code path (same records as before the refactor).
"""
from __future__ import annotations

import pytest

from repro.core import fattree
from repro.core.engine import make_engine
from repro.core.gleam import (ACTIVE, CLOSED, GleamNetwork, IDLE,
                              REGISTERING, UPDATING)
from repro.core.workload import GroupOp, MemberEvent, Workload


def fresh_group(n=4, spares=2, **net_kw):
    net = GleamNetwork(fattree.testbed(n_hosts=n + spares), **net_kw)
    g = net.multicast_group([f"h{i}" for i in range(n)])
    return net, g


# ======================================================== state machine

class TestStateMachine:
    def test_lifecycle_states(self):
        net, g = fresh_group()
        assert g.state == IDLE
        g.register(run=False)
        assert g.state == REGISTERING
        net.sim.run(until=1.0)
        assert g.registered and g.state == ACTIVE
        rec = g.join("h4")
        assert g.state == UPDATING
        g._run_until_op(rec)
        assert g.state == ACTIVE and rec.complete
        g.close()
        assert g.state == CLOSED

    def test_ops_require_active_group(self):
        net, g = fresh_group()
        with pytest.raises(RuntimeError, match="active group"):
            g.join("h4")
        g.register()
        g.close()
        with pytest.raises(RuntimeError, match="active group"):
            g.leave("h1")
        with pytest.raises(RuntimeError, match="closed group"):
            g.bcast(1024)

    def test_membership_validation(self):
        net, g = fresh_group()
        g.register()
        with pytest.raises(ValueError, match="already a member"):
            g.join("h1")
        with pytest.raises(ValueError, match="not a member"):
            g.leave("h9")
        with pytest.raises(ValueError, match="current source"):
            g.fail(g.source)

    def test_events_log_records_latency(self):
        net, g = fresh_group()
        g.register()
        g.join("h4", run=True)
        g.leave("h4", run=True)
        assert [r.kind for r in g.events_log] == ["join", "leave"]
        for r in g.events_log:
            assert r.complete and r.latency > 0


# ============================================== control plane on fabric

class TestJoin:
    def test_joiner_receives_subsequent_messages(self):
        net, g = fresh_group()
        g.register()
        g.join("h4", run=True)
        assert "h4" in g.members and g.n_receivers() == 4
        rec = g.bcast(256 << 10)
        g.run_until_delivered(rec)
        assert "h4" in rec.t_deliver

    def test_join_mid_stream_locks_onto_live_psn(self):
        """A member joining mid-message adopts the live stream's PSN
        (no reset, no NACK storm) and delivers the in-flight message's
        tail; the original receivers are unperturbed."""
        net, g = fresh_group()
        g.register()
        warm = g.bcast(64 << 10)            # advance the PSN stream
        g.run_until_delivered(warm)
        rec = g.bcast(1 << 20)
        sim = net.sim
        sim.run(until=sim.now + 20e-6)
        g.join("h4")
        jct = g.run_until_delivered(rec)
        assert jct != float("inf")
        assert "h4" in rec.t_deliver        # tail delivered to the joiner
        assert g.qps["h4"].retransmitted == 0
        # and the stream did not roll back for anyone
        assert g.qps[g.source].retransmitted == 0

    def test_join_installs_table_entry(self):
        net, g = fresh_group(n=3)
        g.register()
        t = net.sim.switches["SW0"].tables.get(g.group_ip)
        before = t.table_bytes()
        g.join("h3", run=True)
        assert t.table_bytes() > before
        assert g.qps["h3"].ip in t.member_port


class TestLeaveAndFail:
    def test_leave_prunes_table_and_releases_port_load(self):
        net, g = fresh_group()
        g.register()
        sw = net.sim.switches["SW0"]
        t = sw.tables.get(g.group_ip)
        before_bytes = t.table_bytes()
        before_util = sum(sw.port_util.values())
        g.leave("h3", run=True)
        assert t.table_bytes() < before_bytes
        assert sum(sw.port_util.values()) == before_util - 1
        assert not g.qps["h3"].alive        # graceful quiesce
        rec = g.bcast(128 << 10)
        g.run_until_delivered(rec)
        assert "h3" not in rec.t_deliver

    def test_leave_of_straggler_unwedges_aggregation(self):
        """Removing the receiver that owns the aggregate minimum must
        emit the catch-up aggregated ACK so the sender completes."""
        net, g = fresh_group()
        g.register()
        rec = g.bcast(1 << 20)
        sim = net.sim
        sim.run(until=sim.now + 10e-6)
        g.qps["h3"].deactivate()            # silent straggler...
        sim.run(until=sim.now + 100e-6)
        assert rec.t_sender_cqe < 0         # ...has wedged the sender
        g.leave("h3")                       # in-band removal
        g.run_until_delivered(rec, timeout=5.0)
        assert rec.t_sender_cqe > 0         # un-wedged and completed

    def test_fail_recovery_is_detection_bound(self):
        net, g = fresh_group()
        g.register()
        rec = g.bcast(4 << 20)
        sim = net.sim
        sim.run(until=sim.now + 20e-6)
        frec = g.fail("h3")
        jct = g.run_until_delivered(rec, timeout=10.0)
        assert jct != float("inf") and rec.t_sender_cqe > 0
        assert frec.complete
        # recovery = detection delay + isolation round trip
        assert frec.latency == pytest.approx(g.fail_detect, rel=0.05)
        assert "h3" not in rec.t_deliver
        # the crashed member's traffic was sunk, not mis-delivered
        assert sim.hosts["h3"].dead_drops > 0

    def test_whole_group_teardown_when_last_member_leaves(self):
        net, g = fresh_group(n=3)
        g.register()
        sw = net.sim.switches["SW0"]
        g.leave("h2", run=True)
        g.leave("h1", run=True)
        # only the source remains -> close releases everything
        g.close()
        assert sw.tables.get(g.group_ip) is None
        assert sum(sw.port_util.values()) == 0


class TestMasterSwitch:
    def test_handover_moves_source_and_master(self):
        net, g = fresh_group()
        g.register()
        rec = g.bcast(128 << 10)
        g.run_until_delivered(rec)
        g.master_switch("h2")
        assert g.master == "h2" and g.source == "h2"
        rec2 = g.bcast(128 << 10)
        g.run_until_delivered(rec2)
        assert set(rec2.t_deliver) == {"h0", "h1", "h3"}

    def test_handover_then_removal_on_multihop_topology(self):
        """Regression: a teardown envelope from a post-handover master
        follows a DIFFERENT path than the install did — switches that
        never indexed the member must relay it along the tree instead
        of dropping it, or leave/fail never complete off the testbed."""
        net = GleamNetwork(fattree.fig4())
        g = net.multicast_group(["h0", "h1", "h2"])
        g.register()
        g.master_switch("h1")
        lrec = g.leave("h2", run=True)
        assert lrec.complete
        rec = g.bcast(256 << 10)
        g.run_until_delivered(rec)
        assert set(rec.t_deliver) == {"h0"}

    def test_handover_then_fail_recovers_on_multihop_topology(self):
        net = GleamNetwork(fattree.fig4())
        g = net.multicast_group(["h0", "h1", "h2"])
        g.register()
        g.master_switch("h1")
        rec = g.bcast(2 << 20)
        sim = net.sim
        sim.run(until=sim.now + 20e-6)
        frec = g.fail("h2")
        jct = g.run_until_delivered(rec, timeout=10.0)
        assert jct != float("inf") and rec.t_sender_cqe > 0
        assert frec.complete

    def test_rejoin_within_detection_window_supersedes_isolation(self):
        """Regression: a member that fails and rejoins before
        ``fail_detect`` elapses must NOT have its fresh install torn
        down by the stale isolation envelope — the rejoin sends the
        teardown itself, immediately ahead of the re-install."""
        net, g = fresh_group()
        g.register()
        sim = net.sim
        sw = sim.switches["SW0"]
        util_before = sum(sw.port_util.values())
        frec = g.fail("h3")
        sim.run(until=sim.now + 100e-6)     # well inside fail_detect
        g.join("h3", run=True)
        assert frec.complete                # rejoin = early detection
        sim.run(until=sim.now + 2 * g.fail_detect)  # stale timer no-ops
        t = sw.tables.get(g.group_ip)
        assert g.qps["h3"].ip in t.member_port
        rec = g.bcast(256 << 10)
        g.run_until_delivered(rec)
        assert "h3" in rec.t_deliver        # the rejoined member is live
        # accounting is exact: the dead port's ref was released once
        assert sum(sw.port_util.values()) == util_before

    def test_new_master_drives_membership_ops(self):
        net, g = fresh_group()
        g.register()
        g.master_switch("h1")
        g.leave("h3", run=True)             # envelopes now from h1
        g.join("h4", run=True)
        assert g.events_log[-1].complete
        rec = g.bcast(128 << 10)
        g.run_until_delivered(rec)
        assert set(rec.t_deliver) == {"h0", "h2", "h4"}


# ================================================ engine-level lowering

MEMBERS8 = [f"h{i}" for i in range(8)]

CASES = {
    "join": ((MemberEvent("join", "h8", 30e-6),), 7),
    "leave": ((MemberEvent("leave", "h7", 30e-6),), 6),
    "fail": ((MemberEvent("fail", "h7", 30e-6),), 6),
    "mix": ((MemberEvent("master-switch", "h1", 10e-6),
             MemberEvent("leave", "h6", 20e-6),
             MemberEvent("join", "h8", 40e-6),
             MemberEvent("fail", "h5", 60e-6)), 5),
}


def _dynamic_jct(engine, events, n_expected):
    eng = make_engine(engine, fattree.testbed(n_hosts=10))
    rec = eng.stage(GroupOp("bcast", MEMBERS8, 1 << 20, events=events))
    eng.run(timeout=60.0)
    jct = rec.jct(n_expected)
    assert jct != float("inf")
    return jct


@pytest.mark.parametrize("case", sorted(CASES))
def test_dynamic_packet_vs_flow_parity(case):
    """Acceptance: dynamic membership scenarios agree between the
    engines within 10% (observed <= ~3%)."""
    events, n = CASES[case]
    jp = _dynamic_jct("packet", events, n)
    jf = _dynamic_jct("flow", events, n)
    assert jf == pytest.approx(jp, rel=0.10)


def test_dynamic_events_run_on_multihop_topology():
    eng = make_engine("packet", fattree.fig4())
    rec = eng.stage(GroupOp("bcast", ["h0", "h1", "h2"], 2 << 20,
                            events=(MemberEvent("join", "h3", 20e-6),
                                    MemberEvent("fail", "h2", 50e-6))))
    eng.run(timeout=60.0)
    assert rec.jct(1) != float("inf")
    assert "h3" in rec.t_deliver and "h2" not in rec.t_deliver


def test_dynamic_run_workloads_serial_equals_parallel():
    """run_workloads's quiesce/fork machinery survives dynamic
    scenarios: serial and workers=2 results are bit-identical."""
    evsets = [(), (MemberEvent("leave", "h5", 10e-6),),
              (MemberEvent("fail", "h4", 15e-6),),
              (MemberEvent("join", "h7", 12e-6),)]
    members = [f"h{i}" for i in range(6)]
    out = {}
    for workers in (None, 2):
        eng = make_engine("packet", fattree.testbed(n_hosts=9),
                          loss_rate=1e-4, seed=7)
        wls = [Workload("dyn", [GroupOp("bcast", members, 1 << 19,
                                        events=evs)]) for evs in evsets]
        recs = [r for (r,) in eng.run_workloads(wls, timeout=60.0,
                                                workers=workers)]
        out[workers] = [(r.msg_id, r.t_submit, r.t_sender_cqe,
                         sorted(r.t_deliver.items())) for r in recs]
    assert out[None] == out[2]


def _contended_dynamic_jcts(engine):
    """Two piecewise-membership ops sharing h1's downlink until their
    30us leave events — the PR-5 'no contention for dynamic segments'
    known-simplification, now modeled."""
    eng = make_engine(engine, fattree.testbed(n_hosts=5),
                      group_kw={"window": 32})
    ra = eng.stage(GroupOp("bcast", ["h0", "h1", "h2"], 1 << 20,
                           events=(MemberEvent("leave", "h2", 30e-6),)))
    rb = eng.stage(GroupOp("bcast", ["h3", "h1", "h4"], 1 << 20,
                           events=(MemberEvent("leave", "h1", 30e-6),)))
    eng.run(timeout=60.0)
    return ra.jct(1), rb.jct(1)


def test_overlapping_dynamic_ops_contend_like_packet():
    """Regression (ISSUE 6): overlapping dynamic ops must share
    bandwidth segment by segment.  Both ops cross h1's downlink until
    the leaves fire, so each runs at half rate first, full rate after —
    packet parity <= 10% (observed ~2%) on BOTH fluid backends.
    window=32 keeps the packet senders ACK-clocked through the shared
    segment; at larger windows go-back-N runahead on the uncontended
    uplinks adds an asymmetry the fluid model cannot express."""
    jp = _contended_dynamic_jcts("packet")
    solo_eng = make_engine("flow", fattree.testbed(n_hosts=5))
    solo_rec = solo_eng.stage(
        GroupOp("bcast", ["h0", "h1", "h2"], 1 << 20,
                events=(MemberEvent("leave", "h2", 30e-6),)))
    solo_eng.run(timeout=60.0)
    solo = solo_rec.jct(1)
    for engine in ("flow", "flow-np"):
        jf = _contended_dynamic_jcts(engine)
        for f, p in zip(jf, jp):
            assert f == pytest.approx(p, rel=0.10)
        # the shared segment really is priced: slower than the same op
        # running alone, far below the old whole-op-at-shared-rate value
        assert jf[0] > solo * 1.05
        assert jf[0] < solo * 2.0 * 0.85


def test_churn_under_loss_packet_engine():
    """Membership churn and loss recovery compose on the packet engine:
    a lossy fabric with master-switch/leave/join/fail mid-message still
    completes, with real drops recovered along the way."""
    events, n = CASES["mix"]
    eng = make_engine("packet", fattree.testbed(n_hosts=10),
                      loss_rate=1e-3, seed=5)
    rec = eng.stage(GroupOp("bcast", MEMBERS8, 1 << 20, events=events))
    eng.run(timeout=60.0)
    assert rec.jct(n) != float("inf")
    assert eng.net.sim.dropped > 0          # loss genuinely exercised


def test_static_groupop_unchanged_by_events_field():
    """No membership events => the exact static code path: records of a
    fixed-seed scenario match a plain (pre-events-field) GroupOp run."""
    members = [f"h{i}" for i in range(6)]
    out = []
    for _ in range(2):
        eng = make_engine("packet", fattree.testbed(n_hosts=6),
                          loss_rate=1e-4, seed=3)
        rec = eng.stage(GroupOp("bcast", members, 1 << 20, events=()))
        eng.run(timeout=60.0)
        out.append((rec.t_submit, rec.t_sender_cqe,
                    sorted(rec.t_deliver.items())))
    assert out[0] == out[1]


def test_workload_roundtrip_with_events():
    wl = Workload("churn")
    wl.bcast(MEMBERS8, 1 << 20,
             events=(MemberEvent("join", "h8", 1e-5),
                     MemberEvent("fail", "h7", 2e-5)))
    back = Workload.from_dict(wl.to_dict())
    assert back.ops == wl.ops
    assert back.ops[0].events[0].kind == "join"


def test_event_validation():
    with pytest.raises(ValueError, match="unknown event kind"):
        MemberEvent("reboot", "h1", 0.0)
    with pytest.raises(ValueError, match=">= 0"):
        MemberEvent("join", "h1", -1.0)
    with pytest.raises(ValueError, match="already a member"):
        GroupOp("bcast", ("h0", "h1"), 1024,
                events=(MemberEvent("join", "h1", 0.0),))
    with pytest.raises(ValueError, match="not a member"):
        GroupOp("bcast", ("h0", "h1"), 1024,
                events=(MemberEvent("leave", "h9", 0.0),))
    with pytest.raises(ValueError, match="current source"):
        GroupOp("bcast", ("h0", "h1"), 1024,
                events=(MemberEvent("fail", "h0", 0.0),))
    # graceful leave is valid on an overlay relay (the engines resplice
    # the relay schedule, ISSUE 8); join/fail/master-switch are not
    GroupOp("bcast", ("h0", "h1", "h2"), 1024, transport="ring",
            events=(MemberEvent("leave", "h2", 0.0),))
    with pytest.raises(ValueError, match="overlay"):
        GroupOp("bcast", ("h0", "h1", "h2"), 1024, transport="ring",
                events=(MemberEvent("join", "h3", 0.0),))
    with pytest.raises(ValueError, match="bcast/write"):
        GroupOp("allreduce", ("h0", "h1", "h2"), 1024,
                events=(MemberEvent("leave", "h2", 0.0),))
    # master-switch re-points the source: failing the old source is OK
    GroupOp("bcast", ("h0", "h1", "h2"), 1024,
            events=(MemberEvent("master-switch", "h1", 1e-6),
                    MemberEvent("fail", "h0", 2e-6)))


def test_surviving_receivers():
    op = GroupOp("bcast", ("h0", "h1", "h2", "h3"), 1024,
                 events=(MemberEvent("leave", "h2", 1e-6),
                         MemberEvent("join", "h4", 2e-6)))
    assert op.surviving_receivers() == ["h1", "h3"]


def test_agg_min_under_churn_seeded_fuzz():
    """Deterministic (no-hypothesis) slice of the agg-min-under-churn
    property: 300 seeded random event sequences, with bases biased to
    straddle the PSN_MOD wrap.  The hypothesis twin in
    test_protocol_properties explores the space adaptively in CI."""
    import random

    from _membership_props import run_churn_case
    from repro.core.packet import PSN_MOD

    rng = random.Random(0x61EA)
    for _ in range(300):
        base = rng.choice([rng.randrange(PSN_MOD),
                           PSN_MOD - rng.randrange(1, 200),
                           rng.randrange(200)])
        events = [(rng.choice(["ack", "ack", "ack", "add", "remove"]),
                   rng.randrange(1, 7), rng.randrange(301))
                  for _ in range(rng.randrange(1, 80))]
        run_churn_case(base, events)
