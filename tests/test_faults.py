"""Fault-injection plane (ISSUE 7): the ``FaultEvent`` IR and its
validation, per-class recovery on the live fabric (link/switch/host/
master), packet-vs-flow recovery parity, the dead-source sever cascade,
bounded-retry endpoint semantics, and fault scenarios under the
parallel ``run_workloads`` path.

The deterministic halves of the two headline properties live here (the
hypothesis twins are in ``test_protocol_properties`` and share the
drivers in ``_fault_props``): re-election converges to exactly one
live master with no orphaned MFT entries, and a severed path costs at
most ``max_retries`` replays before a terminal, attributable error.
"""
from __future__ import annotations

import pytest

from repro.core import fattree
from repro.core.engine import make_engine
from repro.core.faults import (DEFAULT_FAULT_RETRIES, FAULT_CHOICES,
                               FaultEvent, fault_downs,
                               validate_fault_plan)
from repro.core.gleam import GleamNetwork
from repro.core.workload import GroupOp, Workload

from _fault_props import run_bounded_retry_case, run_reelection_case

MEMBERS = ["h0", "h1", "h2", "h3"]
NBYTES = 1 << 17
AT = 3e-6               # mid-stream fault injection point
PARITY_TOL = 0.15       # packet-vs-flow recovery divergence gate


# ========================================================= FaultEvent IR

class TestFaultEventIR:
    def test_valid_events_per_kind(self):
        FaultEvent("link_down", AT, node="L4", peer="S3")
        FaultEvent("link_flap", AT, node="L4", peer="S3", duration=1e-5)
        FaultEvent("switch_fail", AT, node="S3")
        FaultEvent("host_gone_dark", AT, node="h3")
        FaultEvent("master_crash", AT)

    def test_unknown_kind_rejected(self):
        with pytest.raises(ValueError, match="unknown fault kind"):
            FaultEvent("meteor_strike", AT, node="S3")

    def test_negative_time_rejected(self):
        with pytest.raises(ValueError, match=">= 0"):
            FaultEvent("switch_fail", -1e-6, node="S3")

    def test_link_faults_need_both_endpoints(self):
        with pytest.raises(ValueError, match="both link endpoints"):
            FaultEvent("link_down", AT, node="L4")
        with pytest.raises(ValueError, match="node == peer"):
            FaultEvent("link_down", AT, node="L4", peer="L4")

    def test_node_faults_take_no_peer(self):
        with pytest.raises(ValueError, match="no peer"):
            FaultEvent("switch_fail", AT, node="S3", peer="S4")
        with pytest.raises(ValueError, match="needs a target"):
            FaultEvent("host_gone_dark", AT)

    def test_master_crash_takes_no_target(self):
        with pytest.raises(ValueError, match="no node/peer"):
            FaultEvent("master_crash", AT, node="h0")

    def test_flap_duration_rules(self):
        with pytest.raises(ValueError, match="duration > 0"):
            FaultEvent("link_flap", AT, node="L4", peer="S3")
        with pytest.raises(ValueError, match="no duration"):
            FaultEvent("link_down", AT, node="L4", peer="S3",
                       duration=1e-5)

    def test_dict_roundtrip(self):
        for f in (FaultEvent("link_flap", AT, node="L4", peer="S3",
                             duration=1e-5),
                  FaultEvent("master_crash", AT)):
            assert FaultEvent.from_dict(f.to_dict()) == f

    def test_from_dict_rejects_unknown_fields(self):
        with pytest.raises(ValueError, match="unknown FaultEvent fields"):
            FaultEvent.from_dict({"kind": "master_crash", "at": AT,
                                  "severity": 9})

    def test_fault_downs_spans(self):
        topo = fattree.fig4()
        spans = fault_downs(
            (FaultEvent("link_flap", 2e-6, node="L4", peer="S3",
                        duration=1e-5),
             FaultEvent("switch_fail", 1e-6, node="S3"),
             FaultEvent("master_crash", 0.0)), topo)
        # time-ordered, host/master faults carry no fabric links
        assert [s[0] for s in spans] == [1e-6, 2e-6]
        assert spans[0][1] == float("inf")
        assert ("S3", "L4") in spans[0][2] or ("S3", "L3") in spans[0][2]
        assert spans[1] == (2e-6, 2e-6 + 1e-5, [("L4", "S3")])


# ==================================================== plan validation

class TestFaultPlanValidation:
    def test_fabric_faults_require_native_transport(self):
        for faults in ((FaultEvent("link_down", AT, node="L4",
                                   peer="S3"),),
                       (FaultEvent("switch_fail", AT, node="S3"),),
                       (FaultEvent("master_crash", AT),)):
            with pytest.raises(ValueError, match="native"):
                GroupOp("bcast", MEMBERS, NBYTES, transport="ring",
                        faults=faults).fault_roles()

    def test_host_gone_dark_allowed_on_overlay(self):
        op = GroupOp("bcast", MEMBERS, NBYTES, transport="ring",
                     faults=(FaultEvent("host_gone_dark", AT,
                                        node="h2"),))
        assert op.surviving_receivers() == ["h1", "h3"]

    def test_dark_source_must_be_master_crash(self):
        with pytest.raises(ValueError, match="use master_crash"):
            GroupOp("bcast", MEMBERS, NBYTES,
                    faults=(FaultEvent("host_gone_dark", AT,
                                       node="h0"),)).fault_roles()

    def test_master_crash_needs_a_survivor(self):
        with pytest.raises(ValueError, match="no survivor"):
            GroupOp("bcast", ["h0", "h1"], NBYTES,
                    faults=(FaultEvent("master_crash", 1e-6),
                            FaultEvent("master_crash", 2e-3),)
                    ).fault_roles()

    def test_surviving_receivers_excuse_dark_and_sources(self):
        op = GroupOp("bcast", MEMBERS, NBYTES,
                     faults=(FaultEvent("master_crash", AT),
                             FaultEvent("host_gone_dark", 2e-3,
                                        node="h2"),))
        # h0 died, h1 re-elected (source role), h2 went dark
        assert op.surviving_receivers() == ["h3"]

    def test_disconnecting_plan_rejected_at_staging(self):
        topo = fattree.fig4()
        op = GroupOp("bcast", MEMBERS, NBYTES,
                     faults=(FaultEvent("link_down", AT, node="L4",
                                        peer="S3"),
                             FaultEvent("link_down", AT, node="L4",
                                        peer="S4"),))
        with pytest.raises(ValueError, match="disconnects"):
            validate_fault_plan(topo, op)
        with pytest.raises(ValueError, match="disconnects"):
            make_engine("packet", fattree.fig4()).stage(op)
        # the single-uplink variant leaves a surviving path: accepted
        validate_fault_plan(topo, GroupOp(
            "bcast", MEMBERS, NBYTES,
            faults=(FaultEvent("link_down", AT, node="L4",
                               peer="S3"),)))

    def test_validator_restores_topology(self):
        topo = fattree.fig4()
        validate_fault_plan(topo, GroupOp(
            "bcast", MEMBERS, NBYTES,
            faults=(FaultEvent("switch_fail", AT, node="S3"),)))
        assert not topo._down


# ============================================= per-class engine recovery

def _fault_cases():
    return [
        ("link_down", (FaultEvent("link_down", AT, node="L4",
                                  peer="S3"),)),
        ("link_flap", (FaultEvent("link_flap", AT, node="L4", peer="S3",
                                  duration=2e-5),)),
        ("switch_fail", (FaultEvent("switch_fail", AT, node="S3"),)),
        ("host_gone_dark", (FaultEvent("host_gone_dark", AT,
                                       node="h3"),)),
        ("master_crash", (FaultEvent("master_crash", AT),)),
    ]


def _run_once(engine_name, faults=(), transport="gleam"):
    eng = make_engine(engine_name, fattree.fig4(),
                      **({"seed": 7} if engine_name == "packet" else {}))
    op = GroupOp("bcast", MEMBERS, NBYTES, transport=transport,
                 faults=faults)
    rec = eng.stage(op)
    eng.run(timeout=60.0)
    assert not rec.error
    for m in op.surviving_receivers():
        assert m in rec.t_deliver, f"{m} never delivered"
    return rec.io_latency       # sender CQE: sees every recovery class


@pytest.mark.parametrize("label,faults", _fault_cases())
def test_every_fault_class_recovers_with_engine_parity(label, faults):
    """Each fault class completes on BOTH engines — no hangs, every
    surviving receiver delivered — and the measured recovery latency
    (sender-CQE penalty over the clean run) agrees within the gate."""
    base_p = _run_once("packet")
    base_f = _run_once("flow")
    jct_p = _run_once("packet", faults)
    jct_f = _run_once("flow", faults)
    assert jct_p > base_p       # the fault cost something
    div = abs(jct_p - jct_f) / jct_p
    assert div <= PARITY_TOL, (
        f"{label}: packet {jct_p * 1e6:.2f}us vs flow {jct_f * 1e6:.2f}us "
        f"({100 * div:.1f}% > {100 * PARITY_TOL:.0f}%)")


def test_overlay_relay_dark_resplices():
    """A dead mid-ring relay: children are respliced onto the dead
    relay's parent; survivors still complete on both engines."""
    jp = _run_once("packet",
                   (FaultEvent("host_gone_dark", AT, node="h2"),),
                   transport="ring")
    jf = _run_once("flow",
                   (FaultEvent("host_gone_dark", AT, node="h2"),),
                   transport="ring")
    assert abs(jp - jf) / jp <= PARITY_TOL

    # flap heals the fabric afterwards: the packet sim restores the link
    eng = make_engine("packet", fattree.fig4(), seed=7)
    rec = eng.stage(GroupOp(
        "bcast", MEMBERS, NBYTES,
        faults=(FaultEvent("link_flap", AT, node="L4", peer="S3",
                           duration=2e-5),)))
    eng.run(timeout=60.0)
    assert not rec.error
    assert not eng.net.topo._down       # the flap healed


@pytest.mark.parametrize("transport", ["ring", "binary-tree",
                                       "multiunicast"])
def test_overlay_graceful_leave_resplices(transport):
    """ISSUE-8 satellite regression: a graceful mid-stream ``leave`` on
    an overlay relay transport must resplice the relay schedule through
    the ``repair_dead_relay`` path — before this fix it raised at
    construction.  Unlike a dark, a leaver's host stays up and the
    splice is immediate (no fail_detect), so the leaver must NOT be
    counted (or keep relaying) even though residual chunks still reach
    its NIC, and survivors must all deliver on BOTH engines.

    The parity gate is looser than PARITY_TOL: the detect-free splice
    races the live stream head-on, where the fluid model's lack of
    in-flight chunk state costs the most (measured ~18% on ring)."""
    from repro.core.workload import MemberEvent
    events = (MemberEvent("leave", "h2", AT),)
    jcts = {}
    for engine_name in ("packet", "flow"):
        eng = make_engine(engine_name, fattree.fig4(),
                          **({"seed": 7} if engine_name == "packet"
                             else {}))
        op = GroupOp("bcast", MEMBERS, NBYTES, transport=transport,
                     events=events)
        assert op.surviving_receivers() == ["h1", "h3"]
        rec = eng.stage(op)
        eng.run(timeout=60.0)
        assert not rec.error
        assert "h2" not in rec.t_deliver, "leaver was still counted"
        for m in ("h1", "h3"):
            assert m in rec.t_deliver, f"{m} never delivered"
        jcts[engine_name] = rec.io_latency
    div = abs(jcts["packet"] - jcts["flow"]) / jcts["packet"]
    assert div <= 0.25, (
        f"{transport}: packet {jcts['packet'] * 1e6:.2f}us vs flow "
        f"{jcts['flow'] * 1e6:.2f}us ({100 * div:.1f}% > 25%)")


# ============================================ re-election + sever cascade

class TestMasterCrashRecovery:
    def test_single_crash_converges(self):
        rec = run_reelection_case([AT])
        assert rec.t_sender_cqe > 0

    def test_double_crash_mid_stream_converges(self):
        # 4MB keeps the stream alive across BOTH fail_detect windows
        rec = run_reelection_case([AT, 1.2e-3], nbytes=1 << 22)
        assert rec.t_sender_cqe > 0

    def test_crash_after_completion_still_reelects(self):
        run_reelection_case([5e-4], nbytes=1 << 14)

    def test_sever_cascade_unwinds_dead_masters_branch(self):
        """The dead master's access leaf is OFF the re-rooted tree, so
        no repair envelope ever visits it: the dead-source sever
        cascade must have unwound its table (and every switch the new
        tree bypassed) instead of leaking it until group teardown."""
        net = GleamNetwork(fattree.fig4())
        g = net.multicast_group(MEMBERS)
        g.register()
        rec = g.bcast(NBYTES, now=0.0)
        net.sim.schedule(AT, lambda now: g.master_crash(now=now))
        net.sim.run(until=0.05)
        assert rec.t_sender_cqe > 0
        # h0's leaf (L1) fed the old tree from the dead source
        assert net.sim.switches["L1"].tables.get(g.group_ip) is None
        live_ips = {g.qps[m].ip for m in g.members}
        for name, sw in net.sim.switches.items():
            t = sw.tables.get(g.group_ip)
            if t is not None:
                assert not set(t.member_port) - live_ips, name

    def test_resume_from_dead_senders_una(self):
        """The survivor resumes at the dead sender's cumulative-ACK
        point: receivers re-ACK the overlap instead of NACKing below
        the new base, and the sender CQE lands ~fail_detect later."""
        net = GleamNetwork(fattree.fig4())
        g = net.multicast_group(MEMBERS)
        g.register()
        rec = g.bcast(NBYTES, now=0.0)
        net.sim.schedule(AT, lambda now: g.master_crash(now=now))
        net.sim.run(until=0.05)
        assert g.master == "h1"
        assert rec.t_sender_cqe == pytest.approx(
            g.fail_detect + AT, rel=0.25)


# ====================================================== bounded retry

class TestBoundedRetry:
    def test_retry_budget_is_terminal_and_attributable(self):
        rec = run_bounded_retry_case(2, AT)
        assert rec.error == "retry_exceeded"

    def test_zero_budget_errors_on_first_unproductive_rto(self):
        rec = run_bounded_retry_case(0, AT)
        assert rec.error == "retry_exceeded"

    def test_sever_after_completion_is_clean(self):
        rec = run_bounded_retry_case(3, 1.0)
        assert not rec.error

    def test_fault_ops_default_to_bounded_retries(self):
        eng = make_engine("packet", fattree.fig4(), seed=7)
        rec = eng.stage(GroupOp(
            "bcast", MEMBERS, NBYTES,
            faults=(FaultEvent("link_down", AT, node="L4",
                               peer="S3"),)))
        eng.run(timeout=60.0)
        assert not rec.error
        g = eng.net.groups_by_ip[next(iter(eng.net.groups_by_ip))]
        assert g.qps["h0"].max_retries == DEFAULT_FAULT_RETRIES

    def test_no_fault_ops_keep_unbounded_legacy_semantics(self):
        eng = make_engine("packet", fattree.fig4(), seed=7)
        eng.stage(GroupOp("bcast", MEMBERS, NBYTES))
        eng.run(timeout=60.0)
        g = eng.net.groups_by_ip[next(iter(eng.net.groups_by_ip))]
        assert g.qps["h0"].max_retries is None


# ============================================= run_workloads + faults

def test_fault_scenarios_serial_equals_workers():
    """Fault scenarios survive the fork/replay parallel path: same
    records serial and with workers=2 (fresh-engine reseed per
    scenario makes the comparison exact)."""
    def _batch(workers):
        eng = make_engine("packet", fattree.fig4(), seed=7)
        clean = GroupOp("bcast", MEMBERS, NBYTES)
        crash = GroupOp("bcast", MEMBERS, NBYTES,
                        faults=(FaultEvent("master_crash", AT),))
        dark = GroupOp("bcast", MEMBERS, NBYTES,
                       faults=(FaultEvent("host_gone_dark", AT,
                                          node="h3"),))
        recs = [r for (r,) in eng.run_workloads(
            [Workload("f", [op]) for op in (clean, crash, dark)],
            timeout=60.0, workers=workers)]
        return [(sorted(r.t_deliver.items()), r.t_sender_cqe, r.error)
                for r in recs]

    assert _batch(None) == _batch(2)


def test_zero_fault_op_is_bit_identical_to_faultless_op():
    """``faults=()`` takes the exact legacy code path: same records as
    an op built without the field at all (the PR-6 bit-identity
    invariant, unit-sized)."""
    def _run(op):
        eng = make_engine("packet", fattree.testbed(n_hosts=6), seed=7)
        rec = eng.stage(op)
        eng.run(timeout=60.0)
        return sorted(rec.t_deliver.items()), rec.t_sender_cqe

    assert _run(GroupOp("bcast", MEMBERS, NBYTES)) == \
        _run(GroupOp("bcast", MEMBERS, NBYTES, faults=()))


def test_fault_choices_cover_engine_lowerings():
    assert set(FAULT_CHOICES) == {"link_down", "link_flap", "switch_fail",
                                  "host_gone_dark", "master_crash"}
