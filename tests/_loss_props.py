"""Shared property drivers for the loss/DCQCN model invariants.

Each ``run_*`` function checks one invariant for one concrete input and
raises AssertionError on violation.  They are driven twice: adaptively
by the hypothesis twins in ``test_protocol_properties.py`` (CI), and by
the deterministic seeded fuzz in ``test_loss_model.py`` (always runs,
no hypothesis dependency) — the same split as ``_membership_props.py``.
"""
from __future__ import annotations

import numpy as np

from repro.core import fattree, flowsim, packet as pk
from repro.core.endpoint import QP
from repro.core.engine import make_engine
from repro.core.gleam import GleamNetwork
from repro.core.workload import GroupOp


def run_monotone_case(group, transport, l1, l2, nbytes):
    """More loss never speeds a flow-engine op up — on arbitrary group
    sizes, transports and message sizes."""
    lo, hi = sorted((l1, l2))

    def jct(loss):
        eng = make_engine("flow", fattree.testbed(n_hosts=group),
                          loss_rate=loss)
        rec = eng.stage(GroupOp("bcast", [f"h{i}" for i in range(group)],
                                nbytes, transport=transport, chunks=2))
        eng.run()
        return rec.jct(group - 1)

    assert jct(hi) >= jct(lo) * (1.0 - 1e-9)


def run_factor_bounds_case(seed):
    """Kernel-level: correction factors are always in (0, 1], so the
    effective rate is positive and never above the solved max-min rate
    (hence never above link capacity) — whatever the q/wsq/ECN mix."""
    from repro.kernels.maxmin import loss_factors
    rng = np.random.default_rng(seed)
    n_links = int(rng.integers(1, 12))
    n_flows = int(rng.integers(1, 24))
    hops = int(rng.integers(1, 5))
    f32 = np.float32        # jax default precision; no x64 ctx needed
    cap = np.append(rng.uniform(1e8, 4e10, n_links), np.inf).astype(f32)
    links = rng.integers(0, n_links + 1, (n_flows, hops)).astype(np.int32)
    rates = rng.uniform(1.0, 4e10, n_flows).astype(f32)
    active = (rng.random(n_flows) < 0.7).astype(f32)
    q = (rng.uniform(0.0, 1.0, n_flows)
         * (rng.random(n_flows) < 0.7)).astype(f32)
    wsq = rng.uniform(0.0, 1e-4, n_flows).astype(f32)
    wnd = rng.uniform(1.0, 1024.0, n_flows).astype(f32)
    ecn = (rng.random(n_flows) < 0.5).astype(f32)
    fac = np.asarray(loss_factors(
        links, rates, active, cap, q, wsq, wnd, ecn,
        dcqcn_num=flowsim.DCQCN_RATE_NUM,
        dcqcn_min=flowsim.DCQCN_MIN_RATE))
    assert np.all(fac > 0.0) and np.all(fac <= 1.0)
    assert np.all(rates * fac <= rates)


def run_gbn_replay_case(base, n_pkts, window, plan):
    """Go-back-N accounting at the QP: however feedback interleaves —
    including PSN streams that wrap through PSN_MOD — the window stays
    closed at ``window`` outstanding and every NACK/timeout rewinds (and
    so replays) at most ``window`` packets.  ``plan`` is a list of
    (kind, psn-offset) feedback events, kind in ack|nack|timeout."""
    qp = QP(1, 1, 2, 3, link_bw=12.5e9, window=window)
    qp.sq_psn = qp.snd_una = qp.snd_nxt = base  # stream starts near wrap
    qp.submit(n_pkts * pk.MTU, 0.0)
    rewinds = 0
    for i, (kind, off) in enumerate(plan):
        now = float(i)
        for _ in range(4):                       # drain a few emissions
            p, _t = qp.next_packet(now)
            if p is None:
                break
            assert qp.outstanding() <= window
        sent = pk.psn_sub(qp.snd_nxt, base)
        psn = pk.psn_add(base, min(off, max(sent - 1, 0)))
        before = qp.retransmitted
        if kind == "ack":
            qp.on_ack(psn, now)
        elif kind == "nack":
            qp.on_nack(psn, now)
        else:
            qp.timer_deadline = now
            qp.on_timeout(now)
        replay = qp.retransmitted - before
        assert 0 <= replay <= window
        rewinds += replay > 0
        assert qp.outstanding() <= window
    assert qp.retransmitted <= rewinds * window


def run_e2e_retrans_case(n_hosts, loss, seed, nbytes):
    """End to end on random group topologies: the sender never replays
    without a drop, and total retransmission stays within the go-back-N
    budget (every drop triggers at most one window replay, plus at most
    one trailing timeout replay for a tail-drop)."""
    net = GleamNetwork(fattree.testbed(n_hosts=n_hosts),
                       loss_rate=loss, seed=seed)
    g = net.multicast_group([f"h{i}" for i in range(n_hosts)])
    g.register()
    rec = g.bcast(nbytes)
    assert g.run_until_delivered(rec, timeout=30.0) < float("inf")
    src = g.qps[g.source]
    if net.sim.dropped == 0:
        assert src.retransmitted == 0
    else:
        assert src.retransmitted <= (net.sim.dropped + 1) * src.window
