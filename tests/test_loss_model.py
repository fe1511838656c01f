"""Differential calibration harness for the flow-engine loss/DCQCN
model (ISSUE 6).

The fluid engines carry an expected-value correction for go-back-N
retransmission and DCQCN rate reduction (``core/flowsim.py``,
``kernels/maxmin.py:loss_factors``).  This file proves it three ways:

- **differential**: flow-engine JCT within 15% of fixed-seed packet
  ground truth across the full calibration grid (gleam + multiunicast,
  groups 4/8, loss 1e-5..1e-2) — the packet side re-measured LIVE, so
  drift in either engine trips the test (the frozen-json twin gate is
  ``tools/check_fig15.py``);
- **bit-exactness**: with loss off, the flow engines take the exact
  pre-loss-model code path — results identical, both backends;
- **invariants** (deterministic seeded fuzz over the shared drivers in
  ``_loss_props.py``; hypothesis twins live in
  ``test_protocol_properties.py``): JCT monotone non-decreasing in
  loss, correction factors in (0, 1] (rates never negative / above the
  max-min allocation), go-back-N retransmission bounded by the window
  replay across PSN_MOD wrap, and the calibration constants pinned to
  the packet engine's actual DCQCN parameters.
"""
from __future__ import annotations

import os
import random
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:                # benchmarks/ lives at repo root
    sys.path.insert(0, REPO)

from benchmarks.fig15_16_loss import (FID_GROUPS, FID_LOSS_RATES,  # noqa: E402
                                      FID_TRANSPORTS, flow_jct, packet_gt)
from _loss_props import (run_e2e_retrans_case, run_factor_bounds_case,  # noqa: E402
                         run_gbn_replay_case, run_monotone_case)
from repro.core import fattree, flowsim, packet as pk  # noqa: E402
from repro.core.endpoint import QP, RateState  # noqa: E402
from repro.core.engine import make_engine  # noqa: E402
from repro.core.workload import GroupOp  # noqa: E402

TOL = 0.15          # calibration bound (observed worst ~11%)
ZERO_TOL = 0.001    # loss off => the engines' pre-existing agreement

GRID = [(t, g, l) for t in FID_TRANSPORTS for g in FID_GROUPS
        for l in FID_LOSS_RATES]


# ===================================================== differential grid

@pytest.mark.parametrize(
    "transport,group,loss", GRID,
    ids=[f"{t}-g{g}-loss{l:g}" for t, g, l in GRID])
def test_flow_jct_matches_packet_ground_truth(transport, group, loss):
    """Acceptance: flow vs packet JCT <= 15% at every calibration-grid
    point, the packet side a live multi-seed ``run_workloads`` mean."""
    jf = flow_jct(group, loss, transport)
    jp = packet_gt(group, loss, transport)
    assert jf == pytest.approx(jp, rel=ZERO_TOL if loss == 0.0 else TOL)


@pytest.mark.parametrize("engine", ["flow", "flow-np"])
def test_zero_loss_path_bit_identical(engine):
    """loss_rate=0 with ECN off must take the EXACT pre-loss-model code
    path: records equal to an engine built without loss kwargs at all."""
    members = [f"h{i}" for i in range(6)]
    outs = []
    for kw in ({}, {"loss_rate": 0.0}):
        eng = make_engine(engine, fattree.testbed(n_hosts=8), **kw)
        recs = [eng.stage(GroupOp("bcast", members, 1 << 20)),
                eng.stage(GroupOp("bcast", members, 1 << 18,
                                  transport="multiunicast", chunks=4)),
                eng.stage(GroupOp("unicast", ["h6", "h7"], 1 << 16))]
        eng.run()
        outs.append([(r.t_sender_cqe, sorted(r.t_deliver.items()))
                     for r in recs])
    assert outs[0] == outs[1]


def test_lossy_backends_agree():
    """The JAX solver's kernel path and the numpy twin implement the
    same model: lossy JCTs agree to solver precision."""
    for loss in (1e-4, 1e-2):
        jf = flow_jct(4, loss, "gleam", "flow")
        jn = flow_jct(4, loss, "gleam", "flow-np")
        assert jf == pytest.approx(jn, rel=1e-6)


def test_op_level_loss_overrides_engine_default():
    """GroupOp.loss_rate overrides the engine-wide rate per op (flow),
    and conflicting values on ONE packet fabric are rejected."""
    members = [f"h{i}" for i in range(4)]

    def jct_one(eng_kw, op_kw):
        eng = make_engine("flow", fattree.testbed(n_hosts=4), **eng_kw)
        rec = eng.stage(GroupOp("bcast", members, 1 << 20, **op_kw))
        eng.run()
        return rec.jct(3)

    j_clean = jct_one({}, {})
    j_lossy = jct_one({"loss_rate": 1e-2}, {})
    assert j_lossy > j_clean * 1.5           # loss visibly slows the op
    # op-level value wins over the engine default, in both directions
    assert jct_one({"loss_rate": 1e-2}, {"loss_rate": 0.0}) == j_clean
    assert jct_one({}, {"loss_rate": 1e-2}) == j_lossy
    peng = make_engine("packet", fattree.testbed(n_hosts=4), seed=1)
    peng.stage(GroupOp("bcast", members, 1 << 16, loss_rate=1e-3))
    with pytest.raises(ValueError, match="conflicting"):
        peng.stage(GroupOp("bcast", members, 1 << 16, loss_rate=1e-4))


def test_dcqcn_constants_pinned_to_packet_engine():
    """The fluid DCQCN equilibrium must be derived from the SAME
    parameters the packet engine's RateState/QP actually use — if one
    side is retuned, this fails before the calibration grid drifts."""
    rs = RateState(rate=1.0, peak=1.0)
    qp = QP(1, 1, 2, 3, link_bw=12.5e9)
    assert flowsim.DCQCN_MIN_RATE == rs.min_rate
    assert flowsim.DCQCN_RATE_NUM == pytest.approx(
        2.0 * rs.inc * qp.cnp_interval / rs.period)


def test_loss_factors_match_numpy_twin():
    """loss_factors (the device path) vs ``FlowSim._apply_loss`` on the
    same solved rates: same math, so float64 agrees to rounding."""
    import jax
    import jax.numpy as jnp
    from repro.core.flowsim import Flow, FlowSim, LossParams
    from repro.kernels.maxmin import loss_factors
    rng = np.random.default_rng(7)
    sim = FlowSim(fattree.testbed(n_hosts=4))
    n_links, n_flows, hops = len(sim.cap), 50, 3
    flows = []
    for _ in range(n_flows):
        links = tuple(int(x) for x in rng.choice(
            n_links, int(rng.integers(1, hops + 1)), replace=False))
        lp = LossParams(q=float(rng.uniform(0.0, 0.3)),
                        wsq=float(rng.uniform(0.0, 1e-5)), wnd=512.0,
                        tail=0.0, ecn=bool(rng.random() < 0.5)) \
            if rng.random() < 0.7 else None
        f = Flow(links, 1e6, loss=lp)
        f.rate = float(rng.uniform(1e8, 2.5e10))
        flows.append(f)
    rates = np.array([f.rate for f in flows])
    links = np.full((n_flows, hops), n_links, np.int32)
    rows = np.zeros((4, n_flows))
    for i, f in enumerate(flows):
        links[i, :len(f.links)] = f.links
        if f.loss is not None:
            rows[:, i] = (f.loss.q, f.loss.wsq, f.loss.wnd, float(f.loss.ecn))
    sim._apply_loss(flows)
    want = np.array([f.rate for f in flows])
    with jax.enable_x64(True):
        fac = loss_factors(
            jnp.asarray(links), jnp.asarray(rates), jnp.ones(n_flows),
            jnp.asarray(np.append(sim.cap, np.inf)),
            *(jnp.asarray(r) for r in rows),
            dcqcn_num=flowsim.DCQCN_RATE_NUM,
            dcqcn_min=flowsim.DCQCN_MIN_RATE)
        got = rates * np.asarray(fac)
    np.testing.assert_allclose(got, want, rtol=1e-12)
    assert np.any(got < rates)               # the loss model did bite


# ========================================= invariants (seeded fuzz)

def test_jct_monotone_in_loss_seeded_fuzz():
    rng = random.Random(0x10551)
    for _ in range(12):
        run_monotone_case(group=rng.randint(2, 8),
                          transport=rng.choice(("gleam", "multiunicast",
                                                "ring")),
                          l1=rng.uniform(0.0, 2e-2),
                          l2=rng.uniform(0.0, 2e-2),
                          nbytes=rng.randrange(1 << 12, 1 << 20))


def test_loss_factor_bounds_seeded_fuzz():
    for seed in range(120):
        run_factor_bounds_case(seed)


def test_gbn_replay_bound_seeded_fuzz():
    """Bases biased to straddle the PSN_MOD wrap, like the agg-min
    churn fuzz in test_membership."""
    rng = random.Random(0x10552)
    for _ in range(150):
        base = rng.choice([rng.randrange(pk.PSN_MOD),
                           pk.PSN_MOD - rng.randrange(1, 700),
                           rng.randrange(700)])
        plan = [(rng.choice(["ack", "nack", "timeout"]),
                 rng.randrange(701)) for _ in range(rng.randint(1, 50))]
        run_gbn_replay_case(base, rng.randint(1, 600),
                            rng.choice((4, 32, 256)), plan)


def test_e2e_retrans_bound_seeded_fuzz():
    rng = random.Random(0x10553)
    for _ in range(8):
        run_e2e_retrans_case(n_hosts=rng.randint(3, 10),
                             loss=rng.choice((0.0, 1e-4, 1e-3, 1e-2)),
                             seed=rng.randrange(1 << 16),
                             nbytes=rng.randrange(1 << 12, 1 << 17))
