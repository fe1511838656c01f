"""Packet-engine hot-path overhaul tests: typed event loop determinism,
serial vs parallel run_workloads equivalence, the typed event-budget error,
the ready-QP set invariant, and the packet free-list pool."""
from __future__ import annotations

import pytest

from repro.core import fattree
from repro.core import packet as pk
from repro.core.engine import make_engine
from repro.core.packetsim import EventBudgetExceeded, PacketSim
from repro.core.workload import GroupOp, Workload

MEMBERS16 = [f"h{i}" for i in range(16)]


def _lossy_engine(n_hosts=16, loss=1e-3, seed=7):
    topo = fattree.testbed(n_hosts=n_hosts, bw=200 * fattree.GBPS)
    return make_engine("packet", topo, loss_rate=loss, seed=seed,
                       group_kw={"window": 512})


def _bcast_workload(members=MEMBERS16, nbytes=1 << 19):
    return Workload("bcast", [GroupOp("bcast", members, nbytes,
                                      transport="gleam", chunks=8)])


def _run_batch(workers, n_scenarios=4, seed=7):
    eng = _lossy_engine(seed=seed)
    recs = [r for recs in eng.run_workloads(
        [_bcast_workload()] * n_scenarios, timeout=60.0, workers=workers)
        for r in recs]
    jcts = [r.jct(len(MEMBERS16) - 1) for r in recs]
    delivers = [dict(r.t_deliver) for r in recs]
    return jcts, delivers, eng.last_run_stats


# ------------------------------------------------------------ determinism

def test_typed_event_loop_deterministic_across_runs():
    """Two fresh engines, same seed -> bit-identical JCTs and drop/
    retransmit counters (the typed event loop has no hidden state)."""
    results = []
    for _ in range(2):
        eng = _lossy_engine()
        rec = eng.stage(GroupOp("bcast", MEMBERS16, 1 << 20,
                                transport="gleam", chunks=8))
        eng.run(timeout=60.0)
        sim = eng.net.sim
        rtx = sum(q.retransmitted for h in sim.hosts.values()
                  for q in h.qps.values())
        results.append((rec.jct(15), dict(rec.t_deliver), sim.dropped,
                        sim.tx_bytes, rtx))
    assert results[0] == results[1]


def test_run_workloads_serial_matches_parallel_bit_for_bit():
    """Satellite: same seed -> identical per-record JCTs, per-receiver
    delivery times, and drop counters between the serial run_workloads and
    the fork-parallel one (lossy fabric, so the RNG stream matters)."""
    js, ds, ss = _run_batch(workers=None)
    jp, dp, sp = _run_batch(workers=2)
    assert js == jp
    assert ds == dp
    assert ss == sp                  # per-scenario counter deltas too
    assert len(js) == 4 and all(j != float("inf") for j in js)


def test_run_workloads_scenarios_reseed_independently():
    """Scenario i's RNG stream depends on (engine seed, i) only, so the
    same batch run twice on fresh engines is identical end to end."""
    a = _run_batch(workers=None, n_scenarios=3)
    b = _run_batch(workers=None, n_scenarios=3)
    assert a == b


def test_run_workloads_parallel_worker_failure_surfaces():
    """A thunk that raises while a WORKER drives its scenario degrades
    gracefully: the parent warns which scenario failed, re-runs it
    serially (same per-index reseed), and the deterministic error then
    reproduces with its REAL type and traceback — it must not vanish
    into a dead child process or an opaque EOFError."""
    eng = _lossy_engine()
    stage = eng.stage

    def boom():
        raise ValueError("deferred submission explodes in the worker")

    def stage_bad(op):
        rec = stage(op)
        if op.key == 1:
            eng._staged.append(boom)    # staged thunks run at drive time
        return rec

    eng.stage = stage_bad
    bad = Workload("bad", [GroupOp("bcast", MEMBERS16, 1 << 19, key=1)])
    with pytest.warns(RuntimeWarning, match=r"re-running scenarios \[1\]"):
        with pytest.raises(ValueError, match="deferred submission"):
            eng.run_workloads([_bcast_workload(), bad], timeout=30.0,
                              workers=2)
    assert any("deferred submission" in e for e in eng.last_run_errors)


# ------------------------------------------------------- event budget

def test_event_budget_exceeded_is_typed_and_inspectable():
    """Satellite: the budget error carries events/now and leaves the
    engine state intact — the run can even be resumed with a larger
    budget."""
    eng = _lossy_engine(loss=0.0)
    rec = eng.stage(GroupOp("bcast", MEMBERS16, 1 << 20,
                            transport="gleam", chunks=8))
    sim = eng.net.sim
    for thunk in eng._staged:
        thunk()
    eng._staged = []
    with pytest.raises(EventBudgetExceeded) as ei:
        sim.run(max_events=sim.events + 500)
    err = ei.value
    assert isinstance(err, RuntimeError)         # back-compat contract
    assert err.events == sim.events              # state is inspectable
    assert err.now == sim.now
    assert sim._q, "queue keeps its remaining events"
    assert "event budget exceeded" in str(err)
    # resume with a larger budget: the bcast completes normally
    sim.run(max_events=50_000_000)
    assert rec.jct(15) != float("inf")


# ------------------------------------------------------- ready-QP set

def test_ready_set_tracks_pending_predicate():
    """The host ready-set holds exactly the QPs with sender-side work:
    populated by submit, emptied when the cumulative ACK covers
    everything."""
    eng = _lossy_engine(loss=0.0)
    rec = eng.stage(GroupOp("bcast", MEMBERS16, 64 << 10,
                            transport="gleam", chunks=1))
    sim = eng.net.sim
    assert all(not h._ready for h in sim.hosts.values()), \
        "registration leaves no pending sender work"
    for thunk in eng._staged:
        thunk()
    eng._staged = []
    src = sim.hosts["h0"]
    assert src._ready, "submit marks the source QP ready"
    qp = next(iter(src._ready.values()))
    assert qp.sq_psn != qp.snd_nxt or qp.snd_una != qp.sq_psn
    sim.run()
    assert rec.jct(15) != float("inf")
    assert all(not h._ready for h in sim.hosts.values()), \
        "completion (snd_una == sq_psn) empties every ready-set"


# ------------------------------------------------------- packet pool

def test_packet_pool_recycles_and_reinitializes():
    p = pk.data_packet(1, 2, 3, psn=9, nbytes=100, msg_id=5, last=True)
    p.ecn = True
    p.payload = {"x": 1}
    before = pk.pool_size()
    pk.release(p)
    assert pk.pool_size() == before + 1
    assert p.payload is None, "release drops payload references"
    q = pk.ack_packet(7, 8, 42, dst_qpn=3)
    assert q is p, "allocation reuses the freed object"
    assert (q.kind, q.src_ip, q.dst_ip, q.psn, q.dst_qpn) == \
        (pk.ACK, 7, 8, 42, 3)
    assert q.ecn is False and q.payload is None and q.last is False
    assert q.size == pk.ACK_SIZE


def test_sim_run_feeds_the_pool():
    """An end-to-end run recycles terminal packets instead of leaking
    every hop-copy to the GC."""
    eng = _lossy_engine(loss=0.0)
    eng.stage(GroupOp("bcast", MEMBERS16, 256 << 10, transport="gleam"))
    eng.run(timeout=60.0)
    assert pk.pool_size() > 0


# ------------------------------------------------------- fixed-seed runs

def test_single_run_unaffected_by_prior_scenarios():
    """A scenario driven through run_workloads equals the same workload
    on a fresh engine driven through run_workloads — PSN offsets and
    table state from earlier scenarios must not leak into timing."""
    recs_a = [r for (r,) in _lossy_engine(seed=3).run_workloads(
        [_bcast_workload()] * 3, timeout=60.0)]
    recs_b = [r for (r,) in _lossy_engine(seed=3).run_workloads(
        [_bcast_workload()] * 2, timeout=60.0)]
    # scenario i is the same experiment no matter the batch size
    assert recs_a[0].jct(15) == recs_b[0].jct(15)
    assert recs_a[1].jct(15) == recs_b[1].jct(15)
