"""Fleet-scale sweep plane: staging cache + vectorized path derivation.

The staging cache (core/staging.py) memoizes derived artifacts —
unicast paths, multicast tree edges, per-receiver latencies, per-op
flow layouts — on the topology, keyed by its (structural revision,
down-set) fingerprint.  The contract under test:

- fixed-seed results are BIT-identical with the cache enabled or
  disabled, on both flow backends, for every transport — including a
  sweep whose fault op forces a mid-sweep invalidation;
- `Topology.paths_many` (batched CSR frontier sweep) returns exactly
  what the scalar `path_links` walk returns, downed links included;
- host paths composed from the switch-pair memo (`LinkMap._route`,
  scalar and batched) equal the host walk, and every pair the memo
  cannot serve (a host with a one-way or second link) walks as before;
  a fresh-placement sweep is bit-identical with the memo switched off,
  and the cache stays bounded on the scalar insertion path;
- fingerprint semantics: `connect` invalidates, a transient
  down/clear round trip does NOT (fault staging relies on this), a
  persistent down DOES;
- the `candidate_ports` memo stays under its byte budget no matter how
  many destinations churn through it;
- the packet engine's `staging_cache=False` mode disables the routing
  memos without changing results.
"""
from __future__ import annotations

import random

import pytest

from repro.core import fattree, staging
from repro.core.engine import FlowEngine, PacketEngine, make_engine
from repro.core.faults import FaultEvent
from repro.core.flowsim import FlowSim, LinkMap
from repro.core.staging import StagingCache
from repro.core.workload import GroupOp, MemberEvent, Workload

def small_fat_tree():
    return fattree.fat_tree(n_pods=2, leaves_per_pod=2, hosts_per_leaf=4,
                            aggs_per_pod=2, bw=100 * fattree.GBPS)


def leaf_of(topo, host):
    """The switch a host hangs off (hosts have exactly one port)."""
    return topo.ports[host][0][0]


def sweep_workloads(hosts):
    """A representative static sweep: every transport + unicast mesh."""
    wls = []
    for transport in ("gleam", "ring", "binary-tree", "multiunicast"):
        wl = Workload(f"sweep/{transport}")
        wl.bcast(hosts[:6], 1 << 20, transport=transport, key=3)
        wl.bcast(hosts[2:9], 256 << 10, transport=transport)
        wls.append(wl)
    mesh = Workload("sweep/mesh")
    for i in range(4):
        mesh.unicast(hosts[i], hosts[(i + 3) % 8], 512 << 10, key=i)
    mesh.allreduce(hosts[:5], 1 << 20)
    wls.append(mesh)
    return wls


def record_tuples(recss):
    return [[(r.msg_id, r.t_submit, r.t_sender_cqe,
              tuple(sorted(r.t_deliver.items())), r.error)
             for r in recs] for recs in recss]


# ===================================================== vectorized routing

def test_paths_many_matches_scalar_walk():
    topo = small_fat_tree()
    reqs = [(src, dst, key)
            for src in topo.hosts[:4]
            for dst in topo.hosts[4:10]
            for key in (0, 1, 7)]
    batched = topo.paths_many(reqs)
    for (src, dst, key), hops in zip(reqs, batched):
        assert hops == tuple(topo.path_links(src, dst, key))


def test_paths_many_respects_downed_links():
    topo = small_fat_tree()
    # take down one leaf->agg uplink; paths must detour identically
    leaf = leaf_of(topo, topo.hosts[0])
    switches = set(topo.switches)
    agg = next(peer for _, (peer, _) in sorted(topo.ports[leaf].items())
               if peer in switches)
    topo.set_link_down(leaf, agg, True)
    reqs = [(topo.hosts[0], dst, k) for dst in topo.hosts[8:16]
            for k in (0, 1)]
    batched = topo.paths_many(reqs)
    for (src, dst, key), hops in zip(reqs, batched):
        assert hops == tuple(topo.path_links(src, dst, key))


def test_paths_many_raises_on_unreachable():
    topo = small_fat_tree()
    with pytest.raises(KeyError):
        topo.paths_many([(topo.hosts[0], "nonexistent-host", 0)])
    # an isolated destination (its only link downed) is unreachable
    iso = topo.hosts[-1]
    topo.set_link_down(iso, leaf_of(topo, iso), True)
    with pytest.raises(ValueError):
        topo.paths_many([(topo.hosts[0], iso, 0)])


# ===================================================== switch-pair memo

def _down_one_way(topo, node, port):
    """Down one direction of a link (the fault plane downs both), with
    the invalidation ``set_link_down`` does."""
    topo._down.add((node, port))
    topo._dist.clear()
    topo._cand.clear()
    topo._csr = None
    topo._fp = (topo._struct_rev, frozenset(topo._down))


def _routed(build, case):
    """(topology, hosts the memo must not serve) of one test case."""
    topo = build()
    hosts = topo.hosts
    switches = set(topo.switches)
    if case == "leaf_agg_down":
        leaf = leaf_of(topo, hosts[0])
        agg = next(peer for _, (peer, _) in sorted(topo.ports[leaf].items())
                   if peer in switches)
        topo.set_link_down(leaf, agg, True)
        return topo, set()
    if case == "leaf_cut_off":
        # every uplink of the last leaf down: its hosts keep their one
        # live port each but reach nothing beyond their leaf
        leaf = leaf_of(topo, hosts[-1])
        for _, (peer, _) in sorted(topo.ports[leaf].items()):
            if peer in switches:
                topo.set_link_down(leaf, peer, True)
        return topo, set()
    if case == "one_way":
        # hosts[1] cannot send, hosts[2] cannot be reached
        _down_one_way(topo, hosts[1], 0)
        _down_one_way(topo, *topo.ports[hosts[2]][0])
        return topo, {hosts[1], hosts[2]}
    if case == "dual_homed":
        topo.connect(hosts[0], leaf_of(topo, hosts[-1]),
                     100 * fattree.GBPS, 0.6e-6)
        return topo, {hosts[0]}
    return topo, set()


def _outcome(fn):
    try:
        return "ok", tuple(fn())
    except Exception as e:             # the walk's own error, as it is
        return "raises", (type(e), str(e))


@pytest.mark.parametrize("case", ["pristine", "leaf_agg_down",
                                  "leaf_cut_off", "one_way", "dual_homed"])
@pytest.mark.parametrize("build", [small_fat_tree, fattree.fig4],
                         ids=["small_fat_tree", "fig4"])
def test_switch_memo_paths_equal_the_host_walk(build, case):
    topo, unserved = _routed(build, case)
    sim = FlowSim(topo, shared_cache=False)
    assert set(sim._homed()) == set(topo.hosts) - unserved
    reqs = [(s, d, k) for s in topo.hosts for d in topo.hosts
            for k in (0, 1, 7)]

    def walk(s, d, k):
        return [sim.link_id[h] for h in topo.path_links(s, d, k)]

    want = {r: _outcome(lambda: walk(*r)) for r in reqs}
    for r in reqs:
        assert _outcome(lambda: sim.unicast_links(*r)) == want[r], r
    served = [r for r in reqs if r[0] != r[1] and want[r][0] == "ok"
              and not {r[0], r[1]} & unserved]
    cache = sim.cache
    assert cache.sw_misses == len(cache.switch_paths) > 0
    assert cache.sw_hits + cache.sw_misses == len(served)
    # the batch path, from a cold cache: routable pairs in one call,
    # each unroutable one raising as ``paths_many`` raises for it
    warm = FlowSim(topo, shared_cache=False)
    ok = [r for r in reqs if want[r][0] == "ok"]
    warm.warm_paths(ok)
    assert {r: warm.cache.paths[r] for r in ok} == \
        {r: want[r][1] for r in ok}
    assert warm.cache.sw_misses == len(warm.cache.switch_paths) > 0
    for r in reqs:
        if want[r][0] == "raises":
            batch = _outcome(lambda: topo.paths_many([r])[0])
            assert batch[0] == "raises"
            assert _outcome(lambda: warm.warm_paths([r])) == batch, r
    assert (case in ("leaf_cut_off", "one_way")) == (len(ok) < len(reqs))


def _fresh_sweep(loss_rate, passes=3):
    """Fresh members every pass over one fabric, as a Monte-Carlo
    placement sweep runs: (records per pass, engines' staging stats)."""
    topo = fattree.fat_tree(n_pods=4, leaves_per_pod=4, hosts_per_leaf=4,
                            aggs_per_pod=2, bw=100 * fattree.GBPS)
    rng = random.Random(11)
    recs, stats = [], []
    for i in range(passes):
        wls = []
        for g in (4, 16):
            wl = Workload(f"fresh/{i}/{g}")
            wl.bcast(rng.sample(topo.hosts, g), 1 << 20)
            wls.append(wl)
        kw = {"loss_rate": loss_rate} if loss_rate else {}
        eng = make_engine("flow", topo, **kw)
        recs.append(record_tuples(eng.run_workloads(wls)))
        stats.append(eng.staging_stats())
    return recs, stats


@pytest.mark.parametrize("loss_rate", [0.0, 1e-4])
def test_fresh_placements_bit_identical_without_switch_memo(loss_rate,
                                                            monkeypatch):
    recs, stats = _fresh_sweep(loss_rate)
    assert stats[1]["sw_hits"] > 0
    monkeypatch.setattr(LinkMap, "_homed", lambda self: {})
    off, off_stats = _fresh_sweep(loss_rate)
    assert recs == off
    assert off_stats[-1]["sw_hits"] == off_stats[-1]["sw_misses"] == 0
    # the memo changes what is derived, not what the cache counts
    for on, no in zip(stats, off_stats):
        assert (on["hits"], on["misses"]) == (no["hits"], no["misses"])


def test_scalar_insertions_stay_bounded(monkeypatch):
    """Fresh placements add paths and latencies on the scalar path every
    pass; the entry cap holds there too, and a wholesale drop costs one
    re-derivation, not a change of result."""
    want, _ = _fresh_sweep(0.0, passes=4)
    monkeypatch.setattr(staging, "MAX_ENTRIES", 40)
    got, stats = _fresh_sweep(0.0, passes=4)
    assert got == want
    assert stats[-1]["invalidations"] > 0
    assert max(stats[-1][k] for k in ("paths", "switch_paths", "lat",
                                      "trees", "ops")) <= 40
    # and for paths asked for one by one, with no latency behind them
    sim = FlowSim(small_fat_tree(), shared_cache=False)
    for src in sim.topo.hosts:
        for dst in sim.topo.hosts:
            sim.unicast_links(src, dst)
            assert len(sim.cache.paths) <= 40
    assert sim.cache.invalidations > 0


# ==================================================== cache-off = cache-on

@pytest.mark.parametrize("backend", ["flow", "flow-np"])
def test_flow_bit_identity_cache_on_vs_off(backend):
    t_on, t_off = small_fat_tree(), small_fat_tree()
    wls = sweep_workloads(t_on.hosts)
    on = make_engine(backend, t_on, staging_cache=True)
    off = make_engine(backend, t_off, staging_cache=False)
    r_on = record_tuples(on.run_workloads(wls))
    r_off = record_tuples(off.run_workloads(wls))
    assert r_on == r_off
    stats = on.staging_stats()
    assert stats["misses"] > 0
    # second pass over the SAME topology must hit and stay identical
    on2 = make_engine(backend, t_on, staging_cache=True)
    assert record_tuples(on2.run_workloads(wls)) == r_on
    assert on2.staging_stats()["hit_rate"] > 0.5


def flat_ops(hosts):
    """One op of each single-flow lowering: gleam bcast, write, unicast."""
    return [GroupOp("bcast", tuple(hosts[:5]), 1 << 20, key=1),
            GroupOp("write", tuple(hosts[4:8]), 256 << 10),
            GroupOp("unicast", (hosts[1], hosts[6]), 512 << 10, key=3)]


@pytest.mark.parametrize("backend", ["flow", "flow-np"])
def test_reused_and_fresh_ops_stage_alike(backend):
    """A sweep that re-passes the very same GroupOp objects and one that
    passes equal new ones stage alike: bit-identical records, and from
    the second pass on every op is one hit of the op-level layout
    cache, whichever objects carry it."""
    def passes(reuse):
        topo = small_fat_tree()
        ops = flat_ops(topo.hosts)
        rows, hits = [], []
        for _ in range(3):
            eng = make_engine(backend, topo)
            wl = Workload("flat", ops if reuse else flat_ops(topo.hosts))
            before = eng.staging_stats()["hits"]
            rows.append(record_tuples(eng.run_workloads([wl])))
            hits.append(eng.staging_stats()["hits"] - before)
        return rows, hits

    rows_same, hits_same = passes(reuse=True)
    rows_new, hits_new = passes(reuse=False)
    assert rows_same == rows_new
    assert hits_same[1:] == hits_new[1:] == [3, 3]


@pytest.mark.parametrize("backend", ["flow", "flow-np"])
def test_flow_bit_identity_with_fault_invalidation_mid_sweep(backend):
    """A sweep mixing static ops, a fault op, and a persistent topology
    change between runs: cache-on must equal cache-off throughout."""
    t_on, t_off = small_fat_tree(), small_fat_tree()
    hosts = t_on.hosts

    def wls():
        wl1 = Workload("pre")
        wl1.bcast(hosts[:6], 1 << 20, key=1)
        leaf = leaf_of(t_on, hosts[1])
        switches = set(t_on.switches)
        agg = next(peer for _, (peer, _) in
                   sorted(t_on.ports[leaf].items()) if peer in switches)
        wl2 = Workload("faulty")
        wl2.bcast(hosts[:6], 1 << 20, key=1, faults=(
            FaultEvent("link_down", 2e-5, node=leaf, peer=agg),))
        wl3 = Workload("dynamic")
        wl3.bcast(hosts[:5], 1 << 20, events=(
            MemberEvent("join", hosts[6], 1e-5),))
        return [wl1, wl2, wl3]

    on = make_engine(backend, t_on, staging_cache=True)
    off = make_engine(backend, t_off, staging_cache=False)
    assert record_tuples(on.run_workloads(wls())) == \
        record_tuples(off.run_workloads(wls()))

    # persistent fabric change: shared cache must invalidate, results
    # must still agree
    for topo in (t_on, t_off):
        topo.set_link_down(topo.hosts[2], leaf_of(topo, topo.hosts[2]),
                           True)
    inv0 = StagingCache.of(t_on).invalidations
    on2 = make_engine(backend, t_on, staging_cache=True)
    off2 = make_engine(backend, t_off, staging_cache=False)
    wl = Workload("post")
    wl.bcast(hosts[:2] + hosts[3:6], 1 << 20, key=1)
    assert record_tuples(on2.run_workloads([wl])) == \
        record_tuples(off2.run_workloads([wl]))
    assert StagingCache.of(t_on).invalidations > inv0


def test_packet_engine_route_cache_off_bit_identity():
    t_on, t_off = small_fat_tree(), small_fat_tree()
    wl = Workload("pkt")
    wl.bcast(t_on.hosts[:5], 256 << 10, key=2)
    wl.unicast(t_on.hosts[5], t_on.hosts[1], 64 << 10)
    on = PacketEngine(t_on, seed=7, staging_cache=True)
    off = PacketEngine(t_off, seed=7, staging_cache=False)
    wl2 = Workload("pkt")
    wl2.bcast(t_off.hosts[:5], 256 << 10, key=2)
    wl2.unicast(t_off.hosts[5], t_off.hosts[1], 64 << 10)
    assert record_tuples(on.run_workloads([wl])) == \
        record_tuples(off.run_workloads([wl2]))
    assert t_on.route_cache and not t_off.route_cache


# ======================================================= fingerprint rules

def test_fingerprint_transient_fault_round_trip_preserves_cache():
    topo = small_fat_tree()
    eng = FlowEngine(topo)
    wl = Workload("w")
    wl.bcast(topo.hosts[:6], 1 << 20)
    eng.run_workloads([wl])
    cache = StagingCache.of(topo)
    n_paths, inv0 = len(cache.paths), cache.invalidations
    assert n_paths > 0
    fp = topo.fingerprint()
    topo.set_link_down(topo.hosts[0], leaf_of(topo, topo.hosts[0]), True)
    assert topo.fingerprint() != fp
    topo.clear_down()
    assert topo.fingerprint() == fp          # state-based, not a counter
    eng2 = FlowEngine(topo)
    eng2.run_workloads([wl])
    assert cache.invalidations == inv0       # artifacts survived
    assert len(cache.paths) == n_paths


def test_fingerprint_connect_invalidates():
    topo = small_fat_tree()
    cache = StagingCache.of(topo)
    cache.paths[("x", "y", 0)] = (1, 2)
    topo.add_host("h-extra")
    topo.connect("h-extra", topo.switches[0], bw=100 * fattree.GBPS,
                 delay=1e-6)
    assert cache.sync().paths == {}
    assert cache.invalidations == 1


# ==================================================== candidate_ports memo

def test_candidate_ports_memo_stays_under_byte_budget():
    """Regression: many-destination churn (every host pairs with every
    other) keeps the memo at its byte-budget cap, evicting LRU —
    unbounded growth was the pre-budget failure mode."""
    topo = fattree.fat_tree(n_pods=4, leaves_per_pod=4, hosts_per_leaf=4,
                            aggs_per_pod=4, bw=100 * fattree.GBPS)
    # shrink the budget to its 1024-entry floor so the sweep overflows
    topo.CAND_CACHE_BYTES = 1
    cap = topo._cand_cache_cap()
    assert cap == 1024
    demand = set()
    for src in topo.hosts:
        for dst in topo.hosts[::3]:
            if src != dst:
                topo.path_links(src, dst, 0)
                demand.add((src, dst))
                assert len(topo._cand) <= cap
    # the sweep genuinely overflowed the cap (else the test is vacuous)
    assert len(demand) > cap
    assert len(topo._cand) == cap
    # routing answers are unaffected by eviction
    default_cap = fattree.Topology.CAND_CACHE_BYTES // \
        fattree.Topology._CAND_ENTRY_BYTES
    assert default_cap >= cap
    assert topo.path_links(topo.hosts[0], topo.hosts[-1], 0)


# ============================================================== telemetry

def test_staging_stats_shape():
    topo = small_fat_tree()
    eng = FlowEngine(topo)
    wl = Workload("w")
    wl.bcast(topo.hosts[:4], 1 << 20)
    eng.run_workloads([wl])
    stats = eng.staging_stats()
    for k in ("hits", "misses", "hit_rate", "sw_hits", "sw_misses",
              "invalidations", "paths", "switch_paths", "trees", "lat",
              "ops"):
        assert k in stats
    assert 0.0 <= stats["hit_rate"] <= 1.0
    # three receivers under the source's own leaf: one switch pair
    # (the leaf to itself) derived, then served twice
    assert stats["sw_misses"] == stats["switch_paths"] == 1
    assert stats["sw_hits"] == 2
