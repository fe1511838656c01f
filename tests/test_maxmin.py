"""The max-min solver stack (kernels/maxmin.py + flowsim_jax.py).

- property-style randomized agreement: the jnp progressive filling
  against the numpy ``FlowSim`` filling, on randomized topologies and
  flow sets, to 0.1%;
- shape bucketing: two sweep points in the same (F, H) bucket must hit
  the jit cache (no recompile);
- float64 auto-promotion once volumes exceed the float32 safe-integer
  range, pinned against a float64 numpy reference, with float64's
  own freeze and completion slacks;
- ``run_workloads`` batched scenarios == serial runs on fresh engines;
- solvers never clobber the staged ``Flow.volume``;
- the float64 filling's int32 link counts against a copy of the
  float-sum round, and no float64 scatter in the float64 solver;
- the dense round over the incidence against the indexed count round,
  and the solver on a batch renumbered to its links against the
  fabric's ids: bit for bit.
"""
from __future__ import annotations

import functools
import re

import numpy as np
import pytest

from repro.core import fattree
from repro.core.engine import make_engine
from repro.core.flowsim import FlowSim
from repro.core.workload import GroupOp, Workload

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import flowsim_jax
from repro.core.flowsim_jax import JaxFlowSim, _bucket, _solver
from repro.kernels import maxmin


def _jit_cache_size() -> int:
    """Compiled-shape count of the solver flavor ``run()`` dispatches."""
    return _solver(False)._cache_size()


def small_fat_tree():
    """8 hosts, heterogeneous tiers — interesting max-min contention."""
    return fattree.fat_tree(n_pods=2, leaves_per_pod=2, hosts_per_leaf=2,
                            aggs_per_pod=2, bw=100 * fattree.GBPS)


def random_flows(rng, sim, n_lo=3, n_hi=12):
    """Random mix of unicast paths and multicast trees with volumes."""
    hosts = list(sim.topo.hosts)
    out = []
    for _ in range(int(rng.integers(n_lo, n_hi + 1))):
        key = int(rng.integers(0, 4))
        if rng.random() < 0.5:
            src, dst = (str(h) for h in
                        rng.choice(hosts, 2, replace=False))
            links = sim.unicast_links(src, dst, key)
        else:
            k = int(rng.integers(2, min(6, len(hosts)) + 1))
            members = [str(h) for h in rng.choice(hosts, k, replace=False)]
            links = sim.multicast_tree_links(members[0], members, key)
        out.append((links, float(rng.uniform(1e5, 5e6))))
    return out


def pack_links(flows, n_links):
    """(F, H) sentinel-padded link-id matrix like the solver builds."""
    h = max(len(links) for links, _ in flows)
    fl = np.full((len(flows), h), n_links, np.int32)
    for i, (links, _) in enumerate(flows):
        fl[i, :len(links)] = links
    return fl


# ================================================ jnp vs numpy filling

@pytest.mark.parametrize("seed", range(5))
def test_maxmin_rates_match_numpy_filling(seed):
    """The jnp progressive filling agrees with the numpy FlowSim
    filling within 0.1% on random cases."""
    rng = np.random.default_rng(seed)
    topo = small_fat_tree() if seed % 2 else fattree.fig4()
    ref_sim = FlowSim(topo)
    flows = random_flows(rng, ref_sim)
    staged = [ref_sim.add(links, vol) for links, vol in flows]
    ref_sim._allocate(staged)
    want = np.asarray([f.rate for f in staged])

    fl = pack_links(flows, len(ref_sim.cap))
    cap = np.append(ref_sim.cap, np.inf).astype(np.float32)
    active = np.ones(len(flows), bool)
    got = np.asarray(maxmin.maxmin_rates(
        jnp.asarray(fl), jnp.asarray(cap), jnp.asarray(active)))
    np.testing.assert_allclose(got, want, rtol=1e-3)


@pytest.mark.parametrize("seed", range(3))
def test_jax_sim_completion_times_match_numpy(seed):
    """Full event loop (epochs + warm start) vs numpy FlowSim, 0.1%."""
    rng = np.random.default_rng(100 + seed)
    topo = small_fat_tree()
    sim_np, sim_jx = FlowSim(topo), JaxFlowSim(topo)
    flows = random_flows(rng, sim_np)
    fn = [sim_np.add(links, vol) for links, vol in flows]
    fj = [sim_jx.add(links, vol) for links, vol in flows]
    sim_np.run()
    sim_jx.run()
    done_np = np.asarray([f.done_t for f in fn])
    done_jx = np.asarray([f.done_t for f in fj])
    np.testing.assert_allclose(done_jx, done_np, rtol=1e-3)


# ======================================================= shape bucketing

def test_bucket_is_pow2_with_floor():
    assert _bucket(1, 16) == 16
    assert _bucket(16, 16) == 16
    assert _bucket(17, 16) == 32
    assert _bucket(1984, 16) == 2048
    assert _bucket(3, 8) == 8


def test_same_bucket_hits_jit_cache():
    """Two sweep points in one (F, H) bucket must NOT recompile."""
    topo = fattree.testbed(n_hosts=8)

    def solve(n_flows):
        sim = JaxFlowSim(topo)
        for i in range(n_flows):
            sim.add(sim.unicast_links("h0", f"h{1 + i % 7}", key=i),
                    1e6 + i)
        sim.run()

    solve(17)                               # F bucket 32
    before = _jit_cache_size()
    solve(25)                               # same bucket -> cache hit
    assert _jit_cache_size() == before
    solve(40)                               # F bucket 64 -> one compile
    assert _jit_cache_size() == before + 1


# ==================================================== float64 promotion

def test_small_volumes_solve_in_float32():
    sim = JaxFlowSim(fattree.testbed())
    sim.add(sim.unicast_links("h0", "h1"), 1 << 20)
    sim.run()
    assert sim.solve_dtype == np.float32


def test_large_volumes_auto_promote_to_float64():
    """Multi-GB volumes (fig12/13 regime) pin the f64 path: dtype
    selection + agreement with a float64 numpy reference at 1e-9 —
    beyond float32's ~6e-8 representation error."""
    topo = fattree.testbed()
    sim_jx, sim_np = JaxFlowSim(topo), FlowSim(topo)
    rng = np.random.default_rng(3)
    pairs = [("h0", "h1"), ("h0", "h2"), ("h1", "h3"), ("h2", "h3")]
    fj, fn = [], []
    for i, (a, b) in enumerate(pairs):
        vol = float(2 << 30) * (1.0 + float(rng.uniform(0, 0.5)))
        fj.append(sim_jx.add(sim_jx.unicast_links(a, b), vol))
        fn.append(sim_np.add(sim_np.unicast_links(a, b), vol))
    sim_jx.run()
    sim_np.run()
    assert sim_jx.solve_dtype == np.float64
    np.testing.assert_allclose([f.done_t for f in fj],
                               [f.done_t for f in fn], rtol=1e-9)


@pytest.mark.parametrize("gap,merged", [(5e-7, False), (5e-10, True)])
def test_float64_solve_keeps_float64_slacks(gap, merged):
    """Two promoted flows on one path whose volumes differ by ``gap``:
    they share the link until the smaller finishes at t1, then the
    larger drains the rest alone, at t1 * (1 + gap / 2).  A float64
    solve finishes the larger within 1e-9 of its volume only: 5e-7 of
    it left is a float32 solve's slack, not a float64 one's."""
    sim = JaxFlowSim(fattree.testbed())
    links = sim.unicast_links("h0", "h1")
    vol = 2 * flowsim_jax.F32_SAFE_MAX
    first = sim.add(links, vol)
    second = sim.add(links, vol * (1.0 + gap))
    sim.run()
    assert sim.solve_dtype == np.float64
    t1 = first.done_t
    want = t1 if merged else t1 * (1.0 + gap / 2.0)
    assert second.done_t == pytest.approx(want, rel=1e-12, abs=0.0)


def test_f32_boundary_is_safe_integer_range():
    sim = JaxFlowSim(fattree.testbed())
    sim.add(sim.unicast_links("h0", "h1"), flowsim_jax.F32_SAFE_MAX)
    sim.run()
    assert sim.solve_dtype == np.float32
    sim2 = JaxFlowSim(fattree.testbed())
    sim2.add(sim2.unicast_links("h0", "h1"),
             flowsim_jax.F32_SAFE_MAX * 1.01)
    sim2.run()
    assert sim2.solve_dtype == np.float64


# ============================================ run_workloads / solve_many

def _pair_workloads():
    a = Workload("a")
    a.bcast(["h0", "h1", "h2"], 1 << 20)
    b = Workload("b")
    b.bcast(["h0", "h3", "h4"], 2 << 20)
    b.unicast("h1", "h2", 1 << 20)
    return [a, b]


def _jcts(recs):
    return [r.jct(len(r.t_deliver)) for r in recs]


@pytest.mark.parametrize("engine", ["flow", "flow-np", "packet"])
def test_run_workloads_matches_serial_fresh_engines(engine):
    """A batch of two workloads gives each one's JCTs as a fresh engine
    that stages its ops and ``run()``s them alone."""
    wls = _pair_workloads()
    eng = make_engine(engine, fattree.testbed(n_hosts=5))
    got = [j for recs in eng.run_workloads(wls, timeout=60.0)
           for j in _jcts(recs)]
    want = []
    for wl in wls:
        solo = make_engine(engine, fattree.testbed(n_hosts=5))
        recs = [solo.stage(op) for op in wl.ops]
        solo.run(timeout=60.0)
        want += _jcts(recs)
    assert len(got) == 3
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_run_workloads_scenarios_are_isolated():
    """Identical scenarios staged together must NOT share bandwidth:
    each must match its solo JCT (unlike one run() batch, which halves
    the shared sender link)."""
    members = ("h0", "h1", "h2", "h3")
    op = GroupOp("bcast", members, 1 << 20)
    solo_eng = make_engine("flow", fattree.testbed())
    solo = solo_eng.stage(op)
    solo_eng.run()
    eng = make_engine("flow", fattree.testbed())
    recss = eng.run_workloads([Workload("a", [op]), Workload("b", [op])])
    for (r,) in recss:
        assert r.jct(3) == pytest.approx(solo.jct(3), rel=1e-6)


def test_run_workloads_heterogeneous_epochs_split_batches():
    """A unicast-mesh epoch (many flows, short paths) next to a
    multicast epoch (few flows, long link lists) exercises the batch
    planner; results must still match serial runs."""
    topo = small_fat_tree()
    hosts = topo.hosts
    mesh = Workload("mesh")
    for i, a in enumerate(hosts):
        for b in hosts[i + 1:]:
            mesh.unicast(a, b, 1 << 18, key=i)
    tree = Workload("tree")
    tree.bcast(list(hosts), 4 << 20)
    mesh_recs, tree_recs = make_engine("flow", topo).run_workloads(
        [mesh, tree])
    e1 = make_engine("flow", small_fat_tree())
    ref_recs = [e1.stage(op) for op in mesh.ops]
    e1.run()
    e2 = make_engine("flow", small_fat_tree())
    rt = e2.stage(tree.ops[0])
    e2.run()
    got = [r.jct(1) for r in mesh_recs] + [tree_recs[0].jct(len(hosts) - 1)]
    want = [r.jct(1) for r in ref_recs] + [rt.jct(len(hosts) - 1)]
    np.testing.assert_allclose(got, want, rtol=1e-4)


def test_run_workloads_rejects_pending_staged_ops():
    eng = make_engine("flow", fattree.testbed())
    eng.stage(GroupOp("bcast", ("h0", "h1"), 1 << 20))
    with pytest.raises(RuntimeError):
        eng.run_workloads([Workload("empty")])


def test_packet_engine_run_workloads_serial_fallback():
    """Serial scenarios run as independent experiments: the fabric
    quiesces and the clock resets between them (matching the flow
    engine's isolated-scenario semantics), so each record measures
    its own scenario, not the accumulated history."""
    eng = make_engine("packet", fattree.testbed())
    recss = eng.run_workloads(
        [Workload("a", [GroupOp("bcast", ("h0", "h1", "h2"), 64 << 10)]),
         Workload("b", [GroupOp("unicast", ("h0", "h3"), 64 << 10)])])
    (r_bcast,), (r_uni,) = recss
    assert r_bcast.jct(2) != float("inf")
    assert r_uni.jct(1) != float("inf")
    assert r_bcast.t_submit == 0.0
    assert r_uni.t_submit == 0.0            # clock reset between scenarios


# ===================================================== volume integrity

@pytest.mark.parametrize("cls", [FlowSim, JaxFlowSim])
def test_solvers_preserve_staged_volume(cls):
    """ISSUE bugfix: run() must record completion via done_t/remaining
    WITHOUT destroying the staged volume."""
    sim = cls(fattree.testbed())
    f = sim.add(sim.unicast_links("h0", "h1"), 1 << 20)
    sim.run()
    assert f.volume == float(1 << 20)
    assert f.remaining == 0.0
    assert f.done_t > 0.0


# ======================================= int32 link counts (float64 fill)

def _float_sum_fill(flow_links, cap, active, tol):
    """The filling as every round once ran it: the live flows and the
    frozen rates scatter-added onto their links in cap dtype, recounted
    each round.  The reference for ``maxmin_fill``'s int32 counts."""
    n_flows, n_caps, dtype = flow_links.shape[0], cap.shape[0], cap.dtype

    def body(st):
        rates, frozen, cap_rem, it = st
        live = 1.0 - frozen
        cnt = jnp.zeros(n_caps, dtype).at[flow_links].add(
            jnp.broadcast_to(live[:, None], flow_links.shape))
        share = jnp.where(cnt > 0.0, cap_rem / jnp.maximum(cnt, 1.0),
                          jnp.inf)
        tightest = jnp.min(share[flow_links], axis=1)
        limit = jnp.where(frozen > 0.5, jnp.inf, tightest)
        b = jnp.min(limit)
        newly = (frozen < 0.5) & (limit <= b * (1.0 + tol))
        newf = newly.astype(dtype)
        rates = jnp.where(newly, b, rates)
        used = jnp.zeros(n_caps, dtype).at[flow_links].add(
            jnp.broadcast_to((newf * b)[:, None], flow_links.shape))
        cap_rem = jnp.maximum(cap_rem - used, 0.0)
        return rates, jnp.minimum(frozen + newf, 1.0), cap_rem, it + 1

    def cond(st):
        return jnp.logical_and(jnp.min(st[1]) < 0.5, st[3] <= n_flows)

    init = (jnp.zeros(n_flows, dtype), 1.0 - active.astype(dtype), cap,
            jnp.int32(0))
    rates, _, _, rounds = lax.while_loop(cond, body, init)
    return jnp.maximum(rates, 1e-9), rounds


def _fill_problem(seed, n_links=300, f_pad=512, h_pad=8):
    """A (512, 8) problem over ``n_links`` links of uneven capacity:
    flows of 1 to 8 distinct links padded with the sentinel, a quarter
    of the used rows inactive, the last rows all sentinel padding."""
    rng = np.random.default_rng(seed)
    n_used = int(rng.integers(f_pad // 2, f_pad - 16))
    fl = np.full((f_pad, h_pad), n_links, np.int32)
    for i in range(n_used):
        hops = int(rng.integers(1, h_pad + 1))
        fl[i, :hops] = rng.choice(n_links, hops, replace=False)
    active = np.zeros(f_pad, bool)
    active[:n_used] = rng.random(n_used) >= 0.25
    cap = np.append(rng.uniform(1e9, 5e10, n_links), np.inf)
    return fl, cap, active


def _fills(fl, cap, active, tol, batched):
    """(new, reference) jitted fills on these arrays, vmapped over a
    leading lane axis when ``batched``."""
    new = functools.partial(maxmin.maxmin_fill, tol=tol)
    ref = functools.partial(_float_sum_fill, tol=tol)
    if batched:
        new = jax.vmap(new, in_axes=(0, None, 0))
        ref = jax.vmap(ref, in_axes=(0, None, 0))
    args = (jnp.asarray(fl), jnp.asarray(cap), jnp.asarray(active))
    return jax.jit(new)(*args), jax.jit(ref)(*args)


@pytest.mark.parametrize("seed", range(4))
def test_float64_fill_counts_match_float_sum_round(seed):
    """int32 counts carried across rounds give the float-sum round's
    rates to 1e-14 and its round count, on tens of rounds."""
    fl, cap, active = _fill_problem(seed)
    with jax.enable_x64(True):
        (got, n), (want, n_ref) = _fills(fl, cap, active, 1e-12, False)
        assert got.dtype == jnp.float64
        got, want = np.asarray(got), np.asarray(want)
    assert int(n) == int(n_ref) >= 10
    np.testing.assert_allclose(got, want, rtol=1e-14, atol=0.0)
    assert np.all(got[~active] == 1e-9)


def test_float64_fill_counts_match_under_vmap():
    """Two lanes whose round counts differ: each lane stops carrying its
    count when it is done, and matches its own reference."""
    fl0, cap, act0 = _fill_problem(10)
    fl1, _, act1 = _fill_problem(11)
    act1[32:] = False                       # a lane of few rounds
    fl, active = np.stack([fl0, fl1]), np.stack([act0, act1])
    with jax.enable_x64(True):
        (got, n), (want, n_ref) = _fills(fl, cap, active, 1e-12, True)
        got, want = np.asarray(got), np.asarray(want)
    n, n_ref = np.asarray(n), np.asarray(n_ref)
    assert n.tolist() == n_ref.tolist()
    assert n[0] != n[1]
    np.testing.assert_allclose(got, want, rtol=1e-14, atol=0.0)


@pytest.mark.parametrize("batched", [False, True])
def test_float32_fill_is_the_float_sum_round(batched):
    """A float32 fill keeps the float-sum round: bit for bit."""
    fl, cap, active = _fill_problem(20)
    cap = cap.astype(np.float32)
    if batched:
        fl2, _, act2 = _fill_problem(21)
        fl, active = np.stack([fl, fl2]), np.stack([active, act2])
    (got, n), (want, n_ref) = _fills(fl, cap, active, 1e-6, batched)
    assert got.dtype == jnp.float32
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.array_equal(np.asarray(n), np.asarray(n_ref))


def _scatter_results(text):
    """The result types of every scatter in lowered StableHLO text."""
    return re.findall(r'"stablehlo\.scatter".*?\}\) : \([^)]*\) -> '
                      r'(tensor<[^>]*>)', text, flags=re.S)


@pytest.mark.parametrize("batched", [False, True])
def test_float64_epoch_solver_scatters_no_float64(batched):
    """The lossless float64 ``jit__simulate`` scatters int32 only (the
    float32 one still scatters float32)."""
    lanes = (2,) if batched else ()
    for dtype in (jnp.float64, jnp.float32):
        with jax.enable_x64(dtype == jnp.float64):
            text = _solver(batched, False).lower(
                jax.ShapeDtypeStruct(lanes + (512, 8), jnp.int32),
                jax.ShapeDtypeStruct((301,), dtype),
                jax.ShapeDtypeStruct(lanes + (512,), dtype)).as_text()
        results = _scatter_results(text)
        assert results
        if dtype == jnp.float64:
            assert all(r.endswith("xi32>") for r in results), results
        else:
            assert any(r.endswith("xf32>") for r in results), results


# ================================== dense round and compacted link ids

def _indexed_and_dense(fl, cap, active, batched):
    """(indexed, dense) float64 fills of these arrays, jitted, vmapped
    over a leading lane axis when ``batched``."""
    def dense(fl, cap, active):
        inc = maxmin.link_incidence(fl, cap.shape[0])
        return maxmin.dense_fill(inc, cap, active, tol=1e-12)

    indexed = functools.partial(maxmin.maxmin_fill, tol=1e-12)
    if batched:
        indexed = jax.vmap(indexed, in_axes=(0, None, 0))
        dense = jax.vmap(dense, in_axes=(0, None, 0))
    args = (jnp.asarray(fl), jnp.asarray(cap), jnp.asarray(active))
    return jax.jit(indexed)(*args), jax.jit(dense)(*args)


@pytest.mark.parametrize("repeat", [False, True])
@pytest.mark.parametrize("seed", range(3))
def test_dense_fill_is_the_indexed_count_fill(seed, repeat):
    """The dense round gives the indexed round's rates and round count
    bit for bit, with inactive rows, all-sentinel padding rows and,
    where ``repeat``, links crossed twice within a row."""
    fl, cap, active = _fill_problem(30 + seed)
    if repeat:
        rows = np.flatnonzero(fl[:, 1] < len(cap) - 1)[::3]
        fl[rows, 1] = fl[rows, 0]
    assert np.all(fl[-16:] == len(cap) - 1) and not np.all(active)
    with jax.enable_x64(True):
        (want, n_want), (got, n) = _indexed_and_dense(fl, cap, active,
                                                      False)
        assert got.dtype == jnp.float64
        assert int(n) == int(n_want) >= 10
        assert np.array_equal(np.asarray(got), np.asarray(want))


def test_dense_fill_is_the_indexed_count_fill_under_vmap():
    """Two lanes whose round counts differ: each matches its indexed
    fill bit for bit."""
    fl0, cap, act0 = _fill_problem(40)
    fl1, _, act1 = _fill_problem(41)
    act1[32:] = False                       # a lane of few rounds
    fl, active = np.stack([fl0, fl1]), np.stack([act0, act1])
    with jax.enable_x64(True):
        (want, n_want), (got, n) = _indexed_and_dense(fl, cap, active,
                                                      True)
        n, n_want = np.asarray(n), np.asarray(n_want)
        assert n.tolist() == n_want.tolist() and n[0] != n[1]
        assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("lossy", [False, True])
def test_simulate_on_compacted_links_is_bit_identical(lossy):
    """Two float64 lanes over 300 of a fabric's 2,000 links, renumbered
    to the links they cross (``JaxFlowSim._compact``): the same
    completion times and loop counts as on the fabric's ids."""
    rng = np.random.default_rng(7)
    n_links = 2000
    lanes = []
    for seed in (50, 51):
        fl, _, active = _fill_problem(seed)
        fl = np.where(fl < 300, 1000 + 3 * fl, n_links).astype(np.int32)
        vol = np.where(active, rng.uniform(2e7, 3e7, len(active)), 0.0)
        lanes.append((fl, vol))
    fl = np.stack([f for f, _ in lanes])
    vol = np.stack([v for _, v in lanes])
    cap = np.append(rng.uniform(1e9, 5e10, n_links), np.inf)
    ids, ccap = JaxFlowSim(fattree.testbed())._compact(fl, cap)
    assert ccap.shape == (512 + 1,) and ids.max() == 512
    assert np.array_equal(ccap[ids[fl < n_links]], cap[fl[fl < n_links]])
    loss = None
    if lossy:
        q = np.where(vol > 0, 1e-4, 0.0)
        loss = (q, q * 4e-3, np.where(vol > 0, 64.0, 0.0), q > 0)
    with jax.enable_x64(True):
        def solve(links, caps):
            args = [jnp.asarray(links), jnp.asarray(caps), jnp.asarray(vol)]
            if loss is not None:
                args.append(tuple(jnp.asarray(a, jnp.float64)
                                  for a in loss))
            return np.asarray(_solver(True, lossy)(*args))

        want, got = solve(fl, cap), solve(ids, ccap)
    assert got.dtype == np.float64
    assert np.array_equal(got, want)
    assert flowsim_jax._split(got)[1][:, 0].min() >= 10
