"""The epoch solver's counters and names (``core/flowsim_jax.py``).

- a batched solve reports, per lane, the fluid epochs and max-min
  filling rounds the lane needed (the same as an unbatched solve of
  that lane) and the exact filling rounds the device ran for the
  batch, and its completion times are bit for bit those of the solver
  loop without counters;
- ``SOLVE_STATS`` adds them up, with each call's serial depth (its
  slowest lane's epochs, the rounds it ran), the lanes solved in
  float64 and those solved by the dense round, keeps the set of
  distinct shapes, and ``reset_solve_stats`` clears every key;
- both solver flavours lower to ``jit__simulate`` (the name the
  benchmark's trace reduction looks for); the segment solver does not;
- the packet engine's module imports no JAX.
"""
from __future__ import annotations

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.core import fattree, flowsim_jax
from repro.core.flowsim_jax import JaxFlowSim, _seg_solver, _solver, _split
from repro.kernels import maxmin

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# links 0-2 (1, 2, 3 GB/s) are each one flow's bottleneck; link 3
# (100 GB/s) is shared by every flow, so each completion dirties the
# survivors and the unbatched solver's warm start never skips a fill
CAP = np.array([1e9, 2e9, 3e9, 1e11, np.inf], np.float32)
SENTINEL = len(CAP) - 1
# lane 0: one flow, 1 epoch of 1 round.  Lane 1: three flows at 1, 2
# and 3 GB/s; the fastest finishes first, so epochs of 3, 2, 1 rounds
LANES = [[((0, 3), 1e6)],
         [((0, 3), 6e6), ((1, 3), 6e6), ((2, 3), 6e6)]]
F_PAD, H_PAD = 16, 8


def _pack(lane):
    fl = np.full((F_PAD, H_PAD), SENTINEL, np.int32)
    vol = np.zeros(F_PAD, np.float32)
    for i, (links, v) in enumerate(lane):
        fl[i, :len(links)] = links
        vol[i] = v
    return fl, vol


def _without_counters(flow_links, cap, vol):
    """The batched epoch loop as it stood before it counted epochs and
    rounds (lossless, no warm start)."""
    n_flows = flow_links.shape[0]
    eps = vol * 1e-6 + 1.0

    def cond(st):
        _, rem, _, _, _, it = st
        return jnp.logical_and(jnp.any(rem > 0.0), it <= n_flows)

    def body(st):
        t, rem, done, rates, dirty, it = st
        active = rem > 0.0
        rates = maxmin.maxmin_rates(flow_links, cap, active)
        dt = jnp.min(jnp.where(active, rem / rates, jnp.inf))
        t = t + dt
        rem = jnp.where(active, rem - rates * dt, 0.0)
        fin = active & (rem <= eps)
        done = jnp.where(fin, t, done)
        rem = jnp.where(fin, 0.0, rem)
        return t, rem, done, rates, dirty, it + 1

    zero = jnp.asarray(0.0, cap.dtype)
    init = (zero, vol, jnp.zeros(n_flows, cap.dtype),
            jnp.zeros(n_flows, cap.dtype), jnp.bool_(True),
            jnp.int32(0))
    return lax.while_loop(cond, body, init)[2]


@pytest.fixture
def sim():
    flowsim_jax.reset_solve_stats()
    yield JaxFlowSim(fattree.testbed(n_hosts=4))
    flowsim_jax.reset_solve_stats()


def test_batched_counts_per_lane_and_exact_rounds_run(sim):
    packed = [_pack(lane) for lane in LANES]
    fl = np.stack([p[0] for p in packed])
    vol = np.stack([p[1] for p in packed])
    out = np.asarray(
        _solver(True)(jnp.asarray(fl), jnp.asarray(CAP), jnp.asarray(vol)))
    # the counts ride in the completion vector's own buffer
    assert out.shape == (2, F_PAD + flowsim_jax.COUNTS)
    done, counts = _split(out)
    epochs, rounds, ran = counts.T
    assert epochs.tolist() == [1, 3]
    assert rounds.tolist() == [1, 3 + 2 + 1]
    # epoch by epoch the device runs the slower lane's rounds: 3, 2, 1
    assert int(ran.max()) == 6

    for row, (f, v) in enumerate(packed):
        d1, c1 = _split(np.asarray(
            _solver(False)(jnp.asarray(f), jnp.asarray(CAP),
                           jnp.asarray(v))))
        assert c1.tolist() == [epochs[row], rounds[row], rounds[row]]
        np.testing.assert_allclose(d1, done[row], rtol=1e-6)

    old = jax.device_get(jax.jit(jax.vmap(_without_counters,
                                          in_axes=(0, None, 0)))(
        jnp.asarray(fl), jnp.asarray(CAP), jnp.asarray(vol)))
    assert old.dtype == done.dtype
    assert old.tobytes() == np.ascontiguousarray(done).tobytes()


def test_solve_stats_add_up_the_counts(sim):
    packed = [_pack(lane) for lane in LANES]
    fl = np.stack([p[0] for p in packed])
    vol = np.stack([p[1] for p in packed])
    sim._dispatch(True, fl, CAP, vol, np.float32)
    sim._dispatch(True, fl, CAP, vol, np.float32)
    sim._dispatch(False, packed[1][0], CAP, packed[1][1], np.float32)
    st = flowsim_jax.SOLVE_STATS
    assert st["calls"] == 3
    assert st["shapes"] == {(2, F_PAD, H_PAD), (F_PAD, H_PAD)}
    assert st["lanes"] == 2 + 2 + 1
    assert st["epochs"] == 4 + 4 + 3
    assert st["rounds"] == 7 + 7 + 6
    # a batch runs its lanes for its slowest lane's rounds; one lane
    # alone runs what it needs
    assert st["lane_rounds_run"] == 2 * 6 + 2 * 6 + 6
    # per call, the slowest lane's epochs and the rounds the device ran
    assert st["epochs_run"] == 3 + 3 + 3
    assert st["rounds_run"] == 6 + 6 + 6
    assert st["x64_lanes"] == 0
    assert st["dense_lanes"] == 0
    flowsim_jax.reset_solve_stats()
    assert st == {"solve_s": 0.0, "calls": 0, "shapes": set(), "lanes": 0,
                  "epochs": 0, "rounds": 0, "lane_rounds_run": 0,
                  "x64_lanes": 0, "epochs_run": 0, "rounds_run": 0,
                  "dense_lanes": 0}


def test_x64_lanes_count_the_promoted_solves(sim):
    packed = [_pack(lane) for lane in LANES]
    fl = np.stack([p[0] for p in packed])
    vol = np.stack([p[1] for p in packed])
    sim._dispatch(True, fl, CAP, vol, np.float32)
    sim._dispatch(True, fl, CAP.astype(np.float64), vol.astype(np.float64),
                  np.float64)
    sim._dispatch(False, packed[1][0], CAP.astype(np.float64),
                  packed[1][1].astype(np.float64), np.float64)
    st = flowsim_jax.SOLVE_STATS
    assert (st["lanes"], st["x64_lanes"]) == (5, 3)
    assert (st["epochs_run"], st["rounds_run"]) == (9, 18)


@pytest.mark.parametrize("backend,lanes_fit,want", [
    ("tpu", 2, 2 + 1), ("tpu", 0, 0), ("cpu", 2, 0), ("tpu", 1, 1)])
def test_dense_lanes_count_the_lanes_of_dense_fills(sim, monkeypatch,
                                                    backend, lanes_fit,
                                                    want):
    """A float64 call's lanes count as dense where the kernel takes the
    dense round: on a TPU, with the call's lanes x F_PAD x 4 links
    within the bound (``lanes_fit`` lanes fit it; 2 is the default
    bound's case).  float32 calls never do."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if lanes_fit < 2:
        monkeypatch.setattr(maxmin, "DENSE_MAX_ENTRIES",
                            (lanes_fit + 1) * F_PAD * (len(CAP) - 1) - 1)
    packed = [_pack(lane) for lane in LANES]
    fl = np.stack([p[0] for p in packed])
    vol = np.stack([p[1] for p in packed])
    cap64 = CAP.astype(np.float64)
    sim._dispatch(True, fl, CAP, vol, np.float32)
    sim._dispatch(False, packed[1][0], CAP, packed[1][1], np.float32)
    assert flowsim_jax.SOLVE_STATS["dense_lanes"] == 0
    sim._dispatch(True, fl, cap64, vol.astype(np.float64), np.float64)
    sim._dispatch(False, packed[1][0], cap64,
                  packed[1][1].astype(np.float64), np.float64)
    st = flowsim_jax.SOLVE_STATS
    assert (st["lanes"], st["x64_lanes"], st["dense_lanes"]) == (6, 3, want)


def test_maxmin_fill_counts_the_rounds_maxmin_rates_runs():
    fl, _ = _pack(LANES[1])
    active = np.zeros(F_PAD, bool)
    active[:3] = True
    rates, n = maxmin.maxmin_fill(jnp.asarray(fl), jnp.asarray(CAP),
                                  jnp.asarray(active))
    assert int(n) == 3
    same = maxmin.maxmin_rates(jnp.asarray(fl), jnp.asarray(CAP),
                               jnp.asarray(active))
    assert np.asarray(same).tobytes() == np.asarray(rates).tobytes()
    _, none = maxmin.maxmin_fill(jnp.asarray(fl), jnp.asarray(CAP),
                                 jnp.zeros(F_PAD, bool))
    assert int(none) == 0


def _module_name(lowered) -> str:
    return lowered.as_text().split("\n")[0].split()[1]


@pytest.mark.parametrize("batched", [False, True])
@pytest.mark.parametrize("lossy", [False, True])
def test_epoch_solvers_lower_as_jit_simulate(batched, lossy):
    lead = (2,) if batched else ()
    f = jax.ShapeDtypeStruct(lead + (F_PAD, H_PAD), jnp.int32)
    v = jax.ShapeDtypeStruct(lead + (F_PAD,), jnp.float32)
    cap = jax.ShapeDtypeStruct(CAP.shape, jnp.float32)
    args = [f, cap, v] + ([(v,) * 4] if lossy else [])
    assert _module_name(_solver(batched, lossy).lower(*args)) \
        == "@jit__simulate"


def test_segment_solver_has_a_name_of_its_own():
    n, f, h = 2, F_PAD, H_PAD
    with jax.enable_x64(True):
        rows = jax.ShapeDtypeStruct((n, f), jnp.float64)
        name = _module_name(_seg_solver().lower(
            jax.ShapeDtypeStruct((n, f, h), jnp.int32), rows,
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct(CAP.shape, jnp.float64), (rows,) * 4))
    assert name == "@jit__segment_rate"
    assert "_simulate" not in name


def test_engine_module_imports_no_jax():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    code = ("import sys, repro.core.engine; "
            "sys.exit(1 if 'jax' in sys.modules else 0)")
    assert subprocess.run([sys.executable, "-c", code], env=env,
                          timeout=120).returncode == 0
