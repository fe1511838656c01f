"""Chip smoke run: the flow engine's device solver on one TPU chip.

Drives the main path users call — ``make_engine("flow", topo)`` then
``run_workloads`` — at fleet scale, in one process that forks nothing,
and checks every answer against the numpy reference engine
``flow-np``:

- A. the 16,384-host fat-tree under a 10-tenant x 100-group fleet
  (1,128 ops), solved twice (cold, then warm) — the jitted epoch
  solver, float32;
- B. the churn x loss x faults matrix on 4,096 hosts (8 cells) — the
  vmapped float64 dynamic-segment solver;
- C. the fig14 HPL sweep at its default scales (1,024 hosts, with the
  ring + ``long`` unicast meshes) — the vmapped batch solver.

Every phase raises when a check fails, so the script exits non-zero.
It refuses to run anywhere but on a TPU.  The last line of its output
is one JSON object naming the device.

    python chip_smoke.py
"""
from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

#: agreement bound between the device engine and ``flow-np``, per record:
#: within JCT_RTOL relative, or within NP_SLACK_S absolute.  The numpy
#: reference ends a flow once less than 1 us of transfer remains at its
#: rate (``FlowSim.run``: ``remaining <= 1e-6 * rate``), so its
#: completions may come up to 1 us early; the device solver drains to
#: ``1e-6 * volume + 1`` bytes.  With the two slacks made equal the
#: fleet agrees to 2.1e-7 relative, float32 rounding alone.
JCT_RTOL = 1e-4
NP_SLACK_S = 1e-6

#: the dynamic-segment solver matches the numpy oracle to this relative
#: bound (``tools/check_matrix.py``'s oracle gate)
SEG_RTOL = 1e-6


def device_info() -> dict:
    import jax
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": jax.device_count()}


def require_tpu(info: dict) -> None:
    """Exit non-zero, naming the platform, anywhere but on a TPU."""
    if info["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found platform "
                         f"{info['platform']!r}")


def _compare(got, want, what: str) -> dict:
    """Per-value agreement under the (JCT_RTOL, NP_SLACK_S) bound."""
    if len(got) != len(want):
        raise AssertionError(f"{what}: {len(got)} values vs {len(want)}")
    max_rel = max_abs_over = 0.0
    over = 0
    for g, w in zip(got, want):
        if not (g == g and w == w and abs(g) != float("inf")):
            raise AssertionError(f"{what}: non-finite value {g} vs {w}")
        rel = abs(g - w) / abs(w)
        max_rel = max(max_rel, rel)
        if rel > JCT_RTOL:
            over += 1
            max_abs_over = max(max_abs_over, abs(g - w))
            if abs(g - w) > NP_SLACK_S:
                raise AssertionError(
                    f"{what}: {g!r} vs flow-np {w!r} differs by "
                    f"{rel:.3g} relative, {abs(g - w):.3g} s absolute "
                    f"(bound {JCT_RTOL:g} or {NP_SLACK_S:g} s)")
    return {"n": len(got), "max_rel": max_rel, "n_over_rtol": over,
            "max_abs_over_rtol_s": max_abs_over}


def _record_jcts(recs) -> list:
    out = []
    for r in recs:
        if r.error:
            raise AssertionError(f"record {r.msg_id} failed: {r.error}")
        out.append(r.jct(len(r.t_deliver)))
    return out


def phase_fleet(topo, spec) -> dict:
    """A: one fleet workload, cold then warm pass, vs ``flow-np``."""
    from repro.apps.fleet import fleet_workload
    from repro.core import flowsim_jax
    from repro.core.engine import make_engine

    wl = fleet_workload(topo.hosts, spec)
    passes = []
    for _ in range(2):
        flowsim_jax.reset_solve_stats()
        eng = make_engine("flow", topo)
        t0 = time.perf_counter()
        recs = eng.run_workloads([wl], timeout=600.0)[0]
        wall = time.perf_counter() - t0
        stats = dict(flowsim_jax.SOLVE_STATS)
        if eng.name != "flow" or stats["calls"] == 0:
            raise AssertionError("the device solver did not run")
        passes.append({"wall_s": wall, "solve_s": stats["solve_s"],
                       "calls": stats["calls"],
                       "shapes": sorted(set(stats["shapes"]))})
    ref = make_engine("flow-np", topo).run_workloads([wl], timeout=600.0)[0]
    got, want = _record_jcts(recs), _record_jcts(ref)
    return {"hosts": len(topo.hosts), "ops": len(wl.ops),
            "cold": passes[0], "warm": passes[1],
            "vs_flow_np": _compare(got, want, "fleet")}


def phase_matrix(topo, n_groups: int, group: int, nbytes: int) -> dict:
    """B: every (churn, loss, flaps) cell vs ``flow-np`` to SEG_RTOL."""
    from benchmarks import fig_matrix
    from repro.core import flowsim_jax

    flowsim_jax.reset_solve_stats()
    t0 = time.perf_counter()
    got = fig_matrix.sweep_grid("flow", topo, n_groups, group, nbytes)
    wall = time.perf_counter() - t0
    stats = dict(flowsim_jax.SOLVE_STATS)
    want = fig_matrix.sweep_grid("flow-np", topo, n_groups, group, nbytes)
    if stats["calls"] == 0 or sorted(got) != sorted(want):
        raise AssertionError("the device solver did not run every cell")
    max_rel = 0.0
    for cell in sorted(want):
        rel = abs(got[cell] - want[cell]) / abs(want[cell])
        max_rel = max(max_rel, rel)
        if not rel <= SEG_RTOL:
            raise AssertionError(
                f"matrix cell {cell}: {got[cell]!r} vs flow-np "
                f"{want[cell]!r} ({rel:.3g} relative, bound {SEG_RTOL:g})")
    return {"hosts": len(topo.hosts), "cells": len(want), "wall_s": wall,
            "solve_s": stats["solve_s"], "calls": stats["calls"],
            "max_rel": max_rel}


def phase_hpl(scales) -> dict:
    """C: the fig14 sweep (one batched ``run_workloads``) vs ``flow-np``."""
    from benchmarks import fig14_scale
    from repro.core import flowsim_jax

    flowsim_jax.reset_solve_stats()
    t0 = time.perf_counter()
    got = fig14_scale.run([], engine="flow", scales=scales)
    wall = time.perf_counter() - t0
    stats = dict(flowsim_jax.SOLVE_STATS)
    want = fig14_scale.run([], engine="flow-np", scales=scales)
    if stats["calls"] == 0:
        raise AssertionError("the device solver did not run")
    if [n for n, _, _ in got] != [n for n, _, _ in want]:
        raise AssertionError("fig14 rows differ between engines")
    cmp = _compare([v * 1e-3 for _, v, _ in got],
                   [v * 1e-3 for _, v, _ in want], "fig14")
    return {"scales": list(scales), "rows": len(got), "wall_s": wall,
            "solve_s": stats["solve_s"], "calls": stats["calls"],
            "vs_flow_np": cmp}


def main() -> int:
    info = device_info()
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    require_tpu(info)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from benchmarks import fig14_scale, fig_matrix
    from repro.apps.fleet import FleetSpec
    from repro.core import fattree

    fleet_topo = fattree.fat_tree(n_pods=32, leaves_per_pod=16,
                                  hosts_per_leaf=32, aggs_per_pod=16,
                                  bw=200 * fattree.GBPS)
    spec = FleetSpec(n_tenants=10, groups_per_tenant=100, group_size=8,
                     nbytes=1 << 20, bg_unicasts=64, bg_incasts=8,
                     bg_fan_in=8, bg_nbytes=1 << 20, seed=0)
    phases = (
        ("A fleet", lambda: phase_fleet(fleet_topo, spec)),
        ("B matrix", lambda: phase_matrix(
            fig_matrix.build_topo(), fig_matrix.N_GROUPS,
            fig_matrix.GROUP, fig_matrix.NBYTES)),
        ("C hpl", lambda: phase_hpl(fig14_scale.SCALES)),
    )
    for name, run in phases:
        t0 = time.perf_counter()
        out = run()
        out["phase_s"] = time.perf_counter() - t0
        print(f"phase {name}: {json.dumps(out)}", flush=True)
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
