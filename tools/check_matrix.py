#!/usr/bin/env python3
"""Matrix-plane smoke gate for the batched dynamic-segment solver
(wired into CI).

Four invariants from ISSUE 10, on the small (16-host) twin of the
``benchmarks/fig_matrix.py`` churn x loss x faults grid:

1. **zero-dynamic bit-identity** — cells with no events and no faults
   never touch the segment machinery: ``batched`` and ``legacy``
   segment-solver modes must agree bit for bit on BOTH flow backends.
2. **batched == per-segment oracle** — the dynamic cells (churn and/or
   flaps) are bit-identical batched-vs-legacy on the numpy backend
   (same solver, same per-segment problems) and <= 1e-6 relative on
   the JAX backend (float64 device solves, reduction order only).
3. **device solver == numpy oracle** — ``segment_rates_many`` on the
   JAX backend matches the numpy per-segment solve + loss factor to
   <= 1e-6 relative on random padded/bucketed problems.
4. **churn x loss x faults parity** — every flow-engine cell agrees
   within 15% with the frozen multi-seed packet-engine ground truth
   (``benchmarks/ref_matrix.json``).  As in ``check_fig15.py``, verify
   runs only the deterministic fluid model (seconds); ``--update``
   re-measures the sampled packet side (64 repetitions per lossy
   cell) and rewrites the reference.

Exit code 0 = clean; 1 = divergence (details on stderr).

    PYTHONPATH=src python tools/check_matrix.py             # verify
    PYTHONPATH=src python tools/check_matrix.py --update    # re-measure
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "src"))
sys.path.insert(0, REPO)

REF_PATH = os.path.join(REPO, "benchmarks", "ref_matrix.json")
TOL = 0.15                 # packet-vs-flow parity bound
SEG_TOL = 1e-6             # device-vs-oracle bound
GT_SEEDS = 64              # packet repetitions per lossy cell


def _key(cell):
    churn, loss, flaps = cell
    return f"c{churn:g}_l{loss:g}_f{flaps}"


def _grid(engine, mode, seeds=1, workers=None):
    from benchmarks import fig_matrix as fm
    topo = fm.build_topo(smoke=True)
    return fm.sweep_grid(
        engine, topo, fm.N_GROUPS_SMALL, fm.GROUP_SMALL,
        fm.NBYTES_SMALL, seeds=seeds, workers=workers,
        engine_kw={"segment_solver": mode} if mode else None)


def check_modes(problems):
    """Invariants 1 + 2: batched vs legacy on both flow backends."""
    for engine in ("flow-np", "flow"):
        batched = _grid(engine, "batched")
        legacy = _grid(engine, "legacy")
        exact = drift = 0
        for cell, want in legacy.items():
            got = batched[cell]
            churn, loss, flaps = cell
            if loss:
                # lossy dynamic cells differ by design: the batched
                # solver folds the loss factor into the SAME segment
                # solves, the legacy closures never did
                continue
            if engine == "flow-np" or (churn == 0 and flaps == 0):
                if got != want:
                    problems.append(
                        f"modes {engine}/{_key(cell)}: batched "
                        f"{got!r} != legacy {want!r} (bit-identity)")
                else:
                    exact += 1
            elif abs(got - want) > SEG_TOL * want:
                problems.append(
                    f"modes {engine}/{_key(cell)}: batched {got!r} vs "
                    f"legacy {want!r} exceeds {SEG_TOL:g} relative")
            else:
                drift += 1
        print(f"check_matrix: modes {engine}: {exact} cells "
              f"bit-identical, {drift} within {SEG_TOL:g}")


def check_oracle(problems):
    """Invariant 3: device ``segment_rates_many`` vs the numpy oracle
    on random duplicate-free problems (with and without loss params)."""
    from benchmarks import fig_matrix as fm
    from repro.core.flowsim import FlowSim, LossParams
    from repro.core.flowsim_jax import JaxFlowSim
    topo = fm.build_topo(smoke=True)
    np_sim, jx_sim = FlowSim(topo), JaxFlowSim(topo)
    rng = np.random.default_rng(0)
    n_links = len(np_sim.cap)
    probs = []
    for _ in range(24):
        n_flows = int(rng.integers(2, 9))
        sets = tuple(
            tuple(int(x) for x in
                  rng.choice(n_links, size=int(rng.integers(1, 7)),
                             replace=False))
            for _ in range(n_flows))
        lp = None
        if rng.random() < 0.7:
            lp = LossParams(q=float(rng.uniform(0, 0.05)),
                            wsq=float(rng.uniform(0, 1e-4)),
                            wnd=256.0, tail=0.0,
                            ecn=bool(rng.random() < 0.5))
        probs.append((sets, lp))
    want = np_sim.segment_rates_many(probs)
    got = jx_sim.segment_rates_many(probs)
    bad = [(i, g, w) for i, (g, w) in enumerate(zip(got, want))
           if abs(g - w) > SEG_TOL * w]
    for i, g, w in bad:
        problems.append(f"oracle problem {i}: device {g!r} vs "
                        f"numpy {w!r} exceeds {SEG_TOL:g} relative")
    if not bad:
        print(f"check_matrix: oracle: {len(probs)} problems within "
              f"{SEG_TOL:g}")


def check_parity(problems):
    """Invariant 4: flow cells vs the frozen packet ground truth."""
    if not os.path.exists(REF_PATH):
        problems.append(f"missing {REF_PATH} — run --update once")
        return
    with open(REF_PATH) as fh:
        ref = json.load(fh)
    flow = _grid("flow", None)
    worst = 0.0
    for cell, jf in flow.items():
        want = ref["cells"].get(_key(cell))
        if want is None:
            problems.append(f"parity {_key(cell)}: missing from ref — "
                            f"run --update")
            continue
        div = abs(jf * 1e6 - want) / want
        worst = max(worst, div)
        if div > TOL:
            problems.append(
                f"parity {_key(cell)}: flow {jf * 1e6:.2f}us vs packet "
                f"GT {want:.2f}us diverges {100 * div:.1f}% (> "
                f"{100 * TOL:.0f}%)")
    print(f"check_matrix: parity: {len(flow)} cells vs frozen GT, "
          f"worst {100 * worst:.1f}%")


def update(workers=0):
    """Re-measure the packet ground truth (sampled: 64 reps per lossy
    cell) and rewrite ``benchmarks/ref_matrix.json``."""
    from benchmarks import fig_matrix as fm
    gt = _grid("packet", None, seeds=GT_SEEDS, workers=workers)
    ref = {
        "meta": {"seeds": GT_SEEDS, "nbytes": fm.NBYTES_SMALL,
                 "groups": [fm.N_GROUPS_SMALL, fm.GROUP_SMALL],
                 "tool": "tools/check_matrix.py --update"},
        "cells": {_key(cell): j * 1e6 for cell, j in sorted(gt.items())},
    }
    with open(REF_PATH, "w") as fh:
        json.dump(ref, fh, indent=1, sort_keys=True)
        fh.write("\n")
    print(f"check_matrix: wrote {len(ref['cells'])} cells -> {REF_PATH}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--update", action="store_true",
                    help="re-measure the packet ground truth (slow) "
                         "and rewrite the reference file")
    ap.add_argument("--workers", type=int, default=0,
                    help="packet scenario workers for --update")
    args = ap.parse_args(argv)
    if args.update:
        update(args.workers)
        return 0
    problems: list = []
    check_modes(problems)
    check_oracle(problems)
    check_parity(problems)
    if problems:
        for p in problems:
            print(f"check_matrix: FAIL: {p}", file=sys.stderr)
        return 1
    print("check_matrix: all invariants hold")
    return 0


if __name__ == "__main__":
    sys.exit(main())
