#!/usr/bin/env python3
"""CI divergence gate for the flow-engine loss/DCQCN model.

The fluid engines carry an expected-value loss correction (go-back-N
replay + timeout tail + DCQCN, ``core/flowsim.py``) calibrated against
the packet engine.  This gate runs the calibration grid — gleam +
multiunicast bcasts, groups 4/8, loss 0..1e-2 on the Fig. 8 testbed —
on the FLOW engine and compares every point against the checked-in
fixed-seed packet ground truth (``benchmarks/ref_fig15_flow.json``).
A relative divergence above 15% on any point fails the build: the two
engines are maintained independently, so drift on either side of the
differential trips the gate.

Unlike ``check_fig09.py`` (flow vs frozen flow), verify and update run
DIFFERENT engines: ``--update`` re-measures the packet ground truth
(multi-seed ``run_workloads`` batches — minutes), while the verify path only
runs the deterministic fluid model (seconds) — cheap enough for CI.
The zero-loss points double as a bit-exactness tripwire: with loss off
the flow engine must reproduce its pre-loss-model results, so they are
held to 0.1%, not 15%.

    PYTHONPATH=src python tools/check_fig15.py             # verify
    PYTHONPATH=src python tools/check_fig15.py --update    # re-measure GT

Exit code 0 = within tolerance; 1 = divergence (listed on stderr).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "src"))
sys.path.insert(0, REPO)

REF_PATH = os.path.join(REPO, "benchmarks", "ref_fig15_flow.json")
TOLERANCE = 0.15          # calibration bound, lossy points
ZERO_TOLERANCE = 0.001    # zero-loss points must stay bit-compatible


def _grid():
    from benchmarks.fig15_16_loss import (FID_GROUPS, FID_LOSS_RATES,
                                          FID_TRANSPORTS, _label)
    for transport in FID_TRANSPORTS:
        for group in FID_GROUPS:
            for loss in FID_LOSS_RATES:
                yield (f"g{group}_loss{_label(loss)}/{transport}",
                       group, loss, transport)


def measure(engine="flow") -> dict:
    """Flow-engine JCT (us) at every calibration-grid point."""
    from benchmarks.fig15_16_loss import flow_jct
    return {key: flow_jct(group, loss, transport, engine) * 1e6
            for key, group, loss, transport in _grid()}


def update(workers=0) -> dict:
    """Packet ground truth (us): multi-seed mean per grid point."""
    from benchmarks.fig15_16_loss import packet_gt
    gt = {}
    for key, group, loss, transport in _grid():
        gt[key] = packet_gt(group, loss, transport, workers) * 1e6
        print(f"check_fig15: GT {key}: {gt[key]:.2f}us")
    return gt


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--update", action="store_true",
                    help="re-measure the packet ground truth (slow) and "
                         "rewrite the reference file")
    ap.add_argument("--engine", default="flow",
                    choices=("flow", "flow-np"),
                    help="fluid backend to verify (default: flow)")
    args = ap.parse_args(argv)
    if args.update:
        from benchmarks.fig15_16_loss import (FID_SEEDS, NBYTES,
                                              FID_GROUPS)
        gt = update()
        flow = measure(args.engine)
        with open(REF_PATH, "w", encoding="utf-8") as f:
            json.dump({"tolerance": TOLERANCE,
                       "zero_tolerance": ZERO_TOLERANCE,
                       "seed": 11, "window": 512, "nbytes": NBYTES,
                       "groups": list(FID_GROUPS),
                       "seeds_per_loss": {f"{k:g}": v
                                          for k, v in FID_SEEDS.items()},
                       "packet_us": gt,
                       "flow_us_at_update": flow},
                      f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"check_fig15: wrote {len(gt)} GT points -> {REF_PATH}")
        return 0
    if not os.path.exists(REF_PATH):
        print(f"check_fig15: missing reference {REF_PATH} "
              f"(run with --update)", file=sys.stderr)
        return 1
    with open(REF_PATH, encoding="utf-8") as f:
        ref = json.load(f)["packet_us"]
    got = measure(args.engine)
    problems = []
    for name, want in sorted(ref.items()):
        have = got.get(name)
        if have is None:
            problems.append(f"missing point {name}")
            continue
        tol = ZERO_TOLERANCE if "_loss0/" in name else TOLERANCE
        dev = abs(have - want) / want
        status = "FAIL" if dev > tol else "ok"
        print(f"check_fig15: {status} {name}: flow {have:.2f}us "
              f"(packet {want:.2f}us, {100 * dev:.1f}% of "
              f"{100 * tol:g}%)")
        if dev > tol:
            problems.append(f"{name}: flow {have:.2f}us vs packet "
                            f"{want:.2f}us ({100 * dev:.1f}% > "
                            f"{100 * tol:g}%)")
    for name in sorted(set(got) - set(ref)):
        problems.append(f"unexpected point {name} (run --update?)")
    if problems:
        for p in problems:
            print(f"check_fig15: {p}", file=sys.stderr)
        return 1
    print(f"check_fig15: OK ({len(ref)} points, lossy within "
          f"{100 * TOLERANCE:.0f}%, zero-loss within "
          f"{100 * ZERO_TOLERANCE:g}%)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
