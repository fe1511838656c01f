"""The control of ``correct``: the plain reference computed in the
nearest precision below the one the configuration states (bfloat16
for a float32 solve), put in the program's place at the cell's own
size.  The benchmark's runs do not run it; it
is how the limits in ``checks/`` were set, and it has to read above
them.  It needs no chip (the reference is numpy), and prints, per
seed, the number a run's check would read.

    python3 bench/control.py --workload loss4k.fresh --seeds 1 2 3
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import ml_dtypes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if sys.path and os.path.abspath(sys.path[0]) == os.path.join(ROOT, "bench"):
    sys.path[0] = ROOT

from bench import check, gen, reference  # noqa: E402

#: passes in the window the control's sample is drawn from
WINDOW_PASSES = 64


def control(cfg: dict) -> reference.Reference:
    """The reference one precision below the configuration's float32
    epoch solve."""
    if cfg["precision"]["epoch_solve"] != "float32":
        raise ValueError("the control knows the float32 epoch solve only")
    return reference.Reference(cfg, epoch_dtype=ml_dtypes.bfloat16)


def reading(cfg: dict, mix: dict, seed: int, n_passes: int,
            size: int) -> float:
    """The check's number with the control in the program's place, over
    a window of ``n_passes`` passes after the set-up passes."""
    n_warm = gen.cycle_len(mix)
    picked = check.Sample(seed, n_warm, size)
    for i in range(n_warm, n_warm + n_passes):
        picked.offer(i % n_warm, i)
    ref, ctl = reference.Reference(cfg), control(cfg)
    gap = 0.0
    for i in picked.items():
        tr = gen.pass_traffic(cfg, mix, seed, i)
        gap = max(gap, check.max_rel_gap(ctl.run_pass(tr)[0],
                                         ref.run_pass(tr)[0]))
    return gap


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    cfg_file = next(c["file"] for c in bench["configs"]
                    if c["name"] == cell["config"])
    with open(os.path.join(ROOT, cfg_file)) as f:
        cfg = json.load(f)
    mix = gen.load_json("traffic", cell["traffic"])
    for seed in args.seeds:
        gap = reading(cfg, mix, seed, WINDOW_PASSES, 8)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_max_rel_gap": gap}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
