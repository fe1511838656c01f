"""The float32 controls of a cell whose configuration states a float64
epoch solve (``control.py`` knows the float32 solve only).  On the
window passes a run checks first, it prints the check's number three
ways for each pass: the program as it is (the sound reading), the plain
reference rounded to float32 in the program's place, and the program
with its float64 promotion turned off (a plain float32 solve).  The
benchmark's runs do not run it; it is how the limit in
``checks/<cell>.json`` was set, with the sound readings below the limit
and both controls above it.  It runs the program, so on a machine with
the chip it takes the chip.

    python3 bench/control_float32.py --workload dsv3ep2k.route \
        --seeds 1 2 --passes 8
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if sys.path and os.path.abspath(sys.path[0]) == os.path.join(ROOT, "bench"):
    sys.path[0] = ROOT

from bench import check, gen, reference, sut  # noqa: E402


def readings(cfg: dict, mix: dict, seed: int, n_passes: int):
    """Per window pass: {"pass", "sound", "reference_f32",
    "program_f32"}, each the check's ``max_rel_gap`` against the
    float64 reference."""
    if cfg["precision"]["epoch_solve"] != "float64":
        raise ValueError("these controls are for a float64 epoch solve")
    sut.import_program()
    from repro.core import flowsim_jax
    topo = sut.build_fabric(cfg["fabric"])
    ref = reference.Reference(cfg)
    ref32 = reference.Reference(cfg, epoch_dtype=np.float32)
    safe = flowsim_jax.F32_SAFE_MAX
    n_warm = gen.cycle_len(mix)
    for i in range(n_warm):                 # the run's set-up passes
        tr = gen.pass_traffic(cfg, mix, seed, i)
        sut.run_pass(topo, sut.workloads(tr), tr["loss_rate"])
    for i in range(n_warm, n_warm + n_passes):
        tr = gen.pass_traffic(cfg, mix, seed, i)
        want = ref.run_pass(tr)[0]
        got = {}
        for name, limit in (("sound", safe), ("program_f32", math.inf)):
            flowsim_jax.F32_SAFE_MAX = limit
            try:
                recs, _ = sut.run_pass(topo, sut.workloads(tr),
                                       tr["loss_rate"])
            finally:
                flowsim_jax.F32_SAFE_MAX = safe
            got[name] = check.max_rel_gap(sut.answers(recs), want)
        got["reference_f32"] = check.max_rel_gap(ref32.run_pass(tr)[0],
                                                 want)
        yield dict(got, **{"pass": i})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--passes", type=int, default=4)
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
        for r in readings(cfg, mix, seed, args.passes):
            print(json.dumps(dict(r, workload=args.workload, seed=seed)),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
