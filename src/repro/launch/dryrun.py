import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input-shape x mesh) cell, ``jit(step).lower()``
against ShapeDtypeStruct stand-ins and ``.compile()`` on the production
mesh — 16x16 (single pod, 256 chips) and 2x16x16 (two pods, 512 chips).
No arrays are allocated: success proves the sharding rules, collective
schedule, and memory plan are consistent; ``memory_analysis()`` proves the
model fits; ``cost_analysis()`` + HLO collective parsing feed §Roofline.

Usage:
    python -m repro.launch.dryrun --arch mixtral_8x7b --shape train_4k \
        --mesh pod --out results/dryrun
    python -m repro.launch.dryrun --all --mesh both --out results/dryrun

Each cell writes one JSON file; failures are recorded with the exception
text so the sweep is restartable and auditable (EXPERIMENTS.md §Dry-run).
"""
import argparse
import json
import pathlib
import sys
import time
import traceback


def _probe_cfg(cfg, k: int, seq: int):
    """k-block unrolled probe config for scan-aware cost extrapolation.

    XLA's cost_analysis counts a while-loop (lax.scan) body ONCE, not
    x trip-count (verified experimentally — see EXPERIMENTS.md §Roofline
    methodology).  We therefore lower two UNROLLED probes (1 and 2 blocks,
    every internal scan disabled: xent in one chunk, dense attention,
    accum=1) and extrapolate linearly:

        term(n_blocks) = probe1 + (n_blocks - 1) * (probe2 - probe1)

    Memory analysis still comes from the real scanned module.
    """
    per_block_enc = cfg.enc_layers // cfg.n_blocks if cfg.enc_layers else 0
    return cfg.replace(
        n_layers=k * len(cfg.pattern),
        enc_layers=k * per_block_enc,
        scan_layers=False,
        xent_chunk=seq,
        kv_chunk=max(seq, cfg.kv_chunk),
        accum_steps=1,
    )


def probe_terms(cfg, shape: str, mesh) -> dict:
    """(flops, bytes, collective bytes) extrapolated from 2 probes."""
    from repro.launch import steps
    from repro.launch.roofline import collective_bytes

    seq = steps.SHAPE_TABLE[shape]["seq"]
    vals = []
    for k in (1, 2):
        pcfg = _probe_cfg(cfg, k, seq)
        lowered, _ = steps.lower_cell(pcfg, shape, mesh)
        compiled = lowered.compile()
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, list) else (cost or {})
        coll = collective_bytes(compiled.as_text())
        vals.append({"flops": float(cost.get("flops", 0.0)),
                     "bytes": float(cost.get("bytes accessed", 0.0)),
                     "coll": float(coll["total_bytes"]),
                     "coll_detail": coll})
    nb = cfg.n_blocks
    out = {}
    for key in ("flops", "bytes", "coll"):
        p1, p2 = vals[0][key], vals[1][key]
        out[key] = p1 + (nb - 1) * (p2 - p1)
    out["probe1"] = vals[0]
    out["probe2"] = vals[1]
    # per-kind collective bytes, same linear fit
    d1 = vals[0]["coll_detail"]["bytes"]
    d2 = vals[1]["coll_detail"]["bytes"]
    out["coll_by_kind"] = {
        k: d1[k] + (nb - 1) * (d2[k] - d1[k]) for k in d1}
    return out


def run_cell(arch: str, shape: str, mesh_name: str, out_dir: pathlib.Path,
             *, schedule: str | None = None, overrides: dict | None = None,
             tag: str = "") -> dict:
    # imports deferred: XLA_FLAGS must be set before jax initializes
    from repro.configs.base import get_config
    from repro.launch import steps
    from repro.launch.mesh import make_production_mesh
    from repro.launch.roofline import summarize

    cfg = get_config(arch)
    if schedule:
        cfg = cfg.replace(collective_schedule=schedule)
    if overrides:
        cfg = cfg.replace(**overrides)
    suffix = f"-{tag}" if tag else ""
    cell_id = f"{arch}-{shape}-{mesh_name}{suffix}"
    out_path = out_dir / f"{cell_id}.json"
    rec: dict = {"arch": arch, "shape": shape, "mesh": mesh_name,
                 "tag": tag, "status": "running"}

    ok, why = steps.shape_runnable(cfg, shape)
    if not ok:
        rec.update(status="skipped", reason=why)
        out_path.write_text(json.dumps(rec, indent=1))
        print(f"[dryrun] {cell_id}: SKIP ({why})")
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_name == "multipod"))
    n_chips = mesh.devices.size
    try:
        t0 = time.time()
        lowered, spec = steps.lower_cell(cfg, shape, mesh)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
        mem = compiled.memory_analysis()
        print(f"[dryrun] {cell_id}: memory_analysis: {mem}")
        cost = compiled.cost_analysis()
        cost_d = cost[0] if isinstance(cost, list) else (cost or {})
        print(f"[dryrun] {cell_id}: cost_analysis flops="
              f"{cost_d.get('flops', 0):.3e} bytes="
              f"{cost_d.get('bytes accessed', 0):.3e}")
        rl = summarize(compiled, None, cfg, shape,
                       steps.SHAPE_TABLE[shape], mesh_name, n_chips,
                       spec.n_params)
        t0 = time.time()
        probes = probe_terms(cfg, shape, mesh)
        t_probe = time.time() - t0
        rl.flops_per_device = probes["flops"]
        rl.bytes_per_device = probes["bytes"]
        rl.coll_bytes_per_device = probes["coll"]
        rl.coll_detail = {"bytes": probes["coll_by_kind"],
                          "fit": {"probe1": probes["probe1"],
                                  "probe2": probes["probe2"]}}
        rec.update(status="ok", t_lower_s=t_lower, t_compile_s=t_compile,
                   t_probe_s=t_probe, n_params=spec.n_params,
                   kind=spec.kind, roofline=rl.to_dict())
        print(f"[dryrun] {cell_id}: OK lower={t_lower:.1f}s "
              f"compile={t_compile:.1f}s bottleneck={rl.bottleneck} "
              f"frac={rl.roofline_fraction:.3f}")
    except Exception as e:  # noqa: BLE001 — sweep must survive any cell
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
        print(f"[dryrun] {cell_id}: FAIL {type(e).__name__}: {e}")
    out_path.write_text(json.dumps(rec, indent=1))
    return rec


def main(argv=None) -> int:
    from repro.configs.base import ARCH_IDS
    from repro.launch.steps import SHAPE_TABLE

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(SHAPE_TABLE))
    ap.add_argument("--mesh", choices=("pod", "multipod", "both"),
                    default="pod")
    ap.add_argument("--all", action="store_true",
                    help="sweep every (arch x shape)")
    ap.add_argument("--schedule", default=None,
                    help="override cfg.collective_schedule")
    ap.add_argument("--set", action="append", default=[],
                    help="cfg override key=value (repeatable)")
    ap.add_argument("--tag", default="", help="suffix for output files")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-done", action="store_true",
                    help="skip cells whose JSON already says ok/skipped")
    args = ap.parse_args(argv)

    out_dir = pathlib.Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    meshes = ("pod", "multipod") if args.mesh == "both" else (args.mesh,)
    # --all sweeps the architectures the model layer builds
    from repro.configs.base import get_config
    from repro.models.model import supports
    cells = ([(a, s) for a in ARCH_IDS if supports(get_config(a))
              for s in SHAPE_TABLE]
             if args.all else [(args.arch, args.shape)])
    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        try:
            v = json.loads(v)
        except json.JSONDecodeError:
            pass
        overrides[k] = v

    n_fail = 0
    for arch, shape in cells:
        if arch is None or shape is None:
            ap.error("--arch/--shape required unless --all")
        for m in meshes:
            suffix = f"-{args.tag}" if args.tag else ""
            f = out_dir / f"{arch}-{shape}-{m}{suffix}.json"
            if args.skip_done and f.exists():
                try:
                    if json.loads(f.read_text())["status"] in (
                            "ok", "skipped"):
                        continue
                except (json.JSONDecodeError, KeyError):
                    pass
            rec = run_cell(arch, shape, m, out_dir,
                           schedule=args.schedule, overrides=overrides,
                           tag=args.tag)
            n_fail += rec["status"] == "error"
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
