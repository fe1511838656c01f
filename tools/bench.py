#!/usr/bin/env python3
"""Engine performance tracking — writes BENCH_flowsim.json /
BENCH_packetsim.json.

``--engine flow`` (default) times the fixed fig14 workload (HPL scales
8/16/32 on the flow engine, 1024-host fat-tree) through two solver
paths, each in its OWN subprocess so neither warms the other's
topology/routing/jit caches:

- **before** — the PR-1 solver discipline: one engine + one solve per
  scenario, shape bucketing off, fresh topology per scenario, an
  emptied persistent compilation cache (PR-1 recompiled every process);
- **after**  — the stage-then-batch path: the whole sweep staged on one
  engine, solved by a single ``run_many`` (shape-bucketed, vmapped
  epoch batches), persistent compilation cache on.  Measured twice:
  a cold process with an emptied cache directory, then a second fresh
  process against the now-warm directory (the steady state every run
  after the first sees).

The cache directory is the program's own (``flowsim_jax.
compile_cache_dir()``: ``JAX_COMPILATION_CACHE_DIR`` when set, else
``.jax_cache/`` at the checkout's root); a cold measurement empties it.

It also records a **dyn-segments** point (the ISSUE-10 churn-under-
loss sweep — 64 dynamic ops cut into 320 piecewise segments on a
1024-host fat-tree — solved by the batched device-resident segment
solver vs the legacy per-segment ``static_maxmin`` closures, with a
zero-loss <= 1e-6 JCT-match tripwire between the two modes), a
**loss-sweep** point (the fig15 flow sweep through
the loss-aware solver path, so a perf regression in ``loss_factors``
shows up next to the fig14 numbers), an **apps-sweep** point (the
fig_apps train-step/serving lowering through the phase-split execution
path, with a gleam-no-slower-than-multiunicast tripwire), and the
**fleet-scale** headline (a 16k-host fat-tree carrying 1k multicast
groups plus background traffic, staged and solved twice over the same
fabric — pass 2 is the staging-cache steady state every sweep pass
after the first sees).

``--engine packet`` times the packet engine's hot path on fig15 loss
points (the fidelity regime only it can simulate):

- **single** — one (group, loss) gleam bcast point, wall around
  ``run()`` (staging/registration excluded — the same basis at every
  ref), two fresh engines per child process;
- **sweep**  — the multi-seed fig15 batch (both sweep points x
  ``seeds`` repetitions) through ``run_many``, serial (workers=1) vs
  scenario-parallel (one worker process per CPU).  The serial and
  parallel record streams are asserted IDENTICAL — the bench doubles
  as a determinism tripwire.  The json records the ``cpu_count`` the
  comparison ran with; on a single-CPU box the parallel leg is skipped
  with a note instead of reporting a meaningless 1-worker "speedup";
- **before_git** — the same single points (and the per-point serial
  basis for the sweep estimate: the old engine had no multi-seed
  batching, so its sweep cost is seeds x the measured single-point
  wall) at the actual tree of ``--before-git REF``.

Every measurement excludes imports, and the ``env`` block records git
sha, interpreter/library versions and platform so numbers are
attributable.

    PYTHONPATH=src python tools/bench.py                     # flow, full
    PYTHONPATH=src python tools/bench.py --before-git HEAD~1 # + git ref
    PYTHONPATH=src python tools/bench.py --smoke             # CI-sized
    PYTHONPATH=src python tools/bench.py --engine packet --before-git REF
    PYTHONPATH=src python tools/bench.py --engine packet --smoke

``--smoke`` shrinks the workload and still writes the json — CI uses it
to catch perf-path regressions (import errors, recompile storms, a
broken parallel path) rather than to produce numbers.

``BENCH_*.json`` writes are refused from a dirty work tree (the json
records a ``git_sha`` the dirty diff would silently invalidate) unless
``--allow-dirty`` is passed.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import re
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "src"))
sys.path.insert(0, REPO)

from repro.core.flowsim_jax import compile_cache_dir  # noqa: E402

DEFAULT_SCALES = (8, 16, 32)

# packet bench workloads: fig15 points (group, loss).  The 512-host
# point is the headline (feedback aggregation scales with group size);
# the sweep uses the cheaper 64-host points at seeds repetitions.
PACKET_SINGLE_POINTS = ((512, 1e-4), (64, 1e-3))
PACKET_SWEEP_POINTS = ((64, 1e-4), (64, 1e-3))
PACKET_SWEEP_SEEDS = 6
PACKET_SMOKE_POINT = (16, 1e-3)
PACKET_SMOKE_SEEDS = 2


def _env_info() -> dict:
    """Provenance block shared by both bench outputs."""
    def _git(*args):
        try:
            return subprocess.run(
                ["git", *args], cwd=REPO, capture_output=True, text=True,
                check=True).stdout.strip()
        except Exception:
            return None

    info = {
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "git_sha": _git("rev-parse", "HEAD"),
        "git_dirty": bool(_git("status", "--porcelain")),
        "python": platform.python_version(),
        "platform": platform.platform(),
        "cpu_count": os.cpu_count(),
    }
    try:
        import numpy
        info["numpy"] = numpy.__version__
    except Exception:
        info["numpy"] = None
    try:
        import jax
        info["jax"] = jax.__version__
    except Exception:
        info["jax"] = None
    return info


# ------------------------------------------------ flow child measurement

def _timed_sweep(scales, batched: bool, bucketing: bool) -> dict:
    """One fig14 sweep in-process; wall/solve/python split + shapes."""
    from benchmarks import fig14_scale
    from repro.core import flowsim_jax

    prev = flowsim_jax.JaxFlowSim.bucketing
    flowsim_jax.JaxFlowSim.bucketing = bucketing
    flowsim_jax.reset_solve_stats()
    rows: list = []
    t0 = time.perf_counter()
    try:
        fig14_scale.run(rows, engine="flow", scales=scales,
                        batched=batched)
    finally:
        flowsim_jax.JaxFlowSim.bucketing = prev
    wall = time.perf_counter() - t0
    stats = dict(flowsim_jax.SOLVE_STATS)
    return {
        "wall_s": round(wall, 4),
        "solve_s": round(stats["solve_s"], 4),
        "python_s": round(wall - stats["solve_s"], 4),
        "solve_calls": stats["calls"],
        "solve_shapes": [list(s) for s in sorted(stats["shapes"])],
        "rows": [[n, round(v, 4)] for n, v, _ in rows],
    }


def _child_flow(kind: str, scales) -> int:
    """Two passes: pass1 pays compilation, pass2 hits the jit cache."""
    if kind == "serial":
        # PR-1 discipline also rebuilt the topology on every scenario
        # call (no lru_cache); bypass the cache to reproduce that
        from benchmarks import fig14_scale
        fig14_scale._build = fig14_scale._build.__wrapped__
    batched = kind == "batched"
    p1 = _timed_sweep(scales, batched, bucketing=batched)
    p2 = _timed_sweep(scales, batched, bucketing=batched)
    print(json.dumps({
        "pass1": p1,
        "pass2": p2,
        "compile_est_s": round(max(p1["wall_s"] - p2["wall_s"], 0.0), 4),
    }))
    return 0


def _flow_apps_sweep(smoke: bool) -> dict:
    """Flow-engine fig_apps point — the application traffic plane's
    lowering + phase-split execution path (ISSUE 8).  Full mode runs
    the train-step sweep (every transport) for both fig_apps configs
    plus one open-loop serving point; smoke runs one config's gleam /
    multiunicast train steps."""
    from benchmarks import fig_apps
    from repro.apps.metrics import run_phased, step_time
    from repro.apps.traffic import ArrivalSpec, ServingGenerator
    from repro.configs.base import get_config
    from repro.core import fattree
    from repro.core.engine import make_engine

    configs = fig_apps.CONFIGS[:1] if smoke else fig_apps.CONFIGS
    transports = ("gleam", "multiunicast") if smoke \
        else fig_apps.TRANSPORTS
    rows: list = []
    t0 = time.perf_counter()
    for name in configs:
        cfg = get_config(name, smoke=True)
        from repro.apps.collectives_lowering import train_step_workload
        for tr in transports:
            eng = make_engine("flow", fattree.testbed(
                n_hosts=fig_apps.TRAIN_MESH.n_chips))
            wl = train_step_workload(
                cfg, fig_apps.TRAIN_MESH, seq=fig_apps.TRAIN_SEQ,
                batch=fig_apps.TRAIN_BATCH, transport=tr)
            st = step_time(*run_phased(eng, wl, timeout=120.0))
            rows.append((f"figapps/train_{name}_{tr}/flow_ms", st * 1e3))
    if not smoke:
        cfg = get_config(configs[0], smoke=True)
        gen = ServingGenerator(
            cfg, fig_apps.N_REPLICAS, fig_apps.TP,
            prompt_len=fig_apps.PROMPT_LEN,
            decode_len=fig_apps.DECODE_LEN,
            kv_replicas=fig_apps.KV_REPLICAS)
        eng = make_engine("flow", fattree.testbed(
            n_hosts=fig_apps.N_REPLICAS * fig_apps.TP))
        rep = gen.run(eng, ArrivalSpec(rate=fig_apps.SERVE_RATE,
                                       n=fig_apps.SERVE_N, seed=0),
                      timeout=120.0)
        rows.append((f"figapps/serve_{configs[0]}_gleam/flow_qps",
                     rep.achieved_qps))
        rows.append((f"figapps/serve_{configs[0]}_gleam/flow_p99_us",
                     rep.quantiles["p99"] * 1e6))
    return {
        "wall_s": round(time.perf_counter() - t0, 4),
        "rows": [[n, round(v, 4)] for n, v in rows],
    }


def _flow_fleet_point(smoke: bool) -> dict:
    """The fleet-scale headline: one contended multi-tenant scenario
    (1k multicast groups + background traffic on a 16k-host fat-tree;
    CI-sized in smoke) staged and solved twice on fresh engines over
    the SAME fabric.  Pass 2 is the sweep steady state: every derived
    artifact (paths, trees, latencies, per-op layouts) replays from the
    staging cache, which is what makes this point feasible at all."""
    from repro.apps.fleet import FleetSpec, fleet_workload
    from repro.core import fattree, flowsim_jax
    from repro.core.engine import make_engine

    if smoke:
        topo = fattree.fat_tree(n_pods=8, leaves_per_pod=8,
                                hosts_per_leaf=16, aggs_per_pod=8,
                                bw=200 * fattree.GBPS)      # 1024 hosts
        spec = FleetSpec(n_tenants=4, groups_per_tenant=16, group_size=8,
                         nbytes=1 << 20, bg_unicasts=16, bg_incasts=4,
                         bg_fan_in=8, bg_nbytes=1 << 20, seed=0)
    else:
        topo = fattree.fat_tree(n_pods=32, leaves_per_pod=16,
                                hosts_per_leaf=32, aggs_per_pod=16,
                                bw=200 * fattree.GBPS)      # 16384 hosts
        spec = FleetSpec(n_tenants=10, groups_per_tenant=100,
                         group_size=8, nbytes=1 << 20, bg_unicasts=64,
                         bg_incasts=8, bg_fan_in=8, bg_nbytes=1 << 20,
                         seed=0)
    wl = fleet_workload(topo.hosts, spec)
    passes = []
    for _ in range(2):
        flowsim_jax.reset_solve_stats()
        eng = make_engine("flow", topo)
        t0 = time.perf_counter()
        recs = eng.run_workloads([wl], timeout=600.0)[0]
        wall = time.perf_counter() - t0
        stats = dict(flowsim_jax.SOLVE_STATS)
        passes.append({
            "wall_s": round(wall, 4),
            "solve_s": round(stats["solve_s"], 4),
            "python_s": round(wall - stats["solve_s"], 4),
            "errors": sum(1 for r in recs if r.error),
            "hit_rate": round(eng.staging_stats()["hit_rate"], 4),
        })
    return {
        "hosts": len(topo.hosts),
        "groups": spec.n_tenants * spec.groups_per_tenant,
        "ops": len(wl.ops),
        "pass1": passes[0],
        "pass2": passes[1],
        "warm_speedup": round(passes[0]["wall_s"]
                              / max(passes[1]["wall_s"], 1e-9), 2),
    }


def _flow_loss_sweep(smoke: bool) -> dict:
    """Flow-engine fig15 loss sweep — the regime the loss/DCQCN
    correction added to the solver hot path.  Full mode runs both
    sweep sections (calibration grid + 4096-host fat-tree scale grid);
    smoke runs one lossy calibration point."""
    from benchmarks import fig15_16_loss
    from repro.core import flowsim_jax

    flowsim_jax.reset_solve_stats()
    rows: list = []
    t0 = time.perf_counter()
    if smoke:
        jct = fig15_16_loss.flow_jct(8, 1e-3, "gleam")
        rows.append(("fig15/diff_g8_loss1e-03/gleam_us", jct * 1e6, ""))
    else:
        fig15_16_loss.run(rows, engine="flow")
    wall = time.perf_counter() - t0
    stats = dict(flowsim_jax.SOLVE_STATS)
    return {
        "wall_s": round(wall, 4),
        "solve_s": round(stats["solve_s"], 4),
        "solve_calls": stats["calls"],
        "rows": [[n, round(v, 4)] for n, v, _ in rows],
    }


def _flow_dyn_segments(smoke: bool, mode: str) -> dict:
    """The dyn_segments point: a churn-under-loss sweep (ISSUE 10) with
    the segment solver pinned to ``mode`` — ``legacy`` is the honest
    "before" leg (per-segment ``static_maxmin_loops`` closures inside
    the staging path), ``batched`` the device-resident timeline solver.

    Two timed passes per mode: pass 1 is cold (jit compile for the
    batched mode), pass 2 the sweep steady state (same process; the
    batched mode additionally replays memoized segment rates from the
    shared staging cache, exactly what later sweep passes see).  The
    zero-loss leg reports full-precision JCTs — the parent asserts the
    two modes agree there, where they solve the SAME per-segment
    problems."""
    from benchmarks import fig_matrix
    from repro.core import fattree
    from repro.core.engine import make_engine

    if smoke:
        topo = fattree.fat_tree(n_pods=2, leaves_per_pod=2,
                                hosts_per_leaf=8, aggs_per_pod=2)
        n_groups = 2                               # 32 hosts
    else:
        topo = fattree.fat_tree(n_pods=8, leaves_per_pod=8,
                                hosts_per_leaf=16, aggs_per_pod=8)
        n_groups = 64                              # 1024 hosts
    ops = fig_matrix.cell_ops(topo.hosts, n_groups, 12, 5e4, 0,
                              nbytes=1 << 20)
    out = {"mode": mode, "ops": len(ops)}

    def timed(loss):
        kw = {"loss_rate": loss} if loss else {}
        eng = make_engine("flow", topo, segment_solver=mode, **kw)
        recs = [eng.stage(op) for op in ops]
        segs = sum(len(tl) for tl in eng._dyn_links.values())
        t0 = time.perf_counter()
        eng.run(timeout=120.0)
        return segs, round(time.perf_counter() - t0, 4), recs

    out["segments"], out["pass1_wall_s"], _ = timed(1e-3)
    _, out["pass2_wall_s"], _ = timed(1e-3)
    out["segments_per_s"] = round(
        out["segments"] / max(out["pass2_wall_s"], 1e-9), 1)
    _, _, recs0 = timed(0.0)
    out["jcts0"] = [r.t_sender_cqe for r in recs0]
    return out


# ---------------------------------------------- packet child measurement

def _packet_single(group: int, loss: float) -> dict:
    """Wall around ``run()`` of one staged fig15 gleam point — the same
    basis as the git-ref driver below."""
    from benchmarks.fig15_16_loss import _point
    eng, rec = _point(group, loss, "gleam")
    t0 = time.perf_counter()
    eng.run(timeout=240.0)
    wall = time.perf_counter() - t0
    sim = eng.net.sim
    return {"group": group, "loss": loss, "wall_s": round(wall, 4),
            "jct_ms": rec.jct(group - 1) * 1e3,     # full precision:
            "events": sim.events, "dropped": sim.dropped}  # ref-compared


def _packet_sweep(points, seeds: int, workers) -> dict:
    """The multi-seed fig15 batch through run_many; returns per-point
    mean/std and the raw per-seed JCTs — the serial==parallel assertion
    compares those record for record, so a scenario-index permutation
    in the parallel scheduler cannot hide behind identical aggregates."""
    from benchmarks.fig15_16_loss import _sweep_point
    out = {"points": [], "jcts": [], "wall_s": 0.0}
    t0 = time.perf_counter()
    for group, loss in points:
        mean, std, jcts = _sweep_point(group, loss, "gleam", seeds,
                                       workers, 240.0)
        out["points"].append({"group": group, "loss": loss,
                              "mean_ms": round(mean * 1e3, 6),
                              "std_ms": round(std * 1e3, 6),
                              "seeds": seeds})
        out["jcts"].append(jcts)
    out["wall_s"] = round(time.perf_counter() - t0, 4)
    return out


def _packet_faults(group: int) -> dict:
    """Fault-sweep point: the fig_faults recovery axis (one scenario per
    fault class, fresh fabric each — see benchmarks/fig_faults.py) on
    the packet engine; wall time plus measured recovery per class."""
    from benchmarks.fig_faults import _sweep, members_for, recovery_cases
    t0 = time.perf_counter()
    jct = _sweep("packet", group)
    wall = time.perf_counter() - t0
    base = jct["r0"][0]
    return {"group": group, "wall_s": round(wall, 4),
            "jct_ms": base * 1e3,
            "recovery_us": {
                label: round((jct[label][0] - base) * 1e6, 3)
                for label, _ in recovery_cases(members_for(group))}}


def _child_packet(kind: str, spec: dict) -> int:
    if kind == "packet-single":
        res = {"passes": [_packet_single(spec["group"], spec["loss"])
                          for _ in range(2)]}
    elif kind == "packet-sweep":
        res = _packet_sweep([tuple(p) for p in spec["points"]],
                            spec["seeds"], spec["workers"])
    elif kind == "packet-faults":
        res = _packet_faults(spec["group"])
    else:
        raise ValueError(kind)
    print(json.dumps(res))
    return 0


# ---------------------------------------------------- parent orchestration

def _run_child(kind: str, *, scales=None, spec: dict = None) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    argv = [sys.executable, os.path.abspath(__file__), "--_child", kind]
    if scales is not None:
        argv += ["--scales", ",".join(str(s) for s in scales)]
    if spec is not None:
        argv += ["--_spec", json.dumps(spec)]
    out = subprocess.run(argv, capture_output=True, text=True, env=env,
                         cwd=REPO, check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


def _empty_compile_cache() -> None:
    """Empty the persistent compilation cache so the next child
    compiles cold."""
    shutil.rmtree(compile_cache_dir(), ignore_errors=True)


def _git_ref_tree(ref: str) -> str:
    tmp = tempfile.mkdtemp(prefix="bench-ref-")
    tar = subprocess.run(["git", "archive", ref], cwd=REPO,
                         capture_output=True, check=True)
    subprocess.run(["tar", "-x", "-C", tmp], input=tar.stdout, check=True)
    return tmp


def _run_git_ref_flow(ref: str, scales) -> dict:
    """Time the fig14 sweep of the ACTUAL tree at ``ref``, same basis as
    the in-tree measurements (wall around ``fig14_scale.run()``, imports
    excluded) and the same ``scales``."""
    tmp = _git_ref_tree(ref)
    driver = (
        "import sys, time\n"
        "sys.path.insert(0, 'src')\n"
        "from benchmarks import fig14_scale\n"
        "rows = []\n"
        "t0 = time.perf_counter()\n"
        f"fig14_scale.run(rows, engine='flow', scales={tuple(scales)!r})\n"
        "print('sweep done in %.4fs' % (time.perf_counter() - t0))\n")
    try:
        walls = []
        # the ref tree compiles cold into this checkout's cache directory
        env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=compile_cache_dir())
        env.pop("PYTHONPATH", None)
        for _ in range(2):
            _empty_compile_cache()
            out = subprocess.run([sys.executable, "-c", driver],
                                 capture_output=True, text=True,
                                 env=env, cwd=tmp, check=True)
            m = re.search(r"done in ([0-9.]+)s", out.stdout)
            walls.append(float(m.group(1)) if m else -1.0)
        return {"ref": ref, "wall_s": walls}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _run_git_ref_packet(ref: str, points) -> dict:
    """Time fig15 single points at the actual tree of ``ref`` — the
    ``_point``+``run()`` basis (both trees carry that helper)."""
    tmp = _git_ref_tree(ref)
    results = []
    try:
        env = dict(os.environ)
        env.pop("PYTHONPATH", None)
        for group, loss in points:
            driver = (
                "import sys, time\n"
                "sys.path.insert(0, 'src'); sys.path.insert(0, '.')\n"
                "from benchmarks.fig15_16_loss import _point\n"
                f"eng, rec = _point({group}, {loss!r}, 'gleam')\n"
                "t0 = time.perf_counter()\n"
                "eng.run(timeout=240.0)\n"
                "print('point done in %.4fs jct %.9g'\n"
                f"      % (time.perf_counter() - t0, rec.jct({group}-1)))\n")
            out = subprocess.run([sys.executable, "-c", driver],
                                 capture_output=True, text=True,
                                 env=env, cwd=tmp, check=True)
            m = re.search(r"done in ([0-9.]+)s jct ([0-9.e+-]+)",
                          out.stdout)
            results.append({"group": group, "loss": loss,
                            "wall_s": float(m.group(1)) if m else -1.0,
                            "jct_ms": float(m.group(2)) * 1e3
                            if m else -1.0})
        return {"ref": ref, "points": results}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


# ------------------------------------------------------------ engines

def _main_flow(args, result: dict) -> None:
    scales = tuple(int(s) for s in args.scales.split(",")) \
        if args.scales else ((8,) if args.smoke else DEFAULT_SCALES)
    result["workload"] = {"figure": "fig14", "engine": "flow",
                          "scales": list(scales), "smoke": args.smoke}
    if not args.smoke:
        # before: PR-1 solver discipline, cold compilation cache
        _empty_compile_cache()
        result["before"] = _run_child("serial", scales=scales)
        if args.before_git:
            result["before_git"] = _run_git_ref_flow(args.before_git,
                                                     scales)
    # after, cold: fresh process + empty compilation-cache dir
    _empty_compile_cache()
    result["after_cold"] = _run_child("batched", scales=scales)
    # after, steady state: fresh process, warm cache dir
    result["after_warm"] = _run_child("batched", scales=scales)
    # loss-sweep point: fig15 on the flow engine (loss-aware solver)
    result["loss_sweep"] = _run_child("flow-loss",
                                      spec={"smoke": args.smoke})
    # app-plane point: fig_apps lowering + phase-split execution
    result["apps_sweep"] = _run_child("flow-apps",
                                      spec={"smoke": args.smoke})
    # fleet-scale headline: 16k hosts x 1k groups, cold vs warm
    # staging cache (CI-sized in smoke)
    result["fleet_scale"] = _run_child("flow-fleet",
                                       spec={"smoke": args.smoke})
    # dyn-segments point: churn-under-loss piecewise segments,
    # batched device solver vs the legacy per-segment closures
    dyn = {mode: _run_child("flow-dyn",
                            spec={"smoke": args.smoke, "mode": mode})
           for mode in ("legacy", "batched")}
    dyn["speedup_cold"] = round(dyn["legacy"]["pass1_wall_s"]
                                / dyn["batched"]["pass1_wall_s"], 2)
    dyn["speedup_steady"] = round(dyn["legacy"]["pass2_wall_s"]
                                  / dyn["batched"]["pass2_wall_s"], 2)
    # zero-loss JCT-match tripwire: both modes solve the same
    # per-segment problems there, so they must agree to 1e-6
    rel = max((abs(a - b) / abs(b) for a, b in
               zip(dyn["legacy"]["jcts0"], dyn["batched"]["jcts0"])),
              default=0.0)
    dyn["jct0_max_rel_diff"] = rel
    assert rel <= 1e-6, \
        f"dyn_segments modes diverge on zero-loss JCTs: {rel:g}"
    result["dyn_segments"] = dyn

    if "before" in result:
        b = result["before"]["pass1"]["wall_s"]
        result["speedup_cold"] = round(
            b / result["after_cold"]["pass1"]["wall_s"], 2)
        result["speedup_steady"] = round(
            b / result["after_warm"]["pass1"]["wall_s"], 2)

    if args.smoke:       # regression tripwires for CI
        cold, warm = result["after_cold"], result["after_warm"]
        assert cold["pass1"]["solve_calls"] > 0
        assert cold["pass1"]["rows"], "sweep produced no rows"
        same = cold["pass1"]["solve_shapes"] == \
            warm["pass1"]["solve_shapes"]
        assert same, "bucketed shapes changed between processes"
        loss = result["loss_sweep"]
        assert loss["solve_calls"] > 0
        assert loss["rows"] and all(v > 0 for _, v in loss["rows"]), \
            "loss sweep produced no positive JCTs"
        apps = result["apps_sweep"]
        assert apps["rows"] and all(v > 0 for _, v in apps["rows"]), \
            "apps sweep produced no positive step times"
        dyn = result["dyn_segments"]
        assert dyn["batched"]["segments"] > 0, \
            "dyn_segments staged no piecewise segments"
        assert dyn["batched"]["segments"] == dyn["legacy"]["segments"]
        fleet = result["fleet_scale"]
        assert fleet["pass1"]["errors"] == fleet["pass2"]["errors"] == 0
        assert fleet["pass2"]["hit_rate"] > 0, \
            "fleet warm pass saw no staging-cache hits"
        by = dict(apps["rows"])
        gleam = [v for n, v in by.items() if n.endswith("gleam/flow_ms")]
        multi = [v for n, v in by.items()
                 if n.endswith("multiunicast/flow_ms")]
        assert gleam and multi and gleam[0] <= multi[0], \
            "gleam train step slower than multiunicast"


def _main_packet(args, result: dict) -> None:
    if args.smoke:
        points = [PACKET_SMOKE_POINT]
        sweep_points, seeds = [PACKET_SMOKE_POINT], PACKET_SMOKE_SEEDS
    else:
        points = [list(p) for p in PACKET_SINGLE_POINTS]
        sweep_points = [list(p) for p in PACKET_SWEEP_POINTS]
        seeds = PACKET_SWEEP_SEEDS
    result["workload"] = {
        "figure": "fig15", "engine": "packet", "smoke": args.smoke,
        "single_points": [list(p) for p in points],
        "sweep": {"points": [list(p) for p in sweep_points],
                  "seeds": seeds}}

    result["single"] = [
        _run_child("packet-single", spec={"group": g, "loss": l})
        for g, l in points]
    result["sweep_serial"] = _run_child(
        "packet-sweep",
        spec={"points": sweep_points, "seeds": seeds, "workers": 1})
    # the parallel-vs-serial comparison is only meaningful with real
    # parallelism; record the cpu count it ran with either way so the
    # speedup number is attributable to the box
    ncpu = os.cpu_count() or 1
    result["sweep_cpu_count"] = ncpu
    if ncpu == 1:
        result["sweep_parallel"] = None
        result["sweep_note"] = (
            "cpu_count == 1: parallel-vs-serial comparison skipped "
            "(a one-worker pool would re-measure the serial path)")
    else:
        result["sweep_parallel"] = _run_child(
            "packet-sweep", spec={"points": sweep_points, "seeds": seeds,
                  "workers": ncpu})
        # determinism tripwire: the serial and parallel sweeps must
        # agree exactly, record for record
        assert result["sweep_serial"]["jcts"] == \
            result["sweep_parallel"]["jcts"], \
            "serial and parallel run_many diverged"
        result["speedup_parallel_vs_serial"] = round(
            result["sweep_serial"]["wall_s"]
            / result["sweep_parallel"]["wall_s"], 2)

    if args.before_git and not args.smoke:
        result["before_git"] = _run_git_ref_packet(
            args.before_git, [tuple(p) for p in points])
        before_sweep = _run_git_ref_packet(
            args.before_git, [tuple(p) for p in sweep_points])
        # the old engine ran scenarios serially at one seed; its
        # multi-seed sweep cost is seeds x the measured per-point wall
        est = sum(p["wall_s"] for p in before_sweep["points"]) * seeds
        result["before_git"]["sweep_points"] = before_sweep["points"]
        result["before_git"]["sweep_est_s"] = round(est, 4)
        # headline gates
        b0 = result["before_git"]["points"][0]
        a0 = result["single"][0]["passes"]
        result["speedup_single"] = round(
            b0["wall_s"] / min(p["wall_s"] for p in a0), 2)
        best_sweep = result["sweep_parallel"] or result["sweep_serial"]
        result["sweep_reduction_vs_before"] = round(
            est / best_sweep["wall_s"], 2)
        # fixed-seed results must be unchanged, ref vs tree
        for b, s in zip(result["before_git"]["points"],
                        result["single"]):
            assert abs(b["jct_ms"] - s["passes"][0]["jct_ms"]) \
                <= 1e-9 + 1e-6 * abs(b["jct_ms"]), \
                f"fixed-seed JCT changed vs {args.before_git}: {b} {s}"

    # fault-sweep point: the ISSUE-7 recovery axis (benchmarks/
    # fig_faults.py) — every fault class must end in measured recovery
    result["fault_sweep"] = _run_child(
        "packet-faults", spec={"group": 4 if args.smoke else 8})

    if args.smoke:       # regression tripwires for CI
        assert result["single"][0]["passes"][0]["events"] > 0
        sweep = result["sweep_parallel"] or result["sweep_serial"]
        assert all(p["mean_ms"] > 0 for p in sweep["points"])
        assert all(v > 0
                   for v in result["fault_sweep"]["recovery_us"].values())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--engine", choices=("flow", "packet"),
                    default="flow",
                    help="which engine's hot path to benchmark")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized: tiny workload, regression tripwires")
    ap.add_argument("--scales", default=None,
                    help="comma-separated fig14 sweep scales, flow only "
                         f"(default {DEFAULT_SCALES})")
    ap.add_argument("--before-git", default=None, metavar="REF",
                    help="also time the actual tree at a git ref "
                         "(ground-truth baseline)")
    ap.add_argument("--out", default=None)
    ap.add_argument("--allow-dirty", action="store_true",
                    help="permit writing BENCH_*.json from a dirty "
                         "work tree (the json records git_sha for "
                         "provenance; a dirty tree makes it a lie)")
    ap.add_argument("--_child", default=None,
                    choices=("batched", "serial", "flow-loss",
                             "flow-apps", "flow-fleet", "flow-dyn",
                             "packet-single", "packet-sweep",
                             "packet-faults"),
                    help=argparse.SUPPRESS)
    ap.add_argument("--_spec", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args._child in ("batched", "serial"):
        scales = tuple(int(s) for s in args.scales.split(",")) \
            if args.scales else DEFAULT_SCALES
        return _child_flow(args._child, scales)
    if args._child == "flow-loss":
        print(json.dumps(_flow_loss_sweep(
            json.loads(args._spec)["smoke"])))
        return 0
    if args._child == "flow-apps":
        print(json.dumps(_flow_apps_sweep(
            json.loads(args._spec)["smoke"])))
        return 0
    if args._child == "flow-fleet":
        print(json.dumps(_flow_fleet_point(
            json.loads(args._spec)["smoke"])))
        return 0
    if args._child == "flow-dyn":
        spec = json.loads(args._spec)
        print(json.dumps(_flow_dyn_segments(spec["smoke"],
                                            spec["mode"])))
        return 0
    if args._child:
        return _child_packet(args._child, json.loads(args._spec))

    out_path = args.out or os.path.join(
        REPO, "BENCH_flowsim.json" if args.engine == "flow"
        else "BENCH_packetsim.json")
    result = {"env": _env_info()}
    if (os.path.basename(out_path).startswith("BENCH_")
            and result["env"]["git_dirty"] and not args.allow_dirty):
        print("bench: refusing to write "
              f"{os.path.basename(out_path)} from a dirty work tree — "
              "the json's git_sha would not describe the measured code. "
              "Commit (or stash) first, or pass --allow-dirty.",
              file=sys.stderr)
        return 2
    t_all = time.perf_counter()
    if args.engine == "flow":
        _main_flow(args, result)
    else:
        _main_packet(args, result)
    result["bench_wall_s"] = round(time.perf_counter() - t_all, 2)

    with open(out_path, "w", encoding="utf-8") as fh:
        json.dump(result, fh, indent=2)
        fh.write("\n")
    print(json.dumps(result, indent=2))
    print(f"# wrote {out_path}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
