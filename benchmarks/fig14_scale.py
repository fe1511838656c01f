"""Fig. 14 — HPL JCT over multicast scales N*N on a 16384-server 3-layer
fat-tree (200Gbps, 1:1 oversubscription), Gleam vs ring(PB)+long(RS).

Paper claims: Gleam reduces JCT 62% (8*8) .. 73% (128*128); Gleam's JCT
stays ~flat with scale while ring/long grow (their parallel-unicast count
expands linearly).

Model: N simultaneous PB groups (one per row) + N RS groups (one per
column), members row-/column-major on the fat-tree, declared as
Workload IR and solved in one max-min fair batch.  The PB baseline is
the same bcast ops over ``--transport`` (default ``ring`` — the HPL
increasing-ring; any registered transport works at this scale, the
point of the IR); `long` spreads then exchanges (volume-optimal when
uniform).

The sweep is stage-then-batch: every (scale, workload) scenario on the
same topology is staged on ONE engine and solved by a single
``run_workloads`` call — the shape-bucketed solver compiles once for
the whole sweep instead of once per point, and the topology (with its
BFS routing caches) is built once per size class.  ``--serial``
restores the PR-1 behavior (fresh engine + solve per scenario) for A/B
timing.

This figure is inherently beyond packet-level reach (the paper
parallelized ns-3 for it); requesting ``--engine packet`` falls back to
``flow`` with a note.

Standalone:

    PYTHONPATH=src python benchmarks/fig14_scale.py --engine flow
    PYTHONPATH=src python benchmarks/fig14_scale.py --engine flow --full
"""
from __future__ import annotations

import argparse
import functools
import os
import sys
import time

if __package__ in (None, ""):      # `python benchmarks/fig14_scale.py`
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "src"))

from repro.core.engine import make_engine
from repro.core.fattree import GBPS, fat_tree
from repro.core.workload import TRANSPORT_CHOICES, Workload

VOLUME = 8 << 20                   # bytes per PB/RS message
CHUNKS = 8
SCALES = (8, 16, 32)               # 1024-host fat-tree
SCALES_FULL = (8, 16, 32, 64, 128)  # adds the 16384-host config


@functools.lru_cache(maxsize=2)
def _build(big: bool):
    """The two §5.3 size classes, cached: the topology AND its BFS
    routing caches are reused across every scale that fits."""
    if big:
        return fat_tree(n_pods=32, leaves_per_pod=16, hosts_per_leaf=32,
                        aggs_per_pod=16, bw=200 * GBPS)
    return fat_tree(n_pods=8, leaves_per_pod=8, hosts_per_leaf=16,
                    aggs_per_pod=8, bw=200 * GBPS)


def build(n):
    """Fat-tree with >= n*n hosts (paper: 16384 hosts, 64-port, 200G)."""
    need = n * n
    topo = _build(need > 1024)
    assert len(topo.hosts) >= need, (len(topo.hosts), need)
    return topo


def _flow_engine(name: str):
    """This figure needs a flow backend; coerce packet -> flow."""
    return "flow" if name == "packet" else name


@functools.lru_cache(maxsize=16)
def _workloads(big: bool, n: int, transport: str):
    """Workload IR for one sweep point, cached: ops are immutable
    (engines lower them into per-epoch records without touching the
    IR), so repeated passes reuse the same ~n*n GroupOps instead of
    re-declaring them."""
    hosts = _build(big).hosts
    return (gleam_workload(hosts, n),
            baseline_workload(hosts, n, transport))


# ------------------------------------------------------------- workloads

def gleam_workload(hosts, n) -> Workload:
    """N PB groups (rows) + N RS groups (columns), one bcast each."""
    wl = Workload(f"fig14/gleam_{n}x{n}")
    for row in range(n):
        wl.bcast(hosts[row * n:(row + 1) * n], VOLUME, key=row)
    for col in range(n):
        wl.bcast([hosts[row * n + col] for row in range(n)], VOLUME,
                 key=n + col)
    return wl


def baseline_workload(hosts, n, transport="ring") -> Workload:
    """PB over the baseline ``transport`` (one bcast op per row — the
    engines lower it to the relay schedule) + RS via the `long`
    neighbor exchange as a concurrent unicast mesh."""
    wl = Workload(f"fig14/{transport}_long_{n}x{n}")
    for row in range(n):
        wl.bcast(hosts[row * n:(row + 1) * n], VOLUME,
                 transport=transport, chunks=CHUNKS, key=row)
    for col in range(n):                       # long: neighbor exchange
        members = [hosts[row * n + col] for row in range(n)]
        for i in range(n - 1):
            wl.unicast(members[i], members[i + 1],
                       VOLUME * (n - 1) // n, key=n + col)
    return wl


def _values(n, g_recs, b_recs) -> tuple:
    jg = max(r.jct(n - 1) for r in g_recs)
    pb = max(r.jct(n - 1) for r in b_recs[:n])          # transport bcasts
    long_jct = max(r.jct(1) for r in b_recs[n:])        # `long` unicasts
    return jg, max(pb, long_jct)


# ---------------------------------------------- per-scenario entry points

def gleam_jct(n, engine="flow") -> float:
    """Standalone (fresh-engine, solve-per-call) gleam point."""
    eng = make_engine(_flow_engine(engine), build(n))
    recs = eng.run_workloads([gleam_workload(eng.topo.hosts, n)])[0]
    return max(r.jct(n - 1) for r in recs)


def ring_long_jct(n, engine="flow", transport="ring") -> float:
    """Standalone (fresh-engine, solve-per-call) baseline point."""
    eng = make_engine(_flow_engine(engine), build(n))
    recs = eng.run_workloads(
        [baseline_workload(eng.topo.hosts, n, transport)])[0]
    pb = max(r.jct(n - 1) for r in recs[:n])
    return max(pb, max(r.jct(1) for r in recs[n:]))


# ----------------------------------------------------------------- sweep

def run(rows, engine="flow", transport="ring", scales=None, batched=True):
    """Default scales stop at 32 (1024 hosts, seconds) in BOTH entry
    points; the 16384-host top end is opt-in (CLI --full).

    ``batched=True`` declares the whole sweep as Workloads on one
    engine per topology and solves it with a single ``run_workloads``;
    ``batched=False`` is the PR-1 serial path (one engine + solve per
    scenario, for A/B timing).  ``transport`` picks the PB baseline
    overlay (``ring`` is the paper's; any registered transport runs).
    """
    engine = _flow_engine(engine)
    if transport == "gleam":                   # baseline must be an overlay
        transport = "ring"
    scales = tuple(scales or SCALES)
    results = {}
    if batched:
        for big in sorted({n * n > 1024 for n in scales}):
            group = [n for n in scales if (n * n > 1024) == big]
            eng = make_engine(engine, _build(big))
            workloads = []
            for n in group:
                workloads.extend(_workloads(big, n, transport))
            recss = eng.run_workloads(workloads)
            for i, n in enumerate(group):
                results[n] = _values(n, recss[2 * i], recss[2 * i + 1])
    else:
        for n in scales:
            results[n] = (gleam_jct(n, engine),
                          ring_long_jct(n, engine, transport))
    for n in scales:
        jg, jb = results[n]
        rows.append((f"fig14/hpl_{n}x{n}/gleam_ms", jg * 1e3,
                     f"engine={engine}"))
        rows.append((f"fig14/hpl_{n}x{n}/{transport}_long_ms", jb * 1e3,
                     f"reduction={100 * (1 - jg / jb):.0f}% "
                     f"(paper 62-73%)"))
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--engine", default="flow",
                    choices=("packet", "flow", "flow-np"),
                    help="simulation backend (packet falls back to flow)")
    ap.add_argument("--transport", default="ring",
                    choices=[t for t in TRANSPORT_CHOICES if t != "gleam"],
                    help="PB baseline overlay transport (paper: ring)")
    ap.add_argument("--full", action="store_true",
                    help=f"sweep {SCALES_FULL} (16384-host top end) "
                         f"instead of {SCALES}; staging the 16k-host "
                         f"trees is python-routing-bound (expect "
                         f"minutes; solver time stays in seconds)")
    ap.add_argument("--serial", action="store_true",
                    help="PR-1 behavior: fresh engine + solve per "
                         "scenario instead of one batched run_workloads")
    args = ap.parse_args(argv)
    rows: list = []
    t0 = time.time()
    run(rows, engine=args.engine, transport=args.transport,
        scales=SCALES_FULL if args.full else SCALES,
        batched=not args.serial)
    print("name,value,derived")
    for n, v, d in rows:
        print(f"{n},{v:.3f},{d}")
    print(f"# fig14 sweep done in {time.time() - t0:.1f}s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
