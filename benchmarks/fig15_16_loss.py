"""Figs. 15-16 — loss tolerance: JCT and normalized goodput under packet
loss rates 1e-8 .. 1e-3, group sizes 64 and 512.

Paper claims: Gleam keeps lower JCT than ring/long at ALL loss rates;
goodput >= 90% at loss <= 1e-4, ~42% at 1e-3 (the multicast sender
retransmits when ANY receiver loses — more loss-sensitive than unicast,
Fig. 16), still 7x lower JCT than the baseline at 0.1%.

``--engine packet`` (default) is the per-packet reference.  Loss
recovery is exactly where a single seed is least trustworthy: which
packets the fabric discards decides whether one go-back-N round or a
timeout-recovery storm follows, so each (scheme, group, loss) point runs
``seeds`` independent repetitions and reports mean±std.  The
repetitions are scenarios of ONE ``run_workloads`` batch on one engine — the
engine quiesces between scenarios and gives scenario *i* the RNG stream
derived from ``(seed, i)``, so the repetitions double as the seed axis
and parallelize across worker processes (``workers``; see
``core/engine.py``).  Each point's packet network is still built lazily
and discarded after its batch — a 512-host PacketSim carries full
endpoint/switch/group state, so keeping ~16 of them resident would
multiply peak memory for nothing.

``--engine flow`` / ``flow-np`` runs the same sweep on the fluid model,
whose expected-value loss/DCQCN correction (``core/flowsim.py``) was
calibrated against the packet engine.  Two sections:

- **diff rows** — the calibration grid (gleam + multiunicast, groups
  4/8, loss 0..1e-2 at the Fig. 8 testbed).  Where the checked-in
  packet ground truth (``benchmarks/ref_fig15_flow.json``, written by
  ``tools/check_fig15.py --update``) has the point, the derived column
  carries the flow-vs-packet divergence — the same numbers the CI gate
  enforces at <= 15%.
- **scale rows** — the loss grid at Fig. 14 scale (512/4096-member
  groups on a 4096-host fat-tree), far beyond packet-level reach.  The
  fluid model is deterministic, so no seed axis.
"""
from __future__ import annotations

import json
import math
import os

from repro.core import fattree
from repro.core.engine import make_engine
from repro.core.workload import GroupOp, Workload

NBYTES = 1 << 20
LOSS_RATES = (0.0, 1e-6, 1e-5, 1e-4, 1e-3)
RING_LOSS_RATES = (0.0, 1e-4, 1e-3)    # baseline at the extremes (slow)
SIZES = (64, 512)
DEFAULT_SEEDS = 3

# Flow-engine calibration grid: the points the loss model was fitted
# and gated on (tools/check_fig15.py, tests/test_loss_model.py).  The
# per-loss seed counts buy a stable packet mean where recovery is
# noisiest; zero loss needs no seed axis.
FID_GROUPS = (4, 8)
FID_TRANSPORTS = ("gleam", "multiunicast")
FID_LOSS_RATES = (0.0, 1e-5, 1e-4, 1e-3, 1e-2)
FID_SEEDS = {0.0: 1, 1e-5: 8, 1e-4: 16, 1e-3: 32, 1e-2: 32}
REF_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "ref_fig15_flow.json")

# Fig. 14-scale section: loss grid on a 4096-host 3-layer fat-tree.
SCALE_FABRIC = dict(n_pods=16, leaves_per_pod=16, hosts_per_leaf=16,
                    aggs_per_pod=16, bw=200 * fattree.GBPS)
SCALE_GROUPS = (512, 4096)


def _label(loss) -> str:
    return f"{loss:.0e}" if loss else "0"


def _point(group, loss, transport):
    """One staged (scheme, group, loss) point: engine + pending record.
    Both schemes are the SAME GroupOp — only the transport differs."""
    topo = fattree.testbed(n_hosts=group, bw=200 * fattree.GBPS)
    eng = make_engine("packet", topo, loss_rate=loss, seed=11,
                      group_kw={"window": 512},
                      relay_kw={"window": 512})
    members = [f"h{i}" for i in range(group)]
    rec = eng.stage(GroupOp("bcast", members, NBYTES,
                            transport=transport, chunks=8))
    return eng, rec


def _sweep_point(group, loss, transport, seeds, workers, timeout):
    """(mean, std, per-seed JCTs) over ``seeds`` independent repetitions
    of one (scheme, group, loss) point, run as one run_workloads batch."""
    topo = fattree.testbed(n_hosts=group, bw=200 * fattree.GBPS)
    eng = make_engine("packet", topo, loss_rate=loss, seed=11,
                      group_kw={"window": 512},
                      relay_kw={"window": 512})
    members = [f"h{i}" for i in range(group)]
    wl = Workload(ops=[GroupOp("bcast", members, NBYTES,
                               transport=transport, chunks=8)])
    recss = eng.run_workloads([wl] * seeds, timeout=timeout,
                              workers=workers)
    jcts = [recs[0].jct(group - 1) for recs in recss]
    mean = sum(jcts) / len(jcts)
    std = math.sqrt(sum((j - mean) ** 2 for j in jcts) / len(jcts))
    return mean, std, jcts


def gleam_jct(group, loss):
    """Single-seed JCT of the Gleam point (bench/bisect helper)."""
    eng, rec = _point(group, loss, "gleam")
    eng.run(timeout=120.0)
    return rec.jct(group - 1)


def ring_jct(group, loss):
    eng, rec = _point(group, loss, "ring")
    eng.run(timeout=240.0)
    return rec.jct(group - 1)


def flow_jct(group, loss, transport, engine="flow"):
    """Deterministic fluid JCT of one testbed (scheme, group, loss)
    point — the flow-side twin of ``_point`` (same topology, tuning
    and GroupOp; the engine name picks the solver backend)."""
    topo = fattree.testbed(n_hosts=group, bw=200 * fattree.GBPS)
    eng = make_engine(engine, topo, loss_rate=loss, seed=11,
                      group_kw={"window": 512},
                      relay_kw={"window": 512})
    members = [f"h{i}" for i in range(group)]
    rec = eng.stage(GroupOp("bcast", members, NBYTES,
                            transport=transport, chunks=8))
    eng.run()
    return rec.jct(group - 1)


def packet_gt(group, loss, transport, workers=0):
    """Fixed-seed packet ground truth for one calibration-grid point:
    the multi-seed mean at that point's ``FID_SEEDS`` repetition count.
    Used by ``tools/check_fig15.py --update`` and the differential
    test harness — NOT by the flow sweep itself (it reads the frozen
    json so a model change shows up as divergence, not a moved target).
    """
    seeds = FID_SEEDS[loss]
    return _sweep_point(group, loss, transport, seeds, workers, 240.0)[0]


def _load_ref() -> dict:
    """Frozen packet ground truth (us) keyed ``g{n}_loss{label}/{t}``;
    empty when the reference json has not been generated yet."""
    try:
        with open(REF_PATH, encoding="utf-8") as fh:
            return json.load(fh)["packet_us"]
    except (OSError, KeyError, ValueError):
        return {}


def _run_flow(rows, engine):
    ref = _load_ref()
    # DIFF: the calibration grid, divergence vs frozen packet GT
    for transport in FID_TRANSPORTS:
        for group in FID_GROUPS:
            base = None
            for loss in FID_LOSS_RATES:
                us = flow_jct(group, loss, transport, engine) * 1e6
                base = us if base is None else base
                key = f"g{group}_loss{_label(loss)}/{transport}"
                want = ref.get(key)
                div = (f"div={100 * abs(us - want) / want:.1f}% "
                       f"vs packet ref" if want else "no packet ref")
                rows.append((f"fig15/diff_{key}_us", us,
                             f"{div} goodput={100 * base / us:.0f}%"))
    # SCALE: the loss grid at fig14 scale — one 4096-host fabric, every
    # (transport, group, loss) point on a fresh engine (loss rate is a
    # fabric property), each solved by the fluid model in one pass.
    topo = fattree.fat_tree(**SCALE_FABRIC)
    hosts = topo.hosts
    for transport in FID_TRANSPORTS:
        for group in SCALE_GROUPS:
            base = None
            for loss in FID_LOSS_RATES:
                eng = make_engine(engine, topo, loss_rate=loss, seed=11,
                                  group_kw={"window": 512},
                                  relay_kw={"window": 512})
                rec = eng.stage(GroupOp("bcast", hosts[:group], NBYTES,
                                        transport=transport, chunks=8))
                eng.run()
                ms = rec.jct(group - 1) * 1e3
                base = ms if base is None else base
                rows.append((f"fig15/scale_g{group}_loss{_label(loss)}/"
                             f"{transport}_ms", ms,
                             f"goodput={100 * base / ms:.0f}% "
                             f"hosts={len(hosts)}"))
    return rows


def run(rows, engine="packet", seeds=DEFAULT_SEEDS, workers=0,
        sizes=SIZES):
    if engine != "packet":
        return _run_flow(rows, engine)
    seeds = max(1, int(seeds))
    # STAGE: declare every point of the sweep before driving any of it
    gleam_pts = [(g, l) for g in sizes for l in LOSS_RATES]
    ring_pts = [(g, l) for g in sizes for l in RING_LOSS_RATES]
    # BATCH: drive the sweep; each point is a seeds-wide run_workloads batch
    # (lazy build-run-discard per point, see module docstring)
    jct_g = {(g, l): _sweep_point(g, l, "gleam", seeds, workers,
                                  120.0)[:2] for g, l in gleam_pts}
    jct_r = {(g, l): _sweep_point(g, l, "ring", seeds, workers,
                                  240.0)[:2] for g, l in ring_pts}
    # DERIVE rows (mean ms; derived column carries ±std and goodput)
    for group in sizes:
        base_g = jct_g[(group, 0.0)][0]
        for loss in LOSS_RATES:
            jg, sg = jct_g[(group, loss)]
            goodput = base_g / jg if jg > 0 else 0.0
            label = _label(loss)
            rows.append((f"fig15/jct_g{group}_loss{label}/gleam_ms",
                         jg * 1e3,
                         f"±{sg * 1e3:.4f}ms n={seeds} "
                         f"goodput={100 * goodput:.0f}%"))
        for loss in RING_LOSS_RATES:
            jr, sr = jct_r[(group, loss)]
            label = _label(loss)
            rows.append((f"fig15/jct_g{group}_loss{label}/ring_ms",
                         jr * 1e3, f"±{sr * 1e3:.4f}ms n={seeds}"))
    return rows
