"""Fleet-scale multi-tenant fabric sharing — per-tenant SLO tails and
connection-state cost as tenant count grows (the fleet sweep plane's
headline benchmark; no single paper figure — this is the §6 "switch
table memory" arithmetic and the §5 contention results run TOGETHER).

Each point packs N tenants' multicast groups (overlapping trees by
construction) plus background mesh/incast RC traffic into ONE contended
scenario (``apps/fleet.py``), runs it on the packet engine AND the flow
engine, and reports:

- worst per-tenant p99 JCT (packet, ms) with the packet-vs-flow
  divergence in the derived column (gate: <= 10%,
  ``tools/check_fleet.py``);
- connection-state accounting: peak QPs on any NIC, total MFT group
  entries and bytes across the fabric (the flow side derives these
  analytically; per-host QP counts must match the packet engine's
  measured census exactly — tests/test_fleet.py);
- staging-cache hit rate for the flow sweep (the cached staging plane
  is what makes the 1k-group point feasible);
- one LRU-pressure point: registration churn (many tenants' groups
  registered through capacity-pinned switch tables), reporting the
  evictions/salvages the fabric eats while the newest tenant still
  broadcasts cleanly.

The sweep starts at 4 tenants: below ~8 concurrent groups the fabric
is so sparse that the p99 of a tenant is the max of 2 samples and the
packet-vs-flow gap is dominated by which ECMP tree each engine happens
to pick, not by contention — the regime the fluid model is for begins
when trees actually overlap.
"""
from __future__ import annotations

from repro.apps.fleet import FleetSpec, mft_pressure_report, run_fleet
from repro.core import fattree

TENANTS = (4, 8)
GROUPS_PER_TENANT = 2
GROUP_SIZE = 6
NBYTES = 2 << 20
BG = dict(bg_unicasts=8, bg_incasts=2, bg_fan_in=4, bg_nbytes=1 << 20)
PRESSURE_GROUPS = 48           # registrations churned through the fabric
PRESSURE_CAPACITY = 8          # table slots per switch under pressure


def _fabric():
    return fattree.fat_tree(n_pods=2, leaves_per_pod=4, hosts_per_leaf=4,
                            aggs_per_pod=4, bw=100 * fattree.GBPS)


def _spec(n_tenants: int) -> FleetSpec:
    return FleetSpec(n_tenants=n_tenants,
                     groups_per_tenant=GROUPS_PER_TENANT,
                     group_size=GROUP_SIZE, nbytes=NBYTES, **BG)


def _worst_tenant(report) -> float:
    return max(q["p99"] for ph, q in report["tenants"].items()
               if ph.startswith("tenant-"))


def run(rows, engine="packet", workers=0):
    # both engines always run — the divergence IS the result; --engine
    # only picks which flow solver the packet run is compared against
    flow_engine = engine if engine.startswith("flow") else "flow"
    for n in TENANTS:
        spec = _spec(n)
        rp = run_fleet("packet", _fabric(), spec, seed=1)
        rf = run_fleet(flow_engine, _fabric(), spec)
        p99p, p99f = _worst_tenant(rp), _worst_tenant(rf)
        div = abs(p99p - p99f) / max(p99p, p99f)
        cp, cf = rp["census"], rf["census"]
        rows.append((
            f"figfleet/{n}tenants/packet_worst_p99_ms", p99p * 1e3,
            f"flow={p99f * 1e3:.4f}ms div={100 * div:.1f}% "
            f"nic_qp_peak={cp['nic_qp_peak']} "
            f"mft_groups={cp['mft_groups_total']} "
            f"mft_bytes={cp['mft_bytes_total']} "
            f"flow_census_qp_match="
            f"{cf['qp_per_host'] == cp['qp_per_host']} "
            f"cache_hit_rate={rf['staging']['hit_rate']:.2f} "
            f"({n}x{GROUPS_PER_TENANT} groups of {GROUP_SIZE} + "
            f"bg mesh/incast)"))
    # LRU pressure: registration churn through capacity-pinned tables
    pr = mft_pressure_report(_fabric(), n_groups=PRESSURE_GROUPS,
                             group_size=GROUP_SIZE,
                             capacity=PRESSURE_CAPACITY, seed=1)
    rows.append((
        f"figfleet/churn{PRESSURE_GROUPS}_cap{PRESSURE_CAPACITY}/"
        "mft_evictions", float(pr["evictions"]),
        f"salvages={pr['salvages']} "
        f"occupancy_peak={pr['occupancy_peak']}/{PRESSURE_CAPACITY} "
        f"last_group_ok={pr['last_group_ok']} "
        f"last_group_jct_ms={pr['last_group_jct'] * 1e3:.4f} "
        f"({PRESSURE_GROUPS} registrations of {GROUP_SIZE} through "
        f"{PRESSURE_CAPACITY}-slot tables)"))
    return rows
