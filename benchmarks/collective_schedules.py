"""Adapted-layer benchmark: broadcast/all-reduce schedule comparison on
the TPU ICI (no paper figure — this is Fig. 9's design space mapped onto
the mesh: multiple-unicast vs overlay-ring vs Gleam-tree vs in-fabric).

Two sources:
- analytic alpha-beta costs (core/collectives.schedule_cost) for the
  production mesh sizes (16, 256 chips; 50GB/s links, 1us hops);
- measured per-schedule HLO collective bytes on an 8-device host mesh
  (lower+compile, countable in the HLO — same methodology as §Roofline).
"""
from __future__ import annotations

import os
import subprocess
import sys

from repro.core.collectives import schedule_cost

SIZES = {"1MB": 1 << 20, "64MB": 64 << 20, "1GB": 1 << 30}
SCHEDULES = ("unicast", "ring", "gleam_tree", "infabric")


def measured_bytes():
    """Compile tree/ring/unicast broadcast on 8 host devices (subprocess:
    device count is locked at jax init) and count HLO collective bytes."""
    src = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.core import collectives as coll
from repro.launch.roofline import collective_bytes

mesh = jax.make_mesh((8,), ("model",))
x = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)   # 4MB payload
for name, fn in [
    ("tree", lambda v: coll.tree_broadcast(v, "model")),
    ("ring", lambda v: coll.ring_broadcast(v, "model", chunks=4)),
    ("unicast", lambda v: coll.unicast_broadcast(v, "model")),
]:
    f = shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                  check_vma=False)
    c = jax.jit(f).lower(x).compile()
    cb = collective_bytes(c.as_text())
    print(f"{name},{cb['total_bytes']},{sum(cb['counts'].values())}")
"""
    env = dict(os.environ)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(repo, "src")
    out = subprocess.run([sys.executable, "-c", src], env=env,
                         capture_output=True, text=True, timeout=600)
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-2000:])
    rows = {}
    for line in out.stdout.strip().splitlines():
        name, nbytes, nops = line.split(",")
        rows[name] = (int(nbytes), int(nops))
    return rows


def run(rows, engine="packet"):
    # engine is irrelevant here: costs are analytic (core/metrics) and
    # HLO-measured; accepted for orchestrator uniformity.
    for label, nbytes in SIZES.items():
        for n in (16, 256):
            for sched in SCHEDULES:
                t = schedule_cost(sched, n, nbytes, chunks=8)
                rows.append(
                    (f"collsched/{label}_n{n}/{sched}_us", t * 1e6,
                     "analytic alpha-beta"))
    try:
        meas = measured_bytes()
        for name, (nbytes, nops) in meas.items():
            rows.append((f"collsched/hlo_4mb_bcast_8dev/{name}_bytes",
                         nbytes, f"{nops} collective ops in HLO"))
    except Exception as e:  # noqa: BLE001
        rows.append(("collsched/hlo_measured/error", 0, str(e)[:80]))
    return rows
