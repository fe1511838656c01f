"""Seeded traffic generator: one general generator for every cell.

A cell names a configuration (``configs/<name>.json``: the fabric and
the deployment that runs on it) and a traffic mix
(``traffic/<name>.json``: how the deployment varies from one sweep
pass to the next).  ``pass_traffic`` turns (configuration, mix, seed,
pass index) into plain data: a list of scenarios, each a list of op
dicts, plus the pass's engine-level loss rate.  Nothing here imports
the program; ``sut.py`` lowers the op dicts onto the program's IR and
``reference.py`` reads them as they are.

An op dict::

    {"op": "bcast" | "unicast", "members": [host, ...], "nbytes": int,
     "key": int, "phase": str}

The configuration's ``deployment.kind`` names the module
``kinds/<kind>.py`` that lays the deployment out; it is found by name,
so a new kind of deployment is a new file.  A kind module defines
``scenarios(hosts, params, rng)``: the pass's scenarios from the
relabelled host list, the deployment's parameters with the mix's cycle
applied, and the pass's placement stream.

The seed does not change the work.  Placements are drawn from the
mix's fixed ``placement_seed`` (default 0) and the pass index;
``--seed`` picks a relabelling of the fat-tree's pods, of the leaves
in each pod and of the hosts on each leaf.  That is a symmetry of the
fabric and of its routing (the only ECMP choice, the uplink plane at
the source leaf, is the same at every leaf), so every seed runs the
same fluid problems in the same order under other names: the inputs
differ from seed to seed, the work does not.

Mix keys (all optional):

- ``cycle``: {deployment key: [values]} — pass ``i`` uses value
  ``i mod len``; ``loss_rate`` is the engine-level loss of the pass.
- ``placement_seed``: the seed of the placement streams.
"""
from __future__ import annotations

import importlib
import json
import os
import random
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))


def load_json(kind: str, name: str) -> dict:
    """``configs/<name>.json`` or ``traffic/<name>.json``."""
    with open(os.path.join(HERE, kind, f"{name}.json")) as f:
        return json.load(f)


def fabric_hosts(fabric: dict) -> List[str]:
    """Host names of the fat-tree in its wiring order (pod, leaf, slot)."""
    return [f"h{p}.{l}.{h}" for p in range(fabric["n_pods"])
            for l in range(fabric["leaves_per_pod"])
            for h in range(fabric["hosts_per_leaf"])]


def relabel(fabric: dict, hosts: List[str], seed: int) -> List[str]:
    """``hosts`` under the seed's permutation of pods, of the leaves in
    each pod and of the hosts on each leaf."""
    rng = _rng(seed, "relabel")
    n_p, n_l, n_h = (fabric["n_pods"], fabric["leaves_per_pod"],
                     fabric["hosts_per_leaf"])
    pods = rng.sample(range(n_p), n_p)
    leaves = [rng.sample(range(n_l), n_l) for _ in range(n_p)]
    slots = [[rng.sample(range(n_h), n_h) for _ in range(n_l)]
             for _ in range(n_p)]
    out = []
    for h in hosts:
        p, l, i = (int(x) for x in h[1:].split("."))
        out.append(f"h{pods[p]}.{leaves[p][l]}.{slots[p][l][i]}")
    return out


def _rng(seed: int, *salt) -> random.Random:
    """A stream that depends on the seed and the salt only (string
    seeding hashes with SHA-512, so it is stable across processes)."""
    return random.Random(":".join(str(x) for x in (seed,) + salt))


def op(kind, members, nbytes, key=0, phase=""):
    """An op dict."""
    return {"op": kind, "members": list(members), "nbytes": int(nbytes),
            "key": int(key), "phase": phase}


def _pass_params(dep: dict, mix: dict, index: int) -> dict:
    params = dict(dep)
    params.setdefault("loss_rate", 0.0)
    for k, values in mix.get("cycle", {}).items():
        params[k] = values[index % len(values)]
    return params


def cycle_len(mix: dict) -> int:
    """Passes before the mix's cycle repeats (every message size or
    loss level once); 1 for a mix with no cycle."""
    return max((len(v) for v in mix.get("cycle", {}).values()), default=1)


def pass_traffic(config: dict, mix: dict, seed: int, index: int) -> Dict:
    """The scenarios of sweep pass ``index``: {"scenarios", "loss_rate"}."""
    dep = config["deployment"]
    p = _pass_params(dep, mix, index)
    kind = importlib.import_module(f"bench.kinds.{dep['kind']}")
    hosts = relabel(config["fabric"], fabric_hosts(config["fabric"]),
                    int(seed))
    rng = _rng(int(mix.get("placement_seed", 0)), "pass", index)
    return {"scenarios": kind.scenarios(hosts, p, rng),
            "loss_rate": float(p["loss_rate"])}
