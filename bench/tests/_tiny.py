"""Shrunken copies of the benchmark's configurations for CPU tests:
the same deployment kinds, mixes and model at a fabric of tens of
hosts."""
import copy
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def bench_json() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(name: str):
    """(configuration, mix) of a cell, the configuration shrunk."""
    from bench import gen
    b = bench_json()
    w = next(x for x in b["workloads"] if x["name"] == name)
    c = next(x for x in b["configs"] if x["name"] == w["config"])
    with open(os.path.join(ROOT, c["file"])) as f:
        cfg = json.load(f)
    return shrink(cfg), gen.load_json("traffic", w["traffic"])


def shrink(cfg: dict) -> dict:
    c = copy.deepcopy(cfg)
    if c["deployment"]["kind"] == "hpl":
        c["fabric"].update(n_pods=4, leaves_per_pod=2, hosts_per_leaf=4,
                           aggs_per_pod=2)
        c["deployment"].update(scales=[2, 4, 5])
    else:
        c["fabric"].update(n_pods=4, leaves_per_pod=4, hosts_per_leaf=4,
                           aggs_per_pod=2)
        c["deployment"].update(group_sizes=[4, 16])
    return c
