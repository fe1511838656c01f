"""``dsv3ep2k.route``: the MoE dispatch/combine generator, its check and
its three solver readers.

- the generator is deterministic in the seed, and every seed runs the
  same work under other names;
- a pass holds the op counts and sizes the configuration states, every
  op rail-aligned (inside one plane) and none inside a node;
- a shrunk run reads ``correct``, and one whose timed path is broken
  underneath, or solves the combine in float32, does not; both float32
  controls read above the cell's limit;
- ``x64_lane_pct``, ``loop_epochs_per_pass`` and
  ``loop_rounds_per_pass`` read known counters, and nothing without
  them.
"""
import copy
import math
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import check, control_float32, gen, reference, run, sut  # noqa: E402
from bench.kinds import moe_ep  # noqa: E402
from bench.tests import _tiny  # noqa: E402
from bench.tests.test_bench_correctness import (  # noqa: E402
    _altered_answer, _half_left_out, _state_unchanged)

CELL = "dsv3ep2k.route"
SEED = 2 ** 31 + 4242
CFG = gen.load_json("configs", "dsv3ep2k")
MIX = gen.load_json("traffic", "route")


def shrink(cfg: dict) -> dict:
    """4 planes of 16 nodes; 2 stages of 2 EP groups of 4 nodes of 4
    GPUs, 32 experts in 4 groups, top-4 from 2 groups; the token count,
    sizes and EP groups a pass as configured, so combines stay over
    2^24 bytes."""
    c = copy.deepcopy(cfg)
    c["fabric"].update(n_pods=4, leaves_per_pod=2, hosts_per_leaf=8,
                       aggs_per_pod=2)
    c["deployment"].update(pipe=2, ep=16, dp=2, gpus_per_node=4, n_group=4,
                           topk_group=2, num_experts_per_tok=4,
                           n_routed_experts=32)
    return c


TINY = shrink(CFG)


def _pod(host: str) -> str:
    return host.split(".")[0]


def test_generator_is_deterministic_in_the_seed():
    for i in (0, 3):
        a = gen.pass_traffic(TINY, MIX, SEED, i)
        assert a == gen.pass_traffic(TINY, MIX, SEED, i)
        assert a != gen.pass_traffic(TINY, MIX, SEED + 1, i)
        assert a != gen.pass_traffic(TINY, MIX, SEED, i + 1)


def test_every_seed_runs_the_same_work_under_other_names():
    ref = reference.Reference(TINY)
    for i in range(2):
        outs = []
        for seed in (SEED, 7):
            tr = gen.pass_traffic(TINY, MIX, seed, i)
            ans, work = ref.run_pass(tr)
            outs.append((tr, sorted(a["cqe"] for s in ans for a in s),
                         sorted(work)))
        (a, cqe_a, work_a), (b, cqe_b, work_b) = outs
        assert a != b and work_a == work_b
        assert sorted(o["nbytes"] for s in a["scenarios"] for o in s) \
            == sorted(o["nbytes"] for s in b["scenarios"] for o in s)
        assert cqe_a == pytest.approx(cqe_b, rel=1e-12)


def test_a_pass_at_the_configured_size():
    dep = CFG["deployment"]
    hosts = set(gen.fabric_hosts(CFG["fabric"]))
    assert len(hosts) == 2048
    tr = gen.pass_traffic(CFG, MIX, SEED, 0)
    assert tr["loss_rate"] == 0.0
    dispatch, combine = tr["scenarios"]
    # one EP group of 64 GPUs, each to the 7 other nodes of the group
    assert dep["ep_groups_per_pass"] == 1 and dep["dp"] == 2
    assert len(dispatch) == len(combine) == 64 * 7
    srcs = {o["members"][0] for o in dispatch}
    assert len(srcs) == 64 and srcs <= hosts
    for d, c in zip(dispatch, combine):
        assert d["op"] == c["op"] == "unicast"
        assert c["members"] == d["members"][::-1]
        tokens, rest = divmod(d["nbytes"], dep["dispatch_token_bytes"])
        assert rest == 0 and c["nbytes"] == tokens * dep["combine_token_bytes"]
        # i.i.d. scores: about half of a GPU's tokens reach each node
        assert 1700 < tokens < 2400
        assert c["nbytes"] > 1 << 24                # the float64 solve
        a, b = d["members"]
        assert _pod(a) == _pod(b) and a != b        # rail-aligned
    # one EP group: 8 nodes, so 8 hosts on each of the 8 planes
    assert {len({h for h in srcs if _pod(h) == p}) for p in
            {_pod(h) for h in srcs}} == {8}


@pytest.mark.parametrize("groups", [1, 2])
def test_tiny_pass_counts_and_node_sets(groups):
    cfg = copy.deepcopy(TINY)
    dep = cfg["deployment"]
    dep["ep_groups_per_pass"] = groups
    tr = gen.pass_traffic(cfg, MIX, SEED, 1)
    dispatch, _ = tr["scenarios"]
    assert len(dispatch) == groups * 16 * 3
    assert len({o["members"][0] for o in dispatch}) == groups * 16
    per_src = {}
    for o in dispatch:
        per_src.setdefault(o["members"][0], []).append(
            o["nbytes"] // dep["dispatch_token_bytes"])
    # a token reaches at most topk_group = 2 nodes, so at most 2 x 4,096
    # remote copies leave a GPU
    assert all(sum(v) <= 2 * 4096 for v in per_src.values())


def test_router_copy_agrees_with_the_program():
    sut.import_program()
    from repro.apps import collectives_lowering as cl
    scores = np.random.default_rng(3).random((3, 500, 256), np.float32)
    assert (moe_ep.node_limited_sets(scores, 8, 4, 8)
            == cl.node_limited_sets(scores, 8, 4, 8)).all()


def _run(monkeypatch, limits=None):
    sut.import_program()
    monkeypatch.setattr(run, "use_compile_cache", lambda: None)
    b = _tiny.bench_json()
    return run.run_cell(TINY, MIX, limits or check.limits(CELL), SEED, 0.3,
                        False, run.metric_names(b, CELL, False),
                        {"platform": "cpu", "kind": "cpu", "count": 1})


def test_sound_run_is_correct(monkeypatch):
    res = _run(monkeypatch)
    assert res["correct"] and res["failed"] == 0
    assert res["checks"]["max_rel_gap"]["value"] < 1e-6
    assert set(res["metrics"]) == {"ops_per_s", "setup_s"}


def _float32_solve(monkeypatch):
    """The promotion turned off: a plain float32 solve of the combine."""
    from repro.core import flowsim_jax
    monkeypatch.setattr(flowsim_jax, "F32_SAFE_MAX", math.inf)


@pytest.mark.parametrize("fault", [_altered_answer, _half_left_out,
                                   _state_unchanged, _float32_solve])
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    sut.import_program()
    fault(monkeypatch)
    assert not _run(monkeypatch)["correct"]


def test_float32_controls_read_above_the_limit():
    """On the shrunk cell's first window passes the sound reading lies
    below the cell's limit and both float32 controls above it."""
    lim = check.limits(CELL)["max_rel_gap"]["limit"]
    for r in control_float32.readings(TINY, MIX, SEED, 2):
        assert r["sound"] < lim < min(r["reference_f32"], r["program_f32"])


READERS = ["x64_lane_pct", "loop_epochs_per_pass", "loop_rounds_per_pass"]


@pytest.mark.parametrize("stats,want", [
    ({"lanes": 8, "x64_lanes": 6, "epochs_run": 1500, "rounds_run": 40000},
     [75.0, 300.0, 8000.0]),
    ({"lanes": 0, "x64_lanes": 0, "epochs_run": 0, "rounds_run": 0},
     [None, 0.0, 0.0]),
    ({"lanes": 8, "epochs": 9, "rounds": 9}, [None, None, None]),
])
def test_solver_readers(stats, want, monkeypatch):
    import importlib
    monkeypatch.setattr(sut, "solve_stats", lambda: stats)
    ctx = {"pass_s": [0.5] * 4, "trace_passes": 1}
    got = [importlib.import_module(f"bench.metrics.{m}").read(ctx)
           for m in READERS]
    assert got == want


def test_readers_are_listed_for_every_cell():
    b = _tiny.bench_json()
    cells = {"hpl16k.decay", "loss4k.fresh", CELL}
    for name in READERS:
        m = next(x for x in b["per_layer"] if x["name"] == name)
        assert cells <= set(m["workloads"]) and m["moves"] == "ops_per_s"
