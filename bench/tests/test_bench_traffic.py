"""The traffic generator: deterministic in the seed, and the op counts
and sizes its configurations state."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import gen  # noqa: E402

CELLS = [("hpl16k", "decay"), ("loss4k", "fresh")]
BIG_SEED = 2 ** 31 + 12345


@pytest.mark.parametrize("config,mix", CELLS)
def test_generator_is_deterministic_in_the_seed(config, mix):
    cfg, m = gen.load_json("configs", config), gen.load_json("traffic", mix)
    for i in (0, 1, 7):
        a = gen.pass_traffic(cfg, m, BIG_SEED, i)
        assert a == gen.pass_traffic(cfg, m, BIG_SEED, i)
        assert a != gen.pass_traffic(cfg, m, BIG_SEED + 1, i)


def test_hpl_decay_counts_and_sizes():
    cfg = gen.load_json("configs", "hpl16k")
    mix = gen.load_json("traffic", "decay")
    sizes = mix["cycle"]["nbytes"]
    assert sizes[0] == cfg["deployment"]["nbytes"]
    assert sizes == sorted(sizes, reverse=True)
    hosts = set(gen.fabric_hosts(cfg["fabric"]))
    assert len(hosts) == 16384
    first = None
    for i in range(len(sizes) + 1):
        tr = gen.pass_traffic(cfg, mix, BIG_SEED, i)
        assert tr["loss_rate"] == 0.0
        scales = cfg["deployment"]["scales"]
        assert len(tr["scenarios"]) == len(scales)
        for n, ops in zip(scales, tr["scenarios"]):
            assert len(ops) == 2 * n
            assert all(o["op"] == "bcast" and len(set(o["members"])) == n
                       and set(o["members"]) <= hosts for o in ops)
            rows = [m for o in ops[:n] for m in o["members"]]
            cols = [m for o in ops[n:] for m in o["members"]]
            assert len(set(rows)) == n * n and set(rows) == set(cols)
        assert {o["nbytes"] for s in tr["scenarios"] for o in s} == \
            {sizes[i % len(sizes)]}
        assert sizes[i % len(sizes)] < 1 << 24       # float32 solve
        placement = [o["members"] for s in tr["scenarios"] for o in s]
        first = first or placement
        assert placement == first       # the same groups every pass
    assert sum(2 * n for n in scales) == 496


def test_loss_fresh_counts_and_sizes():
    cfg = gen.load_json("configs", "loss4k")
    mix = gen.load_json("traffic", "fresh")
    dep = cfg["deployment"]
    hosts = set(gen.fabric_hosts(cfg["fabric"]))
    assert len(hosts) == 4096
    seen = []
    for i in range(6):
        tr = gen.pass_traffic(cfg, mix, BIG_SEED, i)
        levels = mix["cycle"]["loss_rate"]
        assert tr["loss_rate"] == levels[i % len(levels)]
        assert [len(s) for s in tr["scenarios"]] == [1] * 2
        for g, (o,) in zip(dep["group_sizes"], tr["scenarios"]):
            assert o["nbytes"] == dep["nbytes"] < 1 << 24
            assert len(set(o["members"])) == g
            assert set(o["members"]) <= hosts
        seen.append(tr["scenarios"][1][0]["members"])
    assert len({tuple(s) for s in seen}) == 6   # a fresh placement each pass


@pytest.mark.parametrize("cell", ["hpl16k.decay", "loss4k.fresh"])
def test_every_seed_runs_the_same_work_under_other_names(cell):
    from bench import reference
    from bench.tests import _tiny
    cfg, mix = _tiny.cell(cell)
    ref = reference.Reference(cfg)
    for i in range(3):
        outs = []
        for seed in (BIG_SEED, 7):
            tr = gen.pass_traffic(cfg, mix, seed, i)
            ans, work = ref.run_pass(tr)
            outs.append((tr, sorted(a["cqe"] for s in ans for a in s),
                         sorted(work)))
        (a, cqe_a, work_a), (b, cqe_b, work_b) = outs
        assert a != b and work_a == work_b
        assert cqe_a == pytest.approx(cqe_b, rel=1e-12)
