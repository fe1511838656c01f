"""``dense_fill_lane_pct``: the share of the epoch solver's lanes whose
fills took the dense round.

- it reads ``dense_lanes`` against ``lanes``, and nothing without the
  counter (a tree that predates it) or with no lanes;
- its entry lists the three cells it was added for and moves
  ``ops_per_s``.
"""
import importlib
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import sut  # noqa: E402
from bench.tests import _tiny  # noqa: E402

NAME = "dense_fill_lane_pct"


@pytest.mark.parametrize("stats,want", [
    ({"lanes": 8, "dense_lanes": 8}, 100.0),
    ({"lanes": 8, "dense_lanes": 2}, 25.0),
    ({"lanes": 8, "dense_lanes": 0}, 0.0),
    ({"lanes": 0, "dense_lanes": 0}, None),
    ({"lanes": 8, "x64_lanes": 8}, None),
])
def test_dense_fill_reader(stats, want, monkeypatch):
    monkeypatch.setattr(sut, "solve_stats", lambda: stats)
    ctx = {"pass_s": [0.5] * 4, "trace_passes": 1}
    assert importlib.import_module(f"bench.metrics.{NAME}").read(ctx) == want


def test_dense_fill_entry_lists_the_cells():
    b = _tiny.bench_json()
    m = next(x for x in b["per_layer"] if x["name"] == NAME)
    assert {"hpl16k.decay", "loss4k.fresh", "dsv3ep2k.route"} <= set(
        m["workloads"])
    assert m["moves"] == "ops_per_s" and m["unit"] == "%"
