"""``chip_smoke.py``'s phases at tiny sizes on the CPU, and its refusal
to run anywhere but on a TPU."""
from __future__ import annotations

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:                # chip_smoke.py, benchmarks/
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from benchmarks import fig_matrix  # noqa: E402
from repro.apps.fleet import FleetSpec  # noqa: E402
from repro.core import fattree  # noqa: E402


def test_refuses_cpu_and_names_the_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_fleet_phase_tiny():
    topo = fattree.fat_tree(n_pods=2, leaves_per_pod=2, hosts_per_leaf=8,
                            aggs_per_pod=2, bw=200 * fattree.GBPS)
    spec = FleetSpec(n_tenants=2, groups_per_tenant=3, group_size=4,
                     nbytes=1 << 16, bg_unicasts=4, bg_incasts=1,
                     bg_fan_in=2, bg_nbytes=1 << 16, seed=0)
    out = chip_smoke.phase_fleet(topo, spec)
    assert out["ops"] == 2 * 3 + 4 + 2
    assert out["cold"]["calls"] >= 1 and out["warm"]["calls"] >= 1
    assert out["vs_flow_np"]["n"] == out["ops"]


def test_matrix_phase_tiny():
    out = chip_smoke.phase_matrix(fig_matrix.build_topo(smoke=True),
                                  fig_matrix.N_GROUPS_SMALL,
                                  fig_matrix.GROUP_SMALL,
                                  fig_matrix.NBYTES_SMALL)
    assert out["cells"] == 8
    assert out["max_rel"] <= chip_smoke.SEG_RTOL


def test_hpl_phase_tiny():
    out = chip_smoke.phase_hpl((2, 4))
    assert out["rows"] == 4
    assert out["vs_flow_np"]["n_over_rtol"] == 0


@pytest.mark.parametrize("got, ok", [
    (1.00005e-3, True),                 # within JCT_RTOL
    (1.0009e-3, True),                  # over JCT_RTOL, within NP_SLACK_S
    (1.002e-3, False),                  # over both
])
def test_compare_bound(got, ok):
    if ok:
        out = chip_smoke._compare([got], [1e-3], "t")
        assert out["n_over_rtol"] == (abs(got - 1e-3) > 1e-7)
    else:
        with pytest.raises(AssertionError, match="bound"):
            chip_smoke._compare([got], [1e-3], "t")
