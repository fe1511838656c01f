"""BENCHMARK.json parses, and everything it names is found by name."""
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.tests import _tiny  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_names_units_and_moves():
    b = _tiny.bench_json()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    cells = [w["name"] for w in b["workloads"]]
    names = list(e2e) + [m["name"] for m in b["per_layer"]] + cells \
        + [c["name"] for c in b["configs"]]
    assert len(names) == len(set(names))
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for cell in cells:
        reported = [n for n, m in e2e.items()
                    if cell in m.get("workloads", cells)]
        assert "setup_s" in reported and len(reported) >= 2
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in e2e[m["moves"]].get("workloads", cells)


def test_every_named_file_exists():
    b = _tiny.bench_json()
    bench = os.path.join(ROOT, "bench")
    for c in b["configs"]:
        assert c["file"].startswith("bench/configs/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
    for w in b["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert os.path.isfile(os.path.join(bench, "traffic",
                                           w["traffic"] + ".json"))
        assert os.path.isfile(os.path.join(bench, "checks",
                                           w["name"] + ".json"))
    for m in b["end_to_end"] + b["per_layer"]:
        assert os.path.isfile(os.path.join(bench, "metrics",
                                           m["name"] + ".py"))


def test_peaks_table_knows_the_v5e_and_refuses_others():
    import pytest
    from bench import roofline
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
