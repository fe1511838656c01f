"""Share of the epoch solver's lanes solved under the float64
promotion: 100 x ``SOLVE_STATS["x64_lanes"]`` / ``SOLVE_STATS["lanes"]``
since the window's start; None without the counters or with no lanes."""
from bench import sut


def read(ctx):
    stats = sut.solve_stats()
    if "x64_lanes" not in stats or not stats.get("lanes"):
        return None
    return 100.0 * stats["x64_lanes"] / stats["lanes"]
