"""Share of the epoch solver's lanes whose fills took the dense round:
100 x ``SOLVE_STATS["dense_lanes"]`` / ``SOLVE_STATS["lanes"]`` since
the window's start; None without the counter or with no lanes."""
from bench import sut


def read(ctx):
    stats = sut.solve_stats()
    if "dense_lanes" not in stats or not stats.get("lanes"):
        return None
    return 100.0 * stats["dense_lanes"] / stats["lanes"]
