"""Share of the lane-rounds the device ran that some lane needed: a
vmapped solve runs every lane until its slowest lane is done, so
100 x ``SOLVE_STATS["rounds"]`` / ``SOLVE_STATS["lane_rounds_run"]``
since the window's start; None also when no filling round ran."""
from bench import sut


def read(ctx):
    stats = sut.solve_stats()
    if "rounds" not in stats or "lane_rounds_run" not in stats \
            or not stats["lane_rounds_run"]:
        return None
    return 100.0 * stats["rounds"] / stats["lane_rounds_run"]
