"""90th percentile (nearest rank) of the window's pass latencies: from
the ``run_workloads`` call to its records on the host."""
import math


def read(ctx):
    lat = sorted(ctx["pass_s"])
    return lat[max(0, math.ceil(0.9 * len(lat)) - 1)]
