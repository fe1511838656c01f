"""Max-min filling rounds the device executed per pass: each solver
call's rounds, its slowest lane's in every epoch, summed
(``SOLVE_STATS["rounds_run"]`` since the window's start: the window's
passes and the traced ones)."""
from bench import sut


def read(ctx):
    stats = sut.solve_stats()
    if "rounds_run" not in stats:
        return None
    return stats["rounds_run"] / (len(ctx["pass_s"]) + ctx["trace_passes"])
