"""Max-min filling rounds the epoch solver's lanes needed, per pass
(``SOLVE_STATS["rounds"]``, counted on the device since the window's
start: the window's passes and the traced ones)."""
from bench import sut


def read(ctx):
    stats = sut.solve_stats()
    if "rounds" not in stats:
        return None
    return stats["rounds"] / (len(ctx["pass_s"]) + ctx["trace_passes"])
