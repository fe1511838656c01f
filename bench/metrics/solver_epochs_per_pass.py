"""Fluid epochs the epoch solver's lanes needed, per pass
(``SOLVE_STATS["epochs"]``, counted on the device since the window's
start: the window's passes and the traced ones)."""
from bench import sut


def read(ctx):
    stats = sut.solve_stats()
    if "epochs" not in stats:
        return None
    return stats["epochs"] / (len(ctx["pass_s"]) + ctx["trace_passes"])
