"""The device epoch loop's serial depth per pass: each solver call's
most epochs of any of its lanes, summed (``SOLVE_STATS["epochs_run"]``
since the window's start: the window's passes and the traced ones)."""
from bench import sut


def read(ctx):
    stats = sut.solve_stats()
    if "epochs_run" not in stats:
        return None
    return stats["epochs_run"] / (len(ctx["pass_s"]) + ctx["trace_passes"])
