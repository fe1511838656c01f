"""Seconds per pass inside the solver calls, as the program times
them (``SOLVE_STATS.solve_s``: host clock around pack, dispatch and
the copy of the result to the host)."""


def read(ctx):
    return ctx["solve_s"] / len(ctx["pass_s"])
