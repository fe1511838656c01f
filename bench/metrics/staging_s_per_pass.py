"""Host seconds per pass outside the solver calls: the window's
seconds minus the program's ``SOLVE_STATS.solve_s``, over passes."""


def read(ctx):
    return (ctx["window_s"] - ctx["solve_s"]) / len(ctx["pass_s"])
