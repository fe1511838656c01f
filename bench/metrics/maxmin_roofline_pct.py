"""Share of the chip's roofline that the solver reaches: the least time
the chip could take for the traced passes' max-min solves
(``roofline.py``: reckoned from the problem, not from padded shapes),
over the solver's device time."""
from bench import roofline, trace


def read(ctx):
    s = trace.solver_seconds(ctx["planes"])
    if s is None or not ctx["trace_work"]:
        return None
    least = sum(roofline.least_seconds(w, ctx["n_links"], ctx["peaks"])[0]
                for w in ctx["trace_work"])
    return 100.0 * least / s
