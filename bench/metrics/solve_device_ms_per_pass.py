"""Device milliseconds per traced pass in the solver's executables
(``trace.SOLVER_MODULES``), summed from the profiler trace."""
from bench import trace


def read(ctx):
    s = trace.solver_seconds(ctx["planes"])
    return None if s is None else 1e3 * s / ctx["trace_passes"]
