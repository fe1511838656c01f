"""Max-min filling rounds per epoch problem solved: a lane of a batched
call, or one unbatched solve (``SOLVE_STATS["rounds"]`` over
``SOLVE_STATS["lanes"]`` since the window's start).  How deep one
problem's filling goes, whatever the number of problems a pass solves;
None also when nothing was solved."""
from bench import sut


def read(ctx):
    stats = sut.solve_stats()
    if "rounds" not in stats or "lanes" not in stats \
            or not stats["lanes"]:
        return None
    return stats["rounds"] / stats["lanes"]
