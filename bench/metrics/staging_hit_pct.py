"""Share of the staging cache's lookups that hit inside the window
(``FlowEngine.staging_stats()`` hits and misses)."""


def read(ctx):
    total = ctx["staging_hits"] + ctx["staging_misses"]
    if not total:
        return None
    return 100.0 * ctx["staging_hits"] / total
