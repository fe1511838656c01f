"""Host milliseconds per traced pass deriving op layouts on a staging
cache miss (``flow.derive``: trees, paths, latencies, loss parameters)
and batch-deriving a cold cache's paths (``flow.warm``)."""
from bench import spans


def read(ctx):
    return spans.ms_per_pass(ctx, ("flow.derive", "flow.warm"))
