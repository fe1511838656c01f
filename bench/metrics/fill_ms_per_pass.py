"""Host milliseconds per traced pass filling results in: flow
completion times from the solver's output (``flow.finish``) and the
records' delivery and CQE times (``flow.fill``)."""
from bench import spans


def read(ctx):
    return spans.ms_per_pass(ctx, ("flow.finish", "flow.fill"))
