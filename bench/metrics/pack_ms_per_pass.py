"""Host milliseconds per traced pass turning staged ops into solver
input: the epochs' flows (``flow.flows``) and the batch planning and
padded packing (``flow.pack``)."""
from bench import spans


def read(ctx):
    return spans.ms_per_pass(ctx, ("flow.flows", "flow.pack"))
