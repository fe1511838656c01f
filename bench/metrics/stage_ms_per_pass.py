"""Host milliseconds per traced pass in the flow engine's scenario
staging (op replay and the lowerings' dispatch): the self time of its
``flow.stage`` spans, without the ``flow.derive`` spans inside them."""
from bench import spans


def read(ctx):
    return spans.ms_per_pass(ctx, ("flow.stage",))
