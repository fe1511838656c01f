"""One multicast group per scenario under loss (Gleam sec. 5.3, Figs.
15-16): a scenario for each size of ``group_sizes``, its members drawn
from the pass's placement stream, the first of them sending
``nbytes``.  The loss level is the mix's, engine-wide for the pass."""
from bench.gen import op


def scenarios(hosts, p: dict, rng):
    return [[op("bcast", rng.sample(hosts, g), p["nbytes"])]
            for g in p["group_sizes"]]
