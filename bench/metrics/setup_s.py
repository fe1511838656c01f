"""Process start to the first timed pass: the fabric, the warm-up
passes of the cell's own traffic, and any compilation."""


def read(ctx):
    return ctx["setup_s"]
