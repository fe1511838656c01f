"""GroupOps whose records the window's passes filled, over the
window's seconds (the sum of the passes' latencies)."""


def read(ctx):
    return sum(ctx["pass_ops"]) / ctx["window_s"]
