"""Executables JAX compiled or loaded from its persistent cache inside
the window (its own monitoring events); 0 when set-up warmed every
shape."""


def read(ctx):
    return float(ctx["compiles"])
