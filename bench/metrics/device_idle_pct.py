"""Share of the traced window in which no operation ran on the device:
1 - (union of the device-op intervals) / window, averaged over the
chips used."""
from bench import trace


def read(ctx):
    if not trace.device_planes(ctx["planes"]):
        return None
    return 100.0 * trace.idle_share(ctx["planes"],
                                    int(ctx["trace_window_s"] * 1e9))
