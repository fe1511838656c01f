"""Self time of the program's host spans, from a profiler trace.

The flow engine opens a ``jax.profiler.TraceAnnotation`` named
``flow.<phase>`` around each phase of a pass, under one root,
``flow.run_workloads``.  While the profiler runs, they land on the
lines of the ``/host...`` planes beside JAX's own host events, on the
device trace's clock.  A span's self time is its duration less the
part of it that ``flow.*`` spans nested in it on the same line cover,
so the self times of a pass's spans add up to its root span, each
nanosecond counted once.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

ROOT = "flow.run_workloads"
PREFIX = "flow."


def self_ns(planes: Sequence[dict]) -> Dict[str, int]:
    """Nanoseconds of self time per span name, summed over the host
    lines; a name that occurs maps to a number, even 0."""
    out: Dict[str, int] = {}
    for p in planes:
        if not p["name"].startswith("/host"):
            continue
        for line in p["lines"]:
            evs = sorted((s, -d, name) for name, s, d in line["events"]
                         if name.startswith(PREFIX))
            covered = [0] * len(evs)
            open_: list = []               # (end, index), outermost first
            for i, (s, neg_d, _) in enumerate(evs):
                e = s - neg_d
                while open_ and open_[-1][0] <= s:
                    open_.pop()
                if open_:
                    end, parent = open_[-1]
                    covered[parent] += min(e, end) - s
                open_.append((e, i))
            for (s, neg_d, name), c in zip(evs, covered):
                out[name] = out.get(name, 0) + (-neg_d - c)
    return out


def ms_per_pass(ctx: dict, names: Sequence[str]) -> Optional[float]:
    """Self time of the spans ``names``, in milliseconds per traced
    pass; None when the trace holds no root span (a program without
    the spans)."""
    ns = self_ns(ctx["planes"])
    if ROOT not in ns:
        return None
    return 1e-6 * sum(ns.get(n, 0) for n in names) / ctx["trace_passes"]
