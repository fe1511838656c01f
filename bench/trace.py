"""Reduction from a profiler trace to the benchmark's device numbers.

The trace is read into plain data first (``load``): a list of planes,
each ``{"name", "lines": [{"name", "events": [(name, start_ns,
dur_ns), ...]}]}``, so the arithmetic below runs the same on a trace
written by the chip and on a hand-made one in the tests.

- busy time: the union of the intervals in which an operation ran on
  a device (the leaf events of the ``XLA Ops`` line of each
  ``/device:...`` plane: a control-flow op such as ``while`` spans its
  body's ops and the gaps between them, so an event that holds another
  is left out);
- solver time: the sum of the device durations of the executables
  (``XLA Modules`` line) whose names contain the solver's name;
- idle share: 1 - busy / window, per device, averaged over devices;
- idle gaps: the longest stretches with no device op, each named by
  the innermost host event running at its midpoint.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: the program's solver executable: the fluid epoch solver
#: (``flowsim_jax._simulate``)
SOLVER_MODULES = ("jit__simulate",)


def load(trace_dir: str) -> List[dict]:
    """Planes of the newest ``.xplane.pb`` under ``trace_dir``."""
    import jax
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(files[-1])
    planes = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [
                (e.name, int(e.start_ns), int(e.duration_ns))
                for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def device_planes(planes: Sequence[dict]) -> List[dict]:
    return [p for p in planes if DEVICE_PLANE.match(p["name"])]


def _line(plane: dict, name: str) -> List[Tuple[str, int, int]]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged, sorted [start, end) intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def leaf_ops(plane: dict) -> List[Tuple[str, int, int]]:
    """The ``XLA Ops`` events that hold no other event of the line: the
    operations themselves, not the control flow around them."""
    evs = sorted(_line(plane, OPS_LINE), key=lambda e: (e[1], -e[2]))
    ends = [s + d for _, s, d in evs]
    holds = [False] * len(evs)
    enclosing: List[int] = []      # events still open, outermost first
    for i, (_, s, _) in enumerate(evs):
        while enclosing and ends[enclosing[-1]] <= s:
            enclosing.pop()
        if enclosing and ends[i] <= ends[enclosing[-1]]:
            holds[enclosing[-1]] = True
        enclosing.append(i)
    return [e for e, h in zip(evs, holds) if not h]


def busy_ns(plane: dict) -> int:
    return sum(e - s for s, e in union(
        (s, s + d) for _, s, d in leaf_ops(plane)))


def solver_ns(plane: dict, names: Sequence[str]) -> int:
    """Device time of the executables whose names contain a solver
    name (an executable's event spans its whole run on the device)."""
    return sum(d for n, _, d in _line(plane, MODULES_LINE)
               if any(k in n for k in names))


def solver_seconds(planes: Sequence[dict],
                   names: Sequence[str] = SOLVER_MODULES):
    """Solver device seconds averaged over the device planes; None when
    the trace holds no solver executable."""
    devs = device_planes(planes)
    if not devs:
        return None
    ns = sum(solver_ns(p, names) for p in devs) / len(devs)
    return ns * 1e-9 if ns > 0 else None


def idle_share(planes: Sequence[dict], window_ns: int) -> float:
    """1 - busy / window, averaged over the device planes."""
    devs = device_planes(planes)
    if not devs or window_ns <= 0:
        raise ValueError("no device plane or empty window")
    return sum(1.0 - busy_ns(p) / window_ns for p in devs) / len(devs)


def short_name(hlo: str) -> str:
    """An op's name and result shape from its HLO text, without the
    layout and operands (``%fusion.4 = f32[50177]``)."""
    return hlo.split("{")[0].split("(")[0].strip().rstrip(" =")[:80]


def top_ops(planes: Sequence[dict], n: int = 10) -> List[list]:
    """[[op name, seconds]] of the device ops (leaf events) that took
    most time, summed over devices."""
    tot: Dict[str, int] = {}
    for p in device_planes(planes):
        for name, _, d in leaf_ops(p):
            name = short_name(name)
            tot[name] = tot.get(name, 0) + d
    best = sorted(tot.items(), key=lambda x: -x[1])[:n]
    return [[k, v * 1e-9] for k, v in best]


def idle_gaps(planes: Sequence[dict], n: int = 10) -> List[list]:
    """[[label, seconds]] of the longest gaps between device ops on
    the first device, each labelled by the shortest host event that
    spans the gap's midpoint (what the host was doing meanwhile)."""
    devs = device_planes(planes)
    if not devs:
        return []
    busy = union((s, s + d) for _, s, d in leaf_ops(devs[0]))
    gaps = [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    host = [(name, s, s + d) for p in planes if p["name"].startswith("/host")
            for line in p["lines"] for name, s, d in line["events"]]
    out = []
    for g0, g1 in gaps:
        label, best = "no host event", None
        mid = (g0 + g1) // 2
        for name, s, e in host:
            if s <= mid < e and (best is None or e - s < best):
                label, best = name, e - s
        out.append([label, (g1 - g0) * 1e-9])
    return out
