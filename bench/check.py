"""How ``correct`` is decided: the window's answers against the plain
reference (``reference.py``).

Every compared number is a time relative to an op's submission: each
receiver's delivery and the sender's CQE.  A pass compares when the
program and the reference deliver to the same receivers of every op;
its reading is the widest relative gap ``|got - want| / want`` over
all of them.  A missing op, a receiver set that differs, an errored
record or a non-finite time reads infinity.
"""
from __future__ import annotations

import json
import math
import os
import random
from typing import List, Sequence

HERE = os.path.dirname(os.path.abspath(__file__))


def limits(workload: str) -> dict:
    """``checks/<workload>.json``: {number: {"limit": x, ...}}."""
    with open(os.path.join(HERE, "checks", f"{workload}.json")) as f:
        return json.load(f)["numbers"]


def max_rel_gap(got: Sequence[Sequence[dict]],
                want: Sequence[Sequence[dict]]) -> float:
    if len(got) != len(want):
        return math.inf
    worst = 0.0
    for gs, ws in zip(got, want):
        if len(gs) != len(ws):
            return math.inf
        for g, w in zip(gs, ws):
            if g["error"] or g["cqe"] is None \
                    or set(g["deliver"]) != set(w["deliver"]):
                return math.inf
            pairs = [(g["deliver"][m], w["deliver"][m])
                     for m in w["deliver"]] + [(g["cqe"], w["cqe"])]
            for a, b in pairs:
                if not (math.isfinite(a) and b > 0.0):
                    return math.inf
                worst = max(worst, abs(a - b) / b)
    return worst


class Sample:
    """A sample of the window's passes drawn from the seed as the
    passes come, so the window keeps only the passes sampled so far:
    for each class (a cycle position of the mix, so every message size
    or loss level is covered) a reservoir of ``ceil(size / n_classes)``
    passes, each pass of the class equally likely to be in it."""

    def __init__(self, seed: int, n_classes: int, size: int):
        self.rng = random.Random(f"{seed}:check")
        self.k = max(1, math.ceil(size / n_classes))
        self.seen = [0] * n_classes
        self.kept: List[list] = [[] for _ in range(n_classes)]

    def offer(self, cls: int, item) -> None:
        j = self.seen[cls]
        self.seen[cls] += 1
        if j < self.k:
            self.kept[cls].append(item)
        else:
            m = self.rng.randrange(j + 1)
            if m < self.k:
                self.kept[cls][m] = item

    def items(self) -> list:
        return [x for kept in self.kept for x in kept]
