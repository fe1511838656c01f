"""The least time a chip could take for a pass's max-min solves.

Derivation.  A pass's work is a list of max-min solves: one per fluid
completion epoch (the surviving flows re-share the fabric) and one per
distinct dynamic-segment snapshot, as the plain reference makes them
(``reference.Reference.run_pass`` returns (incidence non-zeros, flows)
for each).  Whatever the implementation, one solve has to

- read the link id of every incidence non-zero once: 4 bytes each
  (int32 link ids);
- read and write one state word per flow (its rate or remaining
  bytes): 2 x 4 bytes per flow;
- add each non-zero into its link's demand and compare it against its
  flow's tightest share: 2 operations per non-zero;

and the pass reads the fabric's capacity vector once: 4 bytes per
directed link.  Padding, shape buckets and the number of filling
rounds never enter, so a change to them cannot move the yardstick, and
a faster implementation raises the share.  The time bound is the
larger of bytes over peak memory bandwidth and operations over peak
arithmetic rate; at these sizes the bytes bind by orders of magnitude.
"""
from __future__ import annotations

import json
import os
from typing import Sequence, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """Published peaks of ``device_kind``; an unknown device is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def min_bytes(work: Sequence[Tuple[int, int]], n_links: int) -> int:
    return 4 * n_links + sum(4 * nnz + 8 * flows for nnz, flows in work)


def min_ops(work: Sequence[Tuple[int, int]]) -> int:
    return sum(2 * nnz for nnz, _ in work)


def least_seconds(work, n_links: int, peak: dict) -> Tuple[float, str]:
    """(least seconds, which bound binds: "bytes" or "ops")."""
    t_bytes = min_bytes(work, n_links) / peak["hbm_bytes_per_s"]
    t_ops = min_ops(work) / peak["bf16_flops_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")
