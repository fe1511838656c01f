"""HPL's communication at fat-tree scale (Gleam sec. 5.3, Fig. 14).

One scenario per multicast scale ``N`` of ``scales``: on the first
``N * N`` hosts, laid out row-major, ``N`` Panel Broadcast groups (one
per row, the row's first host sending) and ``N`` Row Swap groups (one
per column, the column's first host sending), every group one
``nbytes`` multicast over the deployment's ``transport``, all
contending.  A copy of the program's
``benchmarks/fig14_scale.py:gleam_workload``.  The placement is the
layout itself, so the pass's stream is not drawn from.
"""
from bench.gen import op


def scenarios(hosts, p: dict, rng):
    out = []
    for n in p["scales"]:
        if n * n > len(hosts):
            raise ValueError(f"scale {n} x {n} needs more hosts than "
                             f"the fabric's {len(hosts)}")
        ops = [op("bcast", hosts[r * n:(r + 1) * n], p["nbytes"], key=r,
                  phase="pb") for r in range(n)]
        ops += [op("bcast", [hosts[r * n + c] for r in range(n)],
                   p["nbytes"], key=n + c, phase="rs") for c in range(n)]
        out.append(ops)
    return out
