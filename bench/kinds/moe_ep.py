"""An expert-parallel MoE layer's dispatch and combine on a rail fabric
(DeepSeek-V3, arXiv:2412.19437 secs. 2.1.2, 3.1-3.2).

Each pass is one MoE layer of one pipeline stage, the stage and
``ep_groups_per_pass`` of its ``dp`` EP groups drawn from the pass's
placement stream.  Each EP group of ``ep`` GPUs
(``ep / gpus_per_node`` nodes) routes ``tokens_per_gpu``
tokens a GPU from i.i.d. uniform float32 affinity scores, redrawn
every pass, by node-limited routing: the ``n_group`` groups are the
nodes of an EP group.  A token crosses the fabric once per target node
other than its own, to the GPU with the same in-node index there
(DeepEP); GPU ``i`` of node ``n`` is host ``n`` of plane (pod) ``i``.
Two scenarios, because the job puts a barrier between them: dispatch
(``dispatch_token_bytes`` a token) and combine, the reverse unicasts
(``combine_token_bytes`` a token).

A copy of the program's ``apps/collectives_lowering.py``
``node_limited_sets``, ``route_ep_groups`` and the multiunicast side
of ``moe_ep_ops``, importing nothing of the program.
"""
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from bench.gen import op


def node_limited_sets(scores, n_group, topk_group, top_k):
    """Group-limited routing of tokens with affinity ``scores`` (...,
    n_experts): the ``topk_group`` groups with the largest sums of
    their top ``top_k // topk_group`` scores (float64, smallest first),
    then the ``top_k`` best experts inside them, ties to the lower
    index.  Each token's groups holding a chosen expert, as a bit mask."""
    *lead, n_exp = scores.shape
    per = n_exp // n_group
    x = scores.reshape(-1, n_group, per)
    k = top_k // topk_group
    best = np.partition(x, per - k, axis=-1)[..., per - k:]
    gs = np.sort(best, axis=-1).astype(np.float64).sum(-1)
    kept = np.sort(np.argsort(-gs, axis=-1, kind="stable")[:, :topk_group],
                   axis=-1)
    vals = np.take_along_axis(x, kept[..., None], axis=1).reshape(len(x), -1)
    m = vals.shape[1]
    tau = np.partition(vals, m - top_k, axis=-1)[:, m - top_k, None]
    hit = np.take_along_axis(best.max(-1), kept, axis=1) >= tau
    for t in np.flatnonzero((vals >= tau).sum(-1) > top_k):
        chosen = np.lexsort((np.arange(m), -vals[t]))[:top_k]
        hit[t] = np.isin(np.arange(topk_group), chosen // per)
    mask = (hit * (np.int64(1) << kept)).sum(-1)
    return mask.reshape(lead)


def route_ep_groups(p: dict, seeds):
    """Per seed, an EP group's (ep, 2**n_group) token counts per
    target-node set: a float32 draw per node from the node's stream of
    ``SeedSequence(seed)``, the nodes routed on a thread pool."""
    g, ep = p["n_group"], p["ep"]
    gpn = ep // g
    streams = [ss for seed in seeds
               for ss in np.random.SeedSequence(seed).spawn(g)]

    def node(ss):
        scores = np.random.default_rng(ss).random(
            (gpn, p["tokens_per_gpu"], p["n_routed_experts"]), np.float32)
        sets = node_limited_sets(scores, g, p["topk_group"],
                                 p["num_experts_per_tok"])
        return np.bincount((sets + (np.arange(gpn)[:, None] << g)).ravel(),
                           minlength=gpn << g).reshape(gpn, 1 << g)

    with ThreadPoolExecutor(min(len(streams), os.cpu_count() or 1)) as ex:
        rows = list(ex.map(node, streams))
    return [np.concatenate(rows[i * g:(i + 1) * g])
            for i in range(len(seeds))]


def scenarios(hosts, p: dict, rng):
    gpn, ep, dp, g = (p["gpus_per_node"], p["ep"], p["dp"], p["n_group"])
    n_nodes = len(hosts) // gpn
    if ep != g * gpn or n_nodes != p["pipe"] * dp * g:
        raise ValueError(f"{len(hosts)} hosts do not hold {p['pipe']} "
                         f"stages of {dp} EP groups of {g} nodes of {gpn}")
    stage = rng.randrange(p["pipe"])
    groups = sorted(rng.sample(range(dp), p["ep_groups_per_pass"]))
    seeds = [rng.getrandbits(63) for _ in groups]
    dispatch, combine = [], []
    for r, hist in zip(groups, route_ep_groups(p, seeds)):
        first = (stage * dp + r) * g            # the group's first node
        bits = (np.arange(1 << g)[:, None] >> np.arange(g)) & 1
        counts = hist @ bits                    # (ep, g) tokens per node
        for e in range(ep):
            a, i = divmod(e, gpn)
            src = hosts[i * n_nodes + first + a]
            for b in range(g):
                c = int(counts[e, b])
                if b == a or not c:
                    continue
                dst = hosts[i * n_nodes + first + b]
                dispatch.append(op("unicast", [src, dst],
                                   c * p["dispatch_token_bytes"],
                                   phase="moe-dispatch"))
                combine.append(op("unicast", [dst, src],
                                  c * p["combine_token_bytes"],
                                  phase="moe-combine"))
    return [dispatch, combine]
