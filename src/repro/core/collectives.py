"""Gleam collectives on the TPU ICI (the adapted layer, DESIGN.md §2.2).

The paper's two data-plane primitives map onto mesh collectives:

- one-to-many *in-fabric multicast*  -> ``tree_broadcast`` (binomial tree of
  collective_permutes; the sender transmits O(log n) times instead of n-1,
  interior "switches" forward — cf. Fig. 4 left).
- many-to-one *feedback aggregation* -> ``tree_reduce`` /
  ``butterfly_allreduce`` with an arbitrary associative combine — exactly
  Algorithm 2/3's min-PSN aggregation generalized to any monoid.  The
  flagship use is ``softmax_combine``: merging split-KV decode-attention
  partials (m, l, acc) up the aggregation tree.

Baselines mirror the paper's §2.3 design space:
- ``unicast_broadcast``  — "multiple unicasts" (root sends n-1 times).
- ``ring_broadcast``     — overlay multicast (store-and-forward pipeline).

All functions are shard_map-compatible: they must be called INSIDE a
shard_map body (they use axis names).  Axis sizes must be powers of two for
the tree/butterfly schedules (production meshes: 2, 16).
"""
from __future__ import annotations

import math
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.lax import axis_size as _axis_size


def _log2(n: int) -> int:
    k = int(math.log2(n))
    assert 2 ** k == n, f"axis size {n} must be a power of two"
    return k


# ---------------------------------------------------------------- schedules

def tree_broadcast(x, axis_name, root: int = 0):
    """Binomial-tree one-to-many multicast (Gleam in-fabric forwarding).

    Round j: ranks [0, 2^j) forward to ranks [2^j, 2^{j+1}) (rank space is
    rotated so `root` is rank 0).  log2(n) rounds; each value crosses each
    link once -> optimal forwarding, no sender bottleneck.
    """
    n = _axis_size(axis_name)
    if n == 1:
        return x
    idx = jax.lax.axis_index(axis_name)
    rank = (idx - root) % n
    for j in range(_log2(n)):
        half = 2 ** j
        perm = [(((r + root) % n), ((r + half + root) % n))
                for r in range(half)]
        recv = jax.lax.ppermute(x, axis_name, perm)
        is_recv = (rank >= half) & (rank < 2 * half)
        x = jax.tree.map(
            lambda a, b: jnp.where(is_recv, b, a), x, recv)
    return x


def unicast_broadcast(x, axis_name, root: int = 0):
    """'Multiple unicasts' baseline: root sends to every receiver in turn
    (n-1 serialized rounds; the sender's link is the bottleneck)."""
    n = _axis_size(axis_name)
    if n == 1:
        return x
    idx = jax.lax.axis_index(axis_name)
    for t in range(1, n):
        dst = (root + t) % n
        recv = jax.lax.ppermute(x, axis_name, [(root, dst)])
        x = jax.tree.map(lambda a, b: jnp.where(idx == dst, b, a), x, recv)
    return x


def ring_broadcast(x, axis_name, root: int = 0, chunks: int = 1):
    """Overlay-multicast baseline: store-and-forward around a ring.

    chunks > 1 pipelines the transfer (the paper's Ring algorithm): total
    rounds = (n - 1) + (chunks - 1) instead of (n - 1) * chunks.
    """
    n = _axis_size(axis_name)
    if n == 1:
        return x
    idx = jax.lax.axis_index(axis_name)
    rank = (idx - root) % n
    perm = [(((r + root) % n), ((r + 1 + root) % n)) for r in range(n - 1)]

    def fwd_rounds(val):
        v = val
        for t in range(n - 1):
            recv = jax.lax.ppermute(v, axis_name, perm)
            v = jax.tree.map(
                lambda a, b: jnp.where(rank == t + 1, b, a), v, recv)
        return v

    if chunks <= 1:
        return fwd_rounds(x)
    leaves, treedef = jax.tree.flatten(x)
    split = [jnp.array_split(leaf, chunks) for leaf in leaves]
    outs = []
    for c in range(chunks):
        piece = jax.tree.unflatten(treedef, [s[c] for s in split])
        outs.append(fwd_rounds(piece))
    out_leaves = [jnp.concatenate([jax.tree.leaves(o)[i] for o in outs])
                  for i in range(len(leaves))]
    return jax.tree.unflatten(treedef, out_leaves)


def tree_reduce(x, axis_name, combine: Callable, root: int = 0):
    """Binomial-tree many-to-one aggregation (Algorithm 2/3 generalized).

    Mirror of tree_broadcast: round j, ranks [2^j, 2^{j+1}) send to ranks
    [0, 2^j) which combine.  After log2(n) rounds rank-0 (root) holds the
    full reduction; other ranks hold partials (garbage to callers).
    """
    n = _axis_size(axis_name)
    if n == 1:
        return x
    idx = jax.lax.axis_index(axis_name)
    rank = (idx - root) % n
    for j in reversed(range(_log2(n))):
        half = 2 ** j
        perm = [(((r + half + root) % n), ((r + root) % n))
                for r in range(half)]
        recv = jax.lax.ppermute(x, axis_name, perm)
        merged = combine(x, recv)
        is_recv = rank < half
        x = jax.tree.map(lambda a, b: jnp.where(is_recv, b, a), x, merged)
    return x


def butterfly_allreduce(x, axis_name, combine: Callable):
    """Recursive-doubling allreduce with an arbitrary associative combine:
    log2(n) full-exchange rounds (reduce+multicast fused)."""
    n = _axis_size(axis_name)
    for j in range(_log2(n)) if n > 1 else []:
        mask = 2 ** j
        perm = [(i, i ^ mask) for i in range(n)]
        recv = jax.lax.ppermute(x, axis_name, perm)
        x = combine(x, recv)
    return x


def tree_allreduce(x, axis_name, combine: Callable, root: int = 0):
    """Gleam round trip: many-to-one aggregation then one-to-many
    multicast of the result (Fig. 4 right then left)."""
    x = tree_reduce(x, axis_name, combine, root)
    return tree_broadcast(x, axis_name, root)


# ---------------------------------------------------------------- combines

def _softmax_merge(a, b):
    """Associative merge of split-KV softmax partials (m, l, acc)."""
    m_a, l_a, acc_a = a
    m_b, l_b, acc_b = b
    m = jnp.maximum(m_a, m_b)
    sa = jnp.exp(m_a - m)
    sb = jnp.exp(m_b - m)
    l = l_a * sa + l_b * sb
    acc = acc_a * sa[..., None] + acc_b * sb[..., None]
    return m, l, acc


def softmax_combine(parts, axis_names: Sequence[str], schedule: str = "xla"):
    """Merge (m, l, acc) decode-attention partials across seq-shard axes.

    schedule:
      "xla"        — pmax/psum (XLA picks its own all-reduce schedule);
      "gleam_tree" — explicit butterfly aggregation tree (the paper's
                     in-fabric feedback aggregation, adapted);
    Both are exact (the merge is associative up to fp error).
    """
    m, l, acc = parts
    if schedule == "gleam_tree":
        for ax in axis_names:
            m, l, acc = butterfly_allreduce((m, l, acc), ax, _softmax_merge)
        return m, l, acc
    m_g = m
    for ax in axis_names:
        m_g = jax.lax.pmax(m_g, ax)
    scale = jnp.exp(m - m_g)
    l_s = l * scale
    acc_s = acc * scale[..., None]
    for ax in axis_names:
        l_s = jax.lax.psum(l_s, ax)
        acc_s = jax.lax.psum(acc_s, ax)
    return m_g, l_s, acc_s


def allreduce_sum(x, axis_names: Sequence[str], schedule: str = "xla"):
    """Gradient-sync allreduce with selectable schedule (DP sync)."""
    if schedule in ("xla", "psum"):
        for ax in axis_names:
            x = jax.tree.map(lambda a: jax.lax.psum(a, ax), x)
        return x
    comb = lambda a, b: jax.tree.map(jnp.add, a, b)  # noqa: E731
    for ax in axis_names:
        if schedule == "gleam_tree":
            x = butterfly_allreduce(x, ax, comb)
        elif schedule == "ring":
            # reduce around the ring then ring-broadcast (overlay baseline)
            x = tree_reduce(x, ax, comb)
            x = ring_broadcast(x, ax)
        elif schedule == "unicast":
            x = tree_reduce(x, ax, comb)
            x = unicast_broadcast(x, ax)
        else:
            raise ValueError(schedule)
    return x


# ------------------------------------------------- schedule cost model

# The analytic alpha-beta JCT model moved to core/metrics.py with the
# rest of the accounting; re-exported here for existing callers.
from repro.core.metrics import schedule_cost  # noqa: E402,F401
