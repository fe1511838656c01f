"""Max-min progressive filling and the loss/DCQCN factors, in jnp.

One round of water-filling over the padded (F, H) flow->link matrix
(see ``core/flowsim_jax.py``) makes four logical passes:

1. per-link demand  — the unfrozen flows on each link;
2. fair share       — ``cap_remaining / demand`` per link;
3. tightest share   — per-flow min-gather over its link list, global
   bottleneck ``b`` = min over unfrozen flows;
4. freeze mask      — flows at the bottleneck freeze at rate ``b`` and
   their bandwidth is subtracted from every link they cross.

The fill picks the round's form from the solve dtype.  A float32 round
scatter-adds the unfrozen flows (pass 1) and the frozen rates (pass 4)
onto their links in float32.  A float64 round scatters no float64: the
fill counts each link's live flows once, as int32, and carries the
count; a round scatter-adds its newly frozen flows as an int32 ``k``
per link, subtracts ``b * k`` from the remaining capacity (every flow
of a round freezes at the same ``b``) and ``k`` from the count.  The
TPU emulates float64, and a float64 scatter-add there costs tens of
times a float32 one per entry; the counts are exact either way, and
``b * k`` rounds once where a sum of ``k`` copies of ``b`` rounds
``k - 1`` times.

A count-carrying fill takes one of two forms of its round.  The
indexed round gathers each flow's link shares and scatter-adds ``k``
over the (F, H) matrix.  The TPU runs those one entry at a time (about
10 ns each), so there a call small enough (``dense_fill_fits``: at
most ``DENSE_MAX_ENTRIES`` lanes x flows x links) runs the dense round
instead, over each lane's (F, L) int32 incidence (``link_incidence``,
built once per solver call): ``tightest`` is a row minimum of
``share`` where the incidence is non-zero and ``k`` a column sum, on
the vector units.  Both take the same minimum over the same float64
shares and the same integer counts, so their rates agree bit for bit.
``with_fill_form`` makes the choice with ``lax.platform_dependent``,
as the program is lowered: the CPU keeps the indexed round, where a
gather is cheap.

This jnp formulation is the solver's path on every platform
(``tests/test_tpu_compile.py`` compiles it for a v5e chip).  The numpy
``flowsim.FlowSim`` filling is its reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

#: the most incidence entries (lanes x flows x links, int32) of a
#: solver call whose float64 fills take the dense round on a TPU: on a
#: v5e every shape measured up to it ran the dense round at least 3x
#: faster than the indexed one, the first tie lies 4x above it, and its
#: incidence stays within 32 MiB (PERF.md)
DENSE_MAX_ENTRIES = 1 << 23


def maxmin_round(flow_links, frozen, rates, cap_rem, cnt=None, *,
                 tol: float = 1e-6):
    """One progressive-filling round of max-min fair allocation.

    flow_links (F, H) int32 link ids padded with the sentinel (last)
    index of ``cap_rem``; frozen (F,) 0/1 mask in cap dtype (padding
    rows enter frozen); rates (F,); cap_rem (L+1,) with cap_rem[-1]=inf.
    ``cnt`` (L+1,) int32 is each link's live-flow count, carried from
    round to round (float64 fills); None recounts the live flows with a
    scatter in cap dtype (float32 fills).
    ``tol`` is the relative freeze slack (1e-6 suits float32 solves;
    float64 solves pass 1e-12 to mirror the numpy
    ``flowsim.static_maxmin`` filling).  Returns the round's
    (rates, frozen, cap_rem, cnt), ``cnt`` None when it came in None.
    """
    n_caps = cap_rem.shape[0]
    dtype = cap_rem.dtype
    with jax.named_scope("maxmin_round"):
        if cnt is None:
            live = 1.0 - frozen
            # per-link demand: scatter every live flow onto its links
            demand = jnp.zeros(n_caps, dtype).at[flow_links].add(
                jnp.broadcast_to(live[:, None], flow_links.shape))
            share = jnp.where(demand > 0.0,
                              cap_rem / jnp.maximum(demand, 1.0), jnp.inf)
        else:
            share = _count_share(cap_rem, cnt)
        # each flow's tightest link share (sentinel gathers inf)
        tightest = jnp.min(share[flow_links], axis=1)
        b, newly, newf, rates = _freeze(tightest, frozen, rates, tol)
        if cnt is None:
            used = jnp.zeros(n_caps, dtype).at[flow_links].add(
                jnp.broadcast_to((newf * b)[:, None], flow_links.shape))
        else:
            k = link_counts(flow_links, newly, n_caps)
            used = b * k.astype(dtype)
            cnt = cnt - k
        cap_rem = jnp.maximum(cap_rem - used, 0.0)
        return rates, jnp.minimum(frozen + newf, 1.0), cap_rem, cnt


def _count_share(cap_rem, cnt):
    """Each link's fair share of its remaining capacity among its
    ``cnt`` live flows (int32); inf on a link with none."""
    return jnp.where(cnt > 0,
                     cap_rem / jnp.maximum(cnt, 1).astype(cap_rem.dtype),
                     jnp.inf)


def _freeze(tightest, frozen, rates, tol):
    """A round's bottleneck ``b`` (the least tightest share of an
    unfrozen flow), the flows within ``tol`` of it that freeze now, as
    a mask and in cap dtype, and the rates with theirs set to ``b``."""
    limit = jnp.where(frozen > 0.5, jnp.inf, tightest)
    b = jnp.min(limit)
    newly = (frozen < 0.5) & (limit <= b * (1.0 + tol))
    return b, newly, newly.astype(frozen.dtype), jnp.where(newly, b, rates)


def link_counts(flow_links, mask, n_caps: int):
    """(n_caps,) int32: how many flows of ``mask`` (F,) cross each link,
    as an int32 scatter-add (a link twice in a row counts twice)."""
    return jnp.zeros(n_caps, jnp.int32).at[flow_links].add(
        jnp.broadcast_to(mask.astype(jnp.int32)[:, None], flow_links.shape))


def maxmin_rates(flow_links, cap, active, *, tol: float = 1e-6,
                 max_rounds=None):
    """Max-min fair rates by progressive filling over ``maxmin_round``
    (``maxmin_fill`` without its round count)."""
    return maxmin_fill(flow_links, cap, active, tol=tol,
                       max_rounds=max_rounds)[0]


def maxmin_fill(flow_links, cap, active, *, tol: float = 1e-6,
                max_rounds=None):
    """Max-min fair rates by progressive filling over ``maxmin_round``,
    and the number of filling rounds it ran.

    flow_links (F, H) int32 padded with the sentinel (last) index of
    ``cap``; cap (L+1,) bytes/s with cap[-1] = inf; active (F,) bool.
    Returns ((F,) rates, int32 rounds); inactive flows get ~0, and an
    all-inactive problem runs 0 rounds.  Terminates in at most F
    rounds (>= 1 flow freezes per round; in practice a handful, since
    whole bottleneck groups freeze together).

    ``tol`` is the relative freeze slack of each round and
    ``max_rounds`` caps the round count (None keeps the default F+1
    bound).  The dynamic-segment solver passes ``tol=1e-12,
    max_rounds=64`` under float64 to mirror the numpy
    ``flowsim.static_maxmin`` filling round for round.

    A float64 fill counts each link's live flows once, as int32, and
    its rounds carry that count (see the module docstring); a float32
    fill's rounds recount it.  This is the indexed form; ``dense_fill``
    is the dense one.
    """
    cnt = link_counts(flow_links, active, cap.shape[0]) \
        if cap.dtype == jnp.float64 else None
    return _fill(functools.partial(maxmin_round, flow_links, tol=tol),
                 cap, active, cnt, max_rounds)


def dense_fill(incidence, cap, active, *, tol: float):
    """``maxmin_fill``'s count-carrying (float64) fill over the lane's
    dense link incidence, with no gather or scatter.

    incidence (F, L) int32 from ``link_incidence`` over a ``cap`` of
    (L+1,) (its sentinel column left out); cap and active as
    ``maxmin_fill`` takes them.  Returns what ``maxmin_fill`` returns,
    bit for bit: the same rates and the same round count.
    """
    cnt = jnp.sum(jnp.where(active[:, None], incidence, 0), axis=0)
    return _fill(functools.partial(dense_round, incidence, tol=tol),
                 cap[:-1], active, cnt, None)


def _fill(round_fn, cap, active, cnt, max_rounds):
    """The progressive-filling loop: ``round_fn`` (a round with its
    links bound) until every flow is frozen or the round cap is hit;
    (rates, rounds) as ``maxmin_fill`` returns them."""
    n_flows = active.shape[0]
    dtype = cap.dtype
    bound = n_flows if max_rounds is None else max_rounds - 1

    def cond(st):
        _, frozen, _, _, it = st
        return jnp.logical_and(jnp.min(frozen) < 0.5, it <= bound)

    def body(st):
        rates, frozen, cap_rem, cnt, it = st
        rates, frozen, cap_rem, cnt = round_fn(frozen, rates, cap_rem, cnt)
        return rates, frozen, cap_rem, cnt, it + 1

    init = (jnp.zeros(n_flows, dtype), 1.0 - active.astype(dtype),
            cap, cnt, jnp.int32(0))
    rates, _, _, _, rounds = lax.while_loop(cond, body, init)
    return jnp.maximum(rates, 1e-9), rounds


def dense_round(incidence, frozen, rates, cap_rem, cnt, *, tol: float):
    """``maxmin_round`` with carried counts over the (F, L) incidence:
    each flow's tightest share is a row minimum of ``share`` where the
    incidence is non-zero, and the newly frozen flows' per-link count
    ``k`` a column sum.  The same float64 shares and the same int32
    counts as the indexed round, so the same rates, bit for bit."""
    with jax.named_scope("maxmin_round"):
        share = _count_share(cap_rem, cnt)
        tightest = jnp.min(jnp.where(incidence > 0, share, jnp.inf),
                           axis=1)
        b, newly, newf, rates = _freeze(tightest, frozen, rates, tol)
        k = jnp.sum(jnp.where(newly[:, None], incidence, 0), axis=0)
        cap_rem = jnp.maximum(cap_rem - b * k.astype(cap_rem.dtype), 0.0)
        return rates, jnp.minimum(frozen + newf, 1.0), cap_rem, cnt - k


def link_incidence(flow_links, n_caps: int):
    """(F, n_caps - 1) int32: how often each flow crosses each link, a
    link twice in a row counting twice as in ``link_counts``.  The
    sentinel (index n_caps - 1) has no column: its capacity is
    infinite, so its share never bounds a flow."""
    return jax.nn.one_hot(flow_links, n_caps - 1, dtype=jnp.int32).sum(1)


def dense_fill_fits(n_flows: int, n_caps: int, dtype) -> bool:
    """Whether a solver call of ``n_flows`` flows over all its lanes and
    ``n_caps`` capacities (sentinel included) in ``dtype`` takes the
    dense round on a TPU: a count-carrying float64 fill whose
    incidences hold at most ``DENSE_MAX_ENTRIES`` entries."""
    return (jnp.dtype(dtype) == jnp.float64
            and n_flows * (n_caps - 1) <= DENSE_MAX_ENTRIES)


def with_fill_form(flow_links, n_caps: int, dtype, run, lanes: int = 1):
    """``run(incidence)``, the lane's ``link_incidence`` built once,
    where the fill takes the dense round: lowered for a TPU, under
    ``dense_fill_fits`` for ``lanes`` such lanes.  Elsewhere
    ``run(None)``, the indexed round; the choice is made as the program
    is lowered."""
    if not dense_fill_fits(lanes * flow_links.shape[0], n_caps, dtype):
        return run(None)
    return lax.platform_dependent(
        tpu=lambda: run(link_incidence(flow_links, n_caps)),
        default=lambda: run(None))


def loss_factors(flow_links, rates, active, cap, q, wsq, wnd, ecn, *,
                 dcqcn_num: float, dcqcn_min: float,
                 util_eps: float = 1e-3):
    """Expected-value loss/DCQCN rate-correction factors, (F,) in (0, 1].

    The per-flow multiplier the fluid solver applies to its max-min
    rates so lossy go-back-N transfers slow down the way the packet
    engine's do (see docs/ARCHITECTURE.md "Loss & congestion model";
    ``flowsim.FlowSim._apply_loss`` is its numpy twin):

    - go-back-N replay: a loss costs ``W = min(sqrt(rate * wsq), wnd)``
      replayed packets (``wsq`` pre-folds the calibrated replay window
      and NACK-merge damping, so ``sqrt(rate * wsq)`` is the geometric
      mean of the flow- and link-BDP in packets); the steady-state
      goodput fraction is ``(1-q) / (1-q + q*W)``.
    - DCQCN: flows crossing a *shared saturated* link (>= 2 active
      flows, utilization at capacity) with ECN marking enabled sit on
      the CNP/recovery sawtooth; the average undershoot is
      ``alpha_eq / 4`` with ``alpha_eq = dcqcn_num / rate`` (clipped to
      [0, 1]), floored so the effective rate never falls below the
      DCQCN minimum rate — and never negative or above capacity, since
      the returned factor is always in (0, 1].

    flow_links (F, H) int32 padded with the sentinel (last) index of
    ``cap``; rates (F,) solved max-min rates; active (F,) 0/1 mask in
    cap dtype; cap (L+1,) with cap[-1] = inf (the sentinel can never be
    saturated); q / wsq / wnd / ecn (F,) per-flow loss-model arrays
    (all-zero rows — padding or lossless flows — get factor exactly 1).
    """
    n_caps = cap.shape[0]
    dtype = cap.dtype
    with jax.named_scope("loss_factors"):
        # per-link utilization + active-flow count (one scatter each)
        util = jnp.zeros(n_caps, dtype).at[flow_links].add(
            jnp.broadcast_to((active * rates)[:, None], flow_links.shape))
        cnt = jnp.zeros(n_caps, dtype).at[flow_links].add(
            jnp.broadcast_to(active[:, None], flow_links.shape))
        hot = ((cnt >= 2.0) & (util >= cap * (1.0 - util_eps))).astype(dtype)
        flow_hot = jnp.max(hot[flow_links], axis=1)
        # go-back-N: replay window in packets, then steady-state goodput
        w = jnp.minimum(jnp.sqrt(jnp.maximum(rates * wsq, 0.0)), wnd)
        gbn = (1.0 - q) / jnp.maximum(1.0 - q + q * w, 1e-30)
        # DCQCN sawtooth undershoot on ECN-marked (shared, saturated) links
        alpha = jnp.clip(dcqcn_num / jnp.maximum(rates, 1e-30), 0.0, 1.0)
        dc = 1.0 - 0.25 * alpha * ecn * flow_hot
        floor = jnp.minimum(dcqcn_min / jnp.maximum(rates, 1e-30), 1.0)
        dc = jnp.maximum(dc, floor)
        return jnp.clip(gbn * dc, 1e-9, 1.0)
