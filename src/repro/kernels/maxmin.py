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

This jnp formulation is the solver's path on every platform: XLA
lowers its scatter-adds and gathers for the CPU and for the TPU alike
(``tests/test_tpu_compile.py`` compiles it for a v5e chip).  The
numpy ``flowsim.FlowSim`` filling is its reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


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
            share = jnp.where(cnt > 0,
                              cap_rem / jnp.maximum(cnt, 1).astype(dtype),
                              jnp.inf)
        # each flow's tightest link share (sentinel gathers inf)
        tightest = jnp.min(share[flow_links], axis=1)
        limit = jnp.where(frozen > 0.5, jnp.inf, tightest)
        b = jnp.min(limit)
        newly = (frozen < 0.5) & (limit <= b * (1.0 + tol))
        newf = newly.astype(dtype)
        rates = jnp.where(newly, b, rates)
        if cnt is None:
            used = jnp.zeros(n_caps, dtype).at[flow_links].add(
                jnp.broadcast_to((newf * b)[:, None], flow_links.shape))
        else:
            k = link_counts(flow_links, newly, n_caps)
            used = b * k.astype(dtype)
            cnt = cnt - k
        cap_rem = jnp.maximum(cap_rem - used, 0.0)
        return rates, jnp.minimum(frozen + newf, 1.0), cap_rem, cnt


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
    fill's rounds recount it.
    """
    n_flows = flow_links.shape[0]
    dtype = cap.dtype
    bound = n_flows if max_rounds is None else max_rounds - 1
    cnt = link_counts(flow_links, active, cap.shape[0]) \
        if dtype == jnp.float64 else None

    def cond(st):
        _, frozen, _, _, it = st
        return jnp.logical_and(jnp.min(frozen) < 0.5, it <= bound)

    def body(st):
        rates, frozen, cap_rem, cnt, it = st
        rates, frozen, cap_rem, cnt = maxmin_round(
            flow_links, frozen, rates, cap_rem, cnt, tol=tol)
        return rates, frozen, cap_rem, cnt, it + 1

    init = (jnp.zeros(n_flows, dtype), 1.0 - active.astype(dtype),
            cap, cnt, jnp.int32(0))
    rates, _, _, _, rounds = lax.while_loop(cond, body, init)
    return jnp.maximum(rates, 1e-9), rounds


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
