"""Vectorized JAX flow-level backend — the scale path of the SimEngine.

Same fluid model as ``flowsim.FlowSim`` (max-min fair shares over the
link-flow incidence; a Gleam multicast tree is ONE flow across the union
of its tree links), but the whole simulation is dense-array loops:

- **inner loop**: progressive-filling max-min fair allocation, one
  round per iteration (``kernels/maxmin.py``, jnp on every platform).
  Each round counts the unfrozen flows on each link, computes every
  link's fair share, gathers each flow's tightest share, and freezes
  the bottleneck group.
  Terminates in at most F rounds (whole bottleneck groups freeze
  together, so in practice a handful).
- **outer loop** (``_simulate``): classic fluid event loop — advance
  time to the next flow completion at the current rates, zero finished
  flows, re-allocate.  Epochs whose completions are link-disjoint from
  every surviving flow *warm-start*: the previous rate vector is reused
  and the filling is skipped entirely (max-min allocations decompose
  over connected components of the flow-link interference graph).

Flows are stored as an (F, H) matrix of link ids padded with a sentinel
link of infinite capacity (H = longest link list in the batch), NOT a
dense (F, L) incidence: a 16k-host fat-tree has ~50k directed links and
fig14's unicast baseline meshes stage ~32k flows, so the dense form
would need gigabytes while the padded form stays at a few MB.  The
dense form is used in one place: a float64 batch is renumbered to the
links its flows cross (``JaxFlowSim._compact``), and on a TPU a call
of at most ``maxmin.DENSE_MAX_ENTRIES`` lanes x flows x links builds
that compacted incidence once and runs its filling rounds over it
(``kernels/maxmin.py``), since the TPU gathers and scatters one entry
at a time.  ``SOLVE_STATS["dense_lanes"]`` counts those lanes.

**Shape bucketing**: F and H are padded up to power-of-two buckets
(``_bucket``) before the jit boundary, so nearby problem sizes share
one compiled executable — a fig14 sweep or a fig12/13 message-size
ladder compiles once, not once per point.  ``solve_many`` goes further:
independent epochs are padded to a common bucket, stacked, and solved
by ONE ``jax.vmap``-ed executable (the batched path behind
``SimEngine.run_workloads``); a byte-budget planner (``_plan_batches``)
splits shape-incompatible epochs so a 32k-flow unicast mesh is never
padded to a multicast tree's hop count.

**Precision**: volumes and capacities solve in float32 until the
largest staged volume exceeds the float32 safe-integer range (2^24
bytes ~ 16MB); beyond that (the multi-GB fig12/13 replication regime)
the solve auto-promotes to float64 under a scoped ``jax.enable_x64``
so completion times keep full precision, at float64's own freeze and
completion slacks (``EPOCH_TOL``).  ``solve_dtype`` records the choice.
A float64 filling round scatters no float64: it keeps each link's live
flows as an int32 count carried across the fill's rounds, and takes
the frozen bandwidth off a link as the round's one bottleneck rate
times the int32 count of flows it froze there (``kernels/maxmin.py``);
the warm start's touched-link test is an int32 count too.

Flows, link ids, and routing come from ``flowsim.LinkMap`` so this
solver and its numpy reference ``flowsim.FlowSim`` are numerically
interchangeable (tested to 0.1%).
"""
from __future__ import annotations

import contextlib
import functools
import os
import threading
import time
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.fattree import Topology
from repro.core.flowsim import DCQCN_MIN_RATE, DCQCN_RATE_NUM, Flow, LinkMap
from repro.kernels.maxmin import (dense_fill, dense_fill_fits, link_counts,
                                  loss_factors, maxmin_fill, maxmin_rates,
                                  with_fill_form)

#: volumes above this lose integer precision in float32 (2^24 bytes)
F32_SAFE_MAX = float(1 << 24)

#: the epoch solver's slacks by solve dtype: (relative freeze slack,
#: relative completion slack, bytes added to it).  float32 leaves room
#: for its rounding; a promoted float64 solve keeps the precision it
#: was promoted for, at the numpy filling's 1e-12 freeze slack
EPOCH_TOL = {"float32": (1e-6, 1e-6, 1.0), "float64": (1e-12, 1e-9, 0.0)}

#: padded-batch budget for ``_plan_batches`` (int32 link-id bytes)
MAX_BATCH_BYTES = 64 << 20

#: split a batch when the padded per-round work exceeds this multiple
#: of the epochs' individual work (e.g. a 2048-flow unicast mesh padded
#: next to a 64-flow multicast epoch would cost ~50x per round)
MAX_PAD_WASTE = 4.0

#: solver telemetry, accumulated by every solve until
#: ``reset_solve_stats``:
#:
#: - ``solve_s``: host seconds around each solve's transfer to the
#:   device, the call and the copy of its results back (a host clock,
#:   not device time: the device's own time is in a profiler trace);
#: - ``calls``: solver calls (epoch and dynamic-segment);
#: - ``shapes``: the set of distinct padded link-id matrix shapes
#:   solved;
#: - ``lanes``: epochs solved by the epoch solver (one lane of a
#:   batched call each, or one unbatched ``run``);
#: - ``epochs``: every lane's fluid epochs (event-loop iterations);
#: - ``rounds``: every lane's max-min filling rounds, as each lane
#:   needed them;
#: - ``lane_rounds_run``: filling rounds the device executed, times the
#:   lanes of the call: a vmapped loop runs every lane until the
#:   slowest is done, so ``rounds / lane_rounds_run`` is the share of
#:   that work some lane needed;
#: - ``x64_lanes``: lanes solved under the float64 promotion;
#: - ``epochs_run``: per call, the most epochs of any of its lanes —
#:   the device loop's serial depth, summed over calls;
#: - ``rounds_run``: per call, the filling rounds the device executed
#:   (``lane_rounds_run`` without the lanes factor), summed over calls;
#: - ``dense_lanes``: lanes whose fills took the dense round
#:   (``kernels/maxmin.py``: a float64 fill on a TPU, under
#:   ``dense_fill_fits``).
#:
#: The counters from ``lanes`` on cover the epoch solver only.
SOLVE_STATS = {"solve_s": 0.0, "calls": 0, "shapes": set(), "lanes": 0,
               "epochs": 0, "rounds": 0, "lane_rounds_run": 0,
               "x64_lanes": 0, "epochs_run": 0, "rounds_run": 0,
               "dense_lanes": 0}
_STATS_LOCK = threading.Lock()

#: dynamic-segment solves mirror the numpy ``flowsim.static_maxmin``
#: filling: float64, the same relative freeze slack, the same 64-round
#: cap — so the batched fairness snapshots match the per-segment
#: oracle to <= 1e-6 (reduction-order rounding only)
SEG_TOL = 1e-12
SEG_ROUNDS = 64


def reset_solve_stats():
    SOLVE_STATS.update(solve_s=0.0, calls=0, shapes=set(), lanes=0,
                       epochs=0, rounds=0, lane_rounds_run=0,
                       x64_lanes=0, epochs_run=0, rounds_run=0,
                       dense_lanes=0)


#: the persistent compilation cache when ``JAX_COMPILATION_CACHE_DIR``
#: is unset: a fixed ``.jax_cache/`` at the checkout's root (the path is
#: part of the cache key, so a directory that moved would never hit)
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")

_CACHE_READY = False


def compile_cache_dir() -> str:
    """The directory the persistent compilation cache lives in."""
    return jax.config.jax_compilation_cache_dir or CACHE_DIR


def _enable_persistent_cache():
    """Turn XLA's persistent compilation cache on (once per process) so
    repeat sweeps skip compilation.  ``JAX_COMPILATION_CACHE_DIR``, when
    set, places it and nothing here overrides it; otherwise it goes to
    ``CACHE_DIR``."""
    global _CACHE_READY
    if _CACHE_READY:
        return
    _CACHE_READY = True
    if jax.config.jax_compilation_cache_dir is None:
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)


def _bucket(n: int, lo: int) -> int:
    """Smallest power of two >= max(n, lo) — the jit-cache shape key."""
    return max(lo, 1 << max(int(n) - 1, 0).bit_length())


#: the vmap axis of the batched epoch solver's lanes
LANES = "lanes"


def _simulate(flow_links, cap, vol, loss=None, warm=True,
              axis_name=None):
    """Fluid event loop: completion times (F,) for every flow, with the
    loop's ``COUNTS`` appended, ``[epochs, rounds, rounds_run]`` in the
    same dtype: one (F + 3,) array, so the counts reach the host in
    ``done``'s own transfer (exact below 2^24 in float32); ``_split``
    takes them apart.

    ``epochs`` is the event-loop iterations and ``rounds`` the max-min
    filling rounds this problem needed.  ``rounds_run`` is the filling
    rounds the device executed while this problem's loop ran: under
    ``jax.vmap`` with ``axis_name`` naming the vmap axis, each epoch runs
    as many rounds as its slowest lane (``lax.pmax``), and the lane
    with the most epochs runs in every iteration, so the largest
    ``rounds_run`` of a batch is exact for the whole call.

    ``warm`` compiles in the completion-epoch warm start: when an
    epoch's completed flows are link-disjoint from every survivor,
    the previous rate vector is reused and the filling skipped.
    The batched (vmapped) solver sets ``warm=False``: under vmap
    ``lax.cond`` lowers to a select that executes both branches, so
    the skip can never fire and the dirty tracking would be pure
    per-epoch overhead.

    ``loss`` (a ``(q, wsq, wnd, ecn)`` tuple of (F,) arrays, or
    None) compiles in the expected-value loss/DCQCN correction: the
    solved max-min rates are scaled by ``kernels/maxmin.py``'s
    ``loss_factors`` each epoch.  The loop state carries the
    RAW max-min rates (so the warm start stays valid and factors
    are never applied twice); only ``dt`` and the drained bytes use
    the effective rates.  ``loss=None`` traces the exact lossless
    graph — zero-loss results are bit-identical.

    The fills take the indexed or the dense round as
    ``maxmin.with_fill_form`` picks for the lowering platform, the solve
    dtype and the shape; the results are the same bit for bit.
    """
    lanes = 1 if axis_name is None else lax.axis_size(axis_name)
    return with_fill_form(
        flow_links, cap.shape[0], cap.dtype,
        lambda inc: _epochs(flow_links, cap, vol, loss, warm, axis_name,
                            inc), lanes)


def _epochs(flow_links, cap, vol, loss, warm, axis_name, incidence):
    """``_simulate``'s event loop; its fills take the dense round over
    ``incidence`` where one is given, else the indexed round."""
    n_flows = flow_links.shape[0]
    n_caps = cap.shape[0]
    tol, slack, pad = EPOCH_TOL[jnp.dtype(cap.dtype).name]
    eps = vol * slack + pad                 # completion slack (bytes)

    def fill(active):
        if incidence is None:
            return maxmin_fill(flow_links, cap, active, tol=tol)
        return dense_fill(incidence, cap, active, tol=tol)

    def cond(st):
        _, rem, _, _, _, it, _, _ = st
        return jnp.logical_and(jnp.any(rem > 0.0), it <= n_flows)

    def body(st):
        t, rem, done, rates, dirty, it, rounds, ran = st
        with jax.named_scope("epoch"):
            active = rem > 0.0
            if warm:
                rates, n = lax.cond(dirty, lambda r: fill(active),
                                    lambda r: (r, jnp.int32(0)), rates)
            else:
                rates, n = fill(active)
            eff = rates
            if loss is not None:
                eff = rates * loss_factors(
                    flow_links, rates, active.astype(cap.dtype), cap, *loss,
                    dcqcn_num=DCQCN_RATE_NUM, dcqcn_min=DCQCN_MIN_RATE)
            dt = jnp.min(jnp.where(active, rem / eff, jnp.inf))
            t = t + dt
            rem = jnp.where(active, rem - eff * dt, 0.0)
            fin = active & (rem <= eps)
            done = jnp.where(fin, t, done)
            rem = jnp.where(fin, 0.0, rem)
            if warm:
                touched = link_counts(flow_links, fin, n_caps)
                touched = touched.at[-1].set(0)     # sentinel: no contention
                survive = active & ~fin
                dirty = jnp.any(
                    survive & (jnp.max(touched[flow_links], axis=1) > 0))
        ran_n = n if axis_name is None else lax.pmax(n, axis_name)
        return t, rem, done, rates, dirty, it + 1, rounds + n, ran + ran_n

    zero = jnp.asarray(0.0, cap.dtype)
    init = (zero, vol, jnp.zeros(n_flows, cap.dtype),
            jnp.zeros(n_flows, cap.dtype), jnp.bool_(True),
            jnp.int32(0), jnp.int32(0), jnp.int32(0))
    _, _, done, _, _, epochs, rounds, ran = lax.while_loop(cond, body, init)
    counts = jnp.stack([epochs, rounds, ran]).astype(done.dtype)
    return jnp.concatenate([done, counts])


#: the loop counts ``_simulate`` appends to each completion vector
COUNTS = 3


def _split(out: np.ndarray):
    """A solve's output as (completion times, int64 counts (..., 3))."""
    return out[..., :-COUNTS], out[..., -COUNTS:].astype(np.int64)


def _solver(batched: bool, lossy: bool = False):
    """Jitted solver, one per (batched, lossy) flavor.

    ``lossy`` selects the flavor that threads the per-flow loss arrays
    — lossless solves keep their exact pre-existing executable.
    """
    # normalize BEFORE the lru_cache: positional and defaulted calls
    # must land on the same memoized jit object (the cache-hit tests
    # introspect it)
    return _solver_impl(bool(batched), bool(lossy))


@functools.lru_cache(maxsize=None)
def _solver_impl(batched: bool, lossy: bool):
    """Every flavor keeps ``_simulate``'s name, so its executable is
    ``jit__simulate``.  Nothing is donated: the output, three slots
    longer than the volume buffer, could not reuse it."""
    if batched:
        sim = functools.partial(_simulate, warm=False, axis_name=LANES)
        fn = jax.vmap(sim, in_axes=(0, None, 0, 0) if lossy
                      else (0, None, 0), axis_name=LANES)
    else:
        fn = functools.partial(_simulate, warm=True)
    return jax.jit(functools.update_wrapper(fn, _simulate))


@functools.lru_cache(maxsize=None)
def _seg_solver():
    """Jitted, vmapped dynamic-segment solver.

    One lane = one fairness-snapshot problem: a padded (F, H)
    link-id matrix, its active-row mask, and the index of the OWN
    flow.  The lane solves max-min rates under the numpy-matched
    ``SEG_TOL``/``SEG_ROUNDS`` regime, applies the loss/DCQCN
    factors (all-zero loss rows give factor exactly 1.0, so one
    always-lossy executable covers lossless problems bit-exactly),
    and returns the own flow's corrected rate.  Its executable is
    ``jit__segment_rate``.
    """
    def _segment_rate(fl, active, own, cap, loss):
        rates = maxmin_rates(fl, cap, active, tol=SEG_TOL,
                             max_rounds=SEG_ROUNDS)
        fac = loss_factors(fl, rates, active, cap, *loss,
                           dcqcn_num=DCQCN_RATE_NUM,
                           dcqcn_min=DCQCN_MIN_RATE)
        return rates[own] * fac[own]

    return jax.jit(jax.vmap(_segment_rate, in_axes=(0, 0, 0, None,
                                                    (0, 0, 0, 0))))


class JaxFlowSim(LinkMap):
    """Drop-in for ``flowsim.FlowSim`` backed by the jitted solver.

    ``add()`` stages flows; ``run()`` builds the padded link-id matrix
    once (bucketed — see module docstring) and solves every completion
    epoch on-device; ``solve_many()`` solves a list of INDEPENDENT flow
    batches in one vmapped executable.
    """

    F_BUCKET_MIN = 16
    H_BUCKET_MIN = 8
    #: the least link bucket of a compacted float64 batch: one TPU
    #: vector's width of incidence columns
    L_BUCKET_MIN = 128

    #: host spans land in a running profiler's trace, on the device
    #: trace's clock (about a microsecond each when none runs)
    span = staticmethod(jax.profiler.TraceAnnotation)

    def __init__(self, topo: Topology, shared_cache: bool = True):
        super().__init__(topo, shared_cache)
        _enable_persistent_cache()
        self.flows: List[Flow] = []
        self.now = 0.0
        self.solve_dtype = None          # dtype of the last solve

    def add(self, links, volume, tag=None, loss=None) -> Flow:
        links = tuple(links)
        assert links, "a flow must traverse at least one link"
        f = Flow(links, float(volume), tag=tag, loss=loss)
        self.flows.append(f)
        return f

    # --------------------------------------------------------- solver glue

    def _select_dtype(self, flows: Sequence[Flow]):
        """float32 until volumes outgrow its integer precision."""
        vmax = max((f.volume for f in flows), default=0.0)
        return np.float64 if vmax > F32_SAFE_MAX else np.float32

    def _pack(self, flows: Sequence[Flow], dtype, f_pad: int, h_pad: int):
        """(f_pad, h_pad) link-id matrix + (f_pad,) volumes; padding
        rows/columns point at the infinite-capacity sentinel link."""
        sentinel = len(self.cap)
        n = len(flows)
        fl = np.full((f_pad, h_pad), sentinel, np.int32)
        vol = np.zeros(f_pad, dtype)
        if n:
            # one flat scatter instead of a per-flow Python row loop —
            # packing a 32k-flow unicast mesh is staging-path work
            lens = np.fromiter((len(f.links) for f in flows), np.int64, n)
            total = int(lens.sum())
            flat = np.fromiter((l for f in flows for l in f.links),
                               np.int32, total)
            rows = np.repeat(np.arange(n), lens)
            cols = np.arange(total) - np.repeat(np.cumsum(lens) - lens,
                                                lens)
            fl[rows, cols] = flat
            vol[:n] = np.fromiter((f.volume for f in flows), np.float64, n)
        return fl, vol

    def _shape(self, flows: Sequence[Flow]):
        n = len(flows)
        h = max(len(f.links) for f in flows)
        return _bucket(n, self.F_BUCKET_MIN), _bucket(h, self.H_BUCKET_MIN)

    def _pack_loss(self, flows: Sequence[Flow], dtype, f_pad: int):
        """(q, wsq, wnd, ecn) per-flow loss-model rows, each (f_pad,).

        All-zero rows — padding and lossless flows — solve at factor
        exactly 1, so mixing lossy and lossless flows in one epoch is
        fine.
        """
        arrs = np.zeros((4, f_pad), dtype)
        lossy = [(i, f.loss) for i, f in enumerate(flows)
                 if f.loss is not None]
        if lossy:
            ii = np.fromiter((i for i, _ in lossy), np.int64, len(lossy))
            arrs[0, ii] = [lp.q for _, lp in lossy]
            arrs[1, ii] = [lp.wsq for _, lp in lossy]
            arrs[2, ii] = [lp.wnd for _, lp in lossy]
            arrs[3, ii] = [1.0 if lp.ecn else 0.0 for _, lp in lossy]
        return tuple(arrs)

    def _cap_ext(self, dtype):
        return np.append(self.cap, np.inf).astype(dtype)

    def _compact(self, fl, cap):
        """``fl`` renumbered to the links its flows cross, and their
        capacities: (ids, cap) in place of (fl, cap).

        The crossed links keep their order as ids 0..n-1; the ids are
        padded to ``_bucket(n, L_BUCKET_MIN)`` links of infinite
        capacity, and the sentinel stays the last index.  The solve's
        arithmetic is unchanged (every link is used elementwise or by
        id), but a float64 fill's dense round then spans the batch's
        own links, not the fabric's (``kernels/maxmin.py``).
        """
        sentinel = len(cap) - 1
        used, ids = np.unique(fl, return_inverse=True)
        n = len(used) - int(used[-1] == sentinel)
        n_pad = _bucket(n, self.L_BUCKET_MIN)
        ids = ids.reshape(fl.shape).astype(np.int32)
        ids[fl == sentinel] = n_pad
        ccap = np.full(n_pad + 1, np.inf, cap.dtype)
        ccap[:n] = cap[used[:n]]
        return ids, ccap

    def _dispatch(self, batched: bool, fl, cap, vol, dtype,
                  loss=None) -> np.ndarray:
        """Run the jitted solver (under x64 when promoted), timed; the
        completion times, with the loop's counts added to
        ``SOLVE_STATS``.

        The ``jnp.asarray`` conversions MUST happen inside the x64
        scope: without it enabled, float64 inputs silently downcast to
        float32 and the promotion is lost.
        """
        solve = _solver(batched, loss is not None)
        ctx = jax.enable_x64(True) if dtype == np.float64 \
            else contextlib.nullcontext()
        with self.span("flow.dispatch"):
            t0 = time.perf_counter()
            with ctx:
                args = [jnp.asarray(fl), jnp.asarray(cap),
                        jnp.asarray(vol)]
                if loss is not None:
                    args.append(tuple(jnp.asarray(a) for a in loss))
                done, counts = _split(np.asarray(solve(*args)))
            dt = time.perf_counter() - t0
        epochs, rounds, ran = counts.reshape(-1, COUNTS).T
        lanes = len(epochs)
        dense = jax.default_backend() == "tpu" and dense_fill_fits(
            int(np.prod(fl.shape[:-1])), len(cap), dtype)
        with _STATS_LOCK:
            SOLVE_STATS["solve_s"] += dt
            SOLVE_STATS["calls"] += 1
            SOLVE_STATS["shapes"].add(tuple(fl.shape))
            SOLVE_STATS["lanes"] += lanes
            SOLVE_STATS["epochs"] += int(epochs.sum())
            SOLVE_STATS["rounds"] += int(rounds.sum())
            SOLVE_STATS["lane_rounds_run"] += lanes * int(ran.max())
            if dtype == np.float64:
                SOLVE_STATS["x64_lanes"] += lanes
            SOLVE_STATS["epochs_run"] += int(epochs.max())
            SOLVE_STATS["rounds_run"] += int(ran.max())
            if dense:
                SOLVE_STATS["dense_lanes"] += lanes
        return done

    def _finish(self, flows: Sequence[Flow], done: np.ndarray) -> float:
        """Back-fill completion bookkeeping WITHOUT touching volumes.

        A flow's expected RTO stall (``LossParams.tail``) lands here:
        it delays the completion timestamp without occupying fabric
        time in the solve (the bandwidth is free during the stall).
        """
        n = len(flows)
        # one float64 conversion + tolist() instead of a per-flow
        # float() call; the loss-tail add stays scalar per lossy flow
        # so the float addition order matches the original exactly
        dts = np.asarray(done[:n], np.float64).tolist()
        end = 0.0
        for f, d in zip(flows, dts):
            if f.loss is not None:
                d += f.loss.tail
            f.done_t = d
            f.remaining = 0.0
            if d > end:
                end = d
        return end

    def run(self) -> float:
        if not self.flows:
            return self.now
        flows = self.flows
        with self.span("flow.pack"):
            dtype = self._select_dtype(flows)
            self.solve_dtype = dtype
            f_pad, h_pad = self._shape(flows)
            fl, vol = self._pack(flows, dtype, f_pad, h_pad)
            loss = self._pack_loss(flows, dtype, f_pad) \
                if any(f.loss is not None for f in flows) else None
            cap = self._cap_ext(dtype)
        done = self._dispatch(False, fl, cap, vol, dtype, loss)
        with self.span("flow.finish"):
            self.now = self._finish(flows, done)
        return self.now

    # ------------------------------------------------------- batched solve

    def _plan_batches(self, epochs, indices, shapes=None):
        """Group epoch ``indices`` into padded stacks.

        Two constraints per batch: stay under ``MAX_BATCH_BYTES``, and
        keep the padded per-round work within ``MAX_PAD_WASTE`` of the
        epochs' individual (F_bucket * H_bucket) work — so a 32k-flow
        unicast mesh (H ~ 8) is never padded to a multicast epoch's hop
        count (H ~ hundreds) or vice versa.  Epochs are sorted by H
        bucket first, which makes shape-compatible epochs adjacent."""
        if shapes is None:
            shapes = {i: self._shape(epochs[i]) for i in indices}
        shaped = sorted(indices, key=lambda i: shapes[i][::-1])
        batches, cur = [], []
        f_max = h_max = own = 0
        for i in shaped:
            f, h = shapes[i]
            nf, nh = max(f_max, f), max(h_max, h)
            ne = len(cur) + 1
            if cur and (ne * nf * nh * 4 > MAX_BATCH_BYTES
                        or ne * nf * nh > MAX_PAD_WASTE * (own + f * h)):
                batches.append(cur)
                cur, nf, nh, own = [], f, h, 0
            cur.append(i)
            f_max, h_max, own = nf, nh, own + f * h
        if cur:
            batches.append(cur)
        return batches

    def solve_many(self, epochs: Sequence[Sequence[Flow]]):
        """Solve INDEPENDENT flow batches (epochs) in one vmapped call.

        Every epoch is an isolated fabric: flows in different epochs do
        not share bandwidth, and every epoch's clock starts at 0.  All
        epochs in a batch are padded to a common (F, H) bucket and the
        batched solver runs once per batch.  Returns the per-epoch
        completion time; per-flow ``done_t`` is filled in as by
        ``run()``.
        """
        with self.span("flow.pack"):
            epochs = [list(ep) for ep in epochs]
            out = [0.0] * len(epochs)
            nonempty = [i for i, ep in enumerate(epochs) if ep]
            if not nonempty:
                return out
            vmax = max(max(f.volume for f in epochs[i]) for i in nonempty)
            dtype = np.float64 if vmax > F32_SAFE_MAX else np.float32
            self.solve_dtype = dtype
            cap = self._cap_ext(dtype)
            shapes = {i: self._shape(epochs[i]) for i in nonempty}
            batches = self._plan_batches(epochs, nonempty, shapes)

        def solve_batch(batch):
            with self.span("flow.pack"):
                f_pad = h_pad = 0
                for i in batch:
                    f, h = shapes[i]
                    f_pad, h_pad = max(f_pad, f), max(h_pad, h)
                packed = [self._pack(epochs[i], dtype, f_pad, h_pad)
                          for i in batch]
                fl = np.stack([p[0] for p in packed])
                vol = np.stack([p[1] for p in packed])
                bcap = cap
                if dtype == np.float64:
                    fl, bcap = self._compact(fl, cap)
                loss = None
                if any(f.loss is not None
                       for i in batch for f in epochs[i]):
                    rows = [self._pack_loss(epochs[i], dtype, f_pad)
                            for i in batch]
                    loss = tuple(np.stack([r[k] for r in rows])
                                 for k in range(4))
            return self._dispatch(True, fl, bcap, vol, dtype, loss)

        # batches solve sequentially: concurrent XLA compiles thrash on
        # small hosts (XLA's own compile parallelism saturates the
        # cores), and the persistent compilation cache already removes
        # repeat-compile cost
        dones = [solve_batch(b) for b in batches]
        for batch, done in zip(batches, dones):
            with self.span("flow.finish"):
                for row, i in enumerate(batch):
                    out[i] = self._finish(epochs[i], done[row])
        self.now = max([self.now] + out)
        return out

    # --------------------------------------------- dynamic-segment solve

    def segment_rates_many(self, problems) -> List[float]:
        """Batched device override of ``LinkMap.segment_rates_many``.

        Same contract as the numpy fallback (one ``(link_sets, loss)``
        problem per dynamic segment, OWN flow last; returns the own
        flow's loss-corrected rate), but every problem becomes one vmap
        lane: problems are bucketed by padded (F, H) shape through the
        same ``_plan_batches`` planner as the epoch solver and solved
        in one jitted call per batch, in float64 under the
        ``SEG_TOL``/``SEG_ROUNDS`` regime that mirrors the numpy
        oracle's filling (matches it to <= 1e-6 relative — only
        reduction-order rounding differs).
        """
        out = [0.0] * len(problems)
        if not problems:
            return out
        dtype = np.float64
        self.solve_dtype = dtype
        cap = self._cap_ext(dtype)
        sentinel = len(self.cap)
        shapes = {}
        for i, (sets, _) in enumerate(problems):
            f, h = len(sets), max(len(ls) for ls in sets)
            shapes[i] = (_bucket(f, self.F_BUCKET_MIN),
                         _bucket(h, self.H_BUCKET_MIN))
        batches = self._plan_batches(problems, list(range(len(problems))),
                                     shapes)
        solve = _seg_solver()
        for batch in batches:
            f_pad = max(shapes[i][0] for i in batch)
            h_pad = max(shapes[i][1] for i in batch)
            nb = len(batch)
            fl = np.full((nb, f_pad, h_pad), sentinel, np.int32)
            act = np.zeros((nb, f_pad), dtype)
            own = np.zeros(nb, np.int32)
            lrows = np.zeros((nb, 4, f_pad), dtype)
            for r, i in enumerate(batch):
                sets, lp = problems[i]
                n = len(sets)
                lens = np.fromiter((len(ls) for ls in sets), np.int64, n)
                total = int(lens.sum())
                flat = np.fromiter((l for ls in sets for l in ls),
                                   np.int32, total)
                rows = np.repeat(np.arange(n), lens)
                cols = np.arange(total) - np.repeat(
                    np.cumsum(lens) - lens, lens)
                fl[r, rows, cols] = flat
                act[r, :n] = 1.0
                own[r] = n - 1
                if lp is not None:
                    lrows[r, :, n - 1] = (lp.q, lp.wsq, lp.wnd,
                                          1.0 if lp.ecn else 0.0)
            with self.span("flow.dispatch"):
                t0 = time.perf_counter()
                with jax.enable_x64(True):
                    vals = np.asarray(solve(
                        jnp.asarray(fl), jnp.asarray(act),
                        jnp.asarray(own), jnp.asarray(cap),
                        tuple(jnp.asarray(lrows[:, k]) for k in range(4))))
                dt = time.perf_counter() - t0
            with _STATS_LOCK:
                SOLVE_STATS["solve_s"] += dt
                SOLVE_STATS["calls"] += 1
                SOLVE_STATS["shapes"].add(tuple(fl.shape))
            for r, i in enumerate(batch):
                out[i] = float(vals[r])
        return out
