"""Plain reference of the flow model the benchmark checks against.

Written from the model's stated semantics, importing nothing of the
program: the fat-tree of the configuration, shortest-path routing with
the ECMP pick ``key mod |candidates|`` over a node's ports in wiring
order, a gleam multicast as ONE fluid flow over the union of its
source->member paths, max-min fair rates by progressive filling
re-solved at every completion, per-receiver delivery = completion +
path latency, sender CQE = last delivery + the longest return
propagation.  Loss folds in the expected-value go-back-N model.  The
constants of the model (wire overheads, go-back-N window and RTO,
calibration factors) are stated in the configuration's ``model``
block.

Every arithmetic result of a solve is rounded to ``epoch_dtype``;
float64 is the reference, a narrower type is the control.

    ref = Reference(config)
    ans, work = ref.run_pass(traffic)      # answers like sut.answers
"""
from __future__ import annotations

import math
from collections import deque
from typing import Dict, List, Sequence, Tuple

import numpy as np

INF = float("inf")


def _rounder(dtype):
    if dtype is None or np.dtype(dtype) == np.float64:
        return lambda x: x
    return lambda x: np.asarray(x, np.float64).astype(dtype).astype(
        np.float64)


class Fabric:
    """The configuration's three-tier fat-tree: hosts, leaves, one agg
    per plane per pod, one core per plane; capacity 1:1 at each tier.
    ``adj[node]`` lists neighbours in port (wiring) order."""

    def __init__(self, f: dict):
        bw = f["host_gbps"] * 1e9 / 8.0
        d = f["link_delay_s"]
        hpl, lpp, app = (f["hosts_per_leaf"], f["leaves_per_pod"],
                         f["aggs_per_pod"])
        leaf_up = hpl * bw / app
        agg_up = lpp * leaf_up
        self.adj: Dict[str, List[str]] = {}
        self.cap: Dict[Tuple[str, str], float] = {}
        self.delay: Dict[Tuple[str, str], float] = {}
        self.leaf_of: Dict[str, str] = {}
        self.hosts: List[str] = []
        self.switches: set = set()

        def node(n, switch=True):
            self.adj[n] = []
            if switch:
                self.switches.add(n)

        def connect(a, b, c):
            self.adj[a].append(b)
            self.adj[b].append(a)
            self.cap[(a, b)] = self.cap[(b, a)] = c
            self.delay[(a, b)] = self.delay[(b, a)] = d

        for j in range(app):
            node(f"C{j}")
        for p in range(f["n_pods"]):
            for j in range(app):
                node(f"A{p}.{j}")
            for l in range(lpp):
                leaf = f"L{p}.{l}"
                node(leaf)
                for h in range(hpl):
                    hn = f"h{p}.{l}.{h}"
                    node(hn, switch=False)
                    self.hosts.append(hn)
                    self.leaf_of[hn] = leaf
                    connect(hn, leaf, bw)
                for j in range(app):
                    connect(leaf, f"A{p}.{j}", leaf_up)
            for j in range(app):
                connect(f"A{p}.{j}", f"C{j}", agg_up)
        self._sw_dist: Dict[str, Dict[str, int]] = {}
        self._paths: Dict[tuple, tuple] = {}

    # ------------------------------------------------------------ routing

    def _switch_dist(self, leaf: str) -> Dict[str, int]:
        """Hop counts from every switch to ``leaf`` (hosts have one
        link, so no shortest path between switches crosses one)."""
        dist = self._sw_dist.get(leaf)
        if dist is None:
            dist = {leaf: 0}
            q = deque([leaf])
            sw = self.switches
            while q:
                n = q.popleft()
                dn = dist[n] + 1
                for m in self.adj[n]:
                    if m in sw and m not in dist:
                        dist[m] = dn
                        q.append(m)
            self._sw_dist[leaf] = dist
        return dist

    def path(self, src: str, dst: str, key: int) -> tuple:
        """Directed links of the ECMP path from ``src`` to ``dst``."""
        ck = (src, dst, key)
        res = self._paths.get(ck)
        if res is not None:
            return res
        sw = self._switch_dist(self.leaf_of[dst])

        def d(n):
            if n == dst:
                return 0
            return sw[n] + 1 if n in sw else None  # a host: never nearer

        out = []
        node, here = src, d(self.leaf_of[src]) + 1
        while node != dst:
            cands = [m for m in self.adj[node] if d(m) == here - 1]
            nxt = cands[key % len(cands)]
            out.append((node, nxt))
            node, here = nxt, here - 1
        res = self._paths[ck] = tuple(out)
        return res

    def latency(self, links, seg_wire: float) -> Tuple[float, float]:
        """(delivery latency, return propagation) along ``links``:
        every hop's propagation plus one segment's store-and-forward at
        each hop after the first."""
        prop = 0.0
        for l in links:
            prop += self.delay[l]
        sf = 0.0
        for l in links[1:]:
            sf += seg_wire / self.cap[l]
        return prop + sf, prop


class Reference:
    def __init__(self, config: dict, epoch_dtype=np.float64):
        self.fab = Fabric(config["fabric"])
        m = config["model"]
        self.m = m
        self.mtu, self.hdr = m["mtu"], m["hdr"]
        self.r_ep = _rounder(epoch_dtype)
        self._ids: Dict[tuple, int] = {}
        self._caps: List[float] = []

    @property
    def n_links(self) -> int:
        """Directed links of the fabric."""
        return len(self.fab.cap)

    # ------------------------------------------------------------- basics

    def wire(self, n: int) -> int:
        return n + max(1, math.ceil(n / self.mtu)) * self.hdr

    def _lid(self, link) -> int:
        i = self._ids.get(link)
        if i is None:
            i = self._ids[link] = len(self._caps)
            self._caps.append(self.fab.cap[link])
        return i

    def _capvec(self) -> np.ndarray:
        return np.asarray(self._caps, np.float64)

    def tree(self, src, members, key):
        links = set()
        for m in members:
            if m != src:
                links.update(self.fab.path(src, m, key))
        return frozenset(links)

    def loss(self, links, nbytes: int, rtt: float, p: float):
        """Expected-value go-back-N inputs (q, wsq, wnd, tail), or None
        for a flow the loss model leaves alone."""
        m = self.m
        hops = float(sum(1 for a, _ in links if a in self.fab.switches))
        q = 1.0 - (1.0 - p) ** hops if p > 0.0 and hops > 0.0 else 0.0
        if q <= 0.0 or not links:
            return None
        pkt_wire = float(self.wire(min(nbytes, self.mtu)))
        cap_min = min(self.fab.cap[l] for l in links)
        bdp_link = cap_min * rtt / pkt_wire
        merge = 1.0 + m["gbn_merge_cal"] * q * bdp_link * (1.0 - 1.0 / hops) \
            if hops > 1.0 else 1.0
        wsq = (m["gbn_replay_cal"] / merge) ** 2 * (rtt / pkt_wire) * bdp_link
        n_pkts = float(max(1, math.ceil(nbytes / self.mtu)))
        tail = m["rto_s"] * (m["gbn_rto_cal"] * n_pkts * q * p + q)
        return (q, wsq, float(m["window_pkts"]), tail)

    @staticmethod
    def _gbn(rate, lp):
        q, wsq, wnd, _ = lp
        w = min(math.sqrt(max(rate * wsq, 0.0)), wnd)
        return min(max((1.0 - q) / max(1.0 - q + q * w, 1e-30), 1e-9), 1.0)

    # ---------------------------------------------------------- max-min

    def maxmin(self, sets: Sequence[np.ndarray], cap: np.ndarray, rnd,
               work: list) -> np.ndarray:
        """Progressive filling: every round, each link's remaining
        capacity is shared equally by its unfrozen flows; the flows at
        the tightest share freeze at it."""
        n = len(sets)
        lens = np.array([len(s) for s in sets], np.int64)
        flat = np.concatenate(sets) if n else np.zeros(0, np.int64)
        row = np.repeat(np.arange(n), lens)
        starts = np.cumsum(lens) - lens
        work.append((int(flat.size), n))
        rates = np.zeros(n)
        frozen = lens == 0
        capr = cap.copy()
        while not frozen.all():
            live = ~frozen[row]
            cnt = np.bincount(flat[live], minlength=len(cap)).astype(float)
            share = np.where(cnt > 0, rnd(capr / np.maximum(cnt, 1.0)), INF)
            limit = np.minimum.reduceat(share[flat], starts)
            limit[frozen] = INF
            b = limit.min()
            newly = ~frozen & (limit <= b * (1.0 + 1e-12))
            rates[newly] = b
            used = np.bincount(flat[newly[row]], minlength=len(cap)) * b
            capr = np.maximum(rnd(capr - used), 0.0)
            frozen |= newly
        return rates

    def fluid(self, flows: List[tuple], work: list) -> np.ndarray:
        """Completion time of every (link ids, volume, loss) flow: the
        fluid event loop, re-solving max-min at each completion."""
        r = self.r_ep
        cap = r(self._capvec())
        n = len(flows)
        rem = r(np.array([v for _, v, _ in flows], np.float64))
        vol = rem.copy()
        done = np.zeros(n)
        active = np.ones(n, bool)
        t = 0.0
        while active.any():
            idx = np.flatnonzero(active)
            rates = self.maxmin([flows[i][0] for i in idx], cap, r, work)
            eff = rates.copy()
            for k, i in enumerate(idx):
                lp = flows[i][2]
                if lp is not None:
                    eff[k] = r(rates[k] * self._gbn(rates[k], lp))
            dt = float(np.min(r(rem[idx] / eff)))
            t = float(r(t + dt))
            rem[idx] = r(rem[idx] - r(eff * dt))
            fin = rem[idx] <= vol[idx] * 1e-9
            done[idx[fin]] = t
            rem[idx[fin]] = 0.0
            active[idx[fin]] = False
        for i, (_, _, lp) in enumerate(flows):
            if lp is not None:
                done[i] += lp[3]
        return done

    # ------------------------------------------------------------ staging

    def _static(self, d: dict, p: float):
        fab = self.fab
        nb = d["nbytes"]
        seg = self.wire(min(nb, self.mtu))
        if d["op"] == "unicast":
            a, b = d["members"]
            links = fab.path(a, b, d["key"])
            lat, prop = fab.latency(links, seg)
            return {"links": frozenset(links), "vol": float(self.wire(nb)),
                    "deliver": {b: lat}, "back": prop,
                    "loss": self.loss(links, nb, 2.0 * prop, p)}
        members = d["members"]
        src = members[0]
        deliver, back = {}, 0.0
        for m in members[1:]:
            lat, prop = fab.latency(fab.path(src, m, d["key"]), seg)
            deliver[m] = lat
            back = max(back, prop)
        links = self.tree(src, members, d["key"])
        return {"links": links, "vol": float(self.wire(nb)),
                "deliver": deliver, "back": back,
                "loss": self.loss(links, nb, 2.0 * back, p)}

    def run_scenario(self, ops: List[dict], p: float, work: list):
        ents = [self._static(d, p) for d in ops]
        flows = [(np.array(sorted(self._lid(l) for l in e["links"]),
                           np.int64), e["vol"], e["loss"]) for e in ents]
        done = self.fluid(flows, work)
        out = []
        for i, e in enumerate(ents):
            t = float(done[i])
            deliver = {x: t + lat for x, lat in e["deliver"].items()}
            cqe = max(deliver.values()) + e["back"]
            out.append({"deliver": deliver, "cqe": cqe, "error": ""})
        return out

    def run_pass(self, traffic: dict):
        """Answers for every scenario of a pass, and the solves made:
        a list of (incidence non-zeros, flows) per max-min solve."""
        work: list = []
        ans = [self.run_scenario(ops, traffic["loss_rate"], work)
               for ops in traffic["scenarios"]]
        return ans, work
