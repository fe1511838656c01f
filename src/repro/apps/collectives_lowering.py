"""Lower LM training/serving steps to the Workload IR.

Everything here is *analytic* config math — no jax, no compiled HLO:
collective sizes are derived from the ``ArchConfig`` tensor shapes
(the same shapes ``models.model.model_defs`` declares; the parameter
count is cross-checked against ``blocks.count_params`` in
``tests/test_apps.py``) and a ``MeshShape``.  The sizing rules, per
phase (bf16 activations = 2 B/elem, f32 grads = 4 B/elem):

- **tp-allreduce** — every mixer (attn / mamba) and every dense FFN
  sublayer ends in a row-parallel projection whose partial sums are
  all-reduced over the ``model`` axis: one ``(batch, seq, d_model)``
  activation per sublayer unit, doubled for the backward pass in
  training.  MoE FFN sublayers count here only in *etp* mode (experts
  not divisible by the model axis — ``models.moe.expert_mode``);
- **moe-alltoall** — in *ep* mode on the model axis (a mesh with no
  expert axis) each MoE sublayer dispatches ``top_k`` routed copies of
  every token and combines them back: an all-to-all, lowered as a
  **unicast fan-mesh** (one GroupOp per ordered rank pair — all pairs
  contend concurrently, which is what an a2a does to the fabric).  Per
  pair per a2a: ``tokens/ep * top_k * d_model * 2 / ep`` bytes;
- **moe-dispatch / moe-combine** — on a mesh with an ``expert`` axis
  (carved from ``data``), DeepSeek-V3's published path (arXiv:2412.19437
  sec. 3.2): a seeded node-limited router sends each token to the nodes
  holding its experts, across the fabric once per target node, to the
  GPU with the same in-node index (NVLink forwards it from there;
  traffic inside a node stays off the fabric).  Dispatch carries FP8
  activations with float32 1x128-tile scales, combine BF16 ones back
  (``dispatch_token_bytes``, ``combine_token_bytes``).  Transport
  ``multiunicast`` is one unicast per (source GPU, target node);
  ``gleam`` one multicast per (source GPU, set of target nodes);
- **pp-boundary** — each microbatch crosses a pipeline cut twice
  (activations fwd, activation-grads bwd): ``micro * seq * d_model *
  2`` bytes per crossing, sharded over the model axis;
- **dp-gradsync** — the optimizer all-reduces f32 gradients of this
  rank's parameter shard across the ``data`` axis:
  ``4 * n_params / (model * pipe)`` bytes; with an expert axis, a
  stage's own parameters (``stage_params``): the non-expert ones over
  all ``data`` ranks, an EP rank's experts over the ``data / expert``
  ranks that hold the same experts;
- **weights** — replica scale-out broadcasts each rank's bf16
  parameter shard: ``2 * n_params / model`` bytes (a *bcast*, Gleam's
  native op);
- **kv-replicate / ckpt-write** — storage-style ``write`` ops sized by
  ``kv_cache_bytes`` / the f32 parameter shard.

Chip placement is linear: chip ``(pipe p, data d, model m)`` maps to
``hosts[(p*data + d)*model + m]`` — model-axis neighbours are adjacent
hosts (the bandwidth-hungriest axis gets the topologically closest
peers, the standard TPU/GPU placement).  ``rail_hosts`` orders a
rail-optimised fabric's NICs so that consecutive chips are the GPUs of
one node, each on its own plane.
"""
from __future__ import annotations

import dataclasses
import math
import os
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional, Sequence, Tuple

import numpy as np

from repro.configs.base import ArchConfig, Sublayer
from repro.core.workload import GroupOp, Workload

__all__ = [
    "MeshShape", "default_hosts", "param_count", "kv_cache_bytes",
    "tp_allreduce_bytes", "moe_a2a_pair_bytes", "pp_boundary_bytes",
    "moe_uses_ep", "train_step_workload", "weight_bcast_workload",
    "prefill_comm_bytes", "decode_comm_bytes", "rail_hosts",
    "stage_layers", "stage_params", "node_limited_sets",
    "route_ep_groups", "node_token_counts", "dispatch_token_bytes",
    "combine_token_bytes", "moe_ep_ops",
]

FP8 = 1                      # dispatched activation bytes per element
BF16 = 2                     # activation / weight bytes per element
F32 = 4                      # gradient / optimizer bytes per element
FP8_TILE = 128               # elements per float32 scale of an FP8 tile


@dataclasses.dataclass(frozen=True)
class MeshShape:
    """Logical chip grid: ``pipe`` stages x ``data`` replicas x
    ``model`` (tensor-parallel) ranks.  ``expert`` ranks of expert
    parallelism are carved from ``data``: data rank ``r * expert + e``
    is EP rank ``e`` of EP group ``r``.  Plain data — serializes into
    ``Workload.meta`` so a staged app workload is replayable."""

    data: int = 1
    model: int = 1
    pipe: int = 1
    expert: int = 1

    def __post_init__(self):
        if min(self.data, self.model, self.pipe, self.expert) < 1:
            raise ValueError(f"mesh axes must be >= 1, got {self}")
        if self.data % self.expert:
            raise ValueError(f"expert {self.expert} does not divide "
                             f"data {self.data}")

    @property
    def n_chips(self) -> int:
        return self.data * self.model * self.pipe

    def host(self, hosts: Sequence[str], p: int, d: int, m: int) -> str:
        return hosts[(p * self.data + d) * self.model + m]

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        if self.expert == 1:
            del d["expert"]         # meshes without one serialize as before
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "MeshShape":
        return cls(**d)


def default_hosts(n: int) -> List[str]:
    """The flat ``h0..h{n-1}`` naming of ``fattree.testbed``."""
    return [f"h{i}" for i in range(n)]


def rail_hosts(hosts: Sequence[str], gpus_per_node: int) -> List[str]:
    """Chip order on a rail-optimised fabric.  ``hosts`` lists the
    NICs plane by plane (``fattree.fat_tree``'s wiring order with one
    pod per plane), and GPU ``i`` of node ``n`` is host ``n`` of plane
    ``i``: chip ``n * gpus_per_node + i`` of the linear placement."""
    if len(hosts) % gpus_per_node:
        raise ValueError(f"{len(hosts)} hosts do not split into "
                         f"{gpus_per_node} planes")
    n_nodes = len(hosts) // gpus_per_node
    return [hosts[i * n_nodes + n] for n in range(n_nodes)
            for i in range(gpus_per_node)]


# ------------------------------------------------------ parameter math

def _attn_params(cfg: ArchConfig) -> int:
    """Mirror of ``model._attn_defs`` (+ the sublayer norm); MLA as
    DeepSeek-V3's modelling code lays it out."""
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    if cfg.kv_lora_rank:
        return d + _mla_params(cfg)
    n = d + d * h * hd + 2 * d * kv * hd + h * hd * d
    if cfg.qkv_bias:
        n += h * hd + 2 * kv * hd
    return n


def _mla_params(cfg: ArchConfig) -> int:
    """Multi-head latent attention: q down (``q_lora_rank``), its norm
    and q up to every head's nope + rope dims; kv down to the latent plus
    the shared rope key, the latent's norm, kv up to every head's nope
    key and value; the output projection."""
    d, h, r = cfg.d_model, cfg.n_heads, cfg.q_lora_rank
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    q = d * r + r + r * h * qk
    c = cfg.kv_lora_rank
    kv = (d * (c + cfg.qk_rope_head_dim) + c
          + c * h * (cfg.qk_nope_head_dim + cfg.v_head_dim))
    return q + kv + h * cfg.v_head_dim * d


def _ssm_params(cfg: ArchConfig) -> int:
    """Mirror of ``ssm.ssm_defs`` (+ the sublayer norm)."""
    d = cfg.d_model
    d_in = cfg.ssm_expand * d
    h = d_in // cfg.ssm_headdim
    n, k = cfg.ssm_state, cfg.ssm_conv
    return (d                               # norm
            + 2 * d * d_in                  # wz, wx
            + 2 * d * n                     # wB, wC
            + d * h + 3 * h                 # wdt, dt_bias, A_log, D
            + k * d_in + 2 * k * n          # conv_x, conv_B, conv_C
            + d_in + d_in * d)              # gnorm, wo


def _ffn_params(cfg: ArchConfig, kind: Optional[str]) -> int:
    """Mirror of ``model._ffn_defs`` / ``moe.moe_defs``."""
    d = cfg.d_model
    if kind is None:
        return 0
    if kind == "mlp":
        return d + 3 * d * cfg.d_ff
    if kind == "dense":                     # a leading dense layer's
        return d + 3 * d * cfg.dense_d_ff
    if kind == "moe":
        e, f = cfg.n_experts, cfg.moe_d_ff
        return (d + d * e + (e if cfg.router_bias else 0)
                + 3 * e * d * f + cfg.n_shared_experts * 3 * d * f)
    raise ValueError(kind)


def _routed_params(cfg: ArchConfig, kind: Optional[str]) -> int:
    """The routed experts' share of ``_ffn_params``."""
    return 3 * cfg.n_experts * cfg.d_model * cfg.moe_d_ff \
        if kind == "moe" else 0


def _layer_params(cfg: ArchConfig, mixer: str, ffn: Optional[str]) -> int:
    if mixer == "attn":
        n = _attn_params(cfg)
    elif mixer == "mamba":
        n = _ssm_params(cfg)
    else:
        raise ValueError(mixer)
    return n + _ffn_params(cfg, ffn)


def _layers(cfg: ArchConfig, mtp: bool = True) -> List[Sublayer]:
    """Every decoder layer in order: the leading dense ones, the
    pattern's repeats, then (``mtp``) each MTP module's block."""
    return ([("attn", "dense")] * cfg.n_dense_layers
            + list(cfg.pattern) * cfg.n_blocks
            + (list(cfg.pattern) * cfg.n_mtp_layers if mtp else []))


def _mtp_extra(cfg: ArchConfig) -> int:
    """An MTP module beyond its block: the norms of the hidden state
    and of the next token's embedding, their 2d -> d projection and
    the module's output norm (the embedding and the head are the
    main model's)."""
    d = cfg.d_model
    return 2 * d + 2 * d * d + d


def param_count(cfg: ArchConfig) -> int:
    """Total parameters, matching ``count_params(model_defs(cfg))``
    exactly for decoder-only archs the model layer builds (the traffic
    plane's scope); MTP modules included."""
    if cfg.enc_layers > 0 or cfg.vision_prefix > 0:
        raise ValueError(
            f"{cfg.name}: encoder/vision frontends are outside the "
            "traffic-plane lowering (decoder-only archs only)")
    d, v = cfg.d_model, cfg.vocab_size
    body = sum(_layer_params(cfg, m, f) for m, f in _layers(cfg))
    return v * d + body + cfg.n_mtp_layers * _mtp_extra(cfg) + d + d * v


def stage_layers(cfg: ArchConfig, pipe: int) -> List[List[Sublayer]]:
    """Each pipeline stage's layers: ``_layers`` cut into ``pipe``
    contiguous runs, stage ``p`` holding ``[p*L//pipe, (p+1)*L//pipe)``
    (DeepSeek-V3's 61 layers and its MTP block over 16 stages: 3 or 4
    each, the three dense ones on stage 0)."""
    layers = _layers(cfg)
    n = len(layers)
    return [layers[p * n // pipe:(p + 1) * n // pipe] for p in range(pipe)]


def stage_params(cfg: ArchConfig, pipe: int) -> List[Tuple[int, int]]:
    """Per stage, (parameters outside the routed experts, routed expert
    parameters): its layers', the embedding on the first stage, and the
    final norm, head and MTP extras on the last."""
    d, v = cfg.d_model, cfg.vocab_size
    out = []
    for p, layers in enumerate(stage_layers(cfg, pipe)):
        routed = sum(_routed_params(cfg, f) for _, f in layers)
        dense = sum(_layer_params(cfg, m, f) for m, f in layers) - routed
        if p == 0:
            dense += v * d
        if p == pipe - 1:
            dense += d + d * v + cfg.n_mtp_layers * _mtp_extra(cfg)
        out.append((dense, routed))
    return out


def kv_cache_bytes(cfg: ArchConfig, seq: int) -> int:
    """Decode-state bytes of ONE sequence: bf16 K+V per attention
    sublayer, f32 SSD recurrent state + conv tail per mamba sublayer
    (sequence-length-free — the hybrid archs' point)."""
    attn = mamba = 0
    for mixer, _ in _layers(cfg, mtp=False):
        if mixer == "attn":
            attn += 1
        elif mixer == "mamba":
            mamba += 1
    d_in = cfg.ssm_expand * cfg.d_model
    h = d_in // max(cfg.ssm_headdim, 1)
    if cfg.kv_lora_rank:        # MLA caches the latent and the rope key
        per_attn = seq * (cfg.kv_lora_rank + cfg.qk_rope_head_dim) * BF16
    else:
        per_attn = 2 * seq * cfg.n_kv_heads * cfg.hd * BF16
    per_mamba = (h * cfg.ssm_headdim * cfg.ssm_state
                 + (cfg.ssm_conv - 1) * d_in) * F32
    return attn * per_attn + mamba * per_mamba


# ------------------------------------------------------ collective math

def moe_uses_ep(cfg: ArchConfig, ep: int) -> bool:
    """Expert-parallel iff the experts divide over ``ep`` > 1 ranks:
    the expert axis, or the model axis on a mesh without one (the
    planner rule of ``models.moe.expert_mode``, reimplemented to stay
    jax-free)."""
    return bool(cfg.n_experts) and ep > 1 and cfg.n_experts % ep == 0


def _sublayer_units(cfg: ArchConfig, tp: int) -> int:
    """Row-parallel reductions per block: one per mixer, one per dense
    FFN; MoE FFNs reduce via the a2a combine in ep mode."""
    ep = moe_uses_ep(cfg, tp)
    units = 0
    for _, ffn in cfg.pattern:
        units += 1                                  # the mixer
        if ffn is not None and not (ffn == "moe" and ep):
            units += 1
    return units


# ------------------------------------------- node-limited expert routing

def node_limited_sets(scores: np.ndarray, n_group: int, topk_group: int,
                      top_k: int) -> np.ndarray:
    """DeepSeek-V3's group-limited routing (arXiv:2412.19437 sec.
    2.1.2, ``topk_method`` noaux_tc) of tokens with affinity
    ``scores`` (..., n_experts): rank the ``n_group`` equal groups by
    the sum of each group's top ``top_k // topk_group`` scores (in
    float64, smallest first), keep the best ``topk_group``, and take
    the ``top_k`` best experts inside them; every tie goes to the lower
    index.  Returns each token's groups holding a chosen expert, as a
    bit mask (bit ``g`` = group ``g``)."""
    *lead, n_exp = scores.shape
    per = n_exp // n_group
    x = scores.reshape(-1, n_group, per)
    k = top_k // topk_group
    best = np.partition(x, per - k, axis=-1)[..., per - k:]
    gs = np.sort(best, axis=-1).astype(np.float64).sum(-1)
    kept = np.sort(np.argsort(-gs, axis=-1, kind="stable")[:, :topk_group],
                   axis=-1)                       # ascending group id
    vals = np.take_along_axis(x, kept[..., None], axis=1).reshape(len(x), -1)
    m = vals.shape[1]
    tau = np.partition(vals, m - top_k, axis=-1)[:, m - top_k, None]
    # a kept group is hit iff its largest score is at least the top_k-th
    # largest kept score; where more than top_k reach it, rank by index
    hit = np.take_along_axis(best.max(-1), kept, axis=1) >= tau
    for t in np.flatnonzero((vals >= tau).sum(-1) > top_k):
        chosen = np.lexsort((np.arange(m), -vals[t]))[:top_k]
        hit[t] = np.isin(np.arange(topk_group), chosen // per)
    mask = (hit * (np.int64(1) << kept)).sum(-1)
    return mask.reshape(lead)


def route_ep_groups(cfg: ArchConfig, ep: int, tokens: int,
                    seeds: Sequence) -> List[np.ndarray]:
    """One micro-batch of each EP group named by a seed: every rank's
    ``tokens`` tokens get i.i.d. uniform affinity scores (a float32
    draw per node, from the node's stream of ``SeedSequence(seed)``)
    and are routed by ``node_limited_sets``, the EP group's
    ``cfg.n_group`` nodes being the routing groups.  Per seed, the
    (ep, 2**n_group) counts of each rank's tokens per target-node set.
    Nodes route on a thread pool (numpy releases the GIL); the result
    does not depend on it."""
    g = cfg.n_group
    if not g or ep % g or cfg.n_experts % ep:
        raise ValueError(f"{cfg.name}: node-limited routing needs n_group "
                         f"({g}) nodes dividing ep {ep} and {ep} ranks "
                         f"dividing {cfg.n_experts} experts")
    gpn = ep // g
    streams = [ss for seed in seeds
               for ss in np.random.SeedSequence(seed).spawn(g)]

    def node(ss):
        scores = np.random.default_rng(ss).random(
            (gpn, tokens, cfg.n_experts), np.float32)
        sets = node_limited_sets(scores, g, cfg.topk_group, cfg.top_k)
        return np.bincount((sets + (np.arange(gpn)[:, None] << g)).ravel(),
                           minlength=gpn << g).reshape(gpn, 1 << g)

    with ThreadPoolExecutor(min(len(streams), os.cpu_count() or 1)) as ex:
        rows = list(ex.map(node, streams))
    return [np.concatenate(rows[i * g:(i + 1) * g])
            for i in range(len(seeds))]


def node_token_counts(hist: np.ndarray) -> np.ndarray:
    """(ranks, n_nodes) tokens each rank sends to each node, from the
    per-node-set counts of ``route_ep_groups``."""
    n = hist.shape[1].bit_length() - 1
    bits = (np.arange(1 << n)[:, None] >> np.arange(n)) & 1
    return hist @ bits


def dispatch_token_bytes(cfg: ArchConfig) -> int:
    """A dispatched token: FP8 activations plus a float32 scale per
    1x128 tile (DeepSeek-V3: 7,168 + 4 x 56 = 7,392 B)."""
    return cfg.d_model * FP8 + F32 * math.ceil(cfg.d_model / FP8_TILE)


def combine_token_bytes(cfg: ArchConfig) -> int:
    """A combined token: BF16 activations (DeepSeek-V3: 14,336 B)."""
    return cfg.d_model * BF16


def moe_ep_ops(wl: Workload, cfg: ArchConfig, hist: np.ndarray,
               group: Sequence[str], scale: int, transport: str,
               chunks: int = 8) -> None:
    """Add one EP group's dispatch and combine to ``wl``.  ``group``
    holds the hosts of EP ranks ``0..ep-1``; rank ``e`` is GPU
    ``e % gpn`` of node ``e // gpn``, ``gpn = ep / len(nodes)``.  A
    token crosses the fabric once per target node other than its own,
    to the same-index GPU there; ``scale`` multiplies every size (MoE
    layers x micro-batches x passes).

    ``multiunicast``: one unicast per (rank, target node) of
    ``tokens * dispatch_token_bytes``.  Any other transport: one
    multicast per (rank, set of remote target nodes) carrying the
    tokens of that set once.  Combine is the reverse unicast per
    (rank, target node) at ``combine_token_bytes`` a token."""
    ep = len(group)
    n = hist.shape[1].bit_length() - 1
    gpn = ep // n
    d_tok, c_tok = dispatch_token_bytes(cfg), combine_token_bytes(cfg)
    counts = node_token_counts(hist)
    peer = [[group[b * gpn + e % gpn] for b in range(n)] for e in range(ep)]
    for e in range(ep):
        own = e // gpn
        if transport == "multiunicast":
            for b in range(n):
                if b != own and counts[e, b]:
                    wl.unicast(group[e], peer[e][b],
                               int(counts[e, b]) * d_tok * scale,
                               phase="moe-dispatch")
            continue
        remote = np.bincount(np.arange(1 << n) & ~(1 << own),
                             weights=hist[e], minlength=1 << n)
        for m in np.flatnonzero(remote[1:]) + 1:
            members = [group[e]] + [peer[e][b] for b in range(n)
                                    if m >> b & 1]
            wl.bcast(members, int(remote[m]) * d_tok * scale,
                     phase="moe-dispatch", transport=transport,
                     chunks=chunks)
    for e in range(ep):
        for b in range(n):
            if b != e // gpn and counts[e, b]:
                wl.unicast(peer[e][b], group[e],
                           int(counts[e, b]) * c_tok * scale,
                           phase="moe-combine")


def _moe_sublayers(cfg: ArchConfig) -> int:
    return sum(1 for _, f in cfg.pattern if f == "moe")


def tp_allreduce_bytes(cfg: ArchConfig, seq: int, batch: int, tp: int,
                       kind: str = "train") -> int:
    """Total activation all-reduce bytes per TP group per step (the
    whole model; divide by ``pipe`` for a stage's share)."""
    act = batch * seq * cfg.d_model * BF16
    passes = 2 if kind == "train" else 1            # bwd grad allreduce
    units = _sublayer_units(cfg, tp) * cfg.n_blocks \
        + 2 * cfg.n_dense_layers                    # mixer + dense FFN
    return units * act * passes


def moe_a2a_pair_bytes(cfg: ArchConfig, seq: int, batch: int, ep: int,
                       kind: str = "train") -> int:
    """Total bytes one ordered rank pair carries per step across every
    MoE sublayer's dispatch+combine (x2 again for the backward)."""
    tokens = batch * seq
    per_a2a = tokens * cfg.top_k * cfg.d_model * BF16 // (ep * ep)
    n_a2a = _moe_sublayers(cfg) * cfg.n_blocks * 2  # dispatch + combine
    if kind == "train":
        n_a2a *= 2
    return per_a2a * n_a2a


def pp_boundary_bytes(cfg: ArchConfig, seq: int, micro_batch: int) -> int:
    """One microbatch's activation tensor at one pipeline cut (one
    direction, full hidden — divide by ``model`` for a rank's shard)."""
    return micro_batch * seq * cfg.d_model * BF16


def prefill_comm_bytes(cfg: ArchConfig, prompt_len: int, tp: int) -> int:
    """TP all-reduce bytes to prefill one request's prompt."""
    return tp_allreduce_bytes(cfg, prompt_len, 1, tp, kind="prefill")


def decode_comm_bytes(cfg: ArchConfig, n_tokens: int, tp: int) -> int:
    """TP all-reduce bytes to decode ``n_tokens`` (one token = one
    seq-1 activation; aggregated so a request is one GroupOp)."""
    return tp_allreduce_bytes(cfg, 1, n_tokens, tp, kind="decode")


# ----------------------------------------------------------- workloads

def train_step_workload(cfg: ArchConfig, mesh: MeshShape,
                        hosts: Optional[Sequence[str]] = None, *,
                        seq: int, batch: int, accum: int = 1,
                        transport: str = "gleam", chunks: int = 8,
                        include_ckpt: bool = False) -> Workload:
    """One training step as a phased ``Workload``.

    Phase order (each phase is barrier-separated in the application;
    ``apps.metrics.step_time`` sums phase maxima): tp-allreduce,
    moe-alltoall, pp-boundary, dp-gradsync[, ckpt-write]; with an
    expert axis, moe-dispatch and moe-combine in moe-alltoall's place
    (``_expert_parallel``).
    """
    if hosts is None:
        hosts = default_hosts(mesh.n_chips)
    if len(hosts) < mesh.n_chips:
        raise ValueError(f"need {mesh.n_chips} hosts, got {len(hosts)}")
    if batch % (mesh.data * max(accum, 1)) != 0:
        raise ValueError(
            f"batch {batch} not divisible by data {mesh.data} x "
            f"accum {accum}")
    if mesh.expert > 1:
        if mesh.model > 1:
            raise ValueError("an expert axis with tensor parallelism is "
                             "not lowered")
        if not moe_uses_ep(cfg, mesh.expert):
            raise ValueError(f"{cfg.name}: {cfg.n_experts} experts do not "
                             f"divide over expert {mesh.expert}")
    elif mesh.pipe > 1 and cfg.n_blocks % mesh.pipe != 0:
        raise ValueError(
            f"{cfg.name}: n_blocks {cfg.n_blocks} not divisible by "
            f"pipe {mesh.pipe}")
    b_shard = batch // mesh.data
    micro = b_shard // max(accum, 1)
    tp, dp, pp = mesh.model, mesh.data, mesh.pipe
    n_params = param_count(cfg)
    wl = Workload(
        f"{cfg.name}/train/{transport}",
        meta={"model": cfg.name, "mesh": mesh.to_dict(), "seq": seq,
              "batch": batch, "accum": accum, "kind": "train",
              "transport": transport})
    kw = dict(transport=transport, chunks=chunks)

    if tp > 1:
        nb = tp_allreduce_bytes(cfg, seq, b_shard, tp) // pp
        for p in range(pp):
            for d in range(dp):
                group = [mesh.host(hosts, p, d, m) for m in range(tp)]
                wl.allreduce(group, nb, phase="tp-allreduce", **kw)

    if mesh.expert > 1:
        _expert_parallel(wl, cfg, mesh, hosts, micro * seq, accum,
                         transport, chunks)
    elif moe_uses_ep(cfg, tp):
        nb = moe_a2a_pair_bytes(cfg, seq, b_shard, tp) // pp
        for p in range(pp):
            for d in range(dp):
                group = [mesh.host(hosts, p, d, m) for m in range(tp)]
                for src in group:
                    for dst in group:
                        if src != dst:
                            wl.unicast(src, dst, nb,
                                       phase="moe-alltoall")

    if pp > 1:
        # accum microbatches cross each cut fwd + bwd, per TP shard
        nb = pp_boundary_bytes(cfg, seq, micro) * accum * 2 // tp
        for p in range(pp - 1):
            for d in range(dp):
                for m in range(tp):
                    wl.unicast(mesh.host(hosts, p, d, m),
                               mesh.host(hosts, p + 1, d, m), nb,
                               phase="pp-boundary")

    if mesh.expert > 1:
        _zero1_gradsync(wl, cfg, mesh, hosts, kw)
    elif dp > 1:
        nb = F32 * n_params // (tp * pp)
        for p in range(pp):
            for m in range(tp):
                group = [mesh.host(hosts, p, d, m) for d in range(dp)]
                wl.allreduce(group, nb, phase="dp-gradsync", **kw)

    if include_ckpt and dp > 1:
        # rank (0, 0, m) snapshots its f32 shard to its data peers
        nb = F32 * n_params // (tp * pp)
        for m in range(tp):
            group = [mesh.host(hosts, 0, d, m) for d in range(dp)]
            wl.write(group, nb, phase="ckpt-write", **kw)

    if not wl.ops:
        raise ValueError(
            f"mesh {mesh} has a single chip: no fabric traffic to lower")
    return wl


def _expert_parallel(wl: Workload, cfg: ArchConfig, mesh: MeshShape,
                     hosts: Sequence[str], tokens: int, accum: int,
                     transport: str, chunks: int) -> None:
    """moe-dispatch and moe-combine of every stage's EP groups: one
    micro-batch of ``tokens`` tokens a rank routed per (stage, EP
    group) from seed 0, every size times the stage's MoE layers x
    ``accum`` micro-batches x 2 (forward, and backward at the forward's
    sizes)."""
    ep = mesh.expert
    jobs = [(p, r, sum(1 for _, f in layers if f == "moe"))
            for p, layers in enumerate(stage_layers(cfg, mesh.pipe))
            for r in range(mesh.data // ep)]
    jobs = [j for j in jobs if j[2]]
    hists = route_ep_groups(cfg, ep, tokens,
                            [(0, p, r) for p, r, _ in jobs])
    for (p, r, n_moe), hist in zip(jobs, hists):
        group = [mesh.host(hosts, p, r * ep + e, 0) for e in range(ep)]
        moe_ep_ops(wl, cfg, hist, group, n_moe * accum * 2, transport,
                   chunks)


def _zero1_gradsync(wl: Workload, cfg: ArchConfig, mesh: MeshShape,
                    hosts: Sequence[str], kw: dict) -> None:
    """dp-gradsync with an expert axis: a stage's f32 gradients outside
    the routed experts over all ``data`` ranks (``4 * dense``), and one
    EP rank's experts of the stage over the ``data / expert`` ranks
    holding the same experts (``4 * routed / expert``)."""
    dp, ep = mesh.data, mesh.expert
    for p, (dense, routed) in enumerate(stage_params(cfg, mesh.pipe)):
        wl.allreduce([mesh.host(hosts, p, d, 0) for d in range(dp)],
                     F32 * dense, phase="dp-gradsync", **kw)
        if dp // ep > 1 and routed:
            for e in range(ep):
                wl.allreduce([mesh.host(hosts, p, r * ep + e, 0)
                              for r in range(dp // ep)],
                             F32 * routed // ep, phase="dp-gradsync", **kw)


def weight_bcast_workload(cfg: ArchConfig, n_replicas: int, tp: int,
                          hosts: Optional[Sequence[str]] = None, *,
                          transport: str = "gleam",
                          chunks: int = 8) -> Workload:
    """Replica scale-out: each TP rank's bf16 weight shard broadcasts
    from replica 0 to every other replica (Gleam's native one-to-many;
    serving layout ``hosts[replica * tp + rank]``)."""
    if n_replicas < 2:
        raise ValueError("weight broadcast needs >= 2 replicas")
    if hosts is None:
        hosts = default_hosts(n_replicas * tp)
    nb = BF16 * param_count(cfg) // tp
    wl = Workload(
        f"{cfg.name}/weights/{transport}",
        meta={"model": cfg.name, "replicas": n_replicas, "tp": tp,
              "kind": "weights", "transport": transport})
    for m in range(tp):
        members = [hosts[r * tp + m] for r in range(n_replicas)]
        wl.bcast(members, nb, phase="weights", transport=transport,
                 chunks=chunks)
    return wl
