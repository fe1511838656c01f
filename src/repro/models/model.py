"""Model assembly: decoder-only / enc-dec / VLM / SSM / hybrid from one
generic repeating-pattern machine, with scan-over-layers and explicit
sharding (shard_map for the attention core and MoE; GSPMD elsewhere).

Decode-path attention uses split-KV: the cache is sharded over sequence,
each shard computes partial softmax statistics (m, l, acc), and a
many-to-one combine merges them — structurally the Gleam ACK-aggregation
tree (DESIGN.md §2.2/2.3).  The combine schedule is selectable
(psum | gleam_tree) via cfg.collective_schedule.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.blocks import (ParamDef, mlp_defs, rms_norm, rope,
                                 sinusoidal_positions, stack_defs, swiglu)

BATCH_AXES = ("pod", "data")


# ================================================================ defs

def _attn_defs(cfg: ArchConfig, cross: bool = False):
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    defs = {
        "norm": ParamDef((d,), ("norm",), init="ones"),
        "wq": ParamDef((d, h, hd), ("embed", "heads", None)),
        "wk": ParamDef((d, kv, hd), ("embed", "kv_heads", None)),
        "wv": ParamDef((d, kv, hd), ("embed", "kv_heads", None)),
        "wo": ParamDef((h, hd, d), ("heads", None, "embed")),
    }
    if cfg.qkv_bias:
        defs["bq"] = ParamDef((h, hd), ("heads", None), init="zeros")
        defs["bk"] = ParamDef((kv, hd), ("kv_heads", None), init="zeros")
        defs["bv"] = ParamDef((kv, hd), ("kv_heads", None), init="zeros")
    if cross:
        defs["xnorm"] = ParamDef((d,), ("norm",), init="ones")
        defs["xwq"] = ParamDef((d, h, hd), ("embed", "heads", None))
        defs["xwk"] = ParamDef((d, kv, hd), ("embed", "kv_heads", None))
        defs["xwv"] = ParamDef((d, kv, hd), ("embed", "kv_heads", None))
        defs["xwo"] = ParamDef((h, hd, d), ("heads", None, "embed"))
    return defs


def _ffn_defs(cfg: ArchConfig, kind):
    d = cfg.d_model
    if kind is None:
        return {}
    norm = {"norm": ParamDef((d,), ("norm",), init="ones")}
    if kind == "mlp":
        return {**norm, **mlp_defs(d, cfg.d_ff)}
    if kind == "moe":
        return {**norm, **moe_mod.moe_defs(cfg)}
    raise ValueError(kind)


def _sublayer_defs(cfg: ArchConfig, mixer, ffn, cross=False):
    if mixer == "attn":
        mdefs = _attn_defs(cfg, cross=cross)
    elif mixer == "mamba":
        mdefs = {"norm": ParamDef((cfg.d_model,), ("norm",), init="ones"),
                 **ssm_mod.ssm_defs(cfg)}
    else:
        raise ValueError(mixer)
    return {"mixer": mdefs, "ffn": _ffn_defs(cfg, ffn)}


def supports(cfg: ArchConfig) -> bool:
    """Whether this layer builds ``cfg``: it has no latent attention,
    no leading dense layers, no shared experts and no MTP modules
    (``apps.collectives_lowering`` lowers such configs all the same)."""
    return not (cfg.kv_lora_rank or cfg.n_dense_layers
                or cfg.n_shared_experts or cfg.n_mtp_layers)


def model_defs(cfg: ArchConfig):
    if not supports(cfg):
        raise NotImplementedError(
            f"{cfg.name}: the model layer has no MLA attention, leading "
            "dense layers, shared experts or MTP modules")
    d, v = cfg.d_model, cfg.vocab_size
    block = {f"sub{i}": _sublayer_defs(cfg, m, f,
                                       cross=(cfg.enc_layers > 0))
             for i, (m, f) in enumerate(cfg.pattern)}
    defs: dict[str, Any] = {
        "embed": ParamDef((v, d), ("vocab_table", "embed_table"),
                          scale=0.02),
        "blocks": stack_defs(block, cfg.n_blocks),
        "final_norm": ParamDef((d,), ("norm",), init="ones"),
        "lm_head": ParamDef((d, v), ("embed", "vocab")),
    }
    if cfg.enc_layers > 0:  # encoder stack (bidirectional, no cross)
        eblock = {"sub0": _sublayer_defs(cfg, "attn", "mlp")}
        defs["enc_blocks"] = stack_defs(eblock, cfg.enc_layers)
        defs["enc_in"] = ParamDef((d, d), ("embed", None))
        defs["enc_norm"] = ParamDef((d,), ("norm",), init="ones")
    if cfg.vision_prefix > 0:
        defs["vis_proj"] = ParamDef((d, d), ("embed", None))
    return defs


# ================================================================ attention

def _project_qkv(p, x, cfg, cd, prefix=""):
    q = jnp.einsum("bsd,dhk->bshk", x, p[prefix + "wq"].astype(cd))
    k = jnp.einsum("bsd,dhk->bshk", x, p[prefix + "wk"].astype(cd))
    v = jnp.einsum("bsd,dhk->bshk", x, p[prefix + "wv"].astype(cd))
    if cfg.qkv_bias and prefix == "":
        q = q + p["bq"].astype(cd)
        k = k + p["bk"].astype(cd)
        v = v + p["bv"].astype(cd)
    return q, k, v


def _bspec(mesh):
    bs = tuple(a for a in BATCH_AXES if a in mesh.axis_names
               and mesh.shape[a] > 1)
    return bs if len(bs) > 1 else (bs[0] if bs else None)


def _heads_sharded(cfg, mesh):
    return cfg.n_heads % mesh.shape["model"] == 0


def _sp_attention(q, k, v, cfg, mesh, *, causal, window):
    """Sequence-parallel attention: q sharded over "model" on the seq
    dim, k/v replicated across it; each shard computes its q rows against
    the full KV with global positions (q_offset).  Activation memory for
    scores and (m, l, acc) shrinks by the model-axis size."""
    m = mesh.shape["model"]
    bspec = _bspec(mesh)
    qspec = P(bspec, "model", None, None)
    kvspec = P(bspec, None, None, None)
    s_local = q.shape[1] // m

    def body(ql, kl, vl):
        off = jax.lax.axis_index("model") * s_local
        return attn.attention(ql, kl, vl, causal=causal, window=window,
                              kv_chunk=cfg.kv_chunk, q_offset=off)

    return shard_map(body, mesh=mesh, in_specs=(qspec, kvspec, kvspec),
                     out_specs=qspec, check_vma=False)(q, k, v)


def attn_core(q, k, v, cfg, mesh, *, causal, window):
    """Train/prefill attention core; shard_map over heads when divisible.

    GQA head layout on an m-way model axis (h_l = H/m local q heads,
    rep = H/KV):
      - KV % m == 0: kv heads shard too (each shard keeps its own groups);
      - m % KV == 0 (kv heads fewer than shards, e.g. kv=8 on m=16): kv
        stays replicated and each shard slices the single kv head its
        local q heads belong to (MaxText-style kv replication).
    """
    m = mesh.shape["model"]
    if m == 1:
        return attn.attention(q, k, v, causal=causal, window=window,
                              kv_chunk=cfg.kv_chunk)
    if not _heads_sharded(cfg, mesh):
        # SP fallback (llama3.2's 24 heads on a 16-way axis): shard the
        # QUERY SEQUENCE over "model" instead of heads.  Without this the
        # whole attention runs replicated per model shard — 280GB HBM
        # peak on train_4k (EXPERIMENTS.md §Perf, llama iteration 1).
        if q.shape[1] % m == 0:
            return _sp_attention(q, k, v, cfg, mesh, causal=causal,
                                 window=window)
        return attn.attention(q, k, v, causal=causal, window=window,
                              kv_chunk=cfg.kv_chunk)
    h, kv = cfg.n_heads, cfg.n_kv_heads
    h_l, rep = h // m, h // kv
    kv_sharded = kv % m == 0
    if not kv_sharded and (m % kv != 0 or rep % h_l != 0):
        return attn.attention(q, k, v, causal=causal, window=window,
                              kv_chunk=cfg.kv_chunk)
    bspec = _bspec(mesh)
    qspec = P(bspec, None, "model", None)
    kvspec = P(bspec, None, "model" if kv_sharded else None, None)

    def body(ql, kl, vl):
        if not kv_sharded:
            idx = jax.lax.axis_index("model")
            start = (idx * h_l) // rep
            kl = jax.lax.dynamic_slice_in_dim(kl, start, 1, axis=2)
            vl = jax.lax.dynamic_slice_in_dim(vl, start, 1, axis=2)
        return attn.attention(ql, kl, vl, causal=causal, window=window,
                              kv_chunk=cfg.kv_chunk)

    return shard_map(body, mesh=mesh, in_specs=(qspec, kvspec, kvspec),
                     out_specs=qspec, check_vma=False)(q, k, v)


def attn_apply(p, x, cfg, mesh, positions, *, causal=True, window=0,
               memory=None):
    """Self-attention sublayer (+ optional cross-attention when memory)."""
    cd = jnp.dtype(cfg.compute_dtype)
    h = rms_norm(x, p["norm"], cfg.norm_eps).astype(cd)
    q, k, v = _project_qkv(p, h, cfg, cd)
    if cfg.use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    o = attn_core(q, k, v, cfg, mesh, causal=causal, window=window)
    x = x + jnp.einsum("bshk,hkd->bsd", o.astype(cd), p["wo"].astype(cd))
    if memory is not None:
        hx = rms_norm(x, p["xnorm"], cfg.norm_eps).astype(cd)
        qx = jnp.einsum("bsd,dhk->bshk", hx, p["xwq"].astype(cd))
        kx = jnp.einsum("bsd,dhk->bshk", memory.astype(cd),
                        p["xwk"].astype(cd))
        vx = jnp.einsum("bsd,dhk->bshk", memory.astype(cd),
                        p["xwv"].astype(cd))
        ox = attn_core(qx, kx, vx, cfg, mesh, causal=False, window=0)
        x = x + jnp.einsum("bshk,hkd->bsd", ox.astype(cd),
                           p["xwo"].astype(cd))
    return x


# ---------------------------------------------------------------- decode

def _seq_axes(mesh, batch_shardable):
    """Mesh axes available to shard the KV-cache sequence dim."""
    axes = []
    for a in mesh.axis_names:
        if mesh.shape[a] <= 1:
            continue
        if a == "model":
            axes.append(a)
        elif a in BATCH_AXES and not batch_shardable:
            axes.append(a)
    return tuple(axes)


def kv_cache_spec(mesh, batch_shardable: bool):
    bspec = _bspec(mesh) if batch_shardable else None
    seq = _seq_axes(mesh, batch_shardable)
    seq = seq if len(seq) > 1 else (seq[0] if seq else None)
    return P(bspec, seq, None, None)


def decode_attn_core(q, kc, vc, step, cfg, mesh, *, window,
                     batch_shardable=True):
    """Split-KV decode attention.  kc/vc sharded over sequence; each shard
    computes partial (m, l, acc); many-to-one combine merges (Gleam
    feedback aggregation).  q: (B,1,H,hd) -> out (B,1,H,hd) replicated
    over the seq axes.

    step: scalar, or (B,) for continuous batching (single-shard KV)."""
    from repro.core import collectives as coll
    seq_axes = _seq_axes(mesh, batch_shardable)
    if jnp.ndim(step) == 1:
        assert not seq_axes, (
            "per-row decode positions require unsharded KV")
        return attn.decode_attention(q, kc, vc, kv_len=step + 1,
                                     window=window)
    if not seq_axes:
        kv_len = jnp.broadcast_to(step + 1, (q.shape[0],))
        return attn.decode_attention(q, kc, vc, kv_len=kv_len, window=window)
    bspec = _bspec(mesh) if batch_shardable else None
    q_in = P(bspec, None, "model", None) if _heads_sharded(cfg, mesh) \
        else P(bspec, None, None, None)
    kv_in = kv_cache_spec(mesh, batch_shardable)
    s_total = kc.shape[1]
    n_shards = 1
    for a in seq_axes:
        n_shards *= mesh.shape[a]
    s_local = s_total // n_shards

    def body(ql, kl, vl, stp):
        if _heads_sharded(cfg, mesh) and mesh.shape["model"] > 1:
            ql = jax.lax.all_gather(ql, "model", axis=2, tiled=True)
        # linear shard index over seq axes
        idx = jnp.int32(0)
        for a in seq_axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        base = idx * s_local
        kpos = base + jnp.arange(s_local)
        if window:
            valid = kpos < jnp.minimum(stp + 1, window)   # rolling buffer
        else:
            valid = kpos <= stp
        b, _, hq, hd = ql.shape
        n_kv = kl.shape[2]
        qg = ql.reshape(b, 1, n_kv, hq // n_kv, hd).astype(jnp.float32)
        logits = jnp.einsum("bqkrd,bskd->bkrqs", qg,
                            kl.astype(jnp.float32)) / jnp.sqrt(hd)
        logits = jnp.where(valid[None, None, None, None, :], logits,
                           attn.NEG_INF)
        m = logits.max(axis=-1)
        pexp = jnp.exp(logits - m[..., None])
        l = pexp.sum(axis=-1)
        acc = jnp.einsum("bkrqs,bskd->bkrqd", pexp, vl.astype(jnp.float32))
        m, l, acc = coll.softmax_combine(
            (m, l, acc), seq_axes, schedule=cfg.collective_schedule)
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.reshape(b, 1, hq, hd).astype(ql.dtype)

    out_spec = P(bspec, None, None, None)
    return shard_map(body, mesh=mesh,
                     in_specs=(q_in, kv_in, kv_in, P()),
                     out_specs=out_spec, check_vma=False)(q, kc, vc, step)


def cache_insert(kc, vc, k_new, v_new, pos, mesh, batch_shardable=True):
    """Insert (B,1,KV,hd) into the seq-sharded cache at global slot pos.

    pos: scalar (synchronized decode) or (B,) int32 (continuous batching,
    single-shard KV only — the serve runtime's per-row positions)."""
    if jnp.ndim(pos) == 1:
        def upd(c, n, p):
            return jax.lax.dynamic_update_slice_in_dim(
                c, n.astype(c.dtype), p, 0)
        return (jax.vmap(upd)(kc, k_new, pos),
                jax.vmap(upd)(vc, v_new, pos))
    seq_axes = _seq_axes(mesh, batch_shardable)
    if not seq_axes:
        return (jax.lax.dynamic_update_slice_in_dim(kc, k_new, pos, 1),
                jax.lax.dynamic_update_slice_in_dim(vc, v_new, pos, 1))
    bspec = _bspec(mesh) if batch_shardable else None
    kv_in = kv_cache_spec(mesh, batch_shardable)
    new_in = P(bspec, None, None, None)
    s_total = kc.shape[1]
    n_shards = 1
    for a in seq_axes:
        n_shards *= mesh.shape[a]
    s_local = s_total // n_shards

    def body(kl, vl, kn, vn, p_):
        idx = jnp.int32(0)
        for a in seq_axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        local_pos = jnp.clip(p_ - idx * s_local, 0, s_local - 1)
        mine = (p_ >= idx * s_local) & (p_ < (idx + 1) * s_local)
        kn = jnp.where(mine, kn, kl[:, local_pos][:, None]
                       .astype(kn.dtype))
        vn = jnp.where(mine, vn, vl[:, local_pos][:, None]
                       .astype(vn.dtype))
        kl = jax.lax.dynamic_update_slice_in_dim(
            kl, kn.astype(kl.dtype), local_pos, 1)
        vl = jax.lax.dynamic_update_slice_in_dim(
            vl, vn.astype(vl.dtype), local_pos, 1)
        return kl, vl

    return shard_map(body, mesh=mesh,
                     in_specs=(kv_in, kv_in, new_in, new_in, P()),
                     out_specs=(kv_in, kv_in), check_vma=False)(
                         kc, vc, k_new, v_new, pos)


def attn_decode_apply(p, x, cache, step, cfg, mesh, *, window=0, memory=None,
                      batch_shardable=True):
    cd = jnp.dtype(cfg.compute_dtype)
    h = rms_norm(x, p["norm"], cfg.norm_eps).astype(cd)
    q, k, v = _project_qkv(p, h, cfg, cd)
    pos = (step[:, None] if jnp.ndim(step) == 1
           else jnp.broadcast_to(step, (x.shape[0], 1)))
    if cfg.use_rope:
        q = rope(q, pos, cfg.rope_theta)
        k = rope(k, pos, cfg.rope_theta)
    slot = jnp.mod(step, cache["k"].shape[1]) if window else step
    kc, vc = cache_insert(cache["k"], cache["v"], k, v, slot, mesh,
                          batch_shardable)
    o = decode_attn_core(q, kc, vc, step, cfg, mesh, window=window,
                         batch_shardable=batch_shardable)
    x = x + jnp.einsum("bshk,hkd->bsd", o.astype(cd), p["wo"].astype(cd))
    if memory is not None:
        hx = rms_norm(x, p["xnorm"], cfg.norm_eps).astype(cd)
        qx = jnp.einsum("bsd,dhk->bshk", hx, p["xwq"].astype(cd))
        kx = jnp.einsum("bsd,dhk->bshk", memory.astype(cd),
                        p["xwk"].astype(cd))
        vx = jnp.einsum("bsd,dhk->bshk", memory.astype(cd),
                        p["xwv"].astype(cd))
        ox = attn.cross_attention(qx, kx, vx)
        x = x + jnp.einsum("bshk,hkd->bsd", ox.astype(cd),
                           p["xwo"].astype(cd))
    return x, {"k": kc, "v": vc}


# ================================================================ sublayers

def ffn_apply(p, x, kind, cfg, mesh, decode=False):
    if kind is None:
        return x, 0.0
    cd = jnp.dtype(cfg.compute_dtype)
    h = rms_norm(x, p["norm"], cfg.norm_eps).astype(cd)
    if kind == "mlp":
        return x + swiglu(h, p["wi"], p["wg"], p["wo"], cd), 0.0
    y, aux = moe_mod.moe_apply(p, h, cfg, mesh, BATCH_AXES, decode=decode)
    if cfg.moe_barrier:
        # pin the shard_map boundary to bf16: stops XLA hoisting the next
        # block's f32 convert above the (B,S,D) boundary all-gather
        # (qwen3 §Perf iteration 3/4)
        y = jax.lax.optimization_barrier(y)
    return x + y, aux


def sublayer_apply(sub, x, mixer, ffn, cfg, mesh, positions, *,
                   causal=True, memory=None):
    if mixer == "attn":
        x = attn_apply(sub["mixer"], x, cfg, mesh, positions, causal=causal,
                       window=cfg.window, memory=memory)
    else:
        hm = rms_norm(x, sub["mixer"]["norm"], cfg.norm_eps)
        y, _ = ssm_mod.ssm_apply(
            {k: v for k, v in sub["mixer"].items() if k != "norm"},
            hm, cfg)
        x = x + y
    x, aux = ffn_apply(sub["ffn"], x, ffn, cfg, mesh)
    return x, aux


def _remat(fn, cfg):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return jax.checkpoint(fn, policy=policy)


def run_blocks(blocks, x, cfg, mesh, positions, *, pattern=None, causal=True,
               memory=None):
    """Scan the stacked block params over the sequence of sublayers."""
    pattern = pattern if pattern is not None else cfg.pattern

    def body(carry, bp):
        x, aux = carry
        for i, (m, f) in enumerate(pattern):
            x, a = sublayer_apply(bp[f"sub{i}"], x, m, f, cfg, mesh,
                                  positions, causal=causal, memory=memory)
            aux = aux + a
        return (x, aux), None

    body = _remat(body, cfg)
    if cfg.scan_layers:
        (x, aux), _ = jax.lax.scan(body, (x, 0.0), blocks)
    else:
        n = jax.tree.leaves(blocks)[0].shape[0]
        aux = 0.0
        for i in range(n):
            bp = jax.tree.map(lambda a: a[i], blocks)
            (x, aux), _ = body((x, aux), bp)
    return x, aux


# ---------------------------------------------------------------- caches

def cache_len(cfg, seq_len):
    return min(seq_len, cfg.window) if cfg.window else seq_len


def init_caches(cfg, batch, seq_len, mesh=None, abstract=False,
                dtype=jnp.bfloat16):
    """Per-layer decode caches stacked over n_blocks (+ encoder memory)."""
    kv, hd = cfg.n_kv_heads, cfg.hd
    sub = {}
    for i, (m, f) in enumerate(cfg.pattern):
        if m == "attn":
            shape = (cfg.n_blocks, batch, cache_len(cfg, seq_len), kv, hd)
            sub[f"sub{i}"] = {
                "k": jax.ShapeDtypeStruct(shape, dtype),
                "v": jax.ShapeDtypeStruct(shape, dtype),
            }
        else:
            d_in, h, p, n, k = ssm_mod.ssm_dims(cfg)
            sub[f"sub{i}"] = {
                "conv": jax.ShapeDtypeStruct(
                    (cfg.n_blocks, batch, k - 1, d_in + 2 * n), dtype),
                "state": jax.ShapeDtypeStruct(
                    (cfg.n_blocks, batch, h, n, p), jnp.float32),
            }
    caches = {"layers": sub}
    if cfg.enc_layers > 0:
        enc_len = max(seq_len // max(cfg.audio_stride, 1), 8)
        caches["memory"] = jax.ShapeDtypeStruct(
            (batch, enc_len, cfg.d_model), dtype)
    if abstract:
        return caches
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), caches,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def cache_specs(cfg, batch, seq_len, mesh, batch_shardable):
    """PartitionSpec tree matching init_caches."""
    kvspec = kv_cache_spec(mesh, batch_shardable)
    bspec = _bspec(mesh) if batch_shardable else None
    model_ok = lambda n: "model" if (  # noqa: E731
        mesh.shape["model"] > 1 and n % mesh.shape["model"] == 0) else None
    sub = {}
    for i, (m, f) in enumerate(cfg.pattern):
        if m == "attn":
            sp = P(None, *kvspec)
            sub[f"sub{i}"] = {"k": sp, "v": sp}
        else:
            d_in, h, p, n, k = ssm_mod.ssm_dims(cfg)
            sub[f"sub{i}"] = {
                "conv": P(None, bspec, None, None),
                "state": P(None, bspec, model_ok(h), None, None),
            }
    specs = {"layers": sub}
    if cfg.enc_layers > 0:
        specs["memory"] = P(bspec, None, None)
    return specs


def run_blocks_decode(blocks, caches, x, step, cfg, mesh, *, memory=None,
                      batch_shardable=True):
    """One decode step through the stacked blocks, updating caches."""

    def body(carry, inp):
        x = carry
        bp, cache = inp
        new_cache = {}
        for i, (m, f) in enumerate(cfg.pattern):
            sub = bp[f"sub{i}"]
            c = cache[f"sub{i}"]
            if m == "attn":
                x, nc = attn_decode_apply(
                    sub["mixer"], x, c, step, cfg, mesh,
                    window=cfg.window, memory=memory,
                    batch_shardable=batch_shardable)
            else:
                hm = rms_norm(x, sub["mixer"]["norm"], cfg.norm_eps)
                y, nc = ssm_mod.ssm_decode_step(
                    {k: v for k, v in sub["mixer"].items() if k != "norm"},
                    hm, c, cfg)
                x = x + y
            x, _ = ffn_apply(sub["ffn"], x, f, cfg, mesh, decode=True)
            new_cache[f"sub{i}"] = nc
        return x, new_cache

    if cfg.scan_layers:
        x, new_caches = jax.lax.scan(body, x, (blocks, caches["layers"]))
    else:
        n = jax.tree.leaves(blocks)[0].shape[0]
        outs = []
        for i in range(n):
            bp = jax.tree.map(lambda a: a[i], blocks)
            cc = jax.tree.map(lambda a: a[i], caches["layers"])
            x, nc = body(x, (bp, cc))
            outs.append(nc)
        new_caches = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    out = {"layers": new_caches}
    if "memory" in caches:
        out["memory"] = caches["memory"]
    return x, out


def decode_forward(params, caches, tokens, step, cfg, mesh, *,
                   batch_shardable=True):
    """Single-token serve forward: (B,1) tokens -> (B,1,V) logits."""
    cd = jnp.dtype(cfg.compute_dtype)
    x = embed_tokens(params, tokens, cfg, cd, mesh)
    memory = caches.get("memory")
    if not cfg.use_rope and cfg.enc_layers > 0:
        from repro.models.blocks import sinusoidal_at
        pe = sinusoidal_at(jnp.broadcast_to(step, (1, 1)), cfg.d_model)
        x = x + pe.astype(cd)
    x, new_caches = run_blocks_decode(
        params["blocks"], caches, x, step, cfg, mesh, memory=memory,
        batch_shardable=batch_shardable)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x.astype(cd),
                        params["lm_head"].astype(cd))
    return logits.astype(jnp.float32), new_caches


# ================================================================ forward

def embed_tokens(params, tokens, cfg, cd, mesh=None):
    """Token embedding lookup.

    When the table's vocab dim is sharded over "model" (vocab % m == 0),
    the lookup runs in shard_map: device (d, m) holds batch-shard d and
    vocab-shard m, computes vocab-shard-m's contribution to its own batch
    rows, and a psum over "model" assembles the rows — a mask+reduce
    instead of GSPMD's involuntary full-table rematerialization, and the
    table GRADIENT stays vocab-sharded (llama §Perf iteration 3: the
    f32 full-table all-gather/all-reduce pair was ~3.4GB/step).
    """
    table = params["embed"]
    v = table.shape[0]
    if (cfg.embed_impl != "psum" or mesh is None
            or "model" not in mesh.axis_names):
        return table.astype(cd)[tokens]
    m = mesh.shape["model"]
    if m <= 1 or v % m != 0:
        return table.astype(cd)[tokens]
    v_local = v // m
    bspec = _bspec(mesh)

    def body(tbl, toks):
        base = jax.lax.axis_index("model") * v_local
        loc = toks - base
        ok = (loc >= 0) & (loc < v_local)
        rows = tbl.astype(cd)[jnp.clip(loc, 0, v_local - 1)]
        rows = jnp.where(ok[..., None], rows, 0)
        return jax.lax.psum(rows, "model")

    return shard_map(body, mesh=mesh,
                     in_specs=(P("model", None), P(bspec, None)),
                     out_specs=P(bspec, None, None),
                     check_vma=False)(table, tokens)


def build_inputs(params, batch, cfg, mesh=None):
    """Assemble the decoder input sequence from tokens + modality stubs."""
    cd = jnp.dtype(cfg.compute_dtype)
    x = embed_tokens(params, batch["tokens"], cfg, cd, mesh)
    if cfg.vision_prefix > 0:
        vis = batch["vision_embed"].astype(cd) @ params["vis_proj"].astype(cd)
        x = jnp.concatenate([vis, x], axis=1)
    if not cfg.use_rope:  # sinusoidal absolute positions (whisper/jamba-attn)
        pe = sinusoidal_positions(x.shape[1], cfg.d_model).astype(cd)
        if cfg.enc_layers > 0:   # whisper decoder gets positions; jamba not
            x = x + pe[None]
    return x


def encode(params, batch, cfg, mesh):
    """Encoder forward for enc-dec archs (audio frontend STUB: batch
    provides precomputed frame embeddings)."""
    cd = jnp.dtype(cfg.compute_dtype)
    frames = batch["frames"].astype(cd)
    x = frames @ params["enc_in"].astype(cd)
    x = x + sinusoidal_positions(x.shape[1], cfg.d_model).astype(cd)[None]
    pos = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
    x, _ = run_blocks(params["enc_blocks"], x, cfg, mesh, pos,
                      pattern=(("attn", "mlp"),), causal=False)
    return rms_norm(x, params["enc_norm"], cfg.norm_eps)


def forward(params, batch, cfg: ArchConfig, mesh):
    """Teacher-forced forward -> logits (B, S, V) in f32."""
    cd = jnp.dtype(cfg.compute_dtype)
    x = build_inputs(params, batch, cfg, mesh)
    memory = encode(params, batch, cfg, mesh) if cfg.enc_layers > 0 else None
    pos = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
    x, aux = run_blocks(params["blocks"], x, cfg, mesh, pos, causal=True,
                        memory=memory)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.vision_prefix > 0:
        x = x[:, cfg.vision_prefix:]
    logits = jnp.einsum("bsd,dv->bsv", x.astype(cd),
                        params["lm_head"].astype(cd))
    return logits.astype(jnp.float32), aux


def forward_hidden(params, batch, cfg: ArchConfig, mesh):
    """Forward up to the final norm; returns hidden states, not logits."""
    x = build_inputs(params, batch, cfg, mesh)
    memory = encode(params, batch, cfg, mesh) if cfg.enc_layers > 0 else None
    pos = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
    x, aux = run_blocks(params["blocks"], x, cfg, mesh, pos, causal=True,
                        memory=memory)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.vision_prefix > 0:
        x = x[:, cfg.vision_prefix:]
    return x, aux


def chunked_xent(x, lm_head, targets, mask, cfg, mesh=None):
    """Cross-entropy without materializing (B, S, V) logits.

    Scans over sequence chunks; each chunk's logits live only inside the
    (rematerialized) scan body, so peak memory is O(B * chunk * V / shards)
    instead of O(B * S * V).  This is what makes the 150k-vocab archs fit
    HBM on the production mesh (EXPERIMENTS.md §Perf, iteration 1).
    """
    cd = jnp.dtype(cfg.compute_dtype)
    b, s, d = x.shape
    chunk = min(cfg.xent_chunk, s)
    if s % chunk != 0:
        chunk = s
    n = s // chunk
    xs = x.reshape(b, n, chunk, d).swapaxes(0, 1)
    ts = targets.reshape(b, n, chunk).swapaxes(0, 1)
    ms = (mask if mask is not None
          else jnp.ones(targets.shape, jnp.float32))
    ms = ms.reshape(b, n, chunk).swapaxes(0, 1)
    w = lm_head.astype(cd)
    v_ax = ("model" if mesh is not None and "model" in mesh.axis_names
            and lm_head.shape[1] % mesh.shape["model"] == 0 else None)
    bspec = _bspec(mesh) if mesh is not None else None

    @jax.checkpoint
    def body(carry, inp):
        xc, tc, mc = inp
        logits = jnp.einsum("bcd,dv->bcv", xc.astype(cd), w)
        if mesh is not None:
            # keep the chunk logits vocab-sharded over "model": local
            # logsumexp partials + a tiny cross-shard reduce, instead of
            # GSPMD's involuntary full-logits rematerialization.
            logits = jax.lax.with_sharding_constraint(
                logits, NamedSharding(mesh, P(bspec, None, v_ax)))
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        # gold logit via one-hot reduce (shards over the vocab axis;
        # take_along_axis would force a cross-shard gather)
        hot = jax.nn.one_hot(tc, logits.shape[-1], dtype=logits.dtype)
        gold = (logits * hot).sum(-1)
        return carry + ((logz - gold) * mc).sum(), None

    nll_sum, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                              (xs, ts, ms))
    denom = jnp.maximum(ms.sum(), 1.0)
    return nll_sum / denom


def loss_fn(params, batch, cfg, mesh):
    x, aux = forward_hidden(params, batch, cfg, mesh)
    loss = chunked_xent(x, params["lm_head"], batch["targets"],
                        batch.get("loss_mask"), cfg, mesh)
    total = loss + cfg.router_aux_coef * aux
    return total, {"loss": loss, "aux_loss": aux,
                   "perplexity": jnp.exp(jnp.clip(loss, max=20.0))}
