"""Mixture-of-Experts with explicit expert-parallel dispatch/combine.

This layer IS the Gleam pattern inside the model (DESIGN.md §2.3): token
dispatch to top-k experts is a one-to-many multicast over the "model" mesh
axis; the weighted combine is a many-to-one feedback aggregation.  Both are
implemented with shard_map + all_to_all so the collective structure is
explicit in the HLO (and countable by the roofline pass).

Two paths:
- ``moe_train``  — tokens resharded seq-wise over "model" (sequence
  parallelism into the block), capacity-bucketed all_to_all to expert
  owners, local grouped GEMM via ``jax.lax.ragged_dot``, reverse all_to_all,
  weighted scatter-add combine at the source.
- ``moe_decode`` — single/few-token step: tokens are small, experts stay
  put; every expert shard computes its local experts' contributions and a
  psum over "model" performs the many-to-one combine.

Expert placement (matches the sharding planner's divisibility fallback):
- "ep"  — n_experts divides the model axis: experts sharded over "model".
- "etp" — (mixtral: 8 experts on a 16-way axis): experts replicated,
  expert d_ff sharded over "model" (tensor parallelism inside experts).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.models.blocks import ParamDef


def expert_mode(cfg, model_axis_size: int) -> str:
    return "ep" if cfg.n_experts % model_axis_size == 0 else "etp"


def moe_defs(cfg):
    e, d, f = cfg.n_experts, cfg.d_model, cfg.moe_d_ff
    # planner resolves: experts->model when divisible (ep), else mlp->model
    # (etp); embed always takes the FSDP axes.  These axes MUST stay in sync
    # with _specs() below.
    return {
        "router": ParamDef((d, e), (None, None), scale=0.02),
        "we_i": ParamDef((e, d, f), ("experts", "embed", "mlp")),
        "we_g": ParamDef((e, d, f), ("experts", "embed", "mlp")),
        "we_o": ParamDef((e, f, d), ("experts", "mlp", "embed")),
    }


def _fsdp_axes(mesh, enabled: bool = True):
    if not enabled:
        return ()
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names
                 and mesh.shape[a] > 1)


def _specs(cfg, mesh):
    """shard_map in_specs for (router, we_i, we_g, we_o)."""
    fs = _fsdp_axes(mesh, cfg.fsdp_weights)
    fspec = fs if len(fs) > 1 else (fs[0] if fs else None)
    mode = expert_mode(cfg, mesh.shape["model"])
    if mode == "ep":
        ig = P("model", fspec, None)
        o = P("model", None, fspec)
    else:
        ig = P(None, fspec, "model")
        o = P(None, "model", fspec)
    return mode, P(None, None), ig, o


def _gather(w, mesh, dim, enabled: bool = True):
    """FSDP all-gather of weight dim `dim` inside shard_map (ZeRO-3 fwd)."""
    for a in _fsdp_axes(mesh, enabled):
        w = jax.lax.all_gather(w, a, axis=dim, tiled=True)
    return w


def _router(x2, wr, top_k):
    """x2: (T, D) -> (gates (T,k), ids (T,k), aux_loss scalar)."""
    logits = x2.astype(jnp.float32) @ wr.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)              # (T, E)
    gates, ids = jax.lax.top_k(probs, top_k)             # (T, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    e = logits.shape[-1]
    density = jnp.mean(jax.nn.one_hot(ids[:, 0], e, dtype=jnp.float32), 0)
    density_proxy = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(density * density_proxy)
    return gates, ids, aux


def _grouped_ffn(xs, gs, we_i, we_g, we_o, cd):
    """Grouped GEMM over expert-sorted rows. xs (M, D), gs (groups,).

    BASELINE implementation (cfg.moe_impl == "ragged"): ragged_dot lowers
    to a DENSE masked dot on this backend — real compute and the counted
    flops inflate by ~n_experts_local / top_k (§Perf, MoE iteration 1)."""
    h = (jax.nn.silu(jax.lax.ragged_dot(xs, we_g.astype(cd), gs))
         * jax.lax.ragged_dot(xs, we_i.astype(cd), gs))
    return jax.lax.ragged_dot(h, we_o.astype(cd), gs)


def _bucket_ffn(rows, eids, n_exp, cap_e, we_i, we_g, we_o, cd,
                weights=None):
    """Capacity-bucketed expert FFN — the TPU-native grouped GEMM.

    rows (M, D); eids (M,) in [0, n_exp] (n_exp = sentinel/dropped).
    Rows scatter into a dense (n_exp, cap_e, D) buffer; the FFN is a
    batched einsum (MXU-shaped; XLA counts exactly n_exp*cap_e*D*F
    flops).  Pays only the capacity-factor padding instead of the
    ragged_dot dense-lowering blowup.  Returns y (M, D), zero for
    dropped rows, scaled by `weights` if given.
    """
    m, d = rows.shape
    order = jnp.argsort(eids)                    # stable; sentinel last
    sorted_e = eids[order]
    counts = jnp.bincount(eids, length=n_exp + 1)
    offsets = jnp.concatenate([jnp.zeros(1, counts.dtype),
                               jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(m) - offsets[sorted_e]
    valid = (sorted_e < n_exp) & (rank < cap_e)
    slot = jnp.where(valid, sorted_e * cap_e + rank, n_exp * cap_e)
    buf = jnp.zeros((n_exp * cap_e + 1, d), cd).at[slot].set(
        rows[order].astype(cd))[:-1]
    xb = buf.reshape(n_exp, cap_e, d)
    h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", xb, we_g.astype(cd)))
         * jnp.einsum("ecd,edf->ecf", xb, we_i.astype(cd)))
    yb = jnp.einsum("ecf,efd->ecd", h, we_o.astype(cd))
    yb = jnp.concatenate([yb.reshape(n_exp * cap_e, d),
                          jnp.zeros((1, d), h.dtype)])
    y_sorted = jnp.where(valid[:, None], yb[slot], 0)
    y = jnp.zeros((m, d), yb.dtype).at[order].set(y_sorted)
    if weights is not None:
        y = y * weights[:, None].astype(y.dtype)
    return y


def _cap(n_tokens, n_exp, cf, floor=8):
    return max(floor, int(math.ceil(cf * n_tokens / n_exp / floor)) * floor)


def _batch_spec(mesh, batch_axes, batch: int | None = None):
    """Batch PartitionSpec; replicated when `batch` doesn't divide the
    batch-axes product (e.g. long_500k's global_batch=1)."""
    bs = tuple(a for a in batch_axes if a in mesh.axis_names
               and mesh.shape[a] > 1)
    if batch is not None:
        n = 1
        for a in bs:
            n *= mesh.shape[a]
        if n == 0 or batch % max(n, 1) != 0:
            return None
    return bs if len(bs) > 1 else (bs[0] if bs else None)


def moe_train(params, x, cfg, mesh, batch_axes):
    """x: (B, S, D), batch sharded over batch_axes. Returns (y, aux)."""
    cd = jnp.dtype(cfg.compute_dtype)
    ep = mesh.shape["model"]
    mode, r_spec, ig_spec, o_spec = _specs(cfg, mesh)
    e = cfg.n_experts
    e_local = e // ep if mode == "ep" else e
    # ep: tokens seq-split over "model" (sequence parallelism into the
    # block).  etp: tokens replicated over "model" — the psum over the
    # f-slice partials must reduce identical token sets.
    if mode == "ep":
        x_spec = P(_batch_spec(mesh, batch_axes, x.shape[0]), "model", None)
    else:
        x_spec = P(_batch_spec(mesh, batch_axes, x.shape[0]), None, None)

    def body(wr, we_i, we_g, we_o, xl):
        b_l, s_l, d = xl.shape
        t_l = b_l * s_l
        x2 = xl.reshape(t_l, d)
        gates, ids, aux = _router(x2, wr, cfg.top_k)
        aux = jax.lax.pmean(aux, "model")
        for a in _fsdp_axes(mesh):
            aux = jax.lax.pmean(aux, a)
        we_i = _gather(we_i, mesh, 1, cfg.fsdp_weights)
        we_g = _gather(we_g, mesh, 1, cfg.fsdp_weights)
        we_o = _gather(we_o, mesh, 2, cfg.fsdp_weights)

        if mode == "etp":
            # experts replicated, d_ff sharded: expert FFN on the local
            # f-slice for every (token, expert) pair; psum over model
            # reduces the partial wo contraction.
            n = t_l * cfg.top_k
            flat_ids = ids.reshape(-1)
            tok = jnp.arange(n) // cfg.top_k
            if cfg.moe_impl == "ragged":
                order = jnp.argsort(flat_ids)
                xs = x2[order // cfg.top_k].astype(cd)
                gs = jnp.bincount(flat_ids, length=e)
                y = _grouped_ffn(xs, gs, we_i, we_g, we_o, cd)
                y = jax.lax.psum(y, "model")
                w = gates.reshape(-1)[order].astype(y.dtype)
                out = jnp.zeros((t_l, d), y.dtype) \
                    .at[order // cfg.top_k].add(y * w[:, None])
                return out.reshape(b_l, s_l, d).astype(xl.dtype), aux
            cap_e = _cap(n, e, cfg.capacity_factor)
            y = _bucket_ffn(x2[tok], flat_ids, e, cap_e,
                            we_i, we_g, we_o, cd,
                            weights=gates.reshape(-1))
            y = jax.lax.psum(y, "model")
            out = jnp.zeros((t_l, d), y.dtype).at[tok].add(y)
            return out.reshape(b_l, s_l, d).astype(xl.dtype), aux

        # ---------------- expert-parallel dispatch (the Gleam multicast)
        n = t_l * cfg.top_k
        cap = max(8, int(math.ceil(cfg.capacity_factor * n / ep / 8)) * 8)
        flat_e = ids.reshape(-1)                       # (N,) global expert id
        dest = flat_e // e_local                       # owner shard
        order = jnp.argsort(dest)                      # stable groups by dest
        sorted_dest = dest[order]
        counts = jnp.bincount(dest, length=ep)
        offsets = jnp.concatenate([jnp.zeros(1, counts.dtype),
                                   jnp.cumsum(counts)[:-1]])
        rank = jnp.arange(n) - offsets[sorted_dest]
        valid = rank < cap
        slot = jnp.where(valid, sorted_dest * cap + rank, ep * cap)
        buf_tok = jnp.full((ep * cap + 1,), -1, jnp.int32).at[slot].set(
            (order // cfg.top_k).astype(jnp.int32))[:-1]
        buf_eid = jnp.full((ep * cap + 1,), e_local, jnp.int32).at[slot].set(
            (flat_e[order] % e_local).astype(jnp.int32))[:-1]
        buf_gate = jnp.zeros((ep * cap + 1,), jnp.float32).at[slot].set(
            gates.reshape(-1)[order])[:-1]
        send_x = jnp.where((buf_tok >= 0)[:, None],
                           x2[jnp.maximum(buf_tok, 0)], 0).astype(cd)
        send_x = send_x.reshape(ep, cap, d)
        send_eid = buf_eid.reshape(ep, cap)
        # one-to-many: tokens travel to their expert owners
        recv_x = jax.lax.all_to_all(send_x, "model", 0, 0)
        recv_eid = jax.lax.all_to_all(send_eid, "model", 0, 0)
        m = ep * cap
        flat_rx = recv_x.reshape(m, d)
        flat_eid = recv_eid.reshape(m)
        if cfg.moe_impl == "ragged":
            lorder = jnp.argsort(flat_eid)             # sentinel last
            xs = flat_rx[lorder]
            gs = jnp.bincount(flat_eid, length=e_local + 1)[:e_local]
            y = _grouped_ffn(xs, gs, we_i, we_g, we_o, cd)
            y_un = jnp.zeros((m, d), y.dtype).at[lorder].set(y)
        else:
            cap_e = _cap(m, e_local, 1.0)              # cf already in cap
            y_un = _bucket_ffn(flat_rx, flat_eid, e_local, cap_e,
                               we_i, we_g, we_o, cd)
        # many-to-one: expert outputs travel home (feedback aggregation)
        back = jax.lax.all_to_all(y_un.reshape(ep, cap, d), "model", 0, 0)
        flat_back = back.reshape(ep * cap, d)
        w = buf_gate.astype(flat_back.dtype)[:, None]
        out = jnp.zeros((t_l, d), flat_back.dtype).at[
            jnp.maximum(buf_tok, 0)].add(
                jnp.where((buf_tok >= 0)[:, None], flat_back * w, 0))
        return out.reshape(b_l, s_l, d).astype(xl.dtype), aux

    fn = shard_map(body, mesh=mesh,
                   in_specs=(r_spec, ig_spec, ig_spec, o_spec, x_spec),
                   out_specs=(x_spec, P()), check_vma=False)
    return fn(params["router"], params["we_i"], params["we_g"],
              params["we_o"], x)


def moe_decode(params, x, cfg, mesh, batch_axes):
    """Few-token MoE step: local experts compute, psum over model combines."""
    cd = jnp.dtype(cfg.compute_dtype)
    ep = mesh.shape["model"]
    mode, r_spec, ig_spec, o_spec = _specs(cfg, mesh)
    e = cfg.n_experts
    e_local = e // ep if mode == "ep" else e
    x_spec = P(_batch_spec(mesh, batch_axes, x.shape[0]), None, None)

    def body(wr, we_i, we_g, we_o, xl):
        b_l, s_l, d = xl.shape
        x2 = xl.reshape(-1, d)
        gates, ids, aux = _router(x2, wr, cfg.top_k)
        we_i = _gather(we_i, mesh, 1, cfg.fsdp_weights)
        we_g = _gather(we_g, mesh, 1, cfg.fsdp_weights)
        we_o = _gather(we_o, mesh, 2, cfg.fsdp_weights)
        if mode == "ep":
            base = jax.lax.axis_index("model") * e_local
            lids = ids - base
        else:
            lids = ids
        flat = jnp.where((lids >= 0) & (lids < e_local),
                         lids, e_local).reshape(-1)
        n = flat.shape[0]
        if cfg.moe_impl == "ragged":
            order = jnp.argsort(flat)
            xs = x2[order // cfg.top_k].astype(cd)
            gs = jnp.bincount(flat, length=e_local + 1)[:e_local]
            y = _grouped_ffn(xs, gs, we_i, we_g, we_o, cd)
            w = gates.reshape(-1)[order].astype(y.dtype)
            out = jnp.zeros((x2.shape[0], d), y.dtype) \
                .at[order // cfg.top_k].add(y * w[:, None])
        else:
            tok = jnp.arange(n) // cfg.top_k
            cap_e = _cap(n, e_local, cfg.capacity_factor * 2)
            y = _bucket_ffn(x2[tok], flat, e_local, cap_e,
                            we_i, we_g, we_o, cd,
                            weights=gates.reshape(-1))
            out = jnp.zeros((x2.shape[0], d), y.dtype).at[tok].add(y)
        out = jax.lax.psum(out, "model")   # many-to-one combine (both modes)
        aux = jax.lax.pmean(aux, "model")
        for a in _fsdp_axes(mesh):
            aux = jax.lax.pmean(aux, a)
        return out.reshape(b_l, s_l, d).astype(xl.dtype), aux

    fn = shard_map(body, mesh=mesh,
                   in_specs=(r_spec, ig_spec, ig_spec, o_spec, x_spec),
                   out_specs=(x_spec, P()), check_vma=False)
    return fn(params["router"], params["we_i"], params["we_g"],
              params["we_o"], x)


def moe_apply(params, x, cfg, mesh, batch_axes, decode=False):
    s = x.shape[1]
    if decode or s % mesh.shape["model"] != 0:
        return moe_decode(params, x, cfg, mesh, batch_axes)
    return moe_train(params, x, cfg, mesh, batch_axes)
