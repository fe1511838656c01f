"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the interpret=True kernel tests compare against
(assert_allclose over shape/dtype sweeps).  They are deliberately the
simplest possible O(S^2)-memory implementations.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def mha_reference(q, k, v, *, causal: bool, window: int = 0):
    """Multi-head attention oracle. q (B,Sq,H,D); k,v (B,Skv,KVH,D).
    GQA: H = KVH * rep.  window > 0 = sliding window (causal band)."""
    b, sq, h, d = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    rep = h // kvh
    qg = q.reshape(b, sq, kvh, rep, d).astype(jnp.float32)
    logits = jnp.einsum("bqkrd,bskd->bkrqs", qg, k.astype(jnp.float32))
    logits = logits / jnp.sqrt(jnp.float32(d))
    qpos = jnp.arange(sq)[:, None]
    kpos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkrqs,bskd->bqkrd", w, v.astype(jnp.float32))
    return out.reshape(b, sq, h, d).astype(q.dtype)


def decode_reference(q, k, v, *, kv_len):
    """Single-token decode oracle. q (B,H,D); k,v (B,S,KVH,D);
    kv_len (B,) valid prefix lengths."""
    b, h, d = q.shape
    s, kvh = k.shape[1], k.shape[2]
    rep = h // kvh
    qg = q.reshape(b, kvh, rep, d).astype(jnp.float32)
    logits = jnp.einsum("bkrd,bskd->bkrs", qg, k.astype(jnp.float32))
    logits = logits / jnp.sqrt(jnp.float32(d))
    valid = jnp.arange(s)[None, :] < kv_len[:, None]
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkrs,bskd->bkrd", w, v.astype(jnp.float32))
    return out.reshape(b, h, d).astype(q.dtype)


def ssd_reference(x, dt, a, B_, C_):
    """Sequential SSD (Mamba-2) oracle — the exact recurrence.

    x (B,S,H,P); dt, a (B,S,H); B_, C_ (B,S,N).
      S_t = exp(a_t) * S_{t-1} + dt_t * B_t x_t^T ;  y_t = C_t . S_t
    Returns (y (B,S,H,P), final state (B,H,N,P))."""
    b, s, h, p = x.shape
    n = B_.shape[-1]
    f32 = jnp.float32
    x, dt, a = x.astype(f32), dt.astype(f32), a.astype(f32)
    B_, C_ = B_.astype(f32), C_.astype(f32)

    def step(S, inp):
        xt, dtt, at, Bt, Ct = inp
        S = S * jnp.exp(at)[:, :, None, None] + jnp.einsum(
            "bn,bh,bhp->bhnp", Bt, dtt, xt)
        y = jnp.einsum("bn,bhnp->bhp", Ct, S)
        return S, y

    S0 = jnp.zeros((b, h, n, p), f32)
    S, ys = jax.lax.scan(step, S0, (x.swapaxes(0, 1), dt.swapaxes(0, 1),
                                    a.swapaxes(0, 1), B_.swapaxes(0, 1),
                                    C_.swapaxes(0, 1)))
    return ys.swapaxes(0, 1), S
