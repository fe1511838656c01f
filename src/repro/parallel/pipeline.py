"""Pipeline parallelism: GPipe-style microbatch pipeline over a mesh axis.

The missing member of the DP/TP/EP/SP family for the archs whose bf16
weights exceed the per-device HBM at pure TP (qwen1.5-110b, qwen3-235b —
see EXPERIMENTS.md §HBM-fit audit): layers are split into S contiguous
stages sharded over a mesh axis; activations flow stage-to-stage through
``lax.ppermute`` (the Gleam mapping: a stage handoff is a one-hop
unicast on the distribution tree; the pipeline IS the overlay chain of
Fig. 2b, deployed where it is the right tool).

``pipeline(fn, n_microbatches)`` runs inside shard_map:

    y = pipeline(stage_fn, mb)(params_stage, x)

- ``params_stage``: this device's stage slice (layers sharded over the
  axis OUTSIDE, dim 0).
- ``x``: (n_micro, mb, ...) microbatched inputs, replicated.
- schedule: n_micro + n_stages - 1 ticks; tick t feeds microbatch t to
  stage 0, bubbles fill/drain as usual; each device computes its stage
  on the activation it received and ppermutes the result forward.

The primitive is intentionally self-contained (a nested shard_map inside
the model's attention shard_map is not composable), with correctness
tests against the unpipelined reference on an 8-device host mesh.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from jax.lax import axis_size


def pipeline(stage_fn, axis_name: str):
    """Build a pipelined runner for ``stage_fn(stage_params, x) -> y``.

    Must be called inside shard_map; the stage axis is ``axis_name``.
    Input x: (n_micro, ...) stacked microbatches (same value on every
    stage; only stage 0 consumes it).  Output: (n_micro, ...) results
    (valid on the LAST stage; callers ppermute/broadcast as needed).
    """

    def run(stage_params, xs):
        n_stages = axis_size(axis_name)
        sid = jax.lax.axis_index(axis_name)
        n_micro = xs.shape[0]
        ticks = n_micro + n_stages - 1
        perm = [(i, i + 1) for i in range(n_stages - 1)]   # forward chain

        buf = jnp.zeros_like(xs)           # completed microbatches (last)
        carry = jnp.zeros_like(xs[0])      # activation entering this stage

        def tick(state, t):
            buf, carry = state
            # stage 0 ingests microbatch t (zeros once drained)
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            feed = jnp.where(t < n_micro, xs[mb_idx], jnp.zeros_like(carry))
            x_in = jnp.where(sid == 0, feed, carry)
            y = stage_fn(stage_params, x_in)
            # the microbatch leaving the LAST stage at tick t is t-(S-1)
            out_idx = t - (n_stages - 1)
            buf = jnp.where(
                (sid == n_stages - 1) & (out_idx >= 0),
                jax.lax.dynamic_update_index_in_dim(
                    buf, y, jnp.clip(out_idx, 0, n_micro - 1), 0),
                buf)
            carry = jax.lax.ppermute(y, axis_name, perm)
            return (buf, carry), None

        (buf, _), _ = jax.lax.scan(tick, (buf, carry), jnp.arange(ticks))
        return buf

    return run


def pipeline_stages(stacked_params, n_stages: int):
    """Reshape (L, ...) stacked layer params to (S, L/S, ...) stage-major
    so dim 0 shards over the stage axis."""
    def reshape(p):
        l = p.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return p.reshape((n_stages, l // n_stages) + p.shape[1:])
    return jax.tree.map(reshape, stacked_params)
