"""Pipeline-parallel primitive: GPipe schedule == unpipelined reference
(8-stage mesh in a subprocess)."""
from __future__ import annotations

import pytest

from tests.conftest import run_devices

SRC = r"""
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.parallel.pipeline import pipeline, pipeline_stages

S = 8            # stages
L = 16           # layers (2 per stage)
D = 32
N_MICRO = 4
MB = 2

mesh = jax.make_mesh((S,), ("stage",))
key = jax.random.PRNGKey(0)
w = jax.random.normal(key, (L, D, D)) * 0.3
b = jax.random.normal(jax.random.PRNGKey(1), (L, D)) * 0.1
xs = jax.random.normal(jax.random.PRNGKey(2), (N_MICRO, MB, D))

def layer(p, x):
    wi, bi = p
    return jnp.tanh(x @ wi + bi)

def stage_fn(stage_params, x):
    def body(xx, p):
        return layer(p, xx), None
    out, _ = jax.lax.scan(body, x, stage_params)
    return out

# ---- reference: plain sequential layers over each microbatch
def reference(xs):
    def full(x):
        out, _ = jax.lax.scan(lambda xx, p: (layer(p, xx), None), x, (w, b))
        return out
    return jax.vmap(full)(xs)

want = np.asarray(reference(xs))

# ---- pipelined: layers stage-major, sharded over "stage"
staged = pipeline_stages((w, b), S)          # (S, L/S, ...)

def body(stage_params, xs):
    # shard_map keeps the size-1 stage dim on the local block: squeeze
    stage_params = jax.tree.map(lambda p: p[0], stage_params)
    out = pipeline(stage_fn, "stage")(stage_params, xs)
    # results live on the LAST stage; every other stage holds zeros, so a
    # psum over the stage axis is a broadcast (Gleam one-to-many, again)
    return jax.lax.psum(out, "stage")

f = shard_map(body, mesh=mesh,
              in_specs=((P("stage"), P("stage")), P()),
              out_specs=P(), check_vma=False)
got = np.asarray(jax.jit(f)(staged, xs))
np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

# bubble accounting: ticks = n_micro + S - 1
print("PIPELINE_OK")
"""


@pytest.mark.slow
def test_gpipe_pipeline_matches_reference():
    out = run_devices(SRC, n_devices=8)
    assert "PIPELINE_OK" in out
