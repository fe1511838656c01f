"""Per-architecture smoke tests (deliverable f).

Each assigned architecture instantiates its REDUCED config (same family:
MoE stays MoE, hybrid stays hybrid, enc-dec keeps its encoder) and runs
one train step and one decode step on CPU, asserting output shapes and
no NaNs.  The FULL configs are exercised only via the dry-run.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ARCH_IDS, get_config
from repro.launch import steps
from repro.launch.mesh import single_device_mesh
from repro.apps.collectives_lowering import param_count
from repro.models import model as mdl
from repro.models.blocks import count_params, init_params
from repro.models.model import model_defs
from repro.optim import adamw

SEQ, BATCH = 64, 2
#: the architectures the model layer builds (``model.supports``)
MODEL_ARCHS = tuple(a for a in ARCH_IDS
                    if mdl.supports(get_config(a, smoke=True)))


def _batch(cfg, *, train: bool, key=0):
    rng = jax.random.PRNGKey(key)
    structs = steps.batch_structs(cfg, SEQ, BATCH, train=train)
    out = {}
    for k, v in structs.items():
        kk, rng = jax.random.split(rng)[0], jax.random.split(rng)[1]
        if v.dtype == jnp.int32:
            out[k] = jax.random.randint(kk, v.shape, 0, cfg.vocab_size)
        elif k == "loss_mask":
            out[k] = jnp.ones(v.shape, v.dtype)
        else:
            out[k] = jax.random.normal(kk, v.shape, jnp.float32).astype(
                v.dtype)
    return out


@pytest.fixture(scope="module")
def mesh():
    return single_device_mesh()


@pytest.mark.parametrize("arch", MODEL_ARCHS)
def test_train_step(arch, mesh):
    cfg = get_config(arch, smoke=True)
    params = init_params(model_defs(cfg), jax.random.PRNGKey(0))
    opt_state = adamw.init(params)
    batch = _batch(cfg, train=True)
    step_fn = steps.make_train_step(cfg, mesh)
    with mesh:
        params2, opt2, metrics = jax.jit(step_fn)(params, opt_state, batch)
    loss = float(metrics["loss"])
    assert jnp.isfinite(loss), f"{arch}: non-finite loss {loss}"
    assert loss > 0.0
    assert float(metrics["grad_norm"]) > 0.0
    assert int(opt2["step"]) == 1
    # params actually moved
    moved = any(
        bool(jnp.any(a != b))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2)))
    assert moved, f"{arch}: optimizer step was a no-op"
    # loss decreases after a few steps on a fixed batch (sanity, not perf)
    for _ in range(3):
        params2, opt2, metrics2 = jax.jit(step_fn)(params2, opt2, batch)
    assert float(metrics2["loss"]) < loss, (
        f"{arch}: loss did not decrease ({loss} -> "
        f"{float(metrics2['loss'])})")


@pytest.mark.parametrize("arch", MODEL_ARCHS)
def test_forward_shapes_and_finite(arch, mesh):
    cfg = get_config(arch, smoke=True)
    params = init_params(model_defs(cfg), jax.random.PRNGKey(1))
    batch = _batch(cfg, train=False)
    with mesh:
        logits, aux = mdl.forward(params, batch, cfg, mesh)
    s_text = SEQ - cfg.vision_prefix if cfg.vision_prefix else SEQ
    assert logits.shape == (BATCH, s_text, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all()), f"{arch}: non-finite logits"
    if cfg.n_experts:
        assert float(aux) > 0.0, f"{arch}: MoE aux loss missing"


@pytest.mark.parametrize("arch", MODEL_ARCHS)
def test_decode_step(arch, mesh):
    cfg = get_config(arch, smoke=True)
    params = init_params(model_defs(cfg), jax.random.PRNGKey(2))
    caches = mdl.init_caches(cfg, BATCH, SEQ)
    serve = steps.make_serve_step(cfg, mesh, batch_shardable=False)
    tok = jnp.ones((BATCH, 1), jnp.int32)
    with mesh:
        jit_serve = jax.jit(serve)
        logits, caches = jit_serve(params, caches, tok, jnp.int32(0))
        logits2, caches = jit_serve(params, caches, tok, jnp.int32(1))
    assert logits.shape == (BATCH, 1, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())
    assert bool(jnp.isfinite(logits2).all())


@pytest.mark.parametrize("arch", MODEL_ARCHS)
def test_decode_matches_teacher_forcing(arch, mesh):
    """Prefill logits at position t == decode logits after feeding tokens
    0..t-1 — the KV-cache path must agree with the parallel path."""
    # f32 compute: this test checks PATH equivalence (cache vs parallel),
    # not bf16 accumulation noise (jamba's 8 heterogeneous sublayers show
    # ~0.45 max log-softmax drift in bf16; 1e-5 in f32).
    cfg = get_config(arch, smoke=True).replace(compute_dtype="float32")
    if cfg.enc_layers > 0:
        pytest.skip("enc-dec decode consumes a fixed encoder memory stub; "
                    "covered by test_decode_step")
    params = init_params(model_defs(cfg), jax.random.PRNGKey(3))
    n = 8
    toks = jax.random.randint(jax.random.PRNGKey(4), (1, n), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks}
    if cfg.vision_prefix:
        pytest.skip("VLM prefix offsets positions; covered by smoke tests")
    with mesh:
        full_logits, _ = mdl.forward(params, batch, cfg, mesh)
        caches = mdl.init_caches(cfg, 1, n, dtype=jnp.float32)
        dec = []
        for t in range(n):
            logits, caches = mdl.decode_forward(
                params, caches, toks[:, t:t + 1], jnp.int32(t), cfg, mesh,
                batch_shardable=False)
            dec.append(logits[:, 0])
    dec = jnp.stack(dec, axis=1)
    err = jnp.max(jnp.abs(jax.nn.log_softmax(full_logits)
                          - jax.nn.log_softmax(dec)))
    assert float(err) < 1e-3, f"{arch}: decode/prefill diverge, max={err}"


def test_all_archs_have_smoke_and_full():
    for arch in ARCH_IDS:
        full = get_config(arch)
        smoke = get_config(arch, smoke=True)
        assert full.name == smoke.name
        assert full.family == smoke.family
        # smoke must be materially smaller
        n = (count_params(model_defs(smoke)) if mdl.supports(smoke)
             else param_count(smoke))
        assert n < 1e7
