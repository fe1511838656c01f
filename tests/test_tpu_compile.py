"""The device solvers compile for a TPU v5e chip at the chip smoke run's
shapes (``chip_smoke.py``), with no chip attached.

The float64 epoch solver takes the dense filling round at
``dsv3ep2k.route``'s packed shape (no gather or scatter in the loop)
and the indexed round at the fleet's, or wherever the call's
incidences would outgrow ``maxmin.DENSE_MAX_ENTRIES``.

The TPU compiler is installed beside JAX and compiles for a described
topology; nothing runs, so these tests say nothing about results or
speed — only that the chip's compiler accepts each program and that
it fits the chip's 16 GB of HBM.  The topology is described inside a
fixture (only one process at a time may load the TPU library, and
every test worker imports this file).
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.flowsim_jax import _seg_solver, _solver
from repro.kernels import maxmin

HBM_BYTES = 16e9

# phase A (16,384-host fleet): one (F, H) epoch bucket over 50,176 links
FLEET = dict(batch=1, flows=2048, hops=32, caps=50177)
# phase B (4,096-host matrix): the largest dynamic-segment bucket
SEGMENTS = dict(lanes=80, flows=16, hops=64, caps=10369)
# the benchmark's dsv3ep2k.route: dispatch and combine, 448 flows each,
# over the 128 links they cross and the sentinel
DSV3 = dict(batch=2, flows=512, hops=8, caps=129)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:              # noqa: BLE001 - any describe error
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits(compiled):
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert total < HBM_BYTES, total


def _indexed_ops(compiled):
    """Gathers and scatters in a compiled program's HLO."""
    return re.findall(r"\b(?:gather|scatter)\(", compiled.as_text())


def _epoch_args(sharding, dtype, lossy, shape=FLEET):
    b, f, h, c = (shape[k] for k in ("batch", "flows", "hops", "caps"))
    args = [_spec((b, f, h), jnp.int32, sharding),
            _spec((c,), dtype, sharding), _spec((b, f), dtype, sharding)]
    if lossy:
        args.append(tuple(_spec((b, f), dtype, sharding) for _ in range(4)))
    return args


def test_fleet_epoch_solver_float32_lossy_compiles(one_chip):
    _fits(_solver(True, True).lower(
        *_epoch_args(one_chip, jnp.float32, lossy=True)).compile())


def test_fleet_epoch_solver_float32_lossless_compiles(one_chip):
    _fits(_solver(True, False).lower(
        *_epoch_args(one_chip, jnp.float32, lossy=False)).compile())


def test_epoch_solver_float64_compiles(one_chip):
    with jax.enable_x64(True):
        compiled = _solver(True, True).lower(
            *_epoch_args(one_chip, jnp.float64, lossy=True)).compile()
    _fits(compiled)
    assert _indexed_ops(compiled)           # over the bound: indexed


def test_dsv3_epoch_solver_float64_takes_the_dense_round(one_chip):
    with jax.enable_x64(True):
        compiled = _solver(True, False).lower(
            *_epoch_args(one_chip, jnp.float64, lossy=False,
                         shape=DSV3)).compile()
    _fits(compiled)
    assert not _indexed_ops(compiled)


def test_epoch_solver_float64_bound_counts_the_lanes(one_chip):
    """One lane more than ``DENSE_MAX_ENTRIES`` holds at dsv3's lane
    shape puts the whole call on the indexed round."""
    lanes = 1 + maxmin.DENSE_MAX_ENTRIES // (DSV3["flows"]
                                             * (DSV3["caps"] - 1))
    with jax.enable_x64(True):
        compiled = _solver(True, False).lower(
            *_epoch_args(one_chip, jnp.float64, lossy=False,
                         shape=dict(DSV3, batch=lanes))).compile()
    _fits(compiled)
    assert _indexed_ops(compiled)


def test_segment_solver_float64_compiles(one_chip):
    n, f, h, c = (SEGMENTS[k] for k in ("lanes", "flows", "hops", "caps"))
    with jax.enable_x64(True):
        f64 = jnp.float64
        _fits(_seg_solver().lower(
            _spec((n, f, h), jnp.int32, one_chip),
            _spec((n, f), f64, one_chip), _spec((n,), jnp.int32, one_chip),
            _spec((c,), f64, one_chip),
            tuple(_spec((n, f), f64, one_chip) for _ in range(4))).compile())
