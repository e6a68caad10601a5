"""Compile the scheduler's device programs for a described TPU v5e chip.

No chip is needed: the TPU compiler compiles for a topology that is described
and not attached, and raises what the chip's compiler would raise (block
shapes that break the tiling rule, dtypes Mosaic lacks). Nothing runs, so
these tests say nothing about results or times.

The topology is described inside a module-scoped fixture, never at import
time: only one process at a time may load the TPU library, and the test
workers all import this file.
"""
from __future__ import annotations

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import jax_coop, jax_general, jax_solve
from repro.kernels.envy import envy_gaps
from repro.kernels.waterfill import waterfill_masses

K = 3  # device types in the paper fleet and the service ladders


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


def test_noncoop_solve_compiles_in_float64_without_a_kernel(spec):
    """The service's top bucket (1024 tenants), warm-started variant: the
    default jnp path, so no float64 reaches a Pallas kernel."""
    n = 1024
    with jax_solve.x64_scope():
        f64 = jnp.float64
        text = jax_solve._solve_padded.lower(
            spec((n, K), f64), spec((K,), f64), spec((n,), f64), spec((), f64),
            use_hint=True).compile().as_text()
    assert "tpu_custom_call" not in text


def test_coop_segment_compiles_in_float64_without_a_kernel(spec):
    G = 16
    with jax_solve.x64_scope():
        f64 = jnp.float64
        text = jax_coop._pd_segment.lower(
            spec((G, K), f64), spec((G,), f64), spec((K,), f64),
            spec((G, G), f64), spec((G, K), f64), spec((G,), f64),
            spec((), f64), spec((G, K), f64), spec((K,), f64),
            spec((G, G), f64)).compile().as_text()
    assert "tpu_custom_call" not in text


@pytest.mark.parametrize("G", [16, 256])
def test_envy_kernel_compiles_in_float32(spec, G):
    f32 = jnp.float32
    text = jax.jit(envy_gaps).lower(
        spec((G, K), f32), spec((G, K), f32)).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n, lanes", [(1024, 8), (1024, 1), (16, 16)])
def test_waterfill_kernel_compiles_in_float32(spec, n, lanes):
    """Several user tiles (1024), one tile narrower than 128 lanes (16), the
    multisection's lane count and the single warm-start probe."""
    f32 = jnp.float32
    text = jax.jit(waterfill_masses).lower(
        spec((lanes,), f32), spec((n, K), f32), spec((K,), f32),
        spec((n,), f32)).compile().as_text()
    assert "tpu_custom_call" in text


def test_general_search_segment_compiles_in_float64(spec):
    """The general non-cooperative price search on the TPU fleet (k = 4) at
    the top bucket, float64 throughout."""
    G, k = 1024, 4
    with jax_solve.x64_scope():
        f64 = jnp.float64
        text = jax_general._search_segment.lower(
            spec((G, k), f64), spec((G,), f64), spec((k,), f64),
            spec((k,), f64), spec((), f64)).compile().as_text()
    assert "tpu_custom_call" not in text
