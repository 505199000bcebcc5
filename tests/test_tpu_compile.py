"""Compile the main-path Pallas kernels for a described TPU v5e (no chip
needed): the TPU compiler refuses here what interpret mode accepts —
unlowerable primitives, VMEM over the limit, unsupported relayouts or
compares.  Each kernel is compiled at a real width, with the tile plan
``dispatch.plan`` picks for the compiled backend.

The topology is described inside a module fixture (never at import), so
every xdist worker collects the same tests and only the worker running
this file loads the TPU compiler."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import local_ops
from repro.kernels import dispatch
from repro.kernels.band_count import band_count
from repro.kernels.fused_select import (byte_histogram, fused_select,
                                        fused_select_multi)
from repro.kernels.partition_count import partition_count
from repro.kernels.segmented_select import segmented_select

N = 1 << 23                 # one partition of the paper's 120 x 2^23 job
CAP_PAD = 100_736           # cap at eps=1e-4 over 10^9 values, lane-padded


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _tile(dtype, **plan_kw):
    p = dispatch.plan("pallas_tpu", "kernel", dtype, N, **plan_kw)
    assert p.backend.name == "pallas_tpu", p.reason
    return p, (N // p.lanes, p.lanes)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_partition_count(one_chip, dtype):
    p, shape = _tile(dtype)
    _compile(lambda x, piv: partition_count(
        x, piv, n_valid=N, block_rows=p.block_rows, interpret=False),
        _spec(one_chip, shape, dtype), _spec(one_chip, (), dtype))


def test_band_count(one_chip):
    p, shape = _tile(jnp.float32)
    f32 = jnp.float32
    _compile(lambda x, lo, hi: band_count(
        x, lo, hi, n_valid=N, block_rows=p.block_rows, interpret=False),
        _spec(one_chip, shape, f32), _spec(one_chip, (), f32),
        _spec(one_chip, (), f32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_fused_select(one_chip, dtype):
    p, shape = _tile(dtype, footprint=dispatch._select_footprint(
        dtype, 1, CAP_PAD))
    _compile(lambda x, piv: fused_select(
        x, piv, n_valid=N, cap_pad=CAP_PAD, block_rows=p.block_rows,
        interpret=False),
        _spec(one_chip, shape, dtype), _spec(one_chip, (), dtype))


def test_fused_select_multi(one_chip):
    f32, Q = jnp.float32, 4
    p, shape = _tile(f32, footprint=dispatch._select_footprint(
        f32, Q, CAP_PAD))
    _compile(lambda x, piv: fused_select_multi(
        x, piv, n_valid=N, cap_pad=CAP_PAD, block_rows=p.block_rows,
        interpret=False),
        _spec(one_chip, shape, f32), _spec(one_chip, (Q,), f32))


def test_segmented_select(one_chip):
    f32, G, Q, cap_pad = jnp.float32, 64, 2, 256
    p, shape = _tile(f32, footprint=dispatch._select_footprint(
        f32, G * Q, cap_pad, streams=2))
    _compile(lambda x, k, piv: segmented_select(
        x, k, piv, n_valid=N, cap_pad=cap_pad, num_groups=G,
        block_rows=p.block_rows, interpret=False),
        _spec(one_chip, shape, f32), _spec(one_chip, shape, jnp.int32),
        _spec(one_chip, (G, Q), f32))


def test_byte_histogram(one_chip):
    p, shape = _tile(jnp.uint32)
    u32 = jnp.uint32
    _compile(lambda u, pre, mask: byte_histogram(
        u, pre, mask, n_valid=N, shift=8, block_rows=p.block_rows,
        interpret=False),
        _spec(one_chip, shape, u32), _spec(one_chip, (), u32),
        _spec(one_chip, (), u32))


@pytest.mark.parametrize("select", [local_ops.kth_smallest,
                                    local_ops.kth_largest])
def test_job_resolve_selection(one_chip, select):
    """The paper's job resolves over a (120, 2^23) float32 candidate
    buffer: the order-key bisection loop, with no sort and no copy of the
    4.03 GB buffer."""
    shape = (120, N)
    compiled = jax.jit(lambda c, k: select(c, k, N)).lower(
        _spec(one_chip, shape, jnp.float32),
        _spec(one_chip, (), jnp.int32)).compile()
    text = compiled.as_text()
    assert " while(" in text and "kth_bisect" in text
    assert " sort(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
