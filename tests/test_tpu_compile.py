"""The serving kernels compile for a TPU v5e at granite-3-8b widths.

Every other kernel test runs the Pallas interpreter, which accepts
shapes and accesses the TPU compiler refuses (a strided head slice, a
4-D query tile).  These tests compile the paged-attention kernels for a
*described* v5e chip — no chip attached — and check that each compiled
program holds the kernel (``tpu_custom_call``).  The topology is
described inside a fixture, so a worker that cannot load the TPU
compiler skips these tests instead of failing to collect them.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention import autotune
from repro.kernels.paged_attention import ops

#: granite-3-8b attention widths; serving pool and batch of chip_smoke.py
B, H, KV, HD, BS, M, N = 8, 32, 8, 128, 128, 32, 256
RAGGED_TOKENS = 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep it out of the cache
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("depth", [1, 2, 4, "tuned"])
def test_fused_decode_compiles(one_chip, depth):
    if depth == "tuned":
        depth = autotune.get_tuning(KV, HD, BS).buffer_depth
    _assert_kernel(
        lambda q, kv, t, n: ops.paged_attention(q, kv, t, n,
                                                buffer_depth=depth),
        _sds(one_chip, (B, H, HD)), _sds(one_chip, (N, 2, KV, BS, HD)),
        _sds(one_chip, (1, B, M), jnp.int32),
        _sds(one_chip, (B,), jnp.int32))


def test_split_decode_compiles(one_chip):
    _assert_kernel(
        ops.paged_attention_split,
        _sds(one_chip, (B, H, HD)), _sds(one_chip, (N, KV, BS, HD)),
        _sds(one_chip, (N, KV, BS, HD)), _sds(one_chip, (B, M), jnp.int32),
        _sds(one_chip, (B,), jnp.int32))


@pytest.mark.parametrize("window", [None, 1024])
def test_ragged_compiles(one_chip, window):
    tiles = RAGGED_TOKENS // ops.QT
    _assert_kernel(
        lambda q, kv, t, r, p, n: ops.ragged_paged_attention(
            q, kv, t, r, p, n, window=window),
        _sds(one_chip, (RAGGED_TOKENS, H, HD)),
        _sds(one_chip, (N, 2, KV, BS, HD)),
        _sds(one_chip, (1, B, M), jnp.int32),
        _sds(one_chip, (tiles,), jnp.int32),
        _sds(one_chip, (tiles,), jnp.int32),
        _sds(one_chip, (B,), jnp.int32))


@pytest.mark.parametrize("kernel", ["decode", "ragged"])
def test_f32_query_compiles(one_chip, kernel):
    """f32 queries over the bf16 pool, as chip_smoke.py's per-layer
    precision check feeds the kernels."""
    pool = _sds(one_chip, (N, 2, KV, BS, HD))
    table = _sds(one_chip, (1, B, M), jnp.int32)
    lens = _sds(one_chip, (B,), jnp.int32)
    if kernel == "decode":
        depth = autotune.get_tuning(KV, HD, BS).buffer_depth
        _assert_kernel(
            lambda q, kv, t, n: ops.paged_attention(q, kv, t, n,
                                                    buffer_depth=depth),
            _sds(one_chip, (B, H, HD), jnp.float32), pool, table, lens)
    else:
        tiles = RAGGED_TOKENS // ops.QT
        _assert_kernel(
            ops.ragged_paged_attention,
            _sds(one_chip, (RAGGED_TOKENS, H, HD), jnp.float32), pool,
            table, _sds(one_chip, (tiles,), jnp.int32),
            _sds(one_chip, (tiles,), jnp.int32), lens)
