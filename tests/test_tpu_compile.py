"""Compile-only checks of the main path's Pallas kernels for a TPU v5e chip.

The TPU compiler is installed even where no chip is attached: it compiles
for a described ``v5e:2x2`` topology, and refuses what the chip would
refuse (unaligned block shapes, primitives Mosaic cannot lower, kernels
that would need partitioning).  Interpret mode on the CPU sees none of
that.  Nothing runs here, so results are checked elsewhere — bit for bit
against the oracles in ``tests/test_wan_codec.py`` (interpret mode) and in
``chip_smoke.py`` (on the chip).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and only the worker given this file
does.  JAX's persistent compilation cache is off around these compiles (an
entry compiled for a described chip cannot be read back without one).
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ref
from repro.kernels.wan_codec import (DEFAULT_BLOCK, k_per_block,
                                     wan_decode_pallas, wan_encode_pallas)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_codec(one_chip, n: int, k_block: int, value_dtype: str):
    """Compile encode and decode for one chip; return their HLO texts."""
    x = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    enc = wan_encode_pallas.lower(x, k_block, value_dtype=value_dtype
                                  ).compile()
    wire = jax.eval_shape(functools.partial(
        ref.wan_encode, k_block=k_block, value_dtype=value_dtype), x)
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in wire]
    dec = wan_decode_pallas.lower(*args, n, value_dtype=value_dtype
                                  ).compile()
    return enc.as_text(), dec.as_text()


@pytest.mark.parametrize("value_dtype", ["int8", "fp8", "int4"])
def test_codec_compiles_for_v5e_at_full_size(one_chip, value_dtype):
    """n = 2^24, block 4096, top-k 0.02: the sync round's kernel shapes."""
    kb = k_per_block(DEFAULT_BLOCK, 0.02)
    for hlo in _compile_codec(one_chip, 1 << 24, kb, value_dtype):
        assert 'custom_call_target="tpu_custom_call"' in hlo


def test_codec_compiles_for_v5e_small_bucket(one_chip):
    """A bucket shorter than one block: block = n, not a lane multiple."""
    for hlo in _compile_codec(one_chip, 300, k_per_block(300, 0.02),
                              "int8"):
        assert 'custom_call_target="tpu_custom_call"' in hlo


def test_codec_compiles_for_v5e_above_128_winners(one_chip):
    """n = 2^24, top-k 0.05: 205 winners a block, so the gather and the
    scatter each take two passes of 128 slots."""
    kb = k_per_block(DEFAULT_BLOCK, 0.05)
    assert kb > 128
    for hlo in _compile_codec(one_chip, 1 << 24, kb, "int8"):
        assert 'custom_call_target="tpu_custom_call"' in hlo
