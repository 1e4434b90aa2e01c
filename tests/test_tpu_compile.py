"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Nothing runs: each test lowers a kernel at gpt2-small widths for one chip
of a ``v5e:2x2`` topology that the installed TPU compiler describes without
the hardware, and checks that Mosaic accepted it (a ``tpu_custom_call`` in
the compiled HLO). This catches what interpret mode cannot: block shapes
the TPU tiling refuses, and kernels that need more fast memory than a core
has. The topology is described inside a fixture, never at import, so every
test worker collects the same tests.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import paged_decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.pier_update import pier_update
from repro.kernels.quantize import dequantize_blockwise, quantize_blockwise
from repro.kernels.rmsnorm import rmsnorm

# gpt2-small (configs/gpt2_small.py): d_model 768, 12 heads of 64, d_ff 3072
D, H, HD, FF, SEQ = 768, 12, 64, 3072, 1024


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    # compiles for a described chip land in the persistent cache but can
    # never be read back without one: keep the cache out of these tests
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _assert_mosaic(fn, *args):
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("n", [D * FF, D], ids=["flat-2.4M", "ragged-768"])
def test_pier_update_compiles(chip, n):
    vec = _spec(chip, (n,), jnp.float32)
    scalar = _spec(chip, (), jnp.float32)
    _assert_mosaic(lambda a, m, d, mu, lr: pier_update(
        a, m, d, mu, lr, interpret=False), vec, vec, vec, scalar, scalar)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
def test_flash_attention_compiles(chip, dtype):
    qkv = _spec(chip, (1, SEQ, H, HD), dtype)
    _assert_mosaic(lambda q, k, v: flash_attention(q, k, v, interpret=False),
                   qkv, qkv, qkv)


def test_rmsnorm_compiles(chip):
    _assert_mosaic(lambda x, s: rmsnorm(x, s, interpret=False),
                   _spec(chip, (8 * SEQ, D), jnp.float32),
                   _spec(chip, (D,), jnp.float32))


@pytest.mark.parametrize("n", [D * FF, D + 5], ids=["flat-2.4M", "ragged"])
def test_quantize_dequantize_compile(chip, n):
    _assert_mosaic(lambda x: quantize_blockwise(x, interpret=False),
                   _spec(chip, (n,), jnp.float32))
    nb = -(-n // 256)
    _assert_mosaic(lambda q, s: dequantize_blockwise(q, s, interpret=False),
                   _spec(chip, (nb * 256,), jnp.int8),
                   _spec(chip, (nb,), jnp.float32))


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_decode_attention_compiles(chip, int8):
    B, N, BS, T = 8, 64, 16, 8  # 8 sequences of up to 128 tokens
    pool_dtype = jnp.int8 if int8 else jnp.bfloat16
    args = [_spec(chip, (B, H, HD), jnp.bfloat16),
            _spec(chip, (N, BS, H, HD), pool_dtype),
            _spec(chip, (N, BS, H, HD), pool_dtype),
            _spec(chip, (B, T), jnp.int32),
            _spec(chip, (B,), jnp.int32)]
    if int8:
        args += [_spec(chip, (N, BS, H), jnp.float32)] * 2
    _assert_mosaic(lambda *a: paged_decode_attention(*a, interpret=False),
                   *args)
