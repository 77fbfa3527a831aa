"""Compile-only checks of the Pallas kernels for a described TPU v5e chip.

Interpret mode (tests/test_kernels.py) cannot see what the TPU compiler
refuses: unaligned tiles, too much VMEM, ops Mosaic cannot lower. These
tests lower each kernel at the widths the chip smoke runs and compile it
for one chip of a ``v5e:2x2`` topology that is described, not attached.
Nothing runs, so they say nothing about results or times.

The topology is described inside a fixture — never at import — because only
one process at a time may load the TPU compiler library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.colocate.ops import colocate_match
from repro.kernels.delta_encode.ops import changed_blocks
from repro.kernels.flash_attention import flash_attention


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without one: keep it out
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile_text(fn, *specs) -> str:
    return jax.jit(fn).lower(*specs).compile().as_text()


@pytest.mark.parametrize(
    "shape,dtype,chunk_bytes",
    [
        ((151936, 2048), jnp.bfloat16, 16 << 20),  # qwen3-1.7b embedding
        ((28, 1, 160, 8, 128), jnp.bfloat16, 1 << 20),  # serve KV cache leaf
        ((4, 2048, 6144), jnp.float32, 16 << 20),  # fp32 master FFN leaf
        ((2048,), jnp.bfloat16, 16 << 20),  # norm scale
    ],
    ids=["embed", "kv_cache", "ffn_master", "norm"],
)
def test_delta_encode_compiles(one_chip, shape, dtype, chunk_bytes):
    from repro.checkpoint.serializer import _chunk_rows

    rows = _chunk_rows(shape, jnp.dtype(dtype).itemsize, chunk_bytes)
    spec = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    text = _compile_text(lambda a, b: changed_blocks(a, b, rows, interpret=False), spec, spec)
    assert "tpu_custom_call" in text


def test_flash_attention_compiles(one_chip):
    b, h, hkv, s, d = 1, 16, 8, 4096, 128  # qwen3-1.7b heads at 4k context
    q = jax.ShapeDtypeStruct((b, h, s, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, hkv, s, d), jnp.bfloat16, sharding=one_chip)
    text = _compile_text(lambda q, k, v: flash_attention(q, k, v, interpret=False), q, kv, kv)
    assert "tpu_custom_call" in text


def test_colocate_compiles(one_chip):
    n, m = 6 * 1600 * 8, 6 * 30 * 9  # examples/navp_colocation.py granules
    u = jax.ShapeDtypeStruct((n, 3), jnp.float32, sharding=one_chip)
    los = jax.ShapeDtypeStruct((m, 3), jnp.float32, sharding=one_chip)
    text = _compile_text(lambda u, los: colocate_match(u, los, interpret=False), u, los)
    assert "tpu_custom_call" in text
