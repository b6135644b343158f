"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each test lowers a kernel at the shapes the chip runs and
compiles it for one chip of a described (not attached) v5e:2x2 topology,
so a tiling or VMEM refusal of the TPU compiler fails here.  Shapes:
phi4-mini-3.8b attention (32 padded heads, 8 kv heads, head_dim 128) at
train seq 2048 and the serving page pool, moonshot-v1-16b-a3b's expert
FFN, and the halo phase's 16384^2 float32 Jacobi grid.
"""

from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import (flash_attention_carry_pallas,
                                           flash_attention_pallas)
from repro.kernels.grouped_matmul import grouped_expert_ffn_pallas
from repro.kernels.paged_attention import paged_attention_pallas
from repro.kernels.stencil import jacobi_ksweep_pallas

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # noqa: BLE001 — any failure means "absent"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Compiles go to one described chip with the persistent compilation
    cache off: an entry written here could not be read back."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_flash_forward_compiles(one_chip):
    _compile(partial(flash_attention_pallas, causal=True), one_chip,
             ((1, 2048, 32, 128), BF16), ((1, 2048, 8, 128), BF16),
             ((1, 2048, 8, 128), BF16))


def test_flash_carry_compiles(one_chip):
    f32 = jnp.float32
    _compile(partial(flash_attention_carry_pallas, causal=True), one_chip,
             ((1, 2048, 32, 128), BF16), ((1, 2048, 8, 128), BF16),
             ((1, 2048, 8, 128), BF16), ((1, 2048, 32), f32),
             ((1, 2048, 32), f32), ((1, 2048, 32, 128), f32))


@pytest.mark.parametrize("page", [8, 16])
def test_paged_attention_compiles(one_chip, page):
    """4 slots of 288 positions (256-token prompts + 32 new tokens)."""
    pages = 4 * 288 // page
    _compile(paged_attention_pallas, one_chip, ((4, 32, 128), BF16),
             ((pages, page, 8, 128), BF16), ((pages, page, 8, 128), BF16),
             ((4, 288 // page), jnp.int32), ((4,), jnp.int32))


def test_grouped_expert_ffn_compiles(one_chip):
    """64 experts, d_model 2048, d_ff 1408 (tiled to fit VMEM)."""
    _compile(partial(grouped_expert_ffn_pallas, mlp="swiglu"), one_chip,
             ((64, 256, 2048), BF16), ((64, 2048, 1408), BF16),
             ((64, 2048, 1408), BF16), ((64, 1408, 2048), BF16),
             ((64,), jnp.int32))


def test_stencil_ksweep_compiles(one_chip):
    """k = 8 sweeps over a 16384^2 float32 shard with 8-row aprons."""
    n, k = 16384, 8
    _compile(lambda u, f: jacobi_ksweep_pallas(u, f, k, k, k), one_chip,
             ((n + 2 * k, n), jnp.float32), ((n + 2 * k, n), jnp.float32))
