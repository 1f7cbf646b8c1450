"""Compile every Pallas kernel for a described TPU v5e chip at real widths.

Nothing runs: the TPU compiler (installed beside jax) compiles for a
chip that is described, not attached, and refuses what the chip would
refuse -- unaligned tiles, scalar stores to vector memory, blocks that
break the (8, 128) tiling rule.  Interpret mode cannot see any of that.

The topology is described inside a module-scoped fixture, never at
import: only one process may hold the TPU library, so describing it
while pytest-xdist workers import this module would make the workers
collect different tests.  Keep these compiles in this one file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# smollm-360m decode: 15 query heads over 5 KV heads of width 64, a
# batch of 4 slots, 16-token pages, 10 pages per slot
B, H, KV, HD, BS, PAGES = 4, 15, 5, 64, 16, 10
NUM_BLOCKS = B * PAGES


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("T", [1, 32])
def test_paged_decode_attention_compiles(one_chip, T):
    """The served paged attention at smollm-360m's cell: 64 slots of 128
    pages over a 1,792-block pool stacked for 32 layers, one token a
    slot (decode bursts) and a 32-token chunk (mixed steps)."""
    from repro.kernels.decode_attention.kernel import paged_attention
    from repro.models.attention import paged_page_shape
    s = functools.partial(_spec, one_chip)
    slots, pages, blocks, layers = 64, 128, 1792, 32
    pool = s((layers, blocks, KV) + paged_page_shape(BS, HD), jnp.bfloat16)
    _compile(functools.partial(paged_attention, interpret=False),
             s((slots, T, H, HD), jnp.bfloat16), pool, pool,
             s((slots, pages), jnp.int32), s((slots,), jnp.int32),
             s((slots,), jnp.int32), s((), jnp.int32))


def test_paged_decode_attention_quant_compiles(one_chip):
    from repro.kernels.decode_attention.kernel import \
        paged_decode_attention_quant
    s = functools.partial(_spec, one_chip)
    _compile(functools.partial(paged_decode_attention_quant, interpret=False),
             s((B, H, HD), jnp.bfloat16),
             s((NUM_BLOCKS, KV, BS, HD), jnp.int8),
             s((NUM_BLOCKS, KV, BS, HD), jnp.int8),
             s((NUM_BLOCKS, KV, BS), jnp.float32),
             s((NUM_BLOCKS, KV, BS), jnp.float32),
             s((B, PAGES), jnp.int32), s((B,), jnp.int32))


def test_decode_attention_compiles(one_chip):
    from repro.kernels.decode_attention.kernel import decode_attention
    s = functools.partial(_spec, one_chip)
    _compile(functools.partial(decode_attention, block_k=512,
                               interpret=False),
             s((B, H, HD), jnp.bfloat16),
             s((B, KV, 2048, HD), jnp.bfloat16),
             s((B, KV, 2048, HD), jnp.bfloat16),
             s((), jnp.int32))


def test_flash_attention_compiles(one_chip):
    from repro.kernels.flash_attention.kernel import flash_attention
    s = functools.partial(_spec, one_chip)
    _compile(functools.partial(flash_attention, causal=True, interpret=False),
             s((1, H, 2048, HD), jnp.bfloat16),
             s((1, KV, 2048, HD), jnp.bfloat16),
             s((1, KV, 2048, HD), jnp.bfloat16))


def test_transform_compiles(one_chip):
    """One 640x480 RGB camera frame, uint8 in, float32 out."""
    from repro.kernels.transform.ops import fused_transform
    _compile(functools.partial(fused_transform, scale=1 / 255.0, bias=-0.5,
                               out_dtype=jnp.float32, interpret=False),
             _spec(one_chip, (480, 640, 3), jnp.uint8))


def test_moe_gating_compiles(one_chip):
    from repro.kernels.moe_gating.ops import topk
    _compile(functools.partial(topk, k=8, interpret=False),
             _spec(one_chip, (4096, 64), jnp.float32))


def test_ssm_scan_compiles(one_chip):
    """Jamba's mamba layers: d_inner 8192, d_state 16, a 2048-token
    bf16 prefill."""
    from repro.kernels.ssm_scan.ops import selective_scan
    S, di, N = 2048, 8192, 16
    s = functools.partial(_spec, one_chip)
    _compile(functools.partial(selective_scan, interpret=False),
             s((1, S, di), jnp.bfloat16), s((1, S, N), jnp.bfloat16),
             s((1, S, N), jnp.bfloat16), s((1, S, di), jnp.bfloat16),
             s((di, N), jnp.float32), s((di,), jnp.float32))
