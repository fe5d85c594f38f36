"""Ahead-of-time compiles of the Pallas kernels the TPU path selects, for
a described (not attached) TPU v5e: Mosaic refuses here what it would
refuse on the chip — unaligned block shapes, VMEM overruns — at no chip
time. Nothing runs, so these check compilation only; numerics are the
interpret-mode sweeps in ``test_kernels.py``.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU compiler library, and every xdist worker
imports every test file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.chunked_prefill import chunked_prefill_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_decode_attention

# (H, Hkv, D) of the served models: qwen3-4b (GQA group 4, D 128) and
# smollm-135m (group 3, D 64); the small layout is the one the former
# native test compiled
WIDTHS = {"qwen3-4b": (32, 8, 128), "smollm-135m": (9, 3, 64),
          "small": (8, 2, 128)}
PAGE = 16
# the served cell's decode dispatch: 8 slots x 128 pages read from
# qwen3-4b's stacked pool of 36 layers x 1025 pages at a given layer
CELL, CELL_POOL = (8, 128), (36, 1025)
# the paged decode kernel also serves musicgen-large (32 KV heads of 64)
# and paligemma-3b (one KV head of 256)
DECODE_WIDTHS = {**WIDTHS, "musicgen-large": (32, 32, 64),
                 "paligemma-3b": (8, 1, 256)}
# the decode kernel reads the pool in place: any temporary it needs is
# far below one layer's pool (2.6 MB for qwen3-4b's 1025 pages)
DECODE_TEMP_BYTES = 1 << 20


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _kernel_in(compiled):
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("width", sorted(DECODE_WIDTHS))
@pytest.mark.parametrize("B,N", [(4, 128), (2, 4), CELL])
def test_paged_decode_compiles_for_v5e(one_chip, width, B, N):
    """Compiles for a v5e and never copies the pool: a view of the pool
    that is not a bitcast would show as a temporary of the pool's size.
    The exception is a stacked pool of heads narrower than the 128 lanes,
    which XLA's default layout stores with the page index in the lanes,
    so the pool is laid out again for any Mosaic kernel that reads it."""
    H, Hkv, D = DECODE_WIDTHS[width]
    pages = CELL_POOL if (B, N) == CELL else (256,)
    pool = ((*pages, PAGE, Hkv, D), jnp.bfloat16)
    c = _compile(lambda q, k, v, bt, vl, ly: paged_decode_attention(
        q, k, v, bt, vl, layer=ly, interpret=False), one_chip,
        ((B, H, D), jnp.bfloat16), pool, pool,
        ((B, N), jnp.int32), ((B,), jnp.int32), ((), jnp.int32))
    assert _kernel_in(c)
    if D % 128 == 0 or len(pages) == 1:
        assert c.memory_analysis().temp_size_in_bytes <= DECODE_TEMP_BYTES


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("B,T,N", [(4, 64, 128), (2, 32, 4), (1, 256, 32)])
def test_chunked_prefill_compiles_for_v5e(one_chip, width, B, T, N):
    H, Hkv, D = WIDTHS[width]
    pool = ((256, PAGE, Hkv, D), jnp.bfloat16)
    c = _compile(lambda q, k, v, bt, sp: chunked_prefill_attention(
        q, k, v, bt, sp, interpret=False), one_chip,
        ((B, T, H, D), jnp.bfloat16), pool, pool,
        ((B, N), jnp.int32), ((B,), jnp.int32))
    assert _kernel_in(c)


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_flash_attention_compiles_for_v5e(one_chip, width):
    H, Hkv, D = WIDTHS[width]
    S = 512
    c = _compile(lambda q, k, v: flash_attention(q, k, v, interpret=False),
                 one_chip, ((H, S, D), jnp.bfloat16),
                 ((Hkv, S, D), jnp.bfloat16), ((Hkv, S, D), jnp.bfloat16))
    assert _kernel_in(c)
