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


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("B,N", [(4, 128), (2, 4)])
def test_paged_decode_compiles_for_v5e(one_chip, width, B, N):
    H, Hkv, D = WIDTHS[width]
    pool = ((256, PAGE, Hkv, D), jnp.bfloat16)
    c = _compile(lambda q, k, v, bt, vl: paged_decode_attention(
        q, k, v, bt, vl, interpret=False), one_chip,
        ((B, H, D), jnp.bfloat16), pool, pool,
        ((B, N), jnp.int32), ((B,), jnp.int32))
    assert _kernel_in(c)


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
