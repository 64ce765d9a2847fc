"""Compile-only checks of the Pallas kernels for a TPU v5e, with no chip.

The TPU compiler is installed with ``libtpu`` and compiles for a chip that
is described, not attached: each test lowers a kernel at real widths for
one chip of a described ``v5e:2x2`` topology and asserts the compiled
program holds the Mosaic kernel (``tpu_custom_call``).  Interpret mode on
the CPU cannot catch what only Mosaic refuses (block shapes off the
(8, 128) tiling, more VMEM than a kernel may use).

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every pytest worker
imports this file.  Keep these tests in this one file.
"""

import jax
import jax.numpy as jnp
import pytest


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A described chip's compiles cannot be read back from the cache."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_daxpy_compiles_for_v5e(one_chip):
    from repro.kernels.daxpy import daxpy_2d
    x = jax.ShapeDtypeStruct((4096, 128), jnp.float32, sharding=one_chip)
    a = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    text = _compiled_text(lambda a, x, y: daxpy_2d(a, x, y), a, x, x)
    assert "tpu_custom_call" in text


def test_fused_adamw_compiles_for_v5e(one_chip):
    from repro.kernels.fused_adamw import adamw_2d
    p = jax.ShapeDtypeStruct((4096, 128), jnp.bfloat16, sharding=one_chip)
    m = jax.ShapeDtypeStruct((4096, 128), jnp.float32, sharding=one_chip)
    hp = jax.ShapeDtypeStruct((1, 8), jnp.float32, sharding=one_chip)
    text = _compiled_text(lambda *a: adamw_2d(*a), p, p, m, m, hp)
    assert "tpu_custom_call" in text


def test_fused_decode_compiles_for_v5e_at_chatglm3_widths(one_chip):
    """chatglm3-6b decode attention (32 heads, 2 KV heads, head_dim 128,
    bf16, half rope) over the smoke test's 160-slot cache."""
    from repro.configs import get_config
    from repro.kernels.decode_attention import fused_decode_attention

    cfg = get_config("chatglm3-6b")
    b, s = 4, 160
    h, kh, d = cfg.num_heads, cfg.num_kv_heads, cfg.qk_head_dim
    w = d // 4                                  # half rope: d/2 rotated

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (sds((b, 1, h, d), jnp.bfloat16), sds((b, 1, kh, d), jnp.bfloat16),
            sds((b, 1, kh, d), jnp.bfloat16), sds((b, s, kh, d), jnp.bfloat16),
            sds((b, s, kh, d), jnp.bfloat16), sds((b,), jnp.int32),
            sds((b, 1, w), jnp.float32), sds((b, 1, w), jnp.float32))
    text = _compiled_text(
        lambda *a: fused_decode_attention(*a, interpret=False), *args)
    assert "tpu_custom_call" in text
