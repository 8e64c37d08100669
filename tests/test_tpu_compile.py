"""The four Pallas kernels compile for a TPU v5e at real widths.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology, and the program must hold a Mosaic kernel (``tpu_custom_call``).
Interpret-mode tests cannot catch what this refuses (tile-illegal blocks,
primitives Mosaic cannot lower, unaligned packed loads).  The topology is
described inside a fixture, never at import: only one process may load the
TPU library, and every test worker imports this file.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.kernel import flash_attention_hmajor
from repro.kernels.mlstm_chunk.kernel import mlstm_chunk_pallas
from repro.kernels.quantize.kernel import quantize_dequantize_pallas
from repro.kernels.rglru_scan.kernel import rglru_scan_pallas

DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A described-device compile is written to the persistent cache but
    cannot be read back without a chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _assert_mosaic(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", DTYPES)
def test_flash_attention_compiles(one_chip, no_compile_cache, dtype):
    # 16 query heads over 8 kv heads of 128, 4k tokens
    fn = lambda q, k, v: flash_attention_hmajor(q, k, v, interpret=False)
    _assert_mosaic(one_chip, fn, ((1, 16, 4096, 128), dtype),
                   ((1, 8, 4096, 128), dtype), ((1, 8, 4096, 128), dtype))


@pytest.mark.parametrize("sq,sk,causal,q_dtype,kv_dtype", [
    (1024, 1024, False, jnp.float32, jnp.float32),     # encoder self
    (512, 512, True, jnp.bfloat16, jnp.bfloat16),      # decoder self
    (512, 1024, False, jnp.bfloat16, jnp.float32),     # cross
])
def test_flash_attention_training_compiles(one_chip, no_compile_cache,
                                           monkeypatch, sq, sk, causal,
                                           q_dtype, kv_dtype):
    """SeamlessM4T-medium's training attention (16 heads of 64, 2 clients
    vmapped, the dtypes its round feeds): the kernel forward and the
    blocked backward compile together, and the kernel is in the program.
    The platform here is the CPU, so the test makes the kernel compile
    rather than interpret."""
    from repro.kernels.flash_attention import kernel, ops
    monkeypatch.setattr(kernel, "interpret_mode", lambda interpret=None:
                        False)
    jax.clear_caches()

    def grads(q, k, v):
        return jax.vmap(jax.grad(lambda q, k, v: ops.flash_attention(
            q, k, v, causal, 0, 0.0).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)))(q, k, v)

    _assert_mosaic(one_chip, grads, ((2, 1, sq, 16, 64), q_dtype),
                   ((2, 1, sk, 16, 64), kv_dtype),
                   ((2, 1, sk, 16, 64), kv_dtype))
    jax.clear_caches()


@pytest.mark.parametrize("dtype", DTYPES)
def test_quantize_compiles(one_chip, no_compile_cache, dtype):
    fn = lambda x, u, s: quantize_dequantize_pallas(x, u, s, qmax=127,
                                                    interpret=False)
    _assert_mosaic(one_chip, fn, ((8192, 128), dtype),
                   ((8192, 128), jnp.float32), ((1, 1), jnp.float32))


@pytest.mark.parametrize("dtype", DTYPES)
def test_mlstm_chunk_compiles(one_chip, no_compile_cache, dtype):
    # xLSTM-350M's mLSTM: 4 heads of 512 over 1024 tokens
    fn = lambda q, k, v, li, lf: mlstm_chunk_pallas(q, k, v, li, lf,
                                                    interpret=False)
    qkv = ((1, 4, 1024, 512), dtype)
    gate = ((1, 4, 1024), jnp.float32)
    _assert_mosaic(one_chip, fn, qkv, qkv, qkv, gate, gate)


@pytest.mark.parametrize("dtype", DTYPES)
def test_rglru_scan_compiles(one_chip, no_compile_cache, dtype):
    # RecurrentGemma-2B's 2560-wide recurrence over 4k tokens
    fn = lambda a, b, h: rglru_scan_pallas(a, b, h, interpret=False)
    _assert_mosaic(one_chip, fn, ((1, 4096, 2560), dtype),
                   ((1, 4096, 2560), dtype), ((1, 2560), jnp.float32))
