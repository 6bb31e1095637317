"""The Pallas kernels compile for a TPU v5e that is described, not attached.

The TPU compiler is installed with jaxlib, so the kernels of the main path
are compiled here at the shapes chip_smoke.py runs them at on the chip —
their capture catalog shapes (capture/workloads.py) and one deployment
width each — and each compiled program must call its kernel
(``tpu_custom_call``).  This catches what interpret mode cannot: block
shapes the TPU lowering refuses, primitives it does not implement, VMEM
overflow.  A pass is a compile, never a run.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and collection in every
test worker must see the same tests.
"""
import jax
import jax.numpy as jnp
import pytest

from conftest import load_chip_smoke
from repro.kernels.block_quant.block_quant import BLOCK, dequantize_pallas, quantize_pallas
from repro.kernels.flash_attention.flash_attention import flash_attention_pallas
from repro.kernels.mamba_scan.mamba_scan import selective_scan_pallas

_SMOKE = load_chip_smoke()
FLASH_CASES = {label: case for label, *case in _SMOKE.flash_cases()}
BLOCK_QUANT_CASES = dict(_SMOKE.block_quant_cases())
MAMBA_CASES = dict(_SMOKE.mamba_cases())


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here: nothing to compile for
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    compilation_cache.reset_cache()


def _compiled_hlo(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("name", list(FLASH_CASES))
def test_flash_attention_compiles_for_v5e(one_chip, name):
    (b, sq, skv, h, kvh, d), dtype, causal, window, bq = FLASH_CASES[name]
    q = jax.ShapeDtypeStruct((b, sq, h, d), dtype, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, skv, kvh, d), dtype, sharding=one_chip)
    hlo = _compiled_hlo(
        lambda q, k, v: flash_attention_pallas(q, k, v, causal=causal, window=window,
                                               bq=bq),
        q, kv, kv)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("name", list(BLOCK_QUANT_CASES))
def test_block_quant_compiles_for_v5e(one_chip, name):
    r, c = BLOCK_QUANT_CASES[name]
    x = jax.ShapeDtypeStruct((r, c), jnp.float32, sharding=one_chip)
    q = jax.ShapeDtypeStruct((r, c), jnp.int8, sharding=one_chip)
    s = jax.ShapeDtypeStruct((r, c // BLOCK), jnp.float32, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_hlo(quantize_pallas, x)
    assert "tpu_custom_call" in _compiled_hlo(
        lambda q, s: dequantize_pallas(q, s, jnp.bfloat16), q, s)


@pytest.mark.parametrize("name", list(MAMBA_CASES))
def test_mamba_scan_compiles_for_v5e(one_chip, name):
    b, s, d, n = MAMBA_CASES[name]

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    hlo = _compiled_hlo(selective_scan_pallas, f32(b, s, d), f32(d, n), f32(b, s, n),
                        f32(b, s, n), f32(b, s, d))
    assert "tpu_custom_call" in hlo

