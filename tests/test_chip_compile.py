"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Interpret mode (every other kernel test) never meets the TPU compiler, which
refuses unaligned lane slices, unsupported primitives and layouts the
interpreter accepts.  These tests lower each kernel with ``interpret=False``
at the sizes ``chip_smoke.py`` runs and compile it for one chip of a
``v5e:2x2`` topology that is described, not attached; a compile that passes
emits the Mosaic kernel as a ``tpu_custom_call``.  Nothing runs.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.conv1d import conv1d
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused import ACC, ewise_chain
from repro.kernels.matmul import mmm
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.sorthist import hist, sort

F32, BF16 = jnp.float32, jnp.bfloat16

# (kernel call with interpret=False, argument shapes and dtypes)
CASES = {
    # h2o-danube-1.8b's FFN up-projection for a 256-token tile
    "MMM": (lambda a, b: mmm(a, b, interpret=False),
            [((256, 2560), BF16), ((2560, 6912), BF16)]),
    "RMSNORM": (lambda x, g: rmsnorm(x, g, eps=1e-5, interpret=False),
                [((1, 512, 2560), BF16), ((2560,), BF16)]),
    # danube's published heads: 32 q, 8 kv, head_dim 80, window 4096
    "FLASH_ATTN": (lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=4096, interpret=False),
        [((1, 32, 2048, 80), BF16), ((1, 8, 2048, 80), BF16),
         ((1, 8, 2048, 80), BF16)]),
    "1DCONV": (lambda x, w: conv1d(x, w, interpret=False),
               [((1 << 22,), F32), ((17,), F32)]),
    "SORT": (lambda x: sort(x, interpret=False), [((64, 4096), F32)]),
    "HIST": (lambda x: hist(x, interpret=False), [((1 << 20,), F32)]),
    # the fusion pass's generated kernel for an EWMM -> EWADD chain
    "FUSED_EWISE": (lambda a, b, c: ewise_chain(
        a, b, c, steps=(("mul", 0, 1), ("add", ACC, 2)), interpret=False),
        [((8192, 8192), F32)] * 3),
}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A TPU executable written to the persistent cache cannot be read back
    without a chip, so the cache stays off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("alias", sorted(CASES))
def test_kernel_compiles_for_v5e(alias, one_chip, no_persistent_cache):
    fn, shapes = CASES[alias]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
