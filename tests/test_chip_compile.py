"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Interpret mode (every other kernel test) never meets the TPU compiler, which
refuses unaligned lane slices, unsupported primitives and layouts the
interpreter accepts.  These tests lower each kernel with ``interpret=False``
at the sizes ``chip_smoke.py`` runs and compile it for one chip of a
``v5e:2x2`` topology that is described, not attached; a compile that passes
emits the Mosaic kernel as a ``tpu_custom_call``.  Nothing runs.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.c2mpi import halo_dispatch
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


def test_dispatch_scope_keeps_the_kernel_name(one_chip, no_persistent_cache):
    """``halo_dispatch`` wraps each record in a ``halo.<ALIAS>`` scope: the
    Pallas MMM's custom call keeps its instruction name ``_mmm_impl`` (what
    a device trace finds it by) and carries the scope in its op_name."""
    pin = {"allowed_platforms": ("pallas",)}

    def step(x, w, g):
        y = halo_dispatch("MMM", x, w, overrides=pin, interpret=False)
        return halo_dispatch("RMSNORM", y[None], g, eps=1e-5, overrides=pin,
                             interpret=False)

    # under the interpret-mode size cap, so the Pallas records are feasible
    # on this host's backend
    shapes = [(8, 2560), (2560, 1280), (1280,)]
    args = [jax.ShapeDtypeStruct(s, BF16, sharding=one_chip) for s in shapes]
    text = jax.jit(step).lower(*args).compile().as_text()
    for alias, name in (("MMM", "_mmm_impl"), ("RMSNORM", "_rmsnorm_impl")):
        calls = [ln for ln in text.splitlines()
                 if re.match(rf"\s*%{name}(\.\d+)? = ", ln)]
        assert calls and all("tpu_custom_call" in ln for ln in calls), name
        assert all(f'op_name="jit(step)/halo.{alias}/' in ln
                   for ln in calls), name


def test_paged_decode_holds_no_arena_or_stack_copy(one_chip,
                                                    no_persistent_cache,
                                                    monkeypatch):
    """``PagedEngine._decode`` at h2o-danube-1.8b's widths (4 of its 24
    layers, so the layers run as a scan), 8 slots of 2,560 positions: its
    temporaries stay under one layer's key and value views, and no
    arena-shaped buffer is copied.  Gathering every layer's view up front
    takes a stack of them, and an arena the device lays out with another
    axis minor than its gathers and scatters want is relaid whole on every
    step."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import build_model
    from repro.serve import engine as engine_mod

    cfg = get_config("h2o-danube-1.8b")
    cfg = dataclasses.replace(cfg, stages=tuple(
        dataclasses.replace(s, repeats=4) for s in cfg.stages))
    init = engine_mod.init_paged
    monkeypatch.setattr(engine_mod, "init_paged",
                        lambda *a: jax.eval_shape(lambda: init(*a)))
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    slots, max_len = 8, 2560
    eng = engine_mod.PagedEngine(model, params, slots, max_len)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    vec = lambda dt: jax.ShapeDtypeStruct((slots,), dt, sharding=one_chip)
    compiled = eng._decode.lower(
        on_chip(params), on_chip(eng.paged),
        jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one_chip),
        on_chip(jnp.asarray(eng.tables)), vec(jnp.int32), vec(bool),
        on_chip(jax.random.PRNGKey(0)), 0.0).compile()

    a = cfg.stages[0].pattern[0].attn
    # one layer's K and V views, (slots, kv, max_len, head_dim) in bf16
    # with head_dim padded to the chip's 128 lanes
    layer_views = 2 * slots * a.n_kv_heads * max_len * 128 * 2
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < layer_views, (temp, layer_views)
    arenas = {",".join(map(str, leaf.shape))
              for leaf in jax.tree.leaves(eng.paged)}
    copies = []
    for ln in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) copy(?:-start)?\(", ln)
        if m and arenas & set(re.findall(r"\[([\d,]+)\]", m.group(1))):
            copies.append(ln)
    assert not copies, copies[:2]
