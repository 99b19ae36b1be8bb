"""HME region: multi-source kernel repository (paper §V-A4).

Each subpackage ships three artifacts per kernel:
  * ``<name>.py`` — the Pallas TPU kernel (pl.pallas_call + BlockSpec),
  * ``ops.py``    — the jit'd public wrapper (padding, layout, interpret),
  * ``ref.py``    — the pure-jnp oracle (the C2MPI fail-safe implementation).

:func:`register_all` publishes every implementation into the HALO registry
with Table-II attributes, so the runtime agent can resolve aliases to the
best feasible substrate (pallas > xla > jnp by default) per invocation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.registry import GLOBAL_REGISTRY, KernelAttributes, KernelRecord
from .common import peak_bf16_flops, small_enough_off_tpu

_REGISTERED = False

_TPU_ATTRS = dict(vid="google", pid="tpu-v5e")
_ANY_ATTRS = dict(vid="*", pid="*")


def _floaty(*args, **kw) -> bool:
    for a in args:
        dt = getattr(a, "dtype", None)
        if dt is not None and dt not in (jnp.float32, jnp.bfloat16, jnp.float16):
            return False
    return True


def _pallas_ok(*args, **kw) -> bool:
    return _floaty(*args) and small_enough_off_tpu(*args)


def _ewise_ok(*args, **kw) -> bool:
    """EW* pallas feasibility: tiled VPU kernels need at least one dim —
    0-d operands (e.g. collective scalar-residual reduces) go to xla/jnp."""
    return (all(getattr(a, "ndim", 0) >= 1 for a in args)
            and _pallas_ok(*args, **kw))


def _rec(alias, fn, platform, prio, *, failsafe=False, supports=None,
         cost=None, space=None, doc=""):
    hw = _TPU_ATTRS if platform == "pallas" else _ANY_ATTRS
    if platform == "pallas" and jax.default_backend() != "tpu":
        # Table-II cost models are per-hardware attributes calibrated for
        # the TPU target; off-TPU the pallas records run in interpret mode
        # (a validation vehicle), where the analytic estimate is off by
        # orders of magnitude and would hijack latency-aware placement.
        cost = None
    return KernelRecord(
        alias=alias, fn=fn, platform=platform, priority=prio,
        attrs=KernelAttributes(sw_fid=f"fid:{alias.lower()}", **hw),
        supports=supports, cost_model=cost, is_failsafe=failsafe,
        tuning_space=space, doc=doc)


def register_all(registry=None) -> None:
    """Idempotently publish all built-in kernels to the registry."""
    global _REGISTERED
    registry = registry or GLOBAL_REGISTRY
    if _REGISTERED and registry is GLOBAL_REGISTRY:
        return

    from .matmul import mmm, mmm_ref
    from .matmul.ref import mmm_xla
    from .matmul.ops import mmm_space
    from .ewise import (ewadd, ewadd_ref, ewmd, ewmd_ref, ewmm, ewmm_ref,
                        ewsub, ewsub_ref)
    from .ewise.ops import ewise_space
    from .spmm import smmm, smmm_ref
    from .spmm.ops import smmm_space
    from .mvm import mvm, mvm_ref
    from .mvm.ops import mvm_space
    from .vdp import vdp, vdp_ref
    from .jacobi import jacobi_step, jacobi_step_ref
    from .jacobi.ops import jacobi_space
    from .conv1d import conv1d, conv1d_ref
    from .conv1d.ops import conv1d_space
    from .flash_attention import attention_ref, flash_attention
    from .flash_attention.ops import fa_space
    from .flash_attention.xla import mea_attention
    from .rmsnorm import rmsnorm, rmsnorm_ref
    from .rmsnorm.ops import rmsnorm_space
    from .rmsnorm.ref import rmsnorm_xla
    from .ssd import ssd_chunked, ssd_decode_step, ssd_ref
    from .moe_ffn import grouped_ffn, grouped_ffn_ref
    from .fft import fft, fft_ref, fft_xla
    from .fft.ops import fft_space
    from .sorthist import hist, hist_ref, sort, sort_ref
    from .sorthist.ops import hist_space, sort_space

    peak = peak_bf16_flops()

    def mmm_cost(a, b, **kw):
        m, k = a.shape
        n = b.shape[1]
        return 2.0 * m * n * k / peak

    # tunable-config axes for the xla records that expose tile kwargs
    # (the chunked mea formulation tiles its q/kv block loop like the
    # pallas kernel does, so it shares the FLASH_ATTN space)
    xla_spaces = {"FLASH_ATTN": fa_space}

    def _fft_ok(x, **kw):
        # DFT-by-matmul: twiddle planes are n×n, so cap the transform
        # length even on TPU (longer signals go to the xla jnp.fft record)
        n = getattr(x, "shape", (0,))[-1]
        return _floaty(x) and n <= 4096 and small_enough_off_tpu(x)

    # per-alias pallas feasibility overrides (default: _pallas_ok, or
    # _ewise_ok for the EW* family)
    pallas_ok = {"FFT": _fft_ok}

    table = [
        # (alias, ref_fn, xla_fn, pallas_fn, cost, pallas_space)
        ("MMM", mmm_ref, mmm_xla, mmm, mmm_cost if peak else None,
         mmm_space),
        ("EWMM", ewmm_ref, ewmm_ref, ewmm, None, ewise_space),
        ("EWMD", ewmd_ref, ewmd_ref, ewmd, None, ewise_space),
        ("EWADD", ewadd_ref, ewadd_ref, ewadd, None, ewise_space),
        ("EWSUB", ewsub_ref, ewsub_ref, ewsub, None, ewise_space),
        ("MVM", mvm_ref, mvm_ref, mvm, None, mvm_space),
        ("VDP", vdp_ref, vdp_ref, vdp, None, None),
        ("JS", jacobi_step_ref, jacobi_step_ref, jacobi_step, None,
         jacobi_space),
        ("1DCONV", conv1d_ref, conv1d_ref, conv1d, None, conv1d_space),
        ("RMSNORM", rmsnorm_ref, rmsnorm_xla, rmsnorm, None, rmsnorm_space),
        ("FLASH_ATTN", attention_ref, mea_attention, flash_attention, None,
         fa_space),
        # data-reorganization + spectral class (paper Table II rows 9–11)
        ("FFT", fft_ref, fft_xla, fft, None, fft_space),
        ("SORT", sort_ref, sort_ref, sort, None, sort_space),
        ("HIST", hist_ref, hist_ref, hist, None, hist_space),
    ]
    for alias, ref_fn, xla_fn, pallas_fn, cost, space in table:
        registry.register(_rec(alias, ref_fn, "jnp", 0, failsafe=True))
        registry.register(_rec(alias, xla_fn, "xla", 10, cost=cost,
                               space=xla_spaces.get(alias)))
        registry.register(_rec(alias, pallas_fn, "pallas", 20,
                               supports=pallas_ok.get(
                                   alias, _ewise_ok if alias.startswith("EW")
                                   else _pallas_ok),
                               cost=cost, space=space))

    # SMMM: the xla variant is a dense-gather einsum over the blocked-ELL
    # parts; it doubles as the jnp fail-safe (the ref.py oracle reconstructs
    # a dense operand and is used by tests/benchmarks directly).
    def smmm_xla(values, indices, b):
        gathered = b.reshape(-1, values.shape[3], b.shape[1])[
            jnp.maximum(indices, 0)]                     # (R,S,bk,N)
        mask = (indices >= 0).astype(values.dtype)[..., None, None]
        out = jnp.einsum("rsmk,rskn->rmn", values * mask, gathered,
                         preferred_element_type=jnp.float32)
        return out.reshape(-1, b.shape[1]).astype(b.dtype)

    registry.register(_rec("SMMM", smmm_xla, "jnp", 0, failsafe=True))
    registry.register(_rec("SMMM", smmm_xla, "xla", 10))
    def _smmm_ok(values, indices, b, **kw):
        # the blocked-ELL column indices are int32 by construction
        return _floaty(values, b) and small_enough_off_tpu(values, indices, b)

    registry.register(_rec("SMMM", smmm, "pallas", 20, supports=_smmm_ok,
                           space=smmm_space))

    # Sequence-model substrate aliases (no pallas variant: the chunked SSD
    # is already MXU-shaped einsums; see EXPERIMENTS.md §Perf).
    registry.register(_rec("SSD", ssd_ref, "jnp", 0, failsafe=True))
    registry.register(_rec("SSD", ssd_chunked, "xla", 10))
    registry.register(_rec("SSD_DECODE", ssd_decode_step, "jnp", 0, failsafe=True))
    registry.register(_rec("SSD_DECODE", ssd_decode_step, "xla", 10))
    registry.register(_rec("MOE_FFN", grouped_ffn_ref, "jnp", 0, failsafe=True))
    registry.register(_rec("MOE_FFN", grouped_ffn, "xla", 10))

    # Decode-time attention (GEMV-bound; XLA codegen is already optimal —
    # registering only jnp/xla exercises selection across substrates).
    def gqa_decode(q, k, v, **kw):
        return attention_ref(q, k, v, causal=True, **kw)

    registry.register(_rec("GQA_DECODE", gqa_decode, "jnp", 0, failsafe=True))
    registry.register(_rec("GQA_DECODE", gqa_decode, "xla", 10))

    # Collective data movement (DESIGN.md §10): staging records exist on
    # every substrate so a device group can pin a bcast fan-out COPY (or a
    # gather CONCAT) to each member agent's worker queue.
    from .staging import concat_blocks, concat_ref, copy_ref, copy_stage
    registry.register(_rec("COPY", copy_ref, "jnp", 0, failsafe=True))
    registry.register(_rec("COPY", copy_stage, "xla", 10))
    registry.register(_rec("COPY", copy_stage, "pallas", 20))
    registry.register(_rec("CONCAT", concat_ref, "jnp", 0, failsafe=True))
    registry.register(_rec("CONCAT", concat_blocks, "xla", 10))
    registry.register(_rec("CONCAT", concat_blocks, "pallas", 20))

    # Training-step builtins (DESIGN.md §15): data-parallel device groups
    # dispatch the forward/backward and the optimizer update as registry
    # aliases, so member ranks — including remote workers, which resolve
    # these rows in their own process — compute bit-identical results.
    # Every platform row shares ONE internally-jitted callable (the
    # single-config tuning space keeps agents from re-jitting it, which
    # would trace the static string kwargs).
    from ..train.step_kernels import adamw_step_vec, lm_grad_vec, step_space
    for alias, fn in (("LM_GRAD", lm_grad_vec),
                      ("ADAMW_STEP", adamw_step_vec)):
        registry.register(_rec(alias, fn, "jnp", 0, failsafe=True,
                               space=step_space))
        registry.register(_rec(alias, fn, "xla", 10, space=step_space))
        registry.register(_rec(alias, fn, "pallas", 20, space=step_space))

    # Fusibility rules (DESIGN.md §12): which aliases the graph fusion pass
    # may collapse into same-agent linear chains.  EW* members carry the
    # element-wise op a generated Pallas chain kernel applies; COPY is a
    # unary pass-through; RMSNORM/MVM/JS fuse via the jitted XLA
    # composition; MMM may only terminate a chain (ewise → matmul
    # epilogues).  Rules are global (alias semantics, not registry state).
    from ..core.fusion import register_fusible
    register_fusible("EWMM", ewise_op="mul")
    register_fusible("EWMD", ewise_op="div")
    register_fusible("EWADD", ewise_op="add")
    register_fusible("EWSUB", ewise_op="sub")
    register_fusible("COPY", unary=True)
    register_fusible("RMSNORM")
    register_fusible("MVM")
    register_fusible("JS")
    register_fusible("MMM", terminal=True)

    if registry is GLOBAL_REGISTRY:
        _REGISTERED = True
