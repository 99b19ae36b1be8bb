"""Performance-portability metrics (paper §VI-A).

* ``performance_penalty``  = (T3_x − T3_baseline) / T3_baseline × 100   [%]
* ``portability_score`` Φ  = T3_baseline / T3_hardware_agnostic ∈ [0, 1]
* ``overhead_ratio``       = T1 / T4, with T4 = T1 + T2 + T3

T-terms (paper definitions):
  T1 = HALO framework overhead (agent/dispatch time only),
  T2 = hardware data-transfer (offload) time,
  T3 = kernel execution time,
  T4 = total runtime.

On this single-host JAX environment T2 ≈ 0 (buffers are device-resident; the
unified-memory model passes references), matching the paper's WSS-invariant
design.  T3 is wall-clock with ``block_until_ready``; T1 is measured from the
runtime agent's dispatch instrumentation.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Sequence

import jax
import numpy as np


@dataclasses.dataclass
class Timing:
    mean_s: float
    std_s: float
    runs: int

    @property
    def mean_us(self) -> float:
        return self.mean_s * 1e6


def time_fn(fn: Callable, *args, warmup: int = 2, iters: int = 10,
            **kwargs) -> Timing:
    """Wall-clock a callable with async-dispatch-safe synchronization."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args, **kwargs))
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kwargs))
        samples.append(time.perf_counter() - t0)
    a = np.asarray(samples)
    return Timing(float(a.mean()), float(a.std()), iters)


def performance_penalty(t3_impl: float, t3_baseline: float) -> float:
    """Percent slowdown vs. the hardware-optimized baseline (Table VI)."""
    return (t3_impl - t3_baseline) / t3_baseline * 100.0


def portability_score(t3_baseline: float, t3_agnostic: float) -> float:
    """Φ = T3_baseline / T3_hardware-agnostic (Table VII). 1.0 = perfect."""
    return t3_baseline / t3_agnostic


def overhead_ratio(t1: float, t4: float) -> float:
    """T1/T4 (Table VIII)."""
    return t1 / t4 if t4 > 0 else 0.0


def percentile_nearest(sorted_vals: Sequence[float], q: float) -> float:
    """Nearest-rank percentile of an ascending-sorted sequence (request
    latency reporting: serving launcher + throughput benchmark)."""
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[i]


@dataclasses.dataclass
class KernelReport:
    """One row of the paper's evaluation: a kernel on one device class."""
    kernel: str
    device: str
    t1_s: float
    t3_baseline_s: float
    t3_halo_s: float
    t3_agnostic_s: float   # deliberately unoptimized hardware-agnostic impl

    @property
    def t4_s(self) -> float:
        return self.t1_s + self.t3_halo_s  # T2≈0 under unified memory

    @property
    def halo_score(self) -> float:
        return portability_score(self.t3_baseline_s, self.t3_halo_s)

    @property
    def agnostic_score(self) -> float:
        return portability_score(self.t3_baseline_s, self.t3_agnostic_s)

    @property
    def halo_gain(self) -> float:
        """HALO/HA score ratio — the paper's bold '(Nx)' column."""
        return self.halo_score / max(self.agnostic_score, 1e-30)

    @property
    def overhead(self) -> float:
        return overhead_ratio(self.t1_s, self.t4_s)

    def csv(self) -> str:
        return (f"{self.kernel},{self.device},{self.t1_s*1e6:.3f},"
                f"{self.t3_baseline_s*1e6:.1f},{self.t3_halo_s*1e6:.1f},"
                f"{self.t3_agnostic_s*1e6:.1f},{self.halo_score:.4f},"
                f"{self.agnostic_score:.2e},{self.halo_gain:.1f},"
                f"{self.overhead*100:.5f}%")

    @staticmethod
    def csv_header() -> str:
        return ("kernel,device,T1_us,T3_base_us,T3_halo_us,T3_agnostic_us,"
                "halo_score,agnostic_score,halo_gain_x,overhead_ratio")


@dataclasses.dataclass
class ServeReport:
    """Serving-path scorecard: the paper's T-term decomposition applied to
    the slot engine's iteration loop (DESIGN.md §6).

    Both terms are host wall time, read from the scheduler's span totals
    (:mod:`repro.core.tracing`).  T3 is the host time inside the engine's
    device calls: the ``serve.admit``, ``serve.chunk`` and ``serve.decode``
    spans.  A call that returns a token waits for the device; a prefill
    chunk that returns none only dispatches its program, whose device time
    then lands in the next call that waits.  T1 is the rest of
    each iteration's ``serve.step`` (admission bookkeeping, slot retirement,
    RNG and mask assembly, token streaming).  T2 ≈ 0 (the cache is
    device-resident between iterations).  The device's own busy time is not
    in either term: it comes from a profiler trace.  ``overhead`` is the
    paper's T1/T4 — the serving path reports the same scorecard as the
    kernel path (Table VIII)."""

    t1_s: float
    t3_s: float
    steps: int
    tokens: int

    @property
    def t4_s(self) -> float:
        return self.t1_s + self.t3_s           # T2≈0 under unified memory

    @property
    def overhead(self) -> float:
        return overhead_ratio(self.t1_s, self.t4_s)

    @property
    def tokens_per_s(self) -> float:
        return self.tokens / self.t4_s if self.t4_s > 0 else 0.0

    def csv(self) -> str:
        return (f"serve,{self.steps},{self.tokens},{self.t1_s * 1e6:.1f},"
                f"{self.t3_s * 1e6:.1f},{self.tokens_per_s:.1f},"
                f"{self.overhead * 100:.4f}%")

    @staticmethod
    def csv_header() -> str:
        return "path,steps,tokens,T1_us,T3_us,tok_per_s,overhead_ratio"
