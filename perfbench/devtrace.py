"""Reduction of a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes an ``.xplane.pb``.  On a TPU each chip is a plane
``/device:TPU:<n>`` with a line ``XLA Modules`` (one event per run of a
jitted program, named ``jit_<function>(<fingerprint>)``) and a line
``XLA Ops`` (one event per top-level HLO operation, named by its HLO text,
``%<instruction> = <shape> <opcode>(...)``).  The harness's host spans are
``TraceAnnotation`` events named ``perfbench.<what>`` on the host plane;
``perfbench.window`` brackets the traced window.

:func:`summarize` works on plain lists, so a test can feed it a synthetic
trace; :func:`load` turns a trace file into those lists.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

Event = Tuple[str, float, float]          # (name, start_ns, end_ns)

WINDOW_SPAN = "perfbench.window"
SPAN_PREFIX = "perfbench."


@dataclasses.dataclass
class Device:
    modules: List[Event]
    ops: List[Event]


@dataclasses.dataclass
class Trace:
    devices: List[Device]
    host: List[Event]                     # harness spans, perfbench.*


def module_base(name: str) -> str:
    """``jit__decode_fn(123)`` → ``jit__decode_fn``."""
    return name.split("(", 1)[0]


_SUFFIX = re.compile(r"\.\d+$")


def op_base(name: str) -> str:
    """``%_mmm_impl.3 = bf16[16,6912] custom-call(...)`` → ``_mmm_impl``."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head)


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            dev = Device(modules=[], ops=[])
            for key, out in (("XLA Modules", dev.modules),
                             ("XLA Ops", dev.ops)):
                if key in lines:
                    out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in lines[key].events)
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in ln.events
                            if e.name.startswith(SPAN_PREFIX))
    return Trace(devices=devices, host=host)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(a: float, b: float, w0: float, w1: float) -> float:
    return max(0.0, min(b, w1) - max(a, w0))


def window_of(tr: Trace) -> Tuple[float, float]:
    spans = [(a, b) for n, a, b in tr.host if n == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
    return spans[-1]


def summarize(tr: Trace, window: Optional[Tuple[float, float]] = None,
              top: int = 10) -> dict:
    """Busy and idle time, device time per program and per kernel, and the
    ledger's breakdown, over the traced window.  Times are seconds; busy
    time is averaged over the chips."""
    w0, w1 = window if window is not None else window_of(tr)
    if not tr.devices:
        raise ValueError("the trace holds no TPU device plane")
    busy_total = 0.0
    module_s: Dict[str, float] = defaultdict(float)
    module_n: Dict[str, int] = defaultdict(int)
    kernel_s: Dict[Tuple[str, str], float] = defaultdict(float)
    gaps: List[Tuple[float, float, str, str]] = []
    for dev in tr.devices:
        mods = sorted(dev.modules, key=lambda e: e[1])
        starts = [m[1] for m in mods]
        for name, a, b in mods:
            t = _clip(a, b, w0, w1)
            if t > 0:
                module_s[module_base(name)] += t
                if w0 <= a < w1:
                    module_n[module_base(name)] += 1
        for name, a, b in dev.ops:
            t = _clip(a, b, w0, w1)
            if t <= 0:
                continue
            i = bisect.bisect_right(starts, a) - 1
            owner = (module_base(mods[i][0])
                     if i >= 0 and a < mods[i][2] else "(none)")
            kernel_s[(owner, op_base(name))] += t
        busy = [(max(a, w0), min(b, w1)) for _, a, b in dev.ops
                if _clip(a, b, w0, w1) > 0]
        merged = _union(busy)
        busy_total += sum(b - a for a, b in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                i = bisect.bisect_right(starts, a) - 1
                before = module_base(mods[i][0]) if i >= 0 else "(start)"
                gaps.append((a, b, before, _host_during(tr.host, a, b)))
    n = len(tr.devices)
    window_s = (w1 - w0) * 1e-9
    gaps.sort(key=lambda g: g[0] - g[1])
    ops_by_time = sorted(kernel_s.items(), key=lambda kv: -kv[1])
    return {
        "window_s": window_s,
        "busy_s": busy_total * 1e-9 / n,
        "module_s": {k: v * 1e-9 / n for k, v in module_s.items()},
        "module_n": dict(module_n),
        "kernel_s": {k: v * 1e-9 / n for k, v in kernel_s.items()},
        "breakdown": {
            "device_ops": [[f"{m}/{o}", t * 1e-9 / n]
                           for (m, o), t in ops_by_time[:top]],
            "idle_gaps": [[f"after {before}; host: {host}", (b - a) * 1e-9]
                          for a, b, before, host in gaps[:top]],
        },
    }


def _host_during(host: List[Event], a: float, b: float) -> str:
    """The harness span that overlaps [a, b] the most, or ``idle``."""
    best, name = 0.0, "no harness span"
    for n, s, e in host:
        if n == WINDOW_SPAN:
            continue
        t = _clip(s, e, a, b)
        if t > best:
            best, name = t, n
    return name


def kernel_time(summary: dict, kernel: str, modules=None) -> float:
    """Device seconds of ``kernel`` events inside the given programs (all
    programs when ``modules`` is None)."""
    return sum(t for (m, o), t in summary["kernel_s"].items()
               if o == kernel and (modules is None or m in modules))
