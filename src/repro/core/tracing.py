"""Host spans and counters of the serving path, on the profiler's clock.

A :class:`Spans` keeps, in memory, the seconds and the number of calls of
each span name, and plain integer counters.  Every span takes two
``time.perf_counter`` readings and opens a ``jax.profiler.TraceAnnotation``
of the same name, which records nothing unless a profiler trace is active;
under ``jax.profiler.trace(trace_dir)`` the span lands in the host plane on
the clock of the device's ``XLA Modules`` and ``XLA Ops`` events.  Readers
take window deltas: ``base = spans.snapshot()``, then ``spans.since(base)``.
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import Dict, Iterator, Optional

import jax

__all__ = ["Spans"]


class Spans:
    """Per-name span seconds and calls, and integer counters; safe to use
    from several threads."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._seconds: Dict[str, float] = collections.defaultdict(float)
        self._calls: Dict[str, int] = collections.defaultdict(int)
        self._counts: Dict[str, int] = collections.defaultdict(int)

    @contextlib.contextmanager
    def span(self, name: str, **args) -> Iterator[None]:
        """Time the body under ``name``.  ``args`` (a request's uid, a
        length) go to the profiler annotation only."""
        with jax.profiler.TraceAnnotation(name, **args):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                dt = time.perf_counter() - t0
                with self._lock:
                    self._seconds[name] += dt
                    self._calls[name] += 1

    def count(self, name: str, n: int) -> None:
        """Add ``n`` to the counter ``name``."""
        with self._lock:
            self._counts[name] += n

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        """A copy of the totals: ``{"seconds": {name: s}, "calls": {name:
        n}, "counts": {name: n}}``."""
        with self._lock:
            return {"seconds": dict(self._seconds),
                    "calls": dict(self._calls),
                    "counts": dict(self._counts)}

    def since(self, base: Dict[str, Dict[str, float]],
              now: Optional[Dict[str, Dict[str, float]]] = None
              ) -> Dict[str, Dict[str, float]]:
        """What the totals gained from ``base`` to ``now`` (snapshots;
        ``now`` defaults to the current totals), in the same form."""
        if now is None:
            now = self.snapshot()
        return {kind: {name: v - base[kind].get(name, 0)
                       for name, v in totals.items()}
                for kind, totals in now.items()}
