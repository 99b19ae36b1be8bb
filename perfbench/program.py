"""The benchmark's one view of the system under test: find the adapter
that builds the program's model configuration from a configuration file,
and note which kernel record served each alias.  Everything else the
benchmark drives through the program's own entry points (``PagedEngine``,
``StepScheduler``)."""
from __future__ import annotations

import importlib
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))


def adapter(cfg: dict):
    """The module ``arch/<architecture>.py`` that maps the configuration
    file's architecture onto the program (``arch/__init__.py`` says what it
    gives)."""
    name = cfg["architecture"]
    try:
        return importlib.import_module(f"arch.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"arch.{name}":
            raise
        raise ValueError(f"no adapter for architecture {name!r}: add "
                         f"perfbench/arch/{name}.py") from None


class Selections:
    """Records which record the runtime selected for each alias while the
    cell's programs are traced (selection happens at trace time only)."""

    def __init__(self):
        from repro.core.c2mpi import halo_session

        self.seen = {}
        self._session = halo_session()
        self._orig = self._session._select

        def select(alias, args, overrides):
            rec = self._orig(alias, args, overrides)
            name = f"{rec.platform}/{getattr(rec.fn, '__name__', '?')}"
            self.seen.setdefault(alias, set()).add(name)
            return rec

        self._session._select = select

    def line(self) -> str:
        return "; ".join(f"{a}: {', '.join(sorted(n))}"
                         for a, n in sorted(self.seen.items()))
