"""Public-API docstring integrity for the core modules (CI twin).

Every module in :data:`MODULES` must declare ``__all__``, every entry must
resolve, and every function/class entry must carry a docstring whose first
line is a real one-line summary.  Runnable standalone (the CI step):

    PYTHONPATH=src python tests/test_docstrings.py
"""
import importlib
import inspect
import sys

MODULES = [
    "repro.core.c2mpi",
    "repro.core.collective",
    "repro.core.graph",
    "repro.core.registry",
    "repro.core.scheduler",
    "repro.core.tracing",
    "repro.core.tuning",
    "repro.testing.faults",
]


def docstring_problems(module_name):
    """All __all__-coverage problems for one module, as strings."""
    mod = importlib.import_module(module_name)
    exported = getattr(mod, "__all__", None)
    if not exported:
        return [f"{module_name}: missing or empty __all__"]
    problems = []
    for sym in exported:
        obj = getattr(mod, sym, None)
        if obj is None:
            problems.append(f"{module_name}.{sym}: in __all__ but undefined")
            continue
        if not (inspect.isfunction(obj) or inspect.isclass(obj)):
            continue                    # constants (tuples, registries, …)
        doc = inspect.getdoc(obj)
        if not doc or not doc.strip().splitlines()[0].strip():
            problems.append(
                f"{module_name}.{sym}: missing one-line docstring summary")
    return problems


def test_public_api_docstrings():
    problems = []
    for name in MODULES:
        problems += docstring_problems(name)
    assert not problems, "\n".join(problems)


def main():
    """Script entry: print problems and exit non-zero if any."""
    problems = []
    for name in MODULES:
        probs = docstring_problems(name)
        problems += probs
        status = "FAIL" if probs else "ok"
        print(f"{name}: {status}")
    for p in problems:
        print(f"  {p}")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
