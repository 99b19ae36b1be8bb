"""Benchmark entry point: ``PYTHONPATH=src python -m benchmarks.run``.

One section per paper table (VI/VII/VIII) + the roofline table from dry-run
artifacts (if present) + the subsystem benchmarks (async dispatch, graph
overlap, collective scaling, serving, tuning gain) + a model-step
microbench.  Output: CSV (``name,us_per_call,derived``) per the harness
contract, with section headers as comments.

Sections with missing *optional* third-party dependencies are skipped with
a notice; any other crash in a requested section is reported, the
remaining sections still run, the summary is still written — and the
process exits **non-zero** (a broken benchmark must not silently produce a
partial ``BENCH_summary.json``).  At the end, every ``BENCH_*.json``
artifact is folded into ``BENCH_summary.json`` with its best speedup/gain
ratio, so one file answers "what did each subsystem buy".

``--smoke`` runs the reduced best-of-N subset (tuning gain at smaller
shapes, collective scaling at fewer repeats, writing
``BENCH_smoke_*.json``) that feeds the CI bench-regression gate
(``benchmarks.check_regression --only BENCH_smoke_``); ``--sections``
selects sections by name.
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.launch.cache import use_compile_cache

ROOT = Path(__file__).resolve().parent.parent


def _section(title: str):
    print(f"# === {title} ===", flush=True)


def _run_section(name: str, fn, failures: list) -> None:
    """Run one benchmark section.  A missing optional *third-party*
    dependency skips it (the harness contract: report, don't crash); an
    ImportError naming one of our own packages, or any other exception, is
    a real failure — recorded so main() can exit non-zero after the
    remaining sections and the summary still ran."""
    try:
        fn()
    except ImportError as exc:
        missing = (getattr(exc, "name", "") or "").split(".")[0]
        if missing in ("repro", "benchmarks"):
            failures.append(name)
            _section(f"{name}: FAILED ({type(exc).__name__}: {exc})")
            traceback.print_exc()
        else:
            _section(f"{name}: skipped (missing optional dependency: {exc})")
    except Exception as exc:  # noqa: BLE001 — keep later sections running
        failures.append(name)
        _section(f"{name}: FAILED ({type(exc).__name__}: {exc})")
        traceback.print_exc()


def _paper_tables() -> None:
    from repro.core.portability import KernelReport
    from .tables import run_tables

    _section("paper tables VI/VII/VIII: kernel portability (per subroutine)")
    print(KernelReport.csv_header())
    reports = run_tables(verbose=True)

    _section("table VI analogue: performance penalty (%) vs baseline")
    print("kernel,halo_penalty_pct,naive_penalty_pct")
    for r in reports:
        halo_pen = (r.t3_halo_s - r.t3_baseline_s) / r.t3_baseline_s * 100
        naive_pen = (r.t3_agnostic_s - r.t3_baseline_s) / r.t3_baseline_s * 100
        print(f"{r.kernel},{halo_pen:.1f},{naive_pen:.1f}")

    _section("table VII analogue: portability score (HALO vs HA-naive)")
    print("kernel,halo_score,naive_score,halo_gain_x")
    for r in reports:
        print(f"{r.kernel},{r.halo_score:.4f},{r.agnostic_score:.4f},"
              f"{r.halo_gain:.1f}")

    _section("table VIII analogue: HALO overhead ratio T1/T4")
    print("kernel,T1_us,T4_us,overhead_ratio_pct")
    for r in reports:
        print(f"{r.kernel},{r.t1_s*1e6:.2f},{r.t4_s*1e6:.1f},"
              f"{r.overhead*100:.5f}")


def _roofline() -> None:
    from .roofline import main as roofline_main

    found = False
    for name, d in [("paper-faithful baseline", "results/dryrun_baseline"),
                    ("optimized (EXPERIMENTS §Perf)", "results/dryrun_opt"),
                    ("dry-run", "results/dryrun")]:
        dr = Path(d)
        if dr.exists() and any(dr.glob("*.json")):
            _section(f"roofline per (arch x shape x mesh) [{name}]")
            roofline_main(str(dr))
            found = True
    if not found:
        _section("roofline: no dry-run artifacts found (run "
                 "`python -m repro.launch.dryrun` first)")


def _model_microbench() -> None:
    _section("model step microbench (reduced configs, CPU)")
    print("name,us_per_call,derived")
    from repro.configs import get_config
    from repro.core.portability import time_fn
    from repro.models import build_model
    from repro.data import SyntheticLM
    from repro.train.trainer import TrainHyper, make_train_step, TrainState
    from repro.optim.adamw import adamw_init
    for arch in ["h2o-danube-1.8b", "mamba2-370m", "moonshot-v1-16b-a3b"]:
        cfg = get_config(arch).reduced()
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        state = TrainState(params=params, opt=adamw_init(params), err_fb=None)
        pipe = SyntheticLM(cfg, seq_len=64, global_batch=4)
        batch = {k: jnp.asarray(v) for k, v in pipe.batch(0).items()}
        step = jax.jit(make_train_step(model, TrainHyper()))
        t = time_fn(lambda s, b: step(s, b)[0].params, state, batch,
                    warmup=1, iters=3)
        tokens = 64 * 4
        print(f"train_step/{arch},{t.mean_us:.1f},"
              f"tok_per_s={tokens / t.mean_s:.0f}")


_RATIO_MARKERS = ("speedup", "ratio", "gain", "_vs_")


def _collect_ratios(obj, path: str, out: dict) -> None:
    """Recursively harvest numeric fields whose key names a ratio."""
    if isinstance(obj, dict):
        for k, v in obj.items():
            _collect_ratios(v, f"{path}.{k}" if path else str(k), out)
    elif isinstance(obj, list):
        for i, v in enumerate(obj):
            _collect_ratios(v, f"{path}[{i}]", out)
    elif isinstance(obj, (int, float)) and not isinstance(obj, bool):
        leaf = path.rsplit(".", 1)[-1].lower()
        if any(m in leaf for m in _RATIO_MARKERS) or leaf.endswith("_x"):
            out[path] = float(obj)


def summarize(root: Path = ROOT, crashed=(), smoke: bool = False) -> dict:
    """Fold every BENCH_*.json into BENCH_summary.json (best ratio each).

    Unreadable artifacts are recorded, not fatal; returns the summary dict.

    ``crashed`` names sections that raised this run.  Each gets a stub
    entry with **empty** ratios — overwriting whatever a *stale* artifact
    from an earlier run folded in — so ``check_regression`` reports its
    baseline keys as *missing* instead of silently gating last week's
    numbers (``smoke`` selects the ``BENCH_smoke_*`` stem).
    """
    summary = {}
    for p in sorted(root.glob("BENCH_*.json")):
        if p.name in ("BENCH_summary.json", "BENCH_baseline.json"):
            continue                    # outputs of this fold, not inputs
        try:
            data = json.loads(p.read_text())
        except (OSError, ValueError):
            summary[p.stem] = {"file": p.name, "error": "unreadable"}
            continue
        ratios: dict = {}
        _collect_ratios(data, "", ratios)
        best = max(ratios.items(), key=lambda kv: kv[1]) if ratios else None
        summary[p.stem] = {
            "file": p.name,
            "best_ratio": best[1] if best else None,
            "best_ratio_field": best[0] if best else None,
            "ratios": ratios,
        }
    for name in crashed:
        stem = f"BENCH_smoke_{name}" if smoke else f"BENCH_{name}"
        summary[stem] = {"file": f"{stem}.json", "error": "crashed",
                         "ratios": {}}
    out = root / "BENCH_summary.json"
    out.write_text(json.dumps(summary, indent=1, sort_keys=True))
    _section(f"summary: wrote {out}")
    print("benchmark,best_ratio,field")
    for name, ent in summary.items():
        print(f"{name},{ent.get('best_ratio')},{ent.get('best_ratio_field')}")
    return summary


def _async():
    from .async_dispatch import main as async_main
    async_main()


def _graph():
    from .graph_overlap import main as graph_main
    graph_main()


def _collective(smoke: bool = False):
    from .collective_scaling import main as collective_main
    collective_main(smoke=smoke)


def _serve(smoke: bool = False):
    from .serve_throughput import main as serve_main
    serve_main(smoke=smoke)


def _tuning(smoke: bool = False):
    from .tuning_gain import main as tuning_main
    tuning_main(smoke=smoke)


def _fusion(smoke: bool = False):
    from .graph_fusion import main as fusion_main
    fusion_main(smoke=smoke)


def _multiproc(smoke: bool = False):
    from .multiproc_scaling import main as multiproc_main
    multiproc_main(smoke=smoke)


def _train(smoke: bool = False):
    from .train_scaling import main as train_main
    train_main(smoke=smoke)


#: name -> full-pass section runner, in execution order
SECTIONS = {
    "tables": _paper_tables,
    "roofline": _roofline,
    "async": _async,
    "graph": _graph,
    "collective": _collective,
    "multiproc": _multiproc,
    "train": _train,
    "serve": _serve,
    "tuning": _tuning,
    "fusion": _fusion,
    "microbench": _model_microbench,
}

#: the tiny CI subset: best-of-N, reduced shapes, BENCH_smoke_*.json
SMOKE_SECTIONS = {
    "collective": lambda: _collective(smoke=True),
    "multiproc": lambda: _multiproc(smoke=True),
    "train": lambda: _train(smoke=True),
    "serve": lambda: _serve(smoke=True),
    "tuning": lambda: _tuning(smoke=True),
    "fusion": lambda: _fusion(smoke=True),
}


def main(argv=None) -> int:
    """Run the requested benchmark sections (all by default; the smoke
    subset with ``--smoke``), then aggregate every BENCH_*.json artifact
    into BENCH_summary.json.  Returns non-zero when any requested section
    crashed — the summary is still written so the partial results stay
    inspectable, but CI must not mistake them for a full pass."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="reduced best-of-N subset for the CI regression gate")
    ap.add_argument("--sections", default=None,
                    help="comma-separated subset (names: %s)"
                         % ",".join(SECTIONS))
    args = ap.parse_args(argv)
    use_compile_cache()
    table = SMOKE_SECTIONS if args.smoke else SECTIONS
    if args.sections:
        requested = [s.strip() for s in args.sections.split(",") if s.strip()]
        unknown = [s for s in requested if s not in table]
        if unknown:
            ap.error(f"unknown section(s) {unknown}; have {sorted(table)}")
        table = {name: table[name] for name in requested}
    failures: list = []
    for name, fn in table.items():
        _run_section(name, fn, failures)
    summarize(crashed=failures, smoke=args.smoke)
    if failures:
        _section(f"FAILED sections: {', '.join(failures)}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
