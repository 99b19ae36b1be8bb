"""The benchmark's entry point.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of this machine and prints
one JSON line last on standard output: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device`` and, traced, the trace's
``breakdown``; ``checks``, last, holds each compared number beside its
limit.  Everything a cell needs is found by name: the configuration file
the cell names, the adapter ``arch/<architecture>.py`` and the plain
reference ``reference/<reference>.py`` that file names,
``traffic/<traffic>.json``, ``cells/<cell>.json`` (the limits of its
comparison) and ``metrics/<metric>.py`` (one reader per metric).
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

import peaks as peaks_mod  # noqa: E402
import program  # noqa: E402,F401  (puts the program's src/ on the path)


def log(msg: str) -> None:
    print(f"perfbench: {msg}", file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_spec(spec: dict, name: str):
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; have "
                         f"{sorted(cells)}")
    cell = cells[name]
    config = next(c for c in spec["configs"] if c["name"] == cell["config"])
    return cell, config


def metrics_for(spec: dict, cell: dict, trace: bool) -> list:
    """The metrics this cell reports: with ``trace`` the per-layer ones,
    else the end-to-end ones; a metric without ``workloads`` goes to every
    cell that reports the end-to-end metric it moves (or to every cell)."""
    e2e = spec["end_to_end"]
    mine = {m["name"] for m in e2e
            if cell["name"] in m.get("workloads", [cell["name"]])}
    if not trace:
        return [m for m in e2e if m["name"] in mine]
    return [m for m in spec["per_layer"]
            if cell["name"] in m.get("workloads", ())
            or ("workloads" not in m and m["moves"] in mine)]


def read_metric(name: str, ctx: dict):
    path = HERE / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(ctx)


def setup_jax():
    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax


def run_cell(spec: dict, cell_name: str, seed: int, seconds: float,
             trace: bool, peaks: dict, device: dict, t_start: float,
             root: Path = ROOT) -> dict:
    """Set up, measure and check one cell; return the result object.
    ``root`` holds the configuration files the spec names, and
    ``perfbench/traffic`` and ``perfbench/cells`` under it."""
    import serve

    cell, config = cell_spec(spec, cell_name)
    cfg = load_json(root / config["file"])
    traffic = load_json(root / "perfbench" / "traffic"
                        / f"{cell['traffic']}.json")
    limits = load_json(root / "perfbench" / "cells"
                       / f"{cell_name}.json")["checks"]
    out = serve.run(cfg, traffic, seed, seconds, trace, peaks, t_start, log)
    attempted, failed = serve.counts(out)
    ctx = dict(out, cfg=cfg, traffic=traffic, peaks=peaks)
    metrics = {}
    for m in metrics_for(spec, cell, trace):
        v = read_metric(m["name"], ctx)
        if v is not None and math.isfinite(v):
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        else:
            log(f"metric {m['name']}: nothing to read ({v})")
    t_ref = time.perf_counter()
    got = serve.check(cfg, seed, out, traffic)
    log(f"reference comparison took {time.perf_counter() - t_ref:.1f}s")
    checks = {k: {"value": got[k] if math.isfinite(got[k]) else None,
                  "limit": lim["limit"]} for k, lim in limits.items()}
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())
    log(f"compared: {json.dumps({k: v for k, v in got.items() if k not in checks})}")
    dev = dict(device, memory_peak_bytes=out["memory_peak_bytes"])
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": dev}
    if trace:
        s = out["trace"]
        dev["busy_s"], dev["window_s"] = s["busy_s"], s["window_s"]
        result["breakdown"] = s["breakdown"]
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_json(ROOT / "BENCHMARK.json")
    cell, _ = cell_spec(spec, args.workload)
    jax = setup_jax()
    devices = jax.devices()
    try:
        peaks = peaks_mod.require_accelerator(devices, cell["chips"])
    except peaks_mod.UnknownDevice as e:
        log(f"cannot measure: {e}")
        return 3
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    result = run_cell(spec, args.workload, args.seed, args.seconds,
                      bool(args.trace), peaks, device, T_START)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
