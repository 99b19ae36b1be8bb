"""The control that sets a serving cell's upper reading, on the chip at the
cell's own size.  The benchmark's own runs do not run it.

For each seed, a whole run of the cell through ``run.run_cell`` with the
fp8 reference put in the program's place: once the window has closed, the
requests the comparison samples get, in place of the tokens the program
served, the tokens that the reference computed in fp8 (the step below the
configuration's bf16) puts first at the same positions.  Each line gives
the run's ``correct`` and the compared numbers; ``correct`` has to read
false.

    python3 perfbench/control.py --workload danube.chat --seconds 20 \
        --seeds 11 12 13
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run  # noqa: E402


def fp8_run(serve):
    """``serve.run``, wrapped so that each run's compared tokens are the
    fp8 reference's."""
    orig = serve.run

    def controlled(cfg, traffic, seed, *args, **kw):
        out = orig(cfg, traffic, seed, *args, **kw)
        serve.fp8_in_place(cfg, seed, out, traffic)
        return out

    return controlled


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    import serve

    spec = run.load_json(run.ROOT / "BENCHMARK.json")
    cell, _ = run.cell_spec(spec, args.workload)
    jax = run.setup_jax()
    devices = jax.devices()
    peaks = run.peaks_mod.require_accelerator(devices, cell["chips"])
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    serve.run = fp8_run(serve)
    for seed in args.seeds:
        res = run.run_cell(spec, args.workload, seed, args.seconds, False,
                           peaks, device, time.perf_counter())
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
