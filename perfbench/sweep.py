"""Find the highest arrival rate a serving cell sustains: run its open-loop
window at each of a few rates in one process, on one engine, and print per
rate the finished requests per second, the TTFT tails, the median and tail
of the gaps between tokens, and the backlog left when the window closed.
Run on the chip, once, when a cell's rate is set:

    python3 perfbench/sweep.py --workload danube.chat --rates 2 3 4 5 6 \
        --seconds 20
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    import loadgen
    import serve

    spec = run.load_json(run.ROOT / "BENCHMARK.json")
    cell, config = run.cell_spec(spec, args.workload)
    jax = run.setup_jax()
    peaks = run.peaks_mod.require_accelerator(jax.devices(), cell["chips"])
    cfg = run.load_json(run.ROOT / config["file"])
    traffic = run.load_json(run.HERE / "traffic" / f"{cell['traffic']}.json")
    server = serve.Server(cfg, traffic, args.seed, run.log)
    for rate in args.rates:
        t = dict(traffic, rate_per_s=rate)
        out = server.window(t, args.seed, args.seconds, False, peaks,
                            time.perf_counter())
        T, W = out["T"], args.seconds
        due = [s for s in out["sent"] if 0 <= s.req.due < W]
        done_in = sum(1 for s in out["sent"] if s.result is not None
                      and s.times and T <= s.times[-1] < T + W)
        ttft = [loadgen.ttft(s.due_abs, s.times) for s in due]
        gaps = loadgen.window_gaps(out["sent"], T, W)
        backlog = sum(1 for s in out["sent"] if s.submitted < T + W and (
            not s.times or s.times[-1] >= T + W))
        print(json.dumps({
            "rate": rate, "due": len(due), "finished_per_s": done_in / W,
            "ttft_p50_ms": loadgen.percentile(ttft, 50) * 1e3,
            "ttft_p90_ms": loadgen.percentile(ttft, 90) * 1e3,
            "itl_p50_ms": loadgen.percentile(gaps, 50) * 1e3,
            "itl_p90_ms": loadgen.percentile(gaps, 90) * 1e3,
            "in_flight_at_close": backlog}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
