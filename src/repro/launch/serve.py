"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Slot-based continuous batching (DESIGN.md §6): a StepScheduler admits
requests into a fixed pool of decode slots, each request retires
independently on its own EOS / ``max_new``, and the run reports throughput,
per-request latency percentiles, and the serving T1/T3 scorecard.
``--legacy`` routes the same workload through the whole-batch RequestQueue
compat path instead; ``--paged`` serves it from the paged KV cache
(refcounted block arena, COW prefix sharing, chunked prefill —
DESIGN.md §14) and reports the allocator scorecard.
"""
from __future__ import annotations

import argparse
import time

import jax

from ..configs import get_config
from ..core.portability import ServeReport, percentile_nearest
from ..models import build_model
from ..serve.engine import (PagedEngine, RequestQueue, ServeEngine,
                            SlotEngine, StepScheduler)
from .cache import use_compile_cache


def workload(cfg, *, seed: int, requests: int, prompt_len: int,
             max_new: int):
    """The launcher's request mix: ``requests`` random prompts of
    ``prompt_len`` tokens drawn from ``seed``, their decode budgets, and
    the per-lane cache length that holds the longest request."""
    rng = jax.random.split(jax.random.PRNGKey(seed), requests)
    prompts = [list(map(int, jax.random.randint(
        rng[i], (prompt_len,), 0, cfg.vocab_size)))
        for i in range(requests)]
    # mixed decode budgets: slot lanes retire independently, the legacy
    # path runs every request to the live batch max
    max_news = [max(1, max_new - (i % 4) * (max_new // 4))
                for i in range(requests)]
    return prompts, max_news, prompt_len + max_new + cfg.prefix_len + 8


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--slots", type=int, default=4,
                    help="decode-slot pool size (legacy: batch size)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16,
                    help="largest per-request decode budget (the workload "
                         "mixes shorter ones in)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--legacy", action="store_true",
                    help="whole-batch RequestQueue compat path")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache: refcounted block arena, COW "
                         "prefix sharing, chunked prefill (DESIGN.md §14)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="tokens per KV block (--paged)")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="arena capacity in blocks (--paged; default: "
                         "dense-parity capacity)")
    ap.add_argument("--chunk", type=int, default=None,
                    help="prefill chunk length in tokens (--paged; 0 = "
                         "whole-prompt admission)")
    args = ap.parse_args(argv)
    if args.legacy and args.paged:
        ap.error("--legacy and --paged are mutually exclusive")
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    prompts, max_news, max_len = workload(
        cfg, seed=args.seed, requests=args.requests,
        prompt_len=args.prompt_len, max_new=args.max_new)

    sched = None
    paged = None
    if args.legacy:
        engine = ServeEngine(model, max_len=max_len)
        front = RequestQueue(engine, params, args.slots, args.prompt_len,
                             temperature=args.temperature)
    elif args.paged:
        paged = PagedEngine(model, params, args.slots, max_len,
                            block_size=args.block_size,
                            num_blocks=args.num_blocks,
                            chunk_tokens=args.chunk)
        sched = StepScheduler(paged, temperature=args.temperature,
                              seed=args.seed)
        front = sched
    else:
        sched = StepScheduler(SlotEngine(model, params, args.slots, max_len),
                              temperature=args.temperature, seed=args.seed)
        front = sched

    lat = []
    t0 = time.perf_counter()
    with front:
        futs = []
        for p, n in zip(prompts, max_news):
            ts = time.perf_counter()
            fut = front.submit(p, max_new=n)
            fut.add_done_callback(
                lambda f, ts=ts: lat.append(time.perf_counter() - ts))
            futs.append(fut)
        results = [f.result() for f in futs]
    dt = time.perf_counter() - t0
    toks = sum(len(r) for r in results)
    # done-callbacks may trail the last result(); wait before aggregating
    deadline = time.perf_counter() + 5.0
    while len(lat) < len(futs) and time.perf_counter() < deadline:
        time.sleep(0.001)
    lat.sort()
    print(f"served {len(results)} requests, {toks} tokens in {dt:.2f}s "
          f"({toks / dt:.1f} tok/s)")
    print(f"request latency p50={percentile_nearest(lat, .5) * 1e3:.0f}ms "
          f"p95={percentile_nearest(lat, .95) * 1e3:.0f}ms")
    if sched is not None:
        print(ServeReport.csv_header())
        print(sched.report().csv())
    if paged is not None:
        s = paged.stats()
        print(f"paged arena: capacity={s['capacity']} "
              f"hit_rate={s['prefix_hit_rate']:.3f} "
              f"blocks_per_token={s['blocks_per_token']:.3f} "
              f"forks={s['forks']} evictions={s['evictions']}")
    for f, r in list(zip(futs, results))[:3]:
        print(f"  req {f.uid}: {r[:8]}…")
    return results


if __name__ == "__main__":
    main()
