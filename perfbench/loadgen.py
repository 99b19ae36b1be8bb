"""The load generator: seeded requests from a traffic file, and the
latency arithmetic on what the clients saw.

A traffic file (``traffic/<name>.json``) gives the parameters of one mix.
Its sizes, their order and the arrival times are drawn once, from the
file's own ``shape_seed``; a run's ``--seed`` draws the tokens.  So every
seed sends the same schedule of sizes at the same times, and warms the
same set of shapes: the order of sizes alone moved the tail of the gaps
between tokens by 4%.

The loop is open: request ``i`` is due at ``due[i]`` seconds from the
window's start, whatever the server is doing; latency is timed from the
due time.  Arrivals start ``ramp_s`` before the window so that the window
sees a loaded server.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np


@dataclasses.dataclass
class Request:
    idx: int
    prompt: np.ndarray          # int32 token ids
    max_new: int
    group: int = -1             # shared system prompt id, -1 when unshared
    due: float = 0.0            # seconds from the window's start


def lengths(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    """``n`` lengths from a spec: lognormal (``median``, ``sigma``) or
    uniform (``uniform``: [lo, hi]), clipped to [``min``, ``max``]."""
    if "uniform" in spec:
        lo, hi = spec["uniform"]
        return rng.integers(lo, hi + 1, n)
    x = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def top(spec: dict) -> int:
    """The longest length a spec allows."""
    return spec["uniform"][1] if "uniform" in spec else spec["max"]


def max_len(traffic: dict) -> int:
    """Longest prompt plus answer the mix can send (sets the cache size)."""
    sys_tokens = traffic.get("system_prompts", {}).get("tokens", 0)
    return (sys_tokens + top(traffic["prompt_tokens"])
            + top(traffic["answer_tokens"]))


def groups(traffic: dict, seconds: float):
    """(name, count, first due, last due) of the requests sent in the ramp
    before the window, in the window, and after it; the count of each is
    the rate times its span."""
    r = traffic["rate_per_s"]
    spans = (("ramp", -traffic["ramp_s"], 0.0), ("window", 0.0, seconds),
             ("tail", seconds, seconds + traffic["tail_s"]))
    return [(name, int(round(r * (b - a))), a, b) for name, a, b in spans]


def shapes(traffic: dict, seconds: float):
    """Per group, the fixed (group, prompt length, answer length, due time)
    of its requests, drawn from the traffic file's ``shape_seed`` alone.
    Due times are the sorted uniform arrivals of a Poisson process with
    the group's count in the group's span."""
    rng = np.random.default_rng(traffic["shape_seed"])
    sp = traffic.get("system_prompts")
    out = []
    for _, n, a, b in groups(traffic, seconds):
        user = lengths(rng, traffic["prompt_tokens"], n)
        answer = lengths(rng, traffic["answer_tokens"], n)
        if sp:
            w = 1.0 / np.arange(1, sp["count"] + 1) ** sp["zipf_s"]
            group = rng.choice(sp["count"], size=n, p=w / w.sum())
        else:
            group = np.full(n, -1)
        due = np.sort(rng.uniform(a, b, n))
        out.append((group, user, answer, due))
    return out


def build(traffic: dict, seed: int, seconds: float,
          vocab: int) -> List[Request]:
    """The run's requests: each group's fixed sizes at the group's fixed
    due times, with tokens drawn from the seed."""
    rng = np.random.default_rng(seed)
    sp = traffic.get("system_prompts")
    systems = (rng.integers(0, vocab, (sp["count"], sp["tokens"]),
                            dtype=np.int32) if sp else None)
    out = []
    for group, user, answer, due in shapes(traffic, seconds):
        for j in range(len(user)):
            body = rng.integers(0, vocab, int(user[j]), dtype=np.int32)
            g = int(group[j])
            prompt = body if g < 0 else np.concatenate([systems[g], body])
            out.append(Request(idx=len(out), prompt=prompt,
                               max_new=int(answer[j]), group=g,
                               due=float(due[j])))
    return out


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``
    percent of the values at or below it.  ``inf`` marks a request that
    failed or never answered; it sorts last, so failures count as misses."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of no values")
    return float(v[max(0, math.ceil(q / 100.0 * len(v)) - 1)])


def ttft(due_abs: float, token_times: List[float]) -> float:
    """First token's arrival minus the time the request was due."""
    return token_times[0] - due_abs if token_times else math.inf


def gaps_in(token_times: List[float], t0: float, t1: float) -> List[float]:
    """Gaps between consecutive tokens whose later token lands in
    [t0, t1)."""
    return [b - a for a, b in zip(token_times, token_times[1:])
            if t0 <= b < t1]


def window_gaps(sent, T: float, seconds: float) -> List[float]:
    """Every inter-token gap of every request whose later token landed in
    the window [T, T + seconds)."""
    return [g for s in sent for g in gaps_in(s.times, T, T + seconds)]


def gap_summary(gaps: List[float]) -> str:
    """The gaps' count, mean and quantiles, and how many fall in each 5 ms
    bin, in milliseconds: one line for the run's log."""
    if not gaps:
        return "no gaps"
    ms = [1e3 * g for g in gaps]
    q = " ".join(f"p{p} {percentile(ms, p):.1f}" for p in (50, 90, 95, 99))
    bins: dict = {}
    for g in ms:
        b = int(g // 5) * 5
        bins[b] = bins.get(b, 0) + 1
    hist = " ".join(f"{b}:{n}" for b, n in sorted(bins.items()))
    return (f"{len(ms)} gaps, mean {sum(ms) / len(ms):.2f} {q} max "
            f"{max(ms):.1f}; per 5 ms bin {hist}")
