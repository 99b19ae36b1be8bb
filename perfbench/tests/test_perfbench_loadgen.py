"""The traffic generator and the latency arithmetic."""
import json
import math
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

import loadgen

TRAFFIC = Path(__file__).resolve().parent.parent / "traffic"
BIG_SEED = 2**31 + 12345


def mix(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["chat"])
def test_same_seed_same_requests(name):
    a = loadgen.build(mix(name), BIG_SEED, 40, 32000)
    b = loadgen.build(mix(name), BIG_SEED, 40, 32000)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new, x.due) == (y.max_new, y.due)


@pytest.mark.parametrize("name", ["chat"])
def test_other_seed_same_schedule_other_tokens(name):
    a = loadgen.build(mix(name), 1, 40, 32000)
    b = loadgen.build(mix(name), 2, 40, 32000)
    key = lambda r: (len(r.prompt), r.max_new, r.group, r.due)  # noqa: E731
    assert [key(r) for r in a] == [key(r) for r in b]
    assert not np.array_equal(a[0].prompt[:16], b[0].prompt[:16])


def test_lengths_within_their_clips():
    t = mix("chat")
    for r in loadgen.build(t, 5, 40, 32000):
        sp = t["system_prompts"]["tokens"]
        assert 16 <= len(r.prompt) - sp <= 1792
        assert 16 <= r.max_new <= 512
        assert 0 <= r.group < 4
        assert r.prompt.max() < 32000
        assert len(r.prompt) + r.max_new <= loadgen.max_len(t)
    spec = {"uniform": [16, 64]}
    n = loadgen.lengths(np.random.default_rng(5), spec, 500)
    assert n.min() >= 16 and n.max() <= 64 and loadgen.top(spec) == 64


def test_shared_system_prompts_are_shared():
    t = mix("chat")
    reqs = loadgen.build(t, 9, 40, 32000)
    heads = {}
    for r in reqs:
        heads.setdefault(r.group, set()).add(r.prompt[:256].tobytes())
    assert all(len(v) == 1 for v in heads.values())
    counts = Counter(r.group for r in reqs)
    assert counts[0] > counts[3]                          # Zipf skew


def test_open_loop_due_times():
    t = mix("chat")
    reqs = loadgen.build(t, 3, 40, 32000)
    due = [r.due for r in reqs]
    assert due == sorted(due)
    assert -t["ramp_s"] <= due[0] < 0 and due[-1] < 40 + t["tail_s"]
    r = t["rate_per_s"]
    window = [d for d in due if 0 <= d < 40]
    assert len(window) == round(r * 40)
    assert len(reqs) == sum(n for _, n, _, _ in loadgen.groups(t, 40))
    # the window's arrivals and sizes are the same set for every seed
    other = loadgen.build(t, 4, 40, 32000)
    assert window == [x.due for x in other if 0 <= x.due < 40]
    sizes = lambda rs: Counter((len(x.prompt), x.max_new)  # noqa: E731
                               for x in rs if 0 <= x.due < 40)
    assert sizes(reqs) == sizes(other)


def test_ttft_is_timed_from_the_due_time():
    # a request due at 10.0 s, sent late at 10.5 s, first token at 11.0 s
    assert loadgen.ttft(10.0, [11.0, 11.1]) == pytest.approx(1.0)
    assert loadgen.ttft(10.0, []) == math.inf


def test_gaps_in_window():
    times = [0.5, 1.0, 1.6, 2.5, 3.1]
    assert loadgen.gaps_in(times, 1.0, 3.0) == pytest.approx([0.5, 0.6, 0.9])


def test_window_gaps_of_every_request_and_their_summary():
    class S:
        def __init__(self, times):
            self.times = times

    sent = [S([0.5, 1.0, 1.6]), S([]), S([1.9, 2.0, 3.5])]
    gaps = loadgen.window_gaps(sent, 1.0, 2.0)
    assert sorted(gaps) == pytest.approx([0.1, 0.5, 0.6])
    line = loadgen.gap_summary(gaps)
    assert line.startswith("3 gaps, mean 400.00 p50 500.0")
    assert line.endswith("per 5 ms bin 100:1 500:1 600:1")
    assert loadgen.gap_summary([]) == "no gaps"


def test_percentile_rule():
    v = list(range(1, 101))
    assert loadgen.percentile(v, 90) == 90
    assert loadgen.percentile(v, 95) == 95
    assert loadgen.percentile([3, 1, 2], 50) == 2
    assert loadgen.percentile([5], 99) == 5
    # failures count as misses: 2 of 10 failed puts p90 at infinity
    assert loadgen.percentile([1] * 8 + [math.inf] * 2, 90) == math.inf
    assert loadgen.percentile([1] * 9 + [math.inf], 90) == 1
    with pytest.raises(ValueError):
        loadgen.percentile([], 50)
