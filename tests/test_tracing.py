"""Host spans and counters of the serving path, and the per-alias scopes
that dispatch gives every record's ops (DESIGN.md §6, Tracing)."""
import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core import tracing
from repro.core.c2mpi import halo_session
from repro.models import build_model
from repro.serve.engine import PagedEngine, StepScheduler


@pytest.fixture(scope="module")
def danube(rng):
    cfg = get_config("h2o-danube-1.8b").reduced()
    model = build_model(cfg)
    return model, model.init(rng)


@pytest.fixture
def annotations(monkeypatch):
    """Stands in for ``jax.profiler.TraceAnnotation``; yields the (name,
    args) of every annotation constructed."""
    made = []

    class Annotation(contextlib.nullcontext):
        def __init__(self, name, **args):
            super().__init__()
            made.append((name, args))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    return made


def _serve(model, params, prompts, max_new=3):
    """Drive a paged engine (chunked prefill, 16-token chunks) to the end;
    returns the scheduler and the active-lane count of each decode step."""
    engine = PagedEngine(model, params, slots=2, max_len=64, block_size=8,
                         chunk_tokens=16)
    stepped = []
    decode_step = engine.decode_step

    def counted(tok, pos, active, key, temperature=0.0):
        stepped.append(int(sum(bool(a) for a in active)))
        return decode_step(tok, pos, active, key, temperature)

    engine.decode_step = counted
    sched = StepScheduler(engine)
    futs = [sched.submit(p, max_new=max_new) for p in prompts]
    sched.drain()
    for f in futs:
        f.result(timeout=60)
    return sched, stepped


PROMPTS = [list(range(1, 41)), [5, 6, 7], list(range(3, 23))]


def test_spans_nest_and_count_the_decode_lanes(danube, annotations):
    model, params = danube
    sched, stepped = _serve(model, params, PROMPTS)
    got = sched.spans.snapshot()
    sec, calls = got["seconds"], got["calls"]
    parts = ("serve.admit", "serve.chunk", "serve.decode")
    assert sec["serve.step"] >= sum(sec[p] for p in parts)
    assert sec["serve.decode"] >= sum(
        sec[f"serve.decode.{p}"] for p in ("prep", "call", "wait"))
    assert calls["serve.decode"] == len(stepped) > 0
    for p in ("prep", "call", "wait"):
        assert calls[f"serve.decode.{p}"] == len(stepped)
    assert got["counts"]["serve.decode_lanes"] == sum(stepped)
    assert calls["serve.admit"] == len(PROMPTS)
    rep = sched.report()
    assert rep.t3_s == pytest.approx(sum(sec[p] for p in parts))
    assert rep.t1_s + rep.t3_s == pytest.approx(sec["serve.step"])

    # one request's admission and its later chunks share its uid; the
    # chunks the loop runs pick up where the first one (run inside the
    # admission) stopped
    admits = {a["uid"]: a for n, a in annotations if n == "serve.admit"}
    long_uid = next(u for u, a in admits.items() if a["prompt_len"] == 40)
    chunks = [a for n, a in annotations if n == "serve.chunk"]
    assert {a["uid"] for a in chunks} <= set(admits)
    mine = [a for a in chunks if a["uid"] == long_uid]
    assert [(a["start"], a["chunk_len"]) for a in mine] == [(16, 16), (32, 8)]
    lanes = [a["lanes"] for n, a in annotations if n == "serve.decode"]
    assert lanes == stepped


def test_report_inside_an_iteration_reads_the_last_one(danube):
    """A report taken while a step is in flight (here from a token hook)
    counts neither that step nor the device calls it has made so far."""
    model, params = danube
    engine = PagedEngine(model, params, slots=2, max_len=64, block_size=8,
                         chunk_tokens=16)
    sched = StepScheduler(engine)
    seen = []

    def hook(tok, index):
        seen.append((sched.report(), sched.spans.snapshot()["seconds"]))

    futs = [sched.submit(p, max_new=3, on_token=hook) for p in PROMPTS]
    sched.drain()
    for f in futs:
        f.result(timeout=60)
    assert seen
    for rep, live in seen:
        assert rep.t1_s >= 0
        assert rep.t1_s + rep.t3_s <= live.get("serve.step", 0.0)
    sched.reset_stats()
    rep = sched.report()
    assert (rep.t1_s, rep.t3_s, rep.steps) == (0.0, 0.0, 0)


def test_background_loop_and_window_deltas(danube):
    model, params = danube
    engine = PagedEngine(model, params, slots=1, max_len=32, block_size=8)
    with StepScheduler(engine) as sched:
        sched.submit([1, 2, 3], max_new=2).result(timeout=60)
        base = sched.spans.snapshot()
        sched.submit([4, 5, 6], max_new=3).result(timeout=60)
    window = sched.spans.since(base)
    assert window["calls"]["serve.admit"] == 1
    assert window["calls"]["serve.decode"] == 2
    assert window["counts"]["serve.decode_lanes"] == 2


def test_a_span_that_raises_is_still_counted():
    spans = tracing.Spans()
    with pytest.raises(ValueError):
        with spans.span("serve.decode"):
            raise ValueError("device call failed")
    spans.count("serve.decode_lanes", 3)
    got = spans.snapshot()
    assert got["calls"] == {"serve.decode": 1}
    assert got["counts"] == {"serve.decode_lanes": 3}


@pytest.mark.parametrize("pallas_only", [False, True])
def test_dispatched_ops_carry_the_alias_scope(danube, pallas_only):
    """A jitted model step's compiled HLO names each dispatched alias in the
    op_name of the ops its record emitted, whichever record served it."""
    model, params = danube
    toks = jnp.ones((1, 8), jnp.int32)
    session = halo_session()
    detached = ([session.detach_agent(p) for p in ("xla", "jnp")]
                if pallas_only else [])
    try:
        # a fresh function object retraces, so selection runs again
        text = jax.jit(lambda p, t: model.prefill(p, {"tokens": t})[0]) \
            .lower(params, toks).compile().as_text()
    finally:
        for agent in detached:
            session.attach_agent(agent)
    ops = set(re.findall(r'op_name="([^"]*)"', text))
    for alias in ("MMM", "RMSNORM"):
        assert any(f"/halo.{alias}/" in o for o in ops), alias
    if pallas_only:
        assert any("/halo.MMM/jit(_mmm_impl)/" in o for o in ops)
