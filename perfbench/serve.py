"""A serving cell: the program's paged engine and its own background
scheduler loop, driven by the load generator through
``StepScheduler.submit``.

Set-up makes the weights, builds the engine and warms every program the
mix will run (the decode step and the prefill chunk of each length the
mix's prompts need) through the scheduler itself.  The window then runs with the
scheduler's thread serving and one generator thread submitting.  With
``trace`` the harness also wraps the engine's host calls in
``perfbench.*`` spans, records what each call computed, and traces a part
of the window with the JAX profiler.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import threading
import time
from typing import Dict, List, Optional

import numpy as np

import loadgen
import program
import workcount


@dataclasses.dataclass
class Sent:
    req: loadgen.Request
    tokens: list                          # prompt as a list of ints
    times: List[float] = dataclasses.field(default_factory=list)
    due_abs: float = math.nan
    submitted: float = math.nan
    future: object = None
    result: Optional[list] = None
    error: Optional[BaseException] = None
    #: the tokens the reference reads after the prompt, where they are not
    #: ``result`` (the control replaces ``result`` but keeps what was served)
    context: Optional[list] = None


class CallLog:
    """Host spans and work counts of the engine's device calls (traced runs
    only): per call, the operations it needs and, per kernel event name,
    the least time that kernel's calls need on this chip."""

    def __init__(self, cfg: dict, slots: int, peaks: dict):
        self.cfg, self.slots, self.peaks = cfg, slots, peaks
        self.calls: List[tuple] = []    # (program, t, flops, {kernel: s})
        #: roofline seconds by (kernel, its calls): most calls repeat a shape
        self._roof: Dict[tuple, float] = {}

    def _record(self, prog: str, work: dict) -> None:
        t = time.perf_counter()
        pf, bw = self.peaks["bf16_flops_per_s"], self.peaks["hbm_bytes_per_s"]
        roof = {}
        for k, w in work["kernels"].items():
            key = (k, tuple(w))
            if key not in self._roof:
                self._roof[key] = workcount.kernel_seconds(key[1], pf, bw)
            roof[k] = self._roof[key]
        self.calls.append((prog, t, work["flops"], roof))

    def wrap(self, engine) -> None:
        import jax

        cfg, slots, record = self.cfg, self.slots, self._record
        arch = program.adapter(cfg)
        decode_step = engine.decode_step
        continue_admission = engine.continue_admission

        def traced_decode(tok, pos, active, key, temperature=0.0):
            lanes = [int(p) for p, a in zip(pos, active) if a]
            record("jit__decode_fn", arch.decode_work(cfg, lanes, slots))
            with jax.profiler.TraceAnnotation("perfbench.decode"):
                return decode_step(tok, pos, active, key, temperature)

        def traced_chunk(slot):
            meta = engine._meta[slot]
            c = min(engine.chunk_tokens, len(meta.prompt) - meta.pos)
            record("jit__chunk_fn", arch.chunk_work(cfg, c, meta.pos))
            with jax.profiler.TraceAnnotation("perfbench.chunk"):
                return continue_admission(slot)

        engine.decode_step = traced_decode
        engine.continue_admission = traced_chunk

    @staticmethod
    def unwrap(engine) -> None:
        del engine.decode_step, engine.continue_admission

    def per_program(self, t0: float, t1: float) -> Dict[str, dict]:
        """Mean operations, and mean roofline seconds of each kernel, per
        call of each program, over the calls issued in [t0, t1)."""
        out: Dict[str, dict] = {}
        for prog, t, fl, roof in self.calls:
            if t0 <= t < t1:
                o = out.setdefault(prog, {"n": 0, "flops": 0.0, "roof_s": {}})
                o["n"] += 1
                o["flops"] += fl
                for k, s in roof.items():
                    o["roof_s"][k] = o["roof_s"].get(k, 0.0) + s
        for o in out.values():
            o["flops_mean"] = o["flops"] / o["n"]
            o["roof_s_mean"] = {k: s / o["n"] for k, s in o["roof_s"].items()}
        return out


def chunk_lengths(reqs, chunk: int, block: int, traffic: dict) -> set:
    """Prefill chunk lengths the requests will run: whole chunks and each
    prompt's tail, from position 0 or, for a prompt that shares a system
    prompt, from the end of any whole block of that prompt (a prefix cache
    hit resumes it there, or earlier where the pool has evicted the
    prefix's later blocks)."""
    shared = traffic.get("system_prompts", {}).get("tokens", 0)
    out = {chunk}
    for r in reqs:
        top = shared // block * block if r.group >= 0 else 0
        for start in range(0, top + 1, block):
            out.add((len(r.prompt) - start) % chunk or chunk)
    return out


def _warm(sched, engine, vocab: int, lengths: set) -> None:
    """Run every program shape the mix uses once, through the scheduler's
    own loop: a prefill chunk of each length in ``lengths`` and the decode
    step."""
    rng = np.random.default_rng(0)
    c = engine.chunk_tokens
    sched.start()
    futs = [sched.submit(rng.integers(0, vocab, c + r).tolist(), max_new=2)
            for r in sorted(lengths)]
    for f in futs:
        f.result()


class Server:
    """The program's paged engine and scheduler for one configuration and
    mix, with the seed's weights, warmed for the mix's shapes."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, log):
        import jax
        from repro.models.transformer import build_model
        from repro.serve.engine import PagedEngine, StepScheduler

        arch = program.adapter(cfg)
        self.cfg, self.traffic, self.log = cfg, traffic, log
        self.slots = traffic["slots"]
        sel = program.Selections()
        t0 = time.perf_counter()
        model = build_model(arch.arch_config(cfg))
        params = arch.make_params(cfg, seed)
        jax.block_until_ready(params)
        t1 = time.perf_counter()
        max_len = loadgen.max_len(traffic)
        self.engine = PagedEngine(model, params, self.slots, max_len,
                                  num_blocks=self.slots * -(-max_len // 16)
                                  + 1)
        self.sched = StepScheduler(self.engine, temperature=0.0)
        self.setup = {"weights_s": t1 - t0,
                      "engine_s": time.perf_counter() - t1}
        self.warmed: set = set()
        self._sel = sel

    def warm(self, reqs) -> None:
        """Run once each program shape these requests will use that has
        not run yet."""
        e = self.engine
        need = chunk_lengths(reqs, e.chunk_tokens, e.block_size,
                             self.traffic) - self.warmed
        if need:
            t0 = time.perf_counter()
            _warm(self.sched, e, self.cfg["vocab_size"], need)
            self.setup["warm_s"] = time.perf_counter() - t0
            self.warmed |= need
            self.log(f"records serving each alias: {self._sel.line()}")

    def window(self, traffic: dict, seed: int, seconds: float, trace: bool,
               peaks: dict, t_start: float) -> dict:
        """Send the mix, measure ``seconds``, and collect what came back."""
        import jax

        cfg, engine, sched, slots = self.cfg, self.engine, self.sched, \
            self.slots
        reqs = loadgen.build(traffic, seed, seconds, cfg["vocab_size"])
        sent = [Sent(req=r, tokens=r.prompt.tolist()) for r in reqs]
        self.warm(reqs)
        calls = None
        if trace:
            calls = CallLog(cfg, slots, peaks)
            calls.wrap(engine)
        compiles = _CompileCounter()
        pool = engine.pool
        sched.start()
        T = time.perf_counter() + traffic["ramp_s"]
        stop = threading.Event()
        gen = threading.Thread(target=_open_loop, args=(sched, sent, T, stop),
                               name="perfbench-gen", daemon=True)
        gen.start()
        setup_s = T - t_start
        parts = dict(self.setup, ramp_s=traffic["ramp_s"])
        parts = dict(start_s=setup_s - sum(parts.values()), **parts)
        self.log("set-up seconds: " + ", ".join(
            f"{k} {v:.2f}" for k, v in parts.items()))
        _sleep_until(T)
        compiles.start()
        hits0, queries0 = pool.prefix_hits, pool.prefix_queries
        trace_info = _traced_window(T, seconds) if trace else None
        _sleep_until(T + seconds)
        hits1, queries1 = pool.prefix_hits, pool.prefix_queries
        in_window, loaded = compiles.stop()
        deadline = T + seconds + traffic["tail_s"]
        while time.perf_counter() < deadline and not _settled(sent, seconds):
            time.sleep(0.05)
        stop.set()
        gen.join()
        sched.stop(drain=False)
        if trace:
            calls.unwrap(engine)
        for s in sent:
            f = s.future
            if f is not None and f.done() and not f.cancelled():
                err = f.exception()
                if err is None:
                    s.result = f.result()
                else:
                    s.error = err
        memory_peak = (jax.devices()[0].memory_stats() or {}).get(
            "peak_bytes_in_use")
        self.log(f"compilations inside the window: {in_window}; programs "
                 f"loaded from the compile cache inside it: {loaded}")
        self.log("inter-token gaps in the window: " + loadgen.gap_summary(
            loadgen.window_gaps(sent, T, seconds)))
        self.log(f"gaps over {STALL_S} s, by the second their later token "
                 f"came: {_stalls(sent, T, seconds)}")
        out = {
            "setup_s": setup_s, "T": T,
            "seconds": seconds, "sent": sent,
            "memory_peak_bytes": memory_peak,
            "prefix": (hits1 - hits0, queries1 - queries0),
        }
        if trace:
            out["trace"] = read_trace(trace_info, self.log)
            out["calls"] = calls.per_program(*trace_info["host_window"])
        return out

    def close(self) -> None:
        """Free the program's state (weights, arena, compiled programs'
        buffers) before the reference runs."""
        self.engine.params = None
        self.engine.paged = None
        self.engine = self.sched = None
        gc.collect()


def run(cfg: dict, traffic: dict, seed: int, seconds: float, trace: bool,
        peaks: dict, t_start: float, log) -> dict:
    """One measured run of a serving cell; the program's state is freed
    when it returns."""
    server = Server(cfg, traffic, seed, log)
    out = server.window(traffic, seed, seconds, trace, peaks, t_start)
    server.close()
    return out


def _settled(sent, seconds: float) -> bool:
    """Every request due in the window has its first token or has
    failed."""
    return all(s.times or (s.future is not None and s.future.done())
               for s in sent if 0 <= s.req.due < seconds)


def _sleep_until(t: float) -> None:
    while True:
        d = t - time.perf_counter()
        if d <= 0:
            return
        time.sleep(min(d, 0.05))


def _submit(sched, s: Sent) -> None:
    times = s.times
    s.submitted = time.perf_counter()
    s.future = sched.submit(s.tokens, s.req.max_new,
                            on_token=lambda tok, i: times.append(
                                time.perf_counter()))


def _open_loop(sched, sent, T, stop) -> None:
    for s in sorted(sent, key=lambda s: s.req.due):
        s.due_abs = T + s.req.due
        while not stop.is_set():
            d = s.due_abs - time.perf_counter()
            if d <= 0:
                break
            stop.wait(min(d, 0.05))
        if stop.is_set():
            return
        _submit(sched, s)


def _traced_window(T: float, seconds: float) -> dict:
    """Trace [T + seconds/4, T + seconds/4 + min(10, seconds/2)) of the
    window; the trace is read after the window has closed."""
    import tempfile

    import jax

    import devtrace as tr

    a = T + seconds / 4
    length = min(10.0, seconds / 2)
    tmp = tempfile.mkdtemp(prefix="perfbench-trace-")
    _sleep_until(a - 1.0)
    jax.profiler.start_trace(tmp)
    _sleep_until(a)
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        h0 = time.perf_counter()
        _sleep_until(a + length)
        h1 = time.perf_counter()
    jax.profiler.stop_trace()
    return {"dir": tmp, "host_window": (h0, h1)}


def read_trace(info: dict, log) -> dict:
    """Reduce the traced window's trace file, then delete it."""
    import os
    import shutil

    import devtrace as tr

    t0 = time.perf_counter()
    size = sum(os.path.getsize(f) for f in _files(info["dir"]))
    summary = tr.summarize(tr.load(info["dir"]))
    log(f"trace: {size} bytes, reduced in {time.perf_counter() - t0:.1f}s")
    shutil.rmtree(info["dir"], ignore_errors=True)
    return summary


def _files(d):
    import glob
    import os
    return iter(glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True))


#: an inter-token gap longer than this is logged with its time
STALL_S = 0.5


def _stalls(sent, T: float, seconds: float) -> str:
    """Where the window's gaps over ``STALL_S`` ended: '+<s> (<longest
    gap> s, <lanes>)' per second of the window."""
    at: Dict[int, list] = {}
    for s in sent:
        for a, b in zip(s.times, s.times[1:]):
            if T <= b < T + seconds and b - a > STALL_S:
                at.setdefault(int(b - T), []).append(b - a)
    return ", ".join(f"+{k} s ({max(v):.2f} s, {len(v)})"
                     for k, v in sorted(at.items())) or "none"


class _CompileCounter:
    """Counts XLA backend compilations, and programs loaded from the
    persistent compile cache instead, between ``start`` and ``stop``."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    LOADED = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.n, self.loaded, self.on = 0, 0, False

        def compiled(event, duration, **kw):
            if self.on and event == self.COMPILE:
                self.n += 1

        def hit(event, **kw):
            if self.on and event == self.LOADED:
                self.loaded += 1

        jax.monitoring.register_event_duration_secs_listener(compiled)
        jax.monitoring.register_event_listener(hit)

    def start(self):
        self.on = True

    def stop(self):
        """(compilations, cache loads) since ``start``."""
        self.on = False
        return self.n, self.loaded


def counts(out: dict):
    """(attempted, failed) over the requests due in the window: a request
    fails when it raised or no token of it arrived."""
    due = [s for s in out["sent"] if 0 <= s.req.due < out["seconds"]]
    return len(due), sum(1 for s in due if s.error is not None
                         or not s.times)


def sample(out: dict, seed: int, check: dict) -> List[Sent]:
    """Finished requests to compare, drawn from the seed: the longest, then
    others until the sample holds ``min_requests`` requests and
    ``min_served_tokens`` served tokens."""
    done = [s for s in out["sent"]
            if s.result is not None and len(s.result) == s.req.max_new]
    if not done:
        return []
    done.sort(key=lambda s: -(len(s.tokens) + len(s.result)))
    rest = done[1:]
    order = np.random.default_rng(seed).permutation(len(rest))
    picked, served = [done[0]], len(done[0].result)
    for i in order:
        if (len(picked) >= check["min_requests"]
                and served >= check["min_served_tokens"]):
            break
        picked.append(rest[i])
        served += len(rest[i].result)
    return picked


def _reference(cfg: dict):
    import importlib

    return importlib.import_module(f"reference.{cfg['reference']}")


def _at_len(traffic: dict) -> int:
    """Positions the reference reads per request: the longest answer."""
    return loadgen.top(traffic["answer_tokens"])


def _positions(s: Sent):
    """The sequence the reference reads for a served request (its prompt
    and every served token but the last) and the positions whose logits
    chose the served tokens."""
    p = len(s.tokens)
    served = s.result if s.context is None else s.context
    return list(s.tokens) + list(served[:-1]), np.arange(
        p - 1, p - 1 + len(s.result))


def logit_gaps(cfg: dict, params, picked: List[Sent], traffic: dict):
    """Per request, the widest gap by which a served token's reference
    logit lies below the reference's best."""
    ref = _reference(cfg)
    gaps = []
    for s in picked:
        toks, at = _positions(s)
        lg = np.asarray(ref.logits(cfg, params, toks, at, _at_len(traffic),
                                   bucket=traffic["check"]["bucket"]),
                        np.float64)
        served = np.asarray(s.result)
        gaps.append(float(np.max(lg.max(-1)
                                 - lg[np.arange(len(at)), served])))
    return gaps


def check(cfg: dict, seed: int, out: dict, traffic: dict) -> dict:
    """The comparison that decides ``correct``, run once the program's
    state is freed: finished requests drawn from the seed, replayed
    through the float32 reference."""
    picked = sample(out, seed, traffic["check"])
    if not picked:
        return {"logit_gap": math.inf, "requests": 0, "served_tokens": 0}
    params = program.adapter(cfg).make_params(cfg, seed)
    gaps = logit_gaps(cfg, params, picked, traffic)
    del params
    gc.collect()
    return {"logit_gap": max(gaps), "requests": len(picked),
            "served_tokens": sum(len(s.result) for s in picked)}


def fp8_in_place(cfg: dict, seed: int, out: dict, traffic: dict) -> None:
    """The control: the requests that ``check`` will compare get, in place
    of the tokens the program served, the tokens the fp8 reference puts
    first at the same positions of the same prompts and served tokens."""
    picked = sample(out, seed, traffic["check"])
    if not picked:
        return
    ref = _reference(cfg)
    params = program.adapter(cfg).make_params(cfg, seed)
    for s in picked:
        toks, at = _positions(s)
        lo = ref.logits(cfg, params, toks, at, _at_len(traffic), fp8=True,
                        bucket=traffic["check"]["bucket"])
        s.context = s.result
        s.result = [int(t) for t in np.asarray(lo).argmax(-1)]
    del params
    gc.collect()
