"""Smoke test of the system's main paths on one TPU chip.

One process, the only one that touches JAX, runs each phase through the
entry points a user calls, then prints one JSON line naming the device:

1. **C²MPI host template** — the eleven subroutines of
   ``examples/quickstart.py`` through ``halo.claim``/``send``/``recv`` at
   chip sizes, once with default selection and once pinned to the Pallas
   records; every result is checked against the alias's jnp fail-safe
   oracle, and any record the runtime quarantined (a silent re-placement
   off the chip's kernels) fails the run.
2. **Serving, full width** — h2o-danube-1.8b at its published widths with
   random weights: ``repro.launch.serve`` serves 8 requests from the paged
   KV cache (chunked and whole-prompt prefill) and from the dense slot
   engine; whole-prompt paged and dense greedy tokens must be identical.
   One prompt's prefill logits with the Pallas records must agree with the
   same prefill traced without them.  Chunked-prefill tokens must equal
   dense tokens up to the first near-tie, shown by replaying both prefills
   on the dense tokens and reading every step's logits.
3. **Training, one chip** — the same model cut to 2 layers, 3
   ``Trainer.run`` steps on synthetic data; the loss must be finite.

``--chips 4`` runs only sharded training instead: the 2-layer model on a
2×2 ``("data", "model")`` mesh with parameters created sharded, compared
step by step with the same run on one device.

Run:  python chip_smoke.py              (one chip, all phases)
      python chip_smoke.py --chips 4    (four chips, sharded training)

Without a TPU the script exits non-zero before any phase runs.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "h2o-danube-1.8b"
SEED = 0
#: the serving workload: requests, prompt tokens, largest decode budget
REQUESTS, PROMPT_LEN, MAX_NEW = 8, 128, 16
#: the paged engine's default prefill chunk: two 16-token blocks
PAGED_CHUNK = 32
#: relative L2 error allowed against a reference, by result dtype: f32
#: results admit the bf16-pass matmuls XLA and Mosaic use by default on TPU
REL_TOL = {"float32": 1e-2, "complex64": 1e-2, "bfloat16": 3e-2}
#: prefill logits (bf16, 24 layers) with and without the Pallas records
PREFILL_TOL = 5e-2
#: sharded vs one-device training loss, relative, per step
LOSS_TOL = 1e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def rel_err(out, ref) -> float:
    import numpy as np
    o, r = np.asarray(out), np.asarray(ref)
    kind = np.complex128 if np.iscomplexobj(r) else np.float64
    o, r = o.astype(kind), r.astype(kind)
    if o.shape != r.shape:
        return float("inf")
    return float(np.linalg.norm(o - r) / max(np.linalg.norm(r), 1e-30))


def check_close(what: str, out, ref, tol: float) -> float:
    err = rel_err(out, ref)
    if not err <= tol:
        fail(f"{what}: relative error {err:.3e} exceeds {tol:.1e}")
    return err


# ---------------------------------------------------------------------------
# Phase 1: the C²MPI host template
# ---------------------------------------------------------------------------
def subroutine_jobs(n: int = 8192, vec: int = 1 << 20, sig: int = 1 << 22,
                    rows: int = 64, cols: int = 4096, sp: int = 4096):
    """The quickstart's eleven subroutines at chip sizes, from ``SEED``."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.spmm import dense_to_bell, random_block_sparse

    k = jax.random.split(jax.random.PRNGKey(SEED), 8)
    a = jax.random.normal(k[0], (n, n), jnp.float32)
    b = jax.random.normal(k[1], (n, n), jnp.float32) + 3.0
    x = jax.random.normal(k[2], (n,), jnp.float32)
    v = jax.random.normal(k[3], (vec,), jnp.float32)
    s = jax.random.normal(k[4], (sig,), jnp.float32)
    taps = jax.random.normal(k[5], (17,), jnp.float32)
    vals, idx = dense_to_bell(random_block_sparse(k[6], sp, sp, 64, 128, 0.25),
                              64, 128)
    bs = jax.random.normal(k[7], (sp, sp), jnp.float32)
    grid = jax.random.normal(k[2], (rows, cols), jnp.float32)
    return {
        "MMM": (a, b),
        "EWMM": (a, b),
        "EWMD": (a, b),
        "MVM": (a, x),
        "VDP": (v, v),
        "JS": (a + n * jnp.eye(n, dtype=jnp.float32), jnp.zeros(n), x),
        "1DCONV": (s, taps),
        "SMMM": (vals, idx, bs),
        "FFT": (grid,),
        "SORT": (grid,),
        "HIST": (jax.nn.sigmoid(v),),
    }


def _served_by(session, before) -> list:
    return [p for p, a in session.agents.items()
            if a.metrics["completed"] > before.get(p, 0)]


def phase_c2mpi(jobs) -> None:
    import jax
    from repro import halo

    session = halo.session()
    for alias, args in jobs.items():
        ref = jax.block_until_ready(session.registry.failsafe(alias).fn(*args))
        ref = jax.tree.leaves(ref)
        line = [f"{alias:7s}"]
        for mode, overrides in (("default", None),
                                ("pallas", {"allowed_platforms": ("pallas",)})):
            before = {p: a.metrics["completed"]
                      for p, a in session.agents.items()}
            t0 = time.perf_counter()
            cr = halo.claim(alias, overrides=overrides)
            halo.send(args, cr)
            out = jax.tree.leaves(halo.recv(cr))
            dt = time.perf_counter() - t0
            served = _served_by(session, before)
            resolved = [r for recs, _ in cr.resolution_cache.values()
                        for r in recs]
            session.free(cr)
            if mode == "pallas" and served != ["pallas"]:
                fail(f"{alias}: the call pinned to Pallas was served by "
                     f"{served}")
            if len(out) != len(ref):
                fail(f"{alias} ({mode}): {len(out)} outputs, oracle has "
                     f"{len(ref)}")
            errs = [check_close(f"{alias} ({mode})", o, r,
                                REL_TOL.get(str(r.dtype), 0.0))
                    for o, r in zip(out, ref)]
            names = ",".join(f"{r.platform}/{getattr(r.fn, '__name__', '?')}"
                             for r in resolved)
            line.append(f"{mode}: {'+'.join(served)} ({names}) "
                        f"err={max(errs):.2e} wall={dt * 1e3:.0f}ms")
        log("  " + " | ".join(line))
    failed = session.scheduler.failed_record_keys()
    if failed:
        fail(f"records quarantined during the C²MPI phase: {failed}")


# ---------------------------------------------------------------------------
# Phase 2: serving at full width
# ---------------------------------------------------------------------------
def phase_serve() -> dict:
    """Paged serving with chunked prefill (the default), paged with
    whole-prompt admission, and the dense slot engine; returns each run's
    tokens.  Whole-prompt admission runs the dense engine's own prefill
    program, so its greedy tokens must equal the dense engine's exactly.
    Chunked prefill reduces attention in another order, so its tokens are
    held to the dense run's by :func:`check_chunked`."""
    from repro.launch import serve

    argv = ["--arch", ARCH, "--slots", "4", "--requests", str(REQUESTS),
            "--prompt-len", str(PROMPT_LEN), "--max-new", str(MAX_NEW),
            "--seed", str(SEED)]
    runs = {}
    for name, extra in (("paged", ["--paged"]),
                        ("paged whole-prompt", ["--paged", "--chunk", "0"]),
                        ("dense", [])):
        t0 = time.perf_counter()
        runs[name] = serve.main(argv + extra)
        gc.collect()
        log(f"serve {name}: {len(runs[name])} requests, "
            f"{sum(map(len, runs[name]))} tokens, wall "
            f"{time.perf_counter() - t0:.1f}s (compile included)")
    for name, res in runs.items():
        if len(res) != REQUESTS or not all(res):
            fail(f"serve {name}: a request returned no tokens: {res}")
    dense = runs["dense"]
    diff = [i for i, (p, d) in enumerate(zip(runs["paged whole-prompt"],
                                             dense)) if p != d]
    if diff:
        fail(f"paged whole-prompt and dense greedy tokens differ in requests "
             f"{diff}: {[(runs['paged whole-prompt'][i], dense[i]) for i in diff]}")
    log("serve: paged whole-prompt and dense greedy tokens identical")
    return runs


def check_prefill(model, params) -> None:
    """Prefill logits with the Pallas records against the same prefill
    traced after the Pallas agent is detached."""
    import jax
    from repro.core.c2mpi import halo_session

    toks = jax.random.randint(jax.random.PRNGKey(SEED + 1), (1, PROMPT_LEN),
                              0, model.cfg.vocab_size)
    batch = {"tokens": toks}

    def compiled():
        # a fresh function object retraces, so selection runs again
        return jax.jit(lambda p, b: model.prefill(p, b)[0]).lower(
            params, batch).compile()

    session = halo_session()
    t0 = time.perf_counter()
    with_pallas = compiled()
    t1 = time.perf_counter()
    agent = session.detach_agent("pallas")
    try:
        without = compiled()
    finally:
        session.attach_agent(agent)
    log(f"prefill lower+compile: {t1 - t0:.1f}s with the Pallas records, "
        f"{time.perf_counter() - t1:.1f}s without")
    if "tpu_custom_call" not in with_pallas.as_text():
        fail("prefill traced with the Pallas agent holds no Pallas kernel")
    if "tpu_custom_call" in without.as_text():
        fail("prefill traced without the Pallas agent still holds a kernel")
    err = check_close("prefill logits, Pallas vs XLA records",
                      with_pallas(params, batch), without(params, batch),
                      PREFILL_TOL)
    log(f"prefill logits Pallas vs XLA records: relative error {err:.3e} "
        f"(limit {PREFILL_TOL})")


def replay_logits(model, params, prompts, tokens, cache_len: int,
                  chunk: int):
    """Per-step logits of each request's ``tokens`` fed back one at a time
    (teacher forcing) after two prefills of its prompt: whole-prompt, as
    the dense engine admits, and ``chunk`` tokens at a time through the
    decode caches, as the paged engine admits by default.  ``tokens`` are
    padded to one length; returns two (requests, steps, vocab) f32 arrays."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.serve.kvcache import pad_caches

    toks = jnp.asarray(prompts, jnp.int32)
    forced = jnp.asarray(tokens, jnp.int32)
    prompt_len, steps = toks.shape[1], forced.shape[1]
    decode = jax.jit(model.decode_step, donate_argnums=(1,))

    def follow(first, caches):
        out = [first]
        for t in range(1, steps):
            logits, caches = decode(params, caches, forced[:, t - 1:t],
                                    jnp.int32(prompt_len + t - 1))
            out.append(logits)
        return np.stack([np.asarray(x, np.float32) for x in out], axis=1)

    first, one = jax.jit(model.prefill)(params, {"tokens": toks})
    whole = follow(first, pad_caches(model.cfg, one, cache_len))
    step = jax.jit(model.prefill_chunk, donate_argnums=(1,))
    caches = model.init_cache(toks.shape[0], cache_len)
    for p0 in range(0, prompt_len, chunk):
        first, caches = step(params, caches, toks[:, p0:p0 + chunk],
                             jnp.int32(p0))
    return whole, follow(first, caches)


def check_chunked(model, params, runs) -> None:
    """The default paged run (chunked prefill) against the dense engine.

    Both prefills are replayed on the dense run's tokens and every step's
    logits compared: chunking reorders attention's reductions, so its
    logits may differ from whole-prompt prefill's, within ``PREFILL_TOL``
    relative L2 at every step.  A logit change of at most δ can flip a
    greedy token only where the winner leads the runner-up by at most 2δ,
    so chunked tokens must equal dense tokens up to the first such step;
    each flip is printed with its lead and δ.  The replay must also be
    faithful: at every step the dense engine's token is the replay's
    greedy choice, to within the largest δ seen."""
    import numpy as np
    from repro.launch import serve

    dense, chunked = runs["dense"], runs["paged"]
    prompts, _, cache_len = serve.workload(
        model.cfg, seed=SEED, requests=REQUESTS, prompt_len=PROMPT_LEN,
        max_new=MAX_NEW)
    steps = max(map(len, dense))
    forced = np.asarray([d + d[-1:] * (steps - len(d)) for d in dense])
    t0 = time.perf_counter()
    whole, chunk = replay_logits(model, params, prompts, forced, cache_len,
                                 PAGED_CHUNK)
    valid = np.arange(steps)[None, :] < np.asarray(
        [len(d) for d in dense])[:, None]
    delta = np.abs(chunk - whole).max(-1)
    rel = (np.linalg.norm(chunk - whole, axis=-1)
           / np.linalg.norm(whole, axis=-1))
    picks = whole.argmax(-1)
    own = np.take_along_axis(whole, forced[..., None], -1)[..., 0]
    np.put_along_axis(whole, forced[..., None], -np.inf, -1)  # the runner-up
    lead = own - whole.max(-1)
    noise = float(delta[valid].max())
    worst = tuple(np.argwhere(valid & (lead == lead[valid].min()))[0])
    log(f"chunked vs whole-prompt replay ({PAGED_CHUNK}-token chunks, "
        f"{int(valid.sum())} steps, {time.perf_counter() - t0:.1f}s): "
        f"logit relative L2 max {rel[valid].max():.3e} (limit "
        f"{PREFILL_TOL}), largest logit change δ {noise:.3e}; dense "
        f"token's lead over the runner-up median {np.median(lead[valid]):.3e},"
        f" min {lead[valid].min():.3e} (request {worst[0]} step {worst[1]})")
    if not rel[valid].max() <= PREFILL_TOL:
        fail(f"chunked-prefill logits differ from whole-prompt prefill by "
             f"{rel[valid].max():.3e} relative L2")
    if not lead[valid].min() >= -noise:
        i, t = np.argwhere(valid & (lead < -noise))[0]
        fail(f"the replay does not follow the dense engine: request {i} "
             f"step {t} token {forced[i, t]} trails the replay's choice by "
             f"{-lead[i, t]:.3e}")
    for i, (c, d) in enumerate(zip(chunked, dense)):
        if len(c) != len(d):
            fail(f"request {i}: chunked paged run returned {len(c)} tokens, "
                 f"dense {len(d)}")
        f = next((t for t, (a, b) in enumerate(zip(c, d)) if a != b), None)
        if f is None:
            continue
        log(f"request {i} step {f}: chunked token {c[f]}, dense {d[f]}; the "
            f"dense token leads by {lead[i, f]:.3e}, chunking moved the "
            f"logits by up to δ={delta[i, f]:.3e} there; the replay picks "
            f"{picks[i, f]} after whole-prompt prefill, "
            f"{chunk[i, f].argmax()} after chunked")
        if not lead[i, f] <= 2 * delta[i, f]:
            fail(f"request {i}: chunked paged tokens leave the dense run at "
                 f"step {f}, where the dense token leads by {lead[i, f]:.3e} "
                 f"> 2δ = {2 * delta[i, f]:.3e}: not a near-tie")
    same = sum(c == d for c, d in zip(chunked, dense))
    log(f"serve: chunked paged tokens equal dense in {same} of {REQUESTS} "
        f"requests; each difference begins at a near-tie")


def phase_logits(cfg, runs) -> None:
    """Full-width logit checks on the serving model's own parameters."""
    import jax
    from repro.models import build_model

    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(SEED))
    log(f"model {cfg.name}: param bytes "
        f"{sum(leaf.nbytes for leaf in jax.tree.leaves(params))}")
    check_prefill(model, params)
    check_chunked(model, params, runs)


# ---------------------------------------------------------------------------
# Phase 3: training
# ---------------------------------------------------------------------------
def two_layer(cfg):
    """``cfg`` cut in depth to 2 layers; widths untouched."""
    return dataclasses.replace(cfg, stages=tuple(
        dataclasses.replace(st, repeats=2) for st in cfg.stages))


def train_losses(cfg, *, steps: int = 3, batch: int = 8, seq: int = 512,
                 mesh=None):
    """``Trainer.run`` for ``steps`` steps; returns the loss history.  With
    a mesh, parameters are created sharded and checked to span it."""
    import jax
    import jax.numpy as jnp
    from repro.data.pipeline import SyntheticLM
    from repro.distributed.sharding import mesh_context
    from repro.models import build_model
    from repro.train.trainer import TrainHyper, Trainer

    model = build_model(cfg)
    trainer = Trainer(model=model, hp=TrainHyper(
        base_lr=3e-4, warmup_steps=1, total_steps=steps), log_every=1)
    pipe = SyntheticLM(cfg, seq_len=seq, global_batch=batch, seed=SEED)

    def data_fn(step):
        return {k: jnp.asarray(v) for k, v in pipe.batch(step).items()}

    with mesh_context(mesh):
        state = trainer.init_state(jax.random.PRNGKey(SEED))
        if mesh is not None:
            _check_spread(state.params, mesh.devices.size)
        t0 = time.perf_counter()
        _, history = trainer.run(state, data_fn, steps)
    dt = time.perf_counter() - t0
    losses = [loss for _, loss in history]
    log(f"train ({'mesh ' + str(dict(mesh.shape)) if mesh else '1 device'}):"
        f" losses {losses}, wall {dt:.1f}s for {steps} steps "
        f"(compile included)")
    if len(losses) != steps or not all(map(math.isfinite, losses)):
        fail(f"training loss not finite: {losses}")
    return losses


def _check_spread(params, n: int) -> None:
    """Every layer matrix is split ``n`` ways, one shard per device."""
    import jax

    def shard_size(x):
        return math.prod(x.sharding.shard_shape(x.shape))

    layer = [x for x in jax.tree.leaves(params["stages"]) if x.ndim >= 3]
    whole = [x for x in layer if shard_size(x) * n != x.size
             or len(x.sharding.device_set) != n]
    if not layer or whole:
        fail(f"{len(whole)} of {len(layer)} layer weights are not split "
             f"over the {n}-device mesh")
    leaves = jax.tree.leaves(params)
    log(f"train: {len(layer)} layer weights each split {n} ways; param "
        f"bytes per device {sum(shard_size(x) * x.dtype.itemsize for x in leaves)}"
        f" of {sum(x.nbytes for x in leaves)}")


def phase_sharded(cfg, **kw) -> None:
    """The same steps on a 2×2 mesh and on device 0 alone."""
    import jax
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 2), ("data", "model"))
    with jax.default_device(jax.devices()[0]):
        one = train_losses(cfg, **kw)
    gc.collect()
    sharded = train_losses(cfg, mesh=mesh, **kw)
    errs = [abs(a - b) / abs(b) for a, b in zip(sharded, one)]
    log(f"sharded vs one device: per-step relative loss difference "
        f"{errs} (limit {LOSS_TOL})")
    if not max(errs) <= LOSS_TOL:
        fail(f"sharded losses {sharded} differ from one-device {one}")


# ---------------------------------------------------------------------------
def _phase(name: str, fn, *args, **kw):
    log(f"== {name}")
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    gc.collect()
    log(f"== {name}: ok, {time.perf_counter() - t0:.1f}s")
    return out


def _peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-training phase on a 2x2 "
                         "mesh, against the same steps on one device")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX found {dev.platform} devices; nothing was run")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} devices, JAX sees "
             f"{len(devices)}")
    # the smoke reads no untracked state: no TuningDB, no autotune cache
    for var in ("HALO_TUNING_DB", "HALO_AUTOTUNE_CACHE"):
        os.environ.pop(var, None)
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro import halo
        from repro.configs import get_config
        from repro.launch.cache import use_compile_cache
    except ImportError as e:
        fail(f"the repository's code is not next to this script: {e}")
    log(f"device: {dev.platform} kind={dev.device_kind!r} "
        f"count={len(devices)} jax={jax.__version__}")
    log(f"compile cache: {use_compile_cache()}")

    cfg = get_config(ARCH)
    t_all = time.perf_counter()
    if args.chips == 4:
        _phase("sharded training, 2x2 mesh vs one device", phase_sharded,
               two_layer(cfg))
    else:
        halo.initialize()
        _phase("C2MPI host template, 11 subroutines", phase_c2mpi,
               subroutine_jobs())
        runs = _phase(f"serving {ARCH}, full width", phase_serve)
        _phase("logits: Pallas vs XLA records, chunked vs whole prefill",
               phase_logits, cfg, runs)
        log(f"peak_bytes_in_use after serving and logits: {_peak_bytes(dev)}")
        _phase(f"training {ARCH} cut to 2 layers, one chip", train_losses,
               two_layer(cfg))
        failed = halo.session().scheduler.failed_record_keys()
        if failed:
            fail(f"records quarantined during the run: {failed}")
        halo.finalize()
    log(f"peak_bytes_in_use: {_peak_bytes(dev)}; total wall "
        f"{time.perf_counter() - t_all:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
