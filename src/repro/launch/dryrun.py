import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()
# the dry run lowers against the forced host platform; never let a locally
# attached accelerator (libtpu) claim the backend instead
os.environ.setdefault("JAX_PLATFORMS", "cpu")

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this lowers the real step function (train_step / prefill /
decode_step) against ShapeDtypeStruct stand-ins carrying production
shardings, compiles it for the 256-chip single-pod mesh and the 512-chip
2-pod mesh, and records:

  * compiled.memory_analysis()  — per-device bytes (proves it fits HBM)
  * compiled.cost_analysis()    — per-device FLOPs / bytes for §Roofline
  * the collective schedule     — parsed from the partitioned HLO

Results are written one JSON per cell under --out; benchmarks/roofline.py
derives the three roofline terms from them.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh single,multi --out results/dryrun
"""

import argparse
import dataclasses
import json
import re
import time
import traceback
from pathlib import Path
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs import ARCH_IDS, get_config
from ..configs.base import SHAPES, ArchConfig, InputShape
from ..configs.shapes import shape_applicable
from ..data.pipeline import batch_specs
from ..distributed.sharding import (MeshContext, ParamSpec, mesh_context,
                                    named_sharding, sp_rules)
from ..models.transformer import build_model, cache_specs, param_specs
from ..optim.adamw import AdamWState
from ..train.trainer import TrainHyper, TrainState, make_train_step
from .mesh import make_production_mesh

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "c64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^\s]*)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_TENSOR_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _tensor_bytes(text: str) -> int:
    total = 0
    for dt, dims in _TENSOR_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*([a-z0-9]+)\[")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")


def parse_collectives(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Sum per-device output bytes of every collective op, by op kind.

    TPU-width normalization: the CPU backend *promotes* bf16 collectives to
    f32 (``to_apply=%…_promoted``; converts fused into neighbouring ops), so
    a naive byte count doubles every activation collective relative to the
    TPU target.  An f32 collective whose producing op consumes only bf16
    operands — or which is explicitly promotion-marked — is counted at bf16
    width.  Raw counts are preserved in "bytes_raw"."""
    lines = hlo_text.splitlines()
    defs: Dict[str, Tuple[str, str]] = {}      # name -> (dtype, line)
    for ln in lines:
        dm = _DEF_RE.match(ln)
        if dm:
            defs[dm.group(1)] = (dm.group(2), ln)

    def _origin_dtype(name: str, depth: int = 4) -> str:
        """Chase an operand through convert/reshape/copy/bitcast/transpose/
        fusion wrappers to its source dtype."""
        while depth > 0:
            d = defs.get(name)
            if d is None:
                return "?"
            dt, dl = d
            if dt == "bf16":
                return "bf16"
            body = dl[dl.index("(", dl.index("=")):] if "(" in dl else ""
            inner = _OPERAND_RE.findall(body)
            if not inner:
                return dt
            # transparent ops: dtype/layout plumbing and promoted math
            if any(op in dl for op in (" convert(", " reshape(", " copy(",
                                       " bitcast(", " transpose(", " dot(",
                                       "_fusion", " fusion(", " add(",
                                       " dynamic-slice(", " slice(")):
                name = inner[0]
                depth -= 1
                continue
            return dt
        return "?"

    def bf16_origin(line: str) -> bool:
        if "_promoted" in line:
            return True
        args = line[line.index("(", line.index("=")):]
        names = _OPERAND_RE.findall(args)
        saw = False
        for n in names[:4]:
            o = _origin_dtype(n)
            if o == "bf16":
                saw = True
            elif o == "?":
                continue
            else:
                return False
        return saw

    out: Dict[str, Dict[str, float]] = {}
    for ln in lines:
        m = _COLL_RE.search(ln)
        if not m:
            continue
        shape_txt, kind = m.group(1), m.group(2)
        b = _tensor_bytes(shape_txt)
        adj = b
        if "f32[" in shape_txt:
            try:
                if bf16_origin(ln):
                    adj = b // 2
            except (ValueError, IndexError):
                pass
        d = out.setdefault(kind, {"count": 0, "bytes": 0, "bytes_raw": 0})
        d["count"] += 1
        d["bytes"] += adj
        d["bytes_raw"] += b
    return out


def collective_link_bytes(colls: Dict[str, Dict[str, float]]) -> float:
    """Ring-model bytes-per-device over ICI: all-reduce moves ~2× its output,
    the others ~1× (within a (n-1)/n factor)."""
    total = 0.0
    for kind, d in colls.items():
        factor = 2.0 if kind == "all-reduce" else 1.0
        total += factor * d["bytes"]
    return total


def count_params(cfg: ArchConfig) -> Tuple[int, int]:
    """(total params, active params per token — MoE top-k aware)."""
    import math
    specs = param_specs(cfg)
    leaves = jax.tree.leaves(specs,
                             is_leaf=lambda x: isinstance(x, ParamSpec))
    total = sum(math.prod(s.shape) for s in leaves)
    # active: only top_k routed experts touch a given token
    active = total
    for st in cfg.stages:
        for b in st.pattern:
            if b.moe is not None:
                e, k = b.moe.n_experts, b.moe.top_k
                per_expert = 3 * cfg.d_model * b.moe.d_ff_expert
                active -= st.repeats * (e - k) * per_expert
    return total, active


def opt_state_specs(p_specs) -> AdamWState:
    def f32spec(s: ParamSpec) -> ParamSpec:
        return ParamSpec(s.shape, jnp.float32, s.logical)
    return AdamWState(
        step=ParamSpec((), jnp.int32, ()),
        mu=jax.tree.map(f32spec, p_specs,
                        is_leaf=lambda x: isinstance(x, ParamSpec)),
        nu=jax.tree.map(f32spec, p_specs,
                        is_leaf=lambda x: isinstance(x, ParamSpec)))


def _structs(tree, ctx: MeshContext):
    return jax.tree.map(lambda s: s.struct(ctx), tree,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def _scalar_struct(dtype=jnp.int32):
    return jax.ShapeDtypeStruct((), dtype)


def lower_cell(cfg: ArchConfig, shape: InputShape, mesh,
               ctx: MeshContext, microbatches: int = 1) -> Any:
    """Build and lower the cell's step function; returns `lowered`."""
    model = build_model(cfg)
    p_specs = param_specs(cfg)
    params = _structs(p_specs, ctx)

    if shape.kind == "train":
        hp = TrainHyper(microbatches=microbatches)
        step = make_train_step(model, hp)
        state = TrainState(params=params,
                           opt=_structs(opt_state_specs(p_specs), ctx),
                           err_fb=None)
        batch = batch_specs(cfg, shape, ctx)
        return jax.jit(step, donate_argnums=(0,)).lower(state, batch)

    if shape.kind == "prefill":
        batch = batch_specs(cfg, shape, ctx)
        batch.pop("labels", None)
        batch.pop("mask", None)
        return jax.jit(model.prefill).lower(params, batch)

    # decode: one new token against a cache of seq_len
    caches = _structs(cache_specs(cfg, shape.global_batch, shape.seq_len), ctx)
    b = shape.global_batch
    if cfg.frontend == "frame_embed":
        tok = jax.ShapeDtypeStruct(
            (b, 1, cfg.d_model), cfg.activation_dtype(),
            sharding=named_sharding((b, 1, cfg.d_model),
                                    ("batch", None, None), ctx))
    else:
        sh = named_sharding((b, 1), ("batch", None), ctx)
        tok = (jax.ShapeDtypeStruct((b, 1), jnp.int32, sharding=sh)
               if sh is not None else jax.ShapeDtypeStruct((b, 1), jnp.int32))
    return jax.jit(model.decode_step, donate_argnums=(1,)).lower(
        params, caches, tok, _scalar_struct())


def _with_repeats(cfg: ArchConfig, reps: Dict[int, int]) -> ArchConfig:
    stages = tuple(
        dataclasses.replace(st, repeats=reps.get(i, 1))
        for i, st in enumerate(cfg.stages))
    return dataclasses.replace(cfg, stages=stages)


def _cost_of(cfg: ArchConfig, shape: InputShape, mesh, ctx,
             microbatches: int) -> Dict[str, float]:
    lowered = lower_cell(cfg, shape, mesh, ctx, microbatches=microbatches)
    compiled = lowered.compile()
    ca = compiled.cost_analysis() or {}
    colls = parse_collectives(compiled.as_text())
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "collective_link_bytes": collective_link_bytes(colls),
    }


def corrected_costs(cfg: ArchConfig, shape: InputShape, mesh, ctx,
                    microbatches: int) -> Dict[str, float]:
    """Trip-count-corrected roofline costs.

    HLO cost analysis visits each instruction once, so scanned layer stacks
    are undercounted by their trip count.  Probe lowerings with 1 vs 2
    repeats of each stage (short stages unroll — no while loop) give the
    exact marginal cost of one layer of that stage; the full model's cost is
    the 1-layer base plus (repeats−1)·marginal per stage."""
    base_reps = {i: 1 for i in range(len(cfg.stages))}
    c1 = _cost_of(_with_repeats(cfg, base_reps), shape, mesh, ctx,
                  microbatches)
    out = dict(c1)
    for i, st in enumerate(cfg.stages):
        if st.repeats == 1:
            continue
        reps = dict(base_reps)
        reps[i] = 2
        c2 = _cost_of(_with_repeats(cfg, reps), shape, mesh, ctx,
                      microbatches)
        for k in out:
            out[k] += (st.repeats - 1) * max(0.0, c2[k] - c1[k])
    return out


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             out_dir: Optional[Path] = None, verbose: bool = True,
             rules: str = "default", microbatches: int = 1
             ) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "kind": shape.kind, "rules": rules, "microbatches": microbatches,
    }
    if not shape_applicable(cfg, shape):
        rec["status"] = "skipped"
        rec["reason"] = "long_500k requires sub-quadratic attention (DESIGN.md §5)"
        _write(rec, out_dir)
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = mesh.devices.size
    rec["chips"] = int(n_chips)
    total, active = count_params(cfg)
    rec["n_params"] = total
    rec["n_params_active"] = active
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    rec["tokens_per_step"] = tokens
    factor = 6 if shape.kind == "train" else 2
    rec["model_flops"] = factor * active * tokens

    t0 = time.time()
    rule_obj = sp_rules() if rules == "sp" else None
    try:
        with mesh_context(mesh, rule_obj) as ctx:
            lowered = lower_cell(cfg, shape, mesh, ctx,
                                 microbatches=microbatches)
            rec["lower_s"] = round(time.time() - t0, 2)
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t1, 2)
            mem = compiled.memory_analysis()
            rec["memory"] = {
                "argument_bytes": int(mem.argument_size_in_bytes),
                "output_bytes": int(mem.output_size_in_bytes),
                "temp_bytes": int(mem.temp_size_in_bytes),
                "alias_bytes": int(mem.alias_size_in_bytes),
                "code_bytes": int(mem.generated_code_size_in_bytes),
            }
            ca = compiled.cost_analysis() or {}
            rec["cost"] = {
                "flops": float(ca.get("flops", 0.0)),
                "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            }
            colls = parse_collectives(compiled.as_text())
            rec["collectives"] = colls
            rec["collective_link_bytes"] = collective_link_bytes(colls)
            # trip-count-corrected roofline costs via stage probes
            try:
                t2 = time.time()
                rec["cost_corrected"] = corrected_costs(
                    cfg, shape, mesh, ctx, microbatches)
                rec["probe_s"] = round(time.time() - t2, 2)
            except Exception as pe:  # fall back to raw costs, loudly
                rec["cost_corrected_error"] = f"{type(pe).__name__}: {pe}"
            rec["status"] = "ok"
    except Exception as e:  # a failing cell is a bug — record it loudly
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = round(time.time() - t0, 2)
    if verbose:
        status = rec["status"]
        extra = ""
        if status == "ok":
            gb = (rec["memory"]["argument_bytes"]
                  + rec["memory"]["temp_bytes"]) / 2**30
            extra = (f" flops/dev={rec['cost']['flops']:.3g}"
                     f" mem/dev={gb:.2f}GiB"
                     f" colls={sum(c['count'] for c in rec['collectives'].values())}"
                     f" [{rec['lower_s']}s lower, {rec['compile_s']}s compile]")
        print(f"[dryrun] {arch} × {shape_name} × {mesh_kind}: {status}{extra}",
              flush=True)
    _write(rec, out_dir)
    return rec


def _write(rec: Dict[str, Any], out_dir: Optional[Path]):
    if out_dir is None:
        return
    out_dir.mkdir(parents=True, exist_ok=True)
    name = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}.json"
    (out_dir / name).write_text(json.dumps(rec, indent=1))


def run_graph_smoke(out_dir: Optional[Path] = None,
                    verbose: bool = True) -> Dict[str, Any]:
    """Validate the execution-graph layer (DESIGN.md §8) in the dry-run
    environment: capture a small diamond DAG with an independent branch,
    run it on the forced-host backend, and record per-node placements and
    wall time to ``graph_smoke.json``."""
    import jax.numpy as jnp

    from ..core import (KernelRegistry, RuntimeAgent, default_manifest,
                        halo_graph)
    from ..kernels import register_all

    registry = KernelRegistry()
    register_all(registry)
    agent = RuntimeAgent(registry=registry, manifest=default_manifest())
    rec: Dict[str, Any] = {"kind": "graph_smoke"}
    t0 = time.time()
    try:
        n = 64
        a = jnp.eye(n) + 0.1
        gamma = jnp.ones(n)
        cr = {al: agent.claim(al) for al in ("EWMM", "MMM", "RMSNORM", "JS")}
        a_dd = a + n * jnp.eye(n)
        with halo_graph(session=agent) as g:
            top = agent.isend((a, a), cr["EWMM"])
            left = agent.isend((top, a), cr["MMM"])
            right = agent.isend((top, gamma), cr["RMSNORM"])
            out = agent.isend((left, right), cr["EWMM"])
            js = agent.isend((a_dd, jnp.zeros(n), jnp.ones(n)), cr["JS"])
        g.wait(timeout=120)
        rec["nodes"] = [
            {"uid": node.uid, "alias": node.alias,
             "parents": [p.uid for p in node.parents],
             "platform": node.platform}
            for node in g.nodes]
        rec["outputs"] = len(g.outputs)
        rec["status"] = "ok"
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    finally:
        agent.finalize()
    rec["total_s"] = round(time.time() - t0, 2)
    if verbose:
        print(f"[dryrun] graph smoke: {rec['status']} "
              f"({len(rec.get('nodes', []))} nodes, {rec['total_s']}s)",
              flush=True)
    if out_dir is not None:
        out_dir.mkdir(parents=True, exist_ok=True)
        (out_dir / "graph_smoke.json").write_text(json.dumps(rec, indent=1))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single,multi")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--rules", default="default", choices=["default", "sp"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--graph-smoke", action="store_true",
                    help="also validate the execution-graph layer on the "
                         "forced-host backend (writes graph_smoke.json)")
    args = ap.parse_args()
    archs = ARCH_IDS if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = args.mesh.split(",")
    out_dir = Path(args.out)
    failures = 0
    if args.graph_smoke:
        failures += run_graph_smoke(out_dir)["status"] == "error"
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                rec = run_cell(arch, shape, mesh_kind, out_dir,
                               rules=args.rules,
                               microbatches=args.microbatches)
                failures += rec["status"] == "error"
    print(f"[dryrun] done; failures={failures}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
