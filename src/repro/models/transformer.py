"""Model assembly: stage-scanned decoder stacks for all assigned families.

A model is a list of *stages* (see configs.base): each stage scans over
``repeats`` stacked copies of a block *pattern* (1..6 heterogeneous blocks
unrolled inside the scan body).  Three entry points per model:

* ``loss_fn(params, batch)``            — training loss (+ MoE aux, metrics)
* ``prefill(params, batch)``            — full-sequence forward → (last-token
                                          logits, decode cache)
* ``decode_step(params, cache, token, pos[, active])`` — one-token serve
  step; ``pos`` may be a per-slot (B,) position vector and ``active`` a
  (B,) slot mask (slot-based continuous batching, DESIGN.md §6)

All hot-spot compute routes through HALO aliases; sharding is logical-axis
based and degrades gracefully to single-device.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig, AttnConfig, BlockSpec, Stage
from ..distributed.sharding import (ParamSpec, current_context,
                                    named_sharding, shard)
from .attention import attn_param_specs, gqa_forward, mla_forward
from .layers import (embed_tokens, ffn, logits_from_hidden, rms_norm,
                     softmax_xent)
from .moe import moe_layer, moe_param_specs
from .ssm import mamba_cache_specs, mamba_forward, mamba_param_specs

PyTree = Any


# ---------------------------------------------------------------------------
# Parameter planning
# ---------------------------------------------------------------------------
def _ffn_specs(d_model: int, d_ff: int, act: str, dtype) -> Dict[str, ParamSpec]:
    s = {
        "wu": ParamSpec((d_model, d_ff), dtype, ("fsdp", "tp")),
        "wd": ParamSpec((d_ff, d_model), dtype, ("tp", "fsdp")),
    }
    if act in ("swiglu", "geglu"):
        s["wg"] = ParamSpec((d_model, d_ff), dtype, ("fsdp", "tp"))
    return s


def _block_specs(cfg: ArchConfig, spec: BlockSpec, dtype) -> Dict[str, Any]:
    d = cfg.d_model
    if spec.kind == "shared_attn":
        return {}                       # weights live in params["shared"]
    if spec.kind == "mamba":
        return {
            "ln": ParamSpec((d,), dtype, (None,), init_kind="ones"),
            "ssm": mamba_param_specs(d, spec.ssm, dtype),
        }
    out: Dict[str, Any] = {
        "ln1": ParamSpec((d,), dtype, (None,), init_kind="ones"),
        "ln2": ParamSpec((d,), dtype, (None,), init_kind="ones"),
        "attn": attn_param_specs(d, spec.attn, dtype),
    }
    if spec.moe is not None:
        out["moe"] = moe_param_specs(d, spec.moe, dtype)
    elif spec.d_ff:
        out["ffn"] = _ffn_specs(d, spec.d_ff, spec.act, dtype)
    return out


def _stack_specs(tree: PyTree, r: int) -> PyTree:
    def f(s: ParamSpec) -> ParamSpec:
        return ParamSpec((r, *s.shape), s.dtype, (None, *s.logical),
                         init_kind=s.init_kind)
    return jax.tree.map(f, tree,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def param_specs(cfg: ArchConfig) -> PyTree:
    dtype = cfg.activation_dtype()
    d = cfg.d_model
    specs: Dict[str, Any] = {
        "embed": ParamSpec((cfg.padded_vocab, d), dtype, (None, "tp")),
        "unembed": ParamSpec((d, cfg.padded_vocab), dtype, (None, "vocab")),
        "final_norm": ParamSpec((d,), dtype, (None,), init_kind="ones"),
        "stages": [],
    }
    for st in cfg.stages:
        blocks = tuple(_stack_specs(_block_specs(cfg, b, dtype), st.repeats)
                       for b in st.pattern)
        specs["stages"].append(blocks)
    if cfg.shared_attn is not None:
        specs["shared"] = {
            "ln1": ParamSpec((d,), dtype, (None,), init_kind="ones"),
            "ln2": ParamSpec((d,), dtype, (None,), init_kind="ones"),
            "attn": attn_param_specs(d, cfg.shared_attn, dtype),
            "ffn": _ffn_specs(d, cfg.shared_d_ff, "swiglu", dtype),
        }
    return specs


def init_params(cfg: ArchConfig, key: jax.Array) -> PyTree:
    """Random parameters, made by one jitted program.  Under a mesh context
    every leaf is created already sharded by its ParamSpec's logical axes,
    so no device ever holds the whole model."""
    specs = param_specs(cfg)
    leaves, treedef = jax.tree.flatten(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))

    def materialize(s: ParamSpec, k):
        if s.init_kind == "ones":
            return jnp.ones(s.shape, s.dtype)
        if s.init_kind == "zeros":
            return jnp.zeros(s.shape, s.dtype)
        if s.init_kind == "a_log":
            base = jnp.log(jnp.arange(1, s.shape[-1] + 1, dtype=jnp.float32))
            return jnp.broadcast_to(base, s.shape).astype(s.dtype)
        if s.init_kind == "dt_bias":
            # softplus^-1 of dt in [1e-3, 1e-1]
            u = jnp.linspace(1e-3, 1e-1, s.shape[-1])
            inv = jnp.log(jnp.expm1(u))
            return jnp.broadcast_to(inv, s.shape).astype(s.dtype)
        fan_in = s.shape[-2] if len(s.shape) >= 2 else s.shape[-1]
        w = jax.random.normal(k, s.shape, jnp.float32) * (fan_in ** -0.5)
        return w.astype(s.dtype)

    def build(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(
            treedef, [materialize(s, k) for s, k in zip(leaves, keys)])

    shardings = None
    if current_context().mesh is not None:
        shardings = jax.tree.unflatten(
            treedef, [named_sharding(s.shape, s.logical) for s in leaves])
    return jax.jit(build, out_shardings=shardings)(key)


# ---------------------------------------------------------------------------
# Cache planning
# ---------------------------------------------------------------------------
def _kv_cache_logical(n_kv: int) -> Tuple:
    """Shard KV heads over tp when divisible, else sequence-parallel."""
    ctx = current_context()
    tp = ctx.axis_size(ctx.rules.tp) if ctx.mesh is not None else 1
    if tp > 1 and n_kv % tp == 0:
        return ("batch", "tp", None, None)
    return ("batch", None, "seq", None)


def ring_len(cfg: ArchConfig, a: Optional[AttnConfig], seq: int) -> int:
    """Serving cache length for one attention layer.

    Sliding-window layers only ever attend to the last ``window`` keys, so
    their decode cache is a ring buffer of ``window`` slots (beyond-paper
    §Perf optimization: cuts long-context cache memory by seq/window; see
    EXPERIMENTS.md).  Disabled when a bidirectional prefix must be retained."""
    if a is not None and a.window is not None and not cfg.prefix_len:
        return min(seq, a.window)
    return seq


def _block_cache_specs(cfg: ArchConfig, spec: BlockSpec, batch: int,
                       seq: int, dtype):
    a = cfg.shared_attn if spec.kind == "shared_attn" else spec.attn
    if spec.kind == "mamba":
        return mamba_cache_specs(cfg.d_model, spec.ssm, batch, dtype)
    if a.kv_lora:
        return (
            ParamSpec((batch, seq, a.kv_lora), dtype, ("batch", "seq", None)),
            ParamSpec((batch, seq, a.rope_head_dim), dtype,
                      ("batch", "seq", None)),
        )
    logical = _kv_cache_logical(a.n_kv_heads)
    shp = (batch, a.n_kv_heads, ring_len(cfg, a, seq), a.head_dim)
    return (ParamSpec(shp, dtype, logical), ParamSpec(shp, dtype, logical))


def cache_specs(cfg: ArchConfig, batch: int, seq: int) -> PyTree:
    dtype = cfg.activation_dtype()
    out = []
    for st in cfg.stages:
        blocks = tuple(_stack_specs(
            _block_cache_specs(cfg, b, batch, seq, dtype), st.repeats)
            for b in st.pattern)
        out.append(blocks)
    return out


def init_cache(cfg: ArchConfig, batch: int, seq: int) -> PyTree:
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        cache_specs(cfg, batch, seq),
                        is_leaf=lambda x: isinstance(x, ParamSpec))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------
def _apply_block(spec: BlockSpec, bp, x, *, cfg: ArchConfig, positions,
                 shared_params, cache=None, cache_pos=None,
                 want_cache: bool = False):
    aux = jnp.zeros((), jnp.float32)
    if spec.kind == "mamba":
        h = rms_norm(x, bp["ln"], cfg.norm_eps)
        y, nc = mamba_forward(bp["ssm"], h, spec.ssm, cache=cache,
                              want_cache=want_cache)
        return x + y, aux, nc

    p = shared_params if spec.kind == "shared_attn" else bp
    a_cfg = cfg.shared_attn if spec.kind == "shared_attn" else spec.attn
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if a_cfg.kv_lora:
        att, nc = mla_forward(p["attn"], h, a_cfg, positions=positions,
                              norm_eps=cfg.norm_eps, cache=cache,
                              cache_pos=cache_pos)
    else:
        att, nc = gqa_forward(p["attn"], h, a_cfg, positions=positions,
                              prefix_len=cfg.prefix_len, cache=cache,
                              cache_pos=cache_pos)
    x = x + att
    h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    if spec.kind != "shared_attn" and spec.moe is not None:
        f, aux = moe_layer(bp["moe"], h2, spec.moe, spec.act)
    else:
        f = ffn(p["ffn"], h2, spec.act if spec.kind != "shared_attn"
                else "swiglu")
    return x + f, aux, nc


def _run_stage(st: Stage, sp, x, *, cfg, positions, shared_params,
               caches=None, cache_pos=None, mode: str = "train"):
    want_cache = mode == "prefill"
    keep_cache = want_cache or caches is not None

    def body(carry, xs):
        x, aux = carry
        # sequence-parallel residual boundary (rules.seq_act; no-op when
        # disabled or indivisible): the scan carry — and therefore the
        # remat-saved per-layer stack — lives seq-sharded over tp
        x = shard(x, "batch", "seq_act", None)
        lp, lc = xs if caches is not None else (xs, None)
        new_lc = []
        for j, spec in enumerate(st.pattern):
            cj = None if lc is None else lc[j]
            x, aux_j, nc = _apply_block(
                spec, lp[j], x, cfg=cfg, positions=positions,
                shared_params=shared_params, cache=cj, cache_pos=cache_pos,
                want_cache=want_cache)
            aux = aux + aux_j
            new_lc.append(nc)
        ys = tuple(new_lc) if keep_cache else None
        return (x, aux), ys

    body_fn = jax.checkpoint(body) if mode == "train" else body
    xs = (sp, caches) if caches is not None else sp
    carry0 = (x, jnp.zeros((), jnp.float32))
    if st.repeats <= 2:
        # short stages run as straight-line code (no while loop): the SPMD
        # partitioner shards loop-free bodies strictly better, and the
        # dry-run cost probes need every instruction visible exactly once
        carry = carry0
        ys_list = []
        for r in range(st.repeats):
            xs_r = jax.tree.map(lambda t: t[r], xs)
            carry, ys_r = body_fn(carry, xs_r)
            ys_list.append(ys_r)
        x, aux = carry
        ys = None if ys_list[0] is None else jax.tree.map(
            lambda *ts: jnp.stack(ts), *ys_list)
        return x, aux, ys
    (x, aux), ys = jax.lax.scan(body_fn, carry0, xs)
    return x, aux, ys


def _embed_inputs(params, batch, cfg: ArchConfig):
    dtype = cfg.activation_dtype()
    if cfg.frontend == "patch_embed":
        tok = embed_tokens(params["embed"], batch["tokens"]).astype(dtype)
        x = jnp.concatenate([batch["patches"].astype(dtype), tok], axis=1)
    elif cfg.frontend == "frame_embed":
        x = batch["frames"].astype(dtype)
    else:
        x = embed_tokens(params["embed"], batch["tokens"]).astype(dtype)
    return shard(x, "batch", None, None)


def _forward(params, x, positions, cfg: ArchConfig, *, caches=None,
             cache_pos=None, mode="train"):
    aux_total = jnp.zeros((), jnp.float32)
    new_caches = []
    for i, st in enumerate(cfg.stages):
        c_i = None if caches is None else caches[i]
        x, aux, nc = _run_stage(
            st, params["stages"][i], x, cfg=cfg, positions=positions,
            shared_params=params.get("shared"), caches=c_i,
            cache_pos=cache_pos, mode=mode)
        aux_total = aux_total + aux
        new_caches.append(nc)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, aux_total, new_caches


def _masked_logits(params, x, cfg: ArchConfig):
    logits = logits_from_hidden(params["unembed"], x)
    if cfg.padded_vocab != cfg.vocab_size:
        tail = jnp.where(jnp.arange(cfg.padded_vocab) < cfg.vocab_size,
                         0.0, -1e30).astype(logits.dtype)
        logits = logits + tail
    return logits


# ---------------------------------------------------------------------------
# Public model object
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Model:
    cfg: ArchConfig

    # -- planning ---------------------------------------------------------
    def param_specs(self) -> PyTree:
        return param_specs(self.cfg)

    def cache_specs(self, batch: int, seq: int) -> PyTree:
        return cache_specs(self.cfg, batch, seq)

    def init(self, key) -> PyTree:
        return init_params(self.cfg, key)

    def init_cache(self, batch: int, seq: int) -> PyTree:
        return init_cache(self.cfg, batch, seq)

    # -- training -----------------------------------------------------------
    def loss_fn(self, params, batch) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        cfg = self.cfg
        x = _embed_inputs(params, batch, cfg)
        b, s = x.shape[0], x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        x, aux, _ = _forward(params, x, positions, cfg, mode="train")
        logits = _masked_logits(params, x, cfg)
        labels = batch["labels"]
        if cfg.frontend == "patch_embed":
            np_ = cfg.prefix_len
            logits = jax.lax.dynamic_slice_in_dim(
                logits, np_ - 1, labels.shape[1], axis=1)
        mask = batch.get("mask")
        xent, _ = softmax_xent(logits, labels, mask)
        loss = xent + aux
        return loss, {"xent": xent, "aux": aux}

    # -- serving -----------------------------------------------------------
    def prefill(self, params, batch) -> Tuple[jax.Array, PyTree]:
        cfg = self.cfg
        x = _embed_inputs(params, batch, cfg)
        b, s = x.shape[0], x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        x, _, caches = _forward(params, x, positions, cfg, mode="prefill")
        logits = _masked_logits(params, x[:, -1:], cfg)
        return logits[:, 0], caches

    def supports_chunked_prefill(self) -> bool:
        """True when prompts can be prefilled in multi-token chunks through
        the decode caches.  Attention blocks (GQA ring/full and MLA) accept
        multi-token cache updates; Mamba's cache path is single-token
        (ssm.mamba_forward has no chunk-with-initial-state form), MoE
        routing is capacity-dependent (expert capacity is sized per
        invocation, so chunked and whole-prompt prefills route — and drop —
        tokens differently), and stub frontends / prefix-LM configs have no
        token chunking — those serve via whole-prompt admission instead."""
        if self.cfg.frontend != "none" or self.cfg.prefix_len:
            return False
        return all(b.kind != "mamba" and b.moe is None
                   for st in self.cfg.stages for b in st.pattern)

    def prefill_chunk(self, params, caches, tokens, p0
                      ) -> Tuple[jax.Array, PyTree]:
        """Run one prefill chunk through the decode caches.

        ``tokens`` (B, C) continues each lane's prompt at positions
        ``p0..p0+C-1`` (``p0`` scalar or (B,)); every attention cache is
        updated in place (ring slots included) and the returned logits are
        the chunk's *last* token's — only the final chunk of a prompt is
        sampled.  Callers must gate on :meth:`supports_chunked_prefill`."""
        cfg = self.cfg
        x = embed_tokens(params["embed"], tokens).astype(
            cfg.activation_dtype())
        x = shard(x, "batch", None, None)
        b, c = tokens.shape
        pos = jnp.asarray(p0, jnp.int32)
        if pos.ndim == 0:
            pos = jnp.full((b,), pos, jnp.int32)
        positions = pos[:, None] + jnp.arange(c)[None, :]
        x, _, new_caches = _forward(params, x, positions, cfg,
                                    caches=caches, cache_pos=pos,
                                    mode="decode")
        logits = _masked_logits(params, x[:, -1:], cfg)
        return logits[:, 0], new_caches

    def decode_step(self, params, caches, token, pos, active=None
                    ) -> Tuple[jax.Array, PyTree]:
        """token (B,1) int32 (or (B,1,D) embeddings for stub frontends).

        ``pos``: scalar int32 (lockstep batch — every lane writes the same
        cache slot) or a (B,) int32 vector of per-slot write positions
        (continuous batching, DESIGN.md §6).  ``active``: optional (B,) bool
        slot mask — cache updates from inactive lanes are dropped, so free /
        retiring slots never corrupt the persistent slot-indexed cache."""
        cfg = self.cfg
        if cfg.frontend == "frame_embed":
            x = token.astype(cfg.activation_dtype())
        else:
            x = embed_tokens(params["embed"], token
                             ).astype(cfg.activation_dtype())
        x = shard(x, "batch", None, None)
        b = x.shape[0]
        pos = jnp.asarray(pos, jnp.int32)
        if pos.ndim == 0:
            pos = jnp.full((b,), pos, jnp.int32)
        positions = pos[:, None]
        x, _, new_caches = _forward(params, x, positions, cfg,
                                    caches=caches, cache_pos=pos,
                                    mode="decode")
        if active is not None:
            act = jnp.asarray(active, bool)

            def keep(new, old):
                # every cache leaf is (R, B, ...): lanes live on axis 1
                m = act.reshape((1, b) + (1,) * (new.ndim - 2))
                return jnp.where(m, new, old.astype(new.dtype))

            new_caches = jax.tree.map(keep, new_caches, caches)
        logits = _masked_logits(params, x, cfg)
        return logits[:, 0], new_caches


def build_model(cfg: ArchConfig) -> Model:
    return Model(cfg=cfg)
