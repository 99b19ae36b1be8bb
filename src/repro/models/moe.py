"""Mixture-of-experts FFN with expert parallelism (GShard/DeepSeek style).

Two routed execution modes, chosen by token count (both exact, both under
``shard_map`` so every collective is explicit in the lowered HLO):

* **a2a mode** (train/prefill): tokens resharded over (fsdp × expert) axes;
  each shard routes its local tokens into capacity slots, `all_to_all`
  exchanges expert rows so each device computes only its local experts, a
  second `all_to_all` returns them, and a gather-combine applies router
  gates.  Dispatch is index-based (argsort-free scatter of at most T·k rows)
  — the (T,E,C) one-hot dispatch tensor of the original GShard formulation is
  never materialized.
* **replicated mode** (decode): token batches too small to split over the
  expert axis are replicated across it; each device serves its local experts
  and a psum combines partial outputs — no all_to_all on the latency path.

Expert weights are sharded (E over "expert", D over "fsdp"); the fsdp shards
are all-gathered inside the shard_map right before use (ZeRO-3 semantics,
overlapping with the previous layer under the scanned-layer schedule).

Shared (always-on) experts run outside the routed region as a plain
tensor-parallel dense FFN.  Router aux loss = Switch-style load-balancing.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import MoEConfig
from ..core.c2mpi import halo_dispatch
from ..distributed.sharding import ParamSpec, current_context, shard
from .layers import act_fn, dense

Params = Dict[str, jax.Array]


def _shard_map(fn, mesh, in_specs, out_specs):
    """jax.shard_map with replication checking off."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def moe_param_specs(d_model: int, m: MoEConfig, dtype) -> Dict[str, ParamSpec]:
    e, f = m.n_experts, m.d_ff_expert
    specs = {
        "router": ParamSpec((d_model, e), jnp.float32, ("fsdp", None)),
        "we_g": ParamSpec((e, d_model, f), dtype, ("expert", "fsdp", None)),
        "we_u": ParamSpec((e, d_model, f), dtype, ("expert", "fsdp", None)),
        "we_d": ParamSpec((e, f, d_model), dtype, ("expert", None, "fsdp")),
    }
    if m.n_shared:
        # shared experts are small (n_shared·d_ff_expert): FSDP-shard only,
        # and compute them on the routed path's (dp×ep) token sharding so no
        # resharding happens at the shard_map boundary (EXPERIMENTS §Perf)
        fs = m.n_shared * f
        specs.update({
            "ws_g": ParamSpec((d_model, fs), dtype, ("fsdp", None)),
            "ws_u": ParamSpec((d_model, fs), dtype, ("fsdp", None)),
            "ws_d": ParamSpec((fs, d_model), dtype, (None, "fsdp")),
        })
    return specs


# ---------------------------------------------------------------------------
# Local (single-shard) routing + expert compute
# ---------------------------------------------------------------------------
def _route(x2: jax.Array, router_w: jax.Array, m: MoEConfig):
    # bf16 matmul with f32 accumulation: converting x2 itself to f32 would
    # make its cotangent f32, doubling the shard_map-boundary reshard cost
    # (observed as 20 GiB involuntary-remat all-gathers; EXPERIMENTS §Perf)
    logits = jnp.einsum("td,de->te", x2, router_w.astype(x2.dtype),
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, m.top_k)         # (T,k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch load-balance aux: E * sum_e (frac_tokens_e * frac_prob_e)
    e = m.n_experts
    onehot = jax.nn.one_hot(eidx[:, 0], e, dtype=jnp.float32)
    frac_tok = onehot.mean(axis=0)
    frac_prob = probs.mean(axis=0)
    aux = e * jnp.sum(frac_tok * frac_prob)
    return gates, eidx, aux


def _capacity(t: int, m: MoEConfig, world: int = 1) -> int:
    c = int(t * m.top_k * m.capacity_factor / m.n_experts) + 1
    return max(4, -(-c // 4) * 4)


def _dispatch_indices(eidx, t: int, c: int, e: int):
    """Capacity-slot assignment.  Returns (slot (T,k), keep (T,k))."""
    fe = eidx.reshape(-1)                               # (T*k,)
    onehot = jax.nn.one_hot(fe, e, dtype=jnp.int32)     # (T*k, E)
    pos = jnp.cumsum(onehot, axis=0) - 1                # position per expert
    pos_in_e = jnp.take_along_axis(pos, fe[:, None], axis=1)[:, 0]
    keep = pos_in_e < c
    slot = fe * c + pos_in_e
    return slot.reshape(t, -1), keep.reshape(t, -1)


def _gather_dispatch(x2, slot, keep, e: int, c: int, k: int):
    """Scatter kept (token, k) rows into (E*C, D) capacity slots."""
    t, d = x2.shape
    token_idx = jnp.repeat(jnp.arange(t), k)
    slot_safe = jnp.where(keep.reshape(-1), slot.reshape(-1), e * c)
    buf = jnp.zeros((e * c + 1, d), x2.dtype)
    buf = buf.at[slot_safe].set(x2[token_idx])
    return buf[:-1].reshape(e, c, d)


def _combine(ye, slot, keep, gates, t: int, k: int):
    e_c, d = ye.reshape(-1, ye.shape[-1]).shape
    ye_flat = ye.reshape(-1, d)
    vals = ye_flat[jnp.clip(slot.reshape(-1), 0, e_c - 1)]
    w = (gates.reshape(-1) * keep.reshape(-1)).astype(jnp.float32)[:, None]
    vals = vals.astype(jnp.float32) * w
    return vals.reshape(t, k, d).sum(axis=1)


def _expert_ffn(xe, wg, wu, wd, act: str):
    return halo_dispatch("MOE_FFN", xe, wg.astype(xe.dtype),
                         wu.astype(xe.dtype), wd.astype(xe.dtype))


def _a2a_int8(xe, ep_axis, split_axis, concat_axis):
    """all_to_all with int8 wire format (per-row absmax scales ride along).

    Halves the dispatch a2a bytes vs bf16; the scales tensor is D/256 of the
    payload.  Gradients flow through the dequantized values (straight-through
    on the rounding)."""
    scale = jnp.max(jnp.abs(xe.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(scale, 1e-12) / 127.0
    q = jnp.round(xe.astype(jnp.float32) / scale)
    q = (q + jax.lax.stop_gradient(jnp.clip(q, -127, 127) - q)).astype(jnp.int8)
    q = jax.lax.all_to_all(q, ep_axis, split_axis=split_axis,
                           concat_axis=concat_axis, tiled=True)
    scale = jax.lax.all_to_all(scale, ep_axis, split_axis=split_axis,
                               concat_axis=concat_axis, tiled=True)
    return (q.astype(jnp.float32) * scale).astype(xe.dtype)


def _moe_local(p: Params, x2: jax.Array, m: MoEConfig, act: str):
    """Single-shard reference path (CPU tests / no mesh)."""
    t = x2.shape[0]
    gates, eidx, aux = _route(x2, p["router"], m)
    c = _capacity(t, m)
    slot, keep = _dispatch_indices(eidx, t, c, m.n_experts)
    xe = _gather_dispatch(x2, slot, keep, m.n_experts, c, m.top_k)
    ye = _expert_ffn(xe, p["we_g"], p["we_u"], p["we_d"], act)
    y = _combine(ye, slot, keep, gates, t, m.top_k)
    return y.astype(x2.dtype), aux


def moe_expert_parallel(p: Params, x: jax.Array, m: MoEConfig, act: str,
                        comm) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE over a C²MPI device group (DESIGN.md §15).

    Host-side eager twin of :func:`moe_layer`'s local path: routing and the
    capacity dispatch run on the session substrate, then the (E,C,D) expert
    blocks and the expert weight stacks ``MPIX_Scatter`` over the group's
    member ranks (E split axis-0, ``E % comm.size == 0``), every member runs
    ``MOE_FFN`` on its expert slice, and ``MPIX_Gather`` reassembles the
    outputs for the gate-combine.  Per-expert FFNs are independent, so the
    split-compute-concat is bit-identical to the single-shard path —
    asserted by the §15 parity test."""
    e, n = m.n_experts, comm.size
    if e % n:
        raise ValueError(f"n_experts ({e}) must divide over the {n}-member "
                         f"device group")
    b, s, d = x.shape
    x2 = x.reshape(b * s, d)
    y_sh = None
    if p.get("ws_g") is not None:
        g = dense(x2, p["ws_g"])
        u = dense(x2, p["ws_u"])
        y_sh = dense(act_fn("swiglu", g, u), p["ws_d"])
    t = b * s
    gates, eidx, aux = _route(x2, p["router"], m)
    c = _capacity(t, m)
    slot, keep = _dispatch_indices(eidx, t, c, e)
    xe = _gather_dispatch(x2, slot, keep, e, c, m.top_k)
    parts = [comm.scatter(jnp.asarray(w, xe.dtype), axis=0)
             for w in (xe, p["we_g"], p["we_u"], p["we_d"])]
    ye_parts = comm.map("MOE_FFN", list(zip(*parts)))
    ye = comm.gather(ye_parts)
    y = _combine(ye, slot, keep, gates, t, m.top_k).astype(x2.dtype)
    if y_sh is not None:
        y = y + y_sh.astype(y.dtype)
    return y.reshape(b, s, d).astype(x.dtype), aux * m.router_aux_weight


# ---------------------------------------------------------------------------
# Distributed paths
# ---------------------------------------------------------------------------
def _moe_a2a_body(x2, router_w, wg, wu, wd, *, m: MoEConfig, act: str,
                  ep_axis: str, n_ep: int, dp_axes: Tuple[str, ...]):
    """shard_map body, a2a mode.  x2 (T_loc, D); wg/wu (E_loc, D_loc, F);
    wd (E_loc, F, D_loc)."""
    if dp_axes:
        wg = jax.lax.all_gather(wg, dp_axes, axis=1, tiled=True)
        wu = jax.lax.all_gather(wu, dp_axes, axis=1, tiled=True)
        wd = jax.lax.all_gather(wd, dp_axes, axis=2, tiled=True)
    t = x2.shape[0]
    gates, eidx, aux = _route(x2, router_w, m)
    c = _capacity(t, m)
    slot, keep = _dispatch_indices(eidx, t, c, m.n_experts)
    xe = _gather_dispatch(x2, slot, keep, m.n_experts, c, m.top_k)
    # (E, C, D) → (E/n_ep, C·n_ep, D): dispatch tokens to expert owners
    if m.a2a_precision == "int8":
        xe = _a2a_int8(xe, ep_axis, split_axis=0, concat_axis=1)
    else:
        xe = jax.lax.all_to_all(xe, ep_axis, split_axis=0, concat_axis=1,
                                tiled=True)
    ye = _expert_ffn(xe, wg, wu, wd, act)
    # inverse exchange: bring expert outputs back to token owners
    ye = jax.lax.all_to_all(ye, ep_axis, split_axis=1, concat_axis=0,
                            tiled=True)
    y = _combine(ye, slot, keep, gates, t, m.top_k)
    aux = jax.lax.pmean(aux, (*dp_axes, ep_axis))
    return y.astype(x2.dtype), aux


def _moe_replicated_body(x2, router_w, wg, wu, wd, *, m: MoEConfig, act: str,
                         ep_axis: str, n_ep: int, dp_axes: Tuple[str, ...]):
    """shard_map body, replicated mode (decode).  x2 (T_loc, D) is identical
    across the expert axis; each rank serves only its local experts and the
    partial outputs psum over the expert axis."""
    if dp_axes:
        wg = jax.lax.all_gather(wg, dp_axes, axis=1, tiled=True)
        wu = jax.lax.all_gather(wu, dp_axes, axis=1, tiled=True)
        wd = jax.lax.all_gather(wd, dp_axes, axis=2, tiled=True)
    t = x2.shape[0]
    e_loc = m.n_experts // n_ep
    my_rank = jax.lax.axis_index(ep_axis)
    gates, eidx, aux = _route(x2, router_w, m)
    # keep only expert assignments owned by this rank
    local = (eidx >= my_rank * e_loc) & (eidx < (my_rank + 1) * e_loc)
    eidx_loc = jnp.where(local, eidx - my_rank * e_loc, 0)
    gates_loc = jnp.where(local, gates, 0.0)
    c = _capacity(t, m, n_ep)
    slot, keep = _dispatch_indices(jnp.where(local, eidx_loc, e_loc), t, c,
                                   e_loc + 1)
    keep = keep & local
    xe = _gather_dispatch(x2, slot, keep, e_loc + 1, c, m.top_k)[:e_loc]
    ye = _expert_ffn(xe, wg, wu, wd, act)
    ye = jnp.concatenate([ye, jnp.zeros_like(ye[:1])], axis=0)
    y = _combine(ye, slot, keep, gates_loc, t, m.top_k)
    y = jax.lax.psum(y, ep_axis)
    aux = jax.lax.pmean(aux, (*dp_axes, ep_axis))
    return y.astype(x2.dtype), aux


def moe_layer(p: Params, x: jax.Array, m: MoEConfig, act: str
              ) -> Tuple[jax.Array, jax.Array]:
    """x (B,S,D) → (y (B,S,D), aux_loss scalar)."""
    b, s, d = x.shape
    x2 = x.reshape(b * s, d)
    ctx = current_context()
    ep_axes = ctx.rules.expert
    t = b * s
    n_dp = ctx.axis_size(tuple(a for a in ctx.rules.fsdp
                               if a not in ep_axes))
    n_ep = ctx.axis_size(ep_axes)
    a2a_capable = (ctx.mesh is not None and ep_axes
                   and m.n_experts % max(n_ep, 1) == 0
                   and t % max(n_dp * n_ep, 1) == 0
                   and t // max(n_dp * n_ep, 1) >= m.top_k)
    if a2a_capable:
        # pin tokens to the routed layout (dp×ep) for the whole MoE block —
        # shared-expert path included — so the shard_map boundary is a no-op
        x2 = shard(x2, ("fsdp", "expert"), None)
    y_sh = None
    if p.get("ws_g") is not None:
        # shared experts: token-local dense FFN (weights FSDP-gathered)
        g = dense(x2, p["ws_g"])
        u = dense(x2, p["ws_u"])
        y_sh = dense(act_fn("swiglu", g, u), p["ws_d"])

    if ctx.mesh is None or not ep_axes:
        y, aux = _moe_local(p, x2, m, act)
    else:
        assert len(ep_axes) == 1, "single expert axis supported"
        ep_axis = ep_axes[0]
        dp_axes = tuple(a for a in ctx.rules.fsdp if a != ep_axis)
        a2a_ok = a2a_capable
        body = _moe_a2a_body if a2a_ok else _moe_replicated_body
        tok_spec = P((*dp_axes, ep_axis), None) if a2a_ok else P(dp_axes, None)
        fn = functools.partial(body, m=m, act=act, ep_axis=ep_axis,
                               n_ep=n_ep, dp_axes=dp_axes)
        y, aux = _shard_map(
            fn, mesh=ctx.mesh,
            in_specs=(tok_spec, P(None, None),
                      P(ep_axis, dp_axes or None, None),
                      P(ep_axis, dp_axes or None, None),
                      P(ep_axis, None, dp_axes or None)),
            out_specs=(tok_spec, P()),
        )(x2, p["router"], p["we_g"], p["we_u"], p["we_d"])

    if y_sh is not None:
        y = y + y_sh.astype(y.dtype)
    return y.reshape(b, s, d).astype(x.dtype), aux * m.router_aux_weight
