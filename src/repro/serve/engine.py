"""Slot-based continuous-batching serving stack (DESIGN.md §6).

Three layers:

* :class:`SlotEngine` — device-facing core: a fixed pool of ``slots`` decode
  lanes backed by one persistent slot-indexed cache.  Exactly one compiled
  decode program (fixed ``(B, 1)`` shapes with per-slot positions and an
  active-slot mask) plus one compiled prefill-insert-sample program per
  distinct prompt length (length-bucketed admission, slot index traced).
* :class:`StepScheduler` — the host loop.  Each engine iteration (a) admits
  queued requests into free slots via prefill-into-slot, (b) runs one jitted
  batched decode step across all occupied slots, and (c) retires slots
  independently on per-request EOS or ``max_new`` — requests join and leave
  mid-flight with no echo padding and no batch-max coupling.  ``submit``
  returns a :class:`~repro.core.agents.HaloFuture` immediately, with
  per-token streaming hooks.  Host spans (``serve.*``,
  :mod:`repro.core.tracing`) time each iteration and its admission, chunk
  and decode calls; their totals give the scorecard the kernel path reports
  (:class:`~repro.core.portability.ServeReport`).
* :class:`ServeEngine` / :class:`RequestQueue` — the legacy whole-batch
  front, kept as a thin compat wrapper over the slot engine: batch
  ``generate`` submits one request per prompt row and drains synchronously;
  ``RequestQueue.flush`` still joins requests at batch boundaries but no
  longer echoes pad lanes.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import logging
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.agents import AgentDeadError, AgentState, HaloFuture
from ..core.portability import ServeReport
from ..core.tracing import Spans
from ..models.transformer import Model
from .kvcache import (BlockPool, LeafSpec, NoFreeBlocks, _is_spec,
                      copy_block, evict_slot, gather_views, init_paged,
                      insert_slot, leaf_layout, pad_caches,
                      prefix_block_keys, ring_lengths, scatter_slots,
                      write_entries)

log = logging.getLogger("repro.serve.engine")

PyTree = Any


class AdmissionError(RuntimeError):
    """Request rejected by the admission/QoS policy: its class queue-depth
    cap was hit at submit, or it aged out of the queue past ``max_delay``."""


@dataclasses.dataclass(frozen=True)
class QoSClass:
    """Per-class admission limits.  ``max_depth`` caps how many requests of
    the class may sit queued (submit past it raises
    :class:`AdmissionError`); ``max_delay`` bounds how long a queued request
    may wait before it is failed instead of admitted (seconds)."""
    max_depth: Optional[int] = None
    max_delay: Optional[float] = None


@dataclasses.dataclass
class AdmissionPolicy:
    """Admission/QoS policy for :class:`StepScheduler` (DESIGN.md §14).

    ``classes`` maps a QoS class name (the ``qos=`` argument to ``submit``)
    to its limits; unknown classes get ``default``.  ``watermark`` is the
    fraction of the paged arena that must remain unreserved *after* an
    admission — requests that would dip below it stay queued (and
    eventually age out via their class ``max_delay``), so sustained
    overload degrades into bounded queueing + rejections instead of an
    allocator failure mid-decode.  Dense slot engines ignore the
    watermark (their memory is fixed at construction)."""
    classes: Dict[str, QoSClass] = dataclasses.field(default_factory=dict)
    default: QoSClass = QoSClass()
    watermark: float = 0.0

    def qos(self, name: str) -> QoSClass:
        return self.classes.get(name, self.default)


def sample_tokens(logits: jax.Array, key: jax.Array, temperature) -> jax.Array:
    """(B, V) logits → (B,) int32 next tokens.

    ``temperature`` is traced, so one compiled program serves both greedy
    (``<= 0``) and stochastic sampling — the slot engine never retraces when
    a caller switches sampling modes."""
    temperature = jnp.asarray(temperature, jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits.astype(jnp.float32) / jnp.maximum(temperature, 1e-6)
    drawn = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temperature > 0.0, drawn, greedy)


@dataclasses.dataclass
class Request:
    uid: int
    prompt: List[int]
    max_new: int
    eos_id: Optional[int] = None
    qos: str = "default"
    result: Optional[List[int]] = None
    future: Optional[HaloFuture] = None
    submitted_at: float = 0.0
    started_at: Optional[float] = None      # admission (prefill-into-slot)
    finished_at: Optional[float] = None
    # streaming hook: called as on_token(token, index) from the step thread
    on_token: Optional[Callable[[int, int], None]] = None

    def stream(self, tok: int, index: int) -> None:
        if self.on_token is not None:
            try:
                self.on_token(tok, index)
            except Exception:
                log.exception("on_token hook raised (request %d)", self.uid)


# ---------------------------------------------------------------------------
# Slot engine: fixed decode-lane pool over a slot-indexed cache
# ---------------------------------------------------------------------------
class SlotEngine:
    """Fixed pool of ``slots`` decode lanes over one persistent cache.

    Device-facing only — no queueing policy lives here.  The decode step
    compiles once (fixed ``(slots, 1)`` token shape, ``(slots,)`` position
    vector and active mask); admission compiles once per distinct prompt
    length, with the target slot index traced so all slots share each
    bucket's program."""

    def __init__(self, model: Model, params: PyTree, slots: int,
                 max_len: int):
        if model.cfg.frontend != "none":   # token-embedding frontend only
            raise ValueError(
                "SlotEngine serves token frontends; patch/frame stub "
                "frontends go through ServeEngine's lockstep fallback")
        self.model = model
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.caches = model.init_cache(slots, max_len)
        self.spans = Spans()
        self._admit = jax.jit(self._admit_fn, donate_argnums=(1,))
        self._decode = jax.jit(self._decode_fn, donate_argnums=(1,))
        self._evict = jax.jit(evict_slot, donate_argnums=(0,))

    # -- compiled bodies -----------------------------------------------------
    def _admit_fn(self, params, caches, toks, slot, key, temperature):
        """Prefill one request, insert its padded cache into ``slot``, and
        sample the request's first token — one program per prompt length."""
        logits, one = self.model.prefill(params, {"tokens": toks})
        one = pad_caches(self.model.cfg, one, self.max_len)
        caches = insert_slot(caches, one, slot)
        return caches, sample_tokens(logits, key, temperature)

    def _decode_fn(self, params, caches, tok, pos, active, key, temperature):
        logits, caches = self.model.decode_step(params, caches, tok, pos,
                                                active)
        return caches, sample_tokens(logits, key, temperature)

    # -- host surface --------------------------------------------------------
    def prefill_into_slot(self, slot: int, prompt: List[int], key,
                          temperature=0.0) -> int:
        """Admit ``prompt`` into lane ``slot``; returns its first token."""
        toks = jnp.asarray(prompt, jnp.int32)[None, :]
        self.caches, tok = self._admit(self.params, self.caches, toks,
                                       jnp.asarray(slot, jnp.int32), key,
                                       float(temperature))
        return int(jax.device_get(tok)[0])

    def decode_step(self, tok, pos, active, key, temperature=0.0):
        """One batched decode step across all lanes.

        ``tok``/``pos``/``active`` are host (B,) arrays; returns the host
        (B,) next-token array (entries for inactive lanes are garbage —
        their cache writes were masked out by ``active``)."""
        with self.spans.span("serve.decode.call"):
            self.caches, nxt = self._decode(
                self.params, self.caches,
                jnp.asarray(tok, jnp.int32)[:, None],
                jnp.asarray(pos, jnp.int32), jnp.asarray(active, bool), key,
                float(temperature))
        with self.spans.span("serve.decode.wait"):
            return jax.device_get(nxt)

    def release_slot(self, slot: int) -> None:
        """Zero a retired lane (see kvcache.evict_slot)."""
        self.caches = self._evict(self.caches, jnp.asarray(slot, jnp.int32))

    def ensure_caches(self) -> bool:
        """Check the pool after a failed jitted call; True if intact.

        ``_admit``/``_decode`` donate the cache buffers, so a *runtime*
        failure inside either (e.g. transient OOM) consumes them even though
        ``self.caches`` still holds the references — every later call would
        die on deleted buffers.  Rebuilding loses all in-flight lane state
        (the caller must fail its active lanes when this returns False);
        trace-time errors never consume the donation, so the common
        bad-request case keeps the pool — and its occupants — intact."""
        leaves = jax.tree.leaves(self.caches)
        if not any(leaf.is_deleted() for leaf in leaves):
            return True
        # a failed call rarely consumes *every* donated buffer: explicitly
        # release the survivors before rebuilding, otherwise they are only
        # freed when GC collects the old tree — a 2x-pool peak that can
        # itself OOM the rebuild (RequestQueue.flush regression test)
        for leaf in leaves:
            if not leaf.is_deleted():
                leaf.delete()
        self.caches = self.model.init_cache(self.slots, self.max_len)
        return False


# ---------------------------------------------------------------------------
# Paged engine: block-paged cache with COW prefix sharing + chunked prefill
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class _SlotMeta:
    """Host bookkeeping for one paged lane."""
    prompt: List[int]
    max_new: int
    key: Any                    # admission PRNG key (reused across chunks)
    temperature: float
    resv: int                   # reservation remaining to draw down
    reserved: int               # worst-case blocks reserved at admission
    nblocks: int = 0            # populated block-table entries
    pos: int = 0                # next prompt position to prefill


class PagedEngine:
    """Block-paged drop-in for :class:`SlotEngine` (DESIGN.md §14).

    Same host surface (``decode_step`` / ``release_slot`` /
    ``ensure_caches``) over block-paged storage: every sequence-bearing
    cache leaf lives in one preallocated arena of ``block_size``-token
    blocks, each lane maps logical positions through a per-slot block
    table, and a :class:`~repro.serve.kvcache.BlockPool` refcounts the
    blocks.  On top of the dense engine it adds:

    * **copy-on-write prefix sharing** — full prompt blocks are registered
      under content keys; a later admission whose prefix matches reuses the
      resident chain (no prefill compute, no new blocks) and forks a
      private copy the first time it writes a shared block (SWA ring wrap
      included);
    * **chunked prefill** — long prompts prefill ``chunk_tokens`` at a time
      (``begin_admission`` → ``continue_admission``), so one long prompt
      interleaves with decode steps instead of stalling active lanes;
    * **admission accounting** — a lane reserves its worst-case block count
      up front (``can_admit``), so decode never exhausts the arena
      mid-flight: overload surfaces at admission, as policy.

    Decode leaves the arenas in place: ``model.paged_decode_step`` reads
    each layer's per-lane view through the block table inside its layer
    scan, runs the unmodified attention math on it, and hands back only the
    entry each lane wrote, which ``write_entries`` scatters into the donated
    arenas — masked garbage beyond each lane's position scores exactly
    -1e30 either way, so paged decode is bit-identical to the dense slot
    engine (the parity suite asserts it).  ``release_slot`` is
    host-only bookkeeping (refcounts, no device work), which is what lets
    failed lanes free their blocks even when the device pool is broken."""

    def __init__(self, model: Model, params: PyTree, slots: int,
                 max_len: int, *, block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 chunk_tokens: Optional[int] = None,
                 prefix_sharing: bool = True):
        if model.cfg.frontend != "none":   # token-embedding frontend only
            raise ValueError(
                "PagedEngine serves token frontends; patch/frame stub "
                "frontends go through ServeEngine's lockstep fallback")
        self.model = model
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.block_size = int(block_size)
        self.blocks_per_lane = -(-max_len // self.block_size)
        self.layout = leaf_layout(model.cfg, max_len)
        self._rings = ring_lengths(self.layout, max_len)
        # chunk length: whole blocks, clamped to the smallest ring so one
        # chunk never writes the same ring slot twice (attention.py)
        cap = min(self._rings) if self._rings else max_len
        if chunk_tokens is None:
            chunk_tokens = 2 * self.block_size
        self.chunk_tokens = (min(int(chunk_tokens), cap)
                             // self.block_size * self.block_size)
        self._chunkable = (model.supports_chunked_prefill()
                           and self.chunk_tokens > 0)
        self.prefix_sharing = bool(prefix_sharing) and self._chunkable
        if num_blocks is None:
            # parity capacity with the dense engine (+1 for the null block),
            # plus per-slot headroom for the worst-case COW fork bound so a
            # full arena of shared-prefix lanes stays admissible
            slack = max((self._fork_bound(s0, max_len - s0)
                         for s0 in range(1, max_len)), default=0)
            num_blocks = slots * (self.blocks_per_lane + slack) + 1
        self.num_blocks = num_blocks
        self.pool = BlockPool(num_blocks, self.block_size)
        self.paged = init_paged(model.cfg, slots, max_len, num_blocks,
                                self.block_size)
        self.tables = np.zeros((slots, self.blocks_per_lane), np.int32)
        self._meta: List[Optional[_SlotMeta]] = [None] * slots
        self.tokens_cached = 0          # positions written (prompt + decode)
        self.spans = Spans()
        self._admit = jax.jit(self._admit_fn, donate_argnums=(1,))
        self._chunk = jax.jit(self._chunk_fn, donate_argnums=(1,))
        self._decode = jax.jit(self._decode_fn, donate_argnums=(1,))
        self._copy = jax.jit(self._copy_fn, donate_argnums=(0,))

    # -- compiled bodies ---------------------------------------------------
    def _admit_fn(self, params, paged, toks, slot, table_row, key,
                  temperature):
        """Whole-prompt admission: the same prefill + pad as the dense
        engine (bit-identical logits), then scatter the padded row into the
        lane's blocks — ring leaves arrive already in ring layout, so every
        leaf writes ring slots 0..min(S0, length)."""
        logits, one = self.model.prefill(params, {"tokens": toks})
        one = pad_caches(self.model.cfg, one, self.max_len)
        s0 = toks.shape[1]

        def w(ls: LeafSpec, arena, view):
            if ls.kind == "lane":
                return jax.tree.map(
                    lambda f, o: jax.lax.dynamic_update_slice_in_dim(
                        f, o.astype(f.dtype), slot, axis=1), arena, view)
            n = min(s0, ls.length)
            return scatter_slots(ls, arena, view, table_row,
                                 jnp.arange(n), self.block_size)

        paged = jax.tree.map(w, self.layout, paged, one, is_leaf=_is_spec)
        return paged, sample_tokens(logits, key, temperature)

    def _chunk_fn(self, params, paged, toks, p0, table_row, key,
                  temperature):
        """One prefill chunk for one lane: gather its view, run the chunk,
        scatter the chunk's ring slots back.  Chunkable configs have no
        lane leaves (no Mamba), so only sequence arenas update."""
        views = gather_views(self.layout, paged, table_row[None, :],
                             self.block_size)
        logits, views = self.model.prefill_chunk(params, views, toks, p0)
        c = toks.shape[1]

        def w(ls: LeafSpec, arena, view):
            if ls.kind == "lane":
                return arena
            slots = jnp.mod(p0 + jnp.arange(c), ls.length)
            return scatter_slots(ls, arena, view, table_row, slots,
                                 self.block_size)

        paged = jax.tree.map(w, self.layout, paged, views, is_leaf=_is_spec)
        return paged, sample_tokens(logits, key, temperature)

    def _decode_fn(self, params, paged, tok, tables, pos, active, key,
                   temperature):
        logits, entries = self.model.paged_decode_step(
            params, paged, self.layout, tables, tok, pos, active,
            self.block_size)
        paged = write_entries(self.layout, paged, entries, tables, pos,
                              active, self.block_size)
        return paged, sample_tokens(logits, key, temperature)

    def _copy_fn(self, paged, src, dst):
        return copy_block(self.layout, paged, src, dst)

    # -- block bookkeeping (host) ------------------------------------------
    def _fork_bound(self, prompt_len: int, max_new: int) -> int:
        """Worst-case COW forks the linear budget does not already cover.

        A *matched* block's fork spends its own (unspent) table-entry unit,
        but a block this lane allocated fresh, registered, and saw another
        lane match can be forced into a fork by a ring-wrap write — a
        second draw for the same entry.  That can only hit registered
        (full-prompt) blocks, and registration only happens when the prompt
        itself never wrapped, so the bound is the wrapped ring slots of the
        decode phase intersected with the registered block range."""
        if not self.prefix_sharing or not self._rings:
            return 0
        if any(prompt_len > length for length in self._rings):
            return 0      # prompt wrapped: its blocks are never registered
        wrapped = set()
        for length in self._rings:
            for p in range(prompt_len, prompt_len + max_new):
                if p >= length:
                    wrapped.add((p % length) // self.block_size)
        return len(wrapped & set(range(prompt_len // self.block_size)))

    def blocks_for(self, prompt_len: int, max_new: int) -> int:
        """Worst-case blocks one request can consume (tail + COW forks)."""
        return (-(-(prompt_len + max_new) // self.block_size)
                + self._fork_bound(prompt_len, max_new))

    def can_admit(self, prompt_len: int, max_new: int, *,
                  watermark: float = 0.0) -> bool:
        """True when the arena can reserve the request's worst case and
        stay above ``watermark`` (fraction of capacity) afterwards."""
        need = self.blocks_for(prompt_len, max_new)
        floor = int(watermark * self.pool.capacity)
        return self.pool.available() - self.pool.reserved - need >= floor

    def _lane_alloc(self, meta: _SlotMeta) -> int:
        if meta.resv > 0:
            meta.resv -= 1
            return self.pool.alloc(reserved=True)
        return self.pool.alloc()

    def _grow_table(self, slot: int, upto: int) -> None:
        """Extend the lane's block chain to cover positions [0, upto)."""
        meta = self._meta[slot]
        need = -(-upto // self.block_size)
        while meta.nblocks < need:
            bid = self._lane_alloc(meta)
            self.tables[slot, meta.nblocks] = bid
            meta.nblocks += 1

    def _prepare_writes(self, slot: int, start: int, count: int) -> None:
        """COW fence: make every block the next write burst touches private.

        The write set for positions [start, start+count) is the full-leaf
        block range plus, per distinct ring length, the wrapped ring slots'
        blocks.  Shared blocks (refcount > 1) fork — host alloc + jitted
        arena row copy — and registered-but-unshared blocks leave the
        prefix cache, since their content is about to stop matching their
        key.  Forked *originals* keep their registration: their content is
        frozen, so later admissions can still match them."""
        meta = self._meta[slot]
        touched = set(range(start // self.block_size,
                            (start + count - 1) // self.block_size + 1))
        for length in self._rings:
            touched.update((p % length) // self.block_size
                           for p in range(start, start + count))
        for j in sorted(touched):
            if j >= meta.nblocks:
                continue                       # fresh block, never shared
            bid = int(self.tables[slot, j])
            if self.pool.refcount(bid) > 1:
                use_resv = meta.resv > 0
                if use_resv:
                    meta.resv -= 1
                new = self.pool.fork(bid, reserved=use_resv)
                self.paged = self._copy(self.paged,
                                        jnp.asarray(bid, jnp.int32),
                                        jnp.asarray(new, jnp.int32))
                self.tables[slot, j] = new
            elif self.pool.is_registered(bid):
                self.pool.unregister(bid)

    def _register_prompt(self, slot: int, meta: _SlotMeta) -> None:
        if not self.prefix_sharing:
            return
        if any(len(meta.prompt) > length for length in self._rings):
            # the SWA ring wrapped during prefill: these blocks no longer
            # hold the prefix KV their content key would promise
            return
        keys = prefix_block_keys(meta.prompt, self.block_size)
        for i, key in enumerate(keys):
            bid = int(self.tables[slot, i])
            if not self.pool.is_registered(bid):
                self.pool.register_prefix(bid, key)

    # -- host surface ------------------------------------------------------
    def begin_admission(self, slot: int, prompt: List[int], max_new: int,
                        key, temperature=0.0) -> Optional[int]:
        """Admit ``prompt`` into lane ``slot``.  Returns its first sampled
        token when the prefill completed in this call, or None when a
        chunked prefill is now in flight (drive it with
        ``continue_admission``, one chunk per engine iteration)."""
        s0 = len(prompt)
        need = self.blocks_for(s0, max_new)
        self.pool.reserve(need)
        meta = _SlotMeta(prompt=list(prompt), max_new=max_new, key=key,
                         temperature=float(temperature), resv=need,
                         reserved=need)
        self._meta[slot] = meta
        if self.prefix_sharing:
            # never match the whole prompt: >= 1 suffix token must prefill
            keys = prefix_block_keys(prompt, self.block_size,
                                     limit=(s0 - 1) // self.block_size)
            for i, bid in enumerate(self.pool.match_prefix(keys)):
                self.tables[slot, i] = bid
                meta.nblocks += 1
        meta.pos = meta.nblocks * self.block_size
        if not self._chunkable or (meta.nblocks == 0
                                   and s0 <= self.chunk_tokens):
            return self._admit_whole(slot, meta)
        return self.continue_admission(slot)

    def _admit_whole(self, slot: int, meta: _SlotMeta) -> int:
        s0 = len(meta.prompt)
        self._grow_table(slot, s0)
        toks = jnp.asarray(meta.prompt, jnp.int32)[None, :]
        row = jnp.asarray(self.tables[slot])
        self.paged, tok = self._admit(self.params, self.paged, toks,
                                      jnp.asarray(slot, jnp.int32), row,
                                      meta.key, meta.temperature)
        meta.pos = s0
        self.tokens_cached += s0
        self._register_prompt(slot, meta)
        return int(jax.device_get(tok)[0])

    def continue_admission(self, slot: int) -> Optional[int]:
        """Run one prefill chunk; returns the first sampled token once the
        whole prompt is in cache, else None."""
        meta = self._meta[slot]
        s0 = len(meta.prompt)
        c = min(self.chunk_tokens, s0 - meta.pos)
        self._grow_table(slot, meta.pos + c)
        self._prepare_writes(slot, meta.pos, c)
        toks = jnp.asarray(meta.prompt[meta.pos:meta.pos + c],
                           jnp.int32)[None, :]
        row = jnp.asarray(self.tables[slot])
        self.paged, tok = self._chunk(self.params, self.paged, toks,
                                      jnp.asarray(meta.pos, jnp.int32), row,
                                      meta.key, meta.temperature)
        meta.pos += c
        self.tokens_cached += c
        if meta.pos < s0:
            return None
        self._register_prompt(slot, meta)
        return int(jax.device_get(tok)[0])

    def decode_step(self, tok, pos, active, key, temperature=0.0):
        """One batched decode step; same contract as the dense engine.

        Host prep per active lane: grow the tail block if this position
        crosses a block boundary, then COW-fence the write set — after
        which every block written this step is private, so the jitted
        decode and its write-back touch no shared storage."""
        with self.spans.span("serve.decode.prep"):
            for i, on in enumerate(active):
                if on:
                    p = int(pos[i])
                    self._grow_table(i, p + 1)
                    self._prepare_writes(i, p, 1)
                    self.tokens_cached += 1
        with self.spans.span("serve.decode.call"):
            self.paged, nxt = self._decode(
                self.params, self.paged, jnp.asarray(tok, jnp.int32)[:, None],
                jnp.asarray(self.tables), jnp.asarray(pos, jnp.int32),
                jnp.asarray(active, bool), key, float(temperature))
        with self.spans.span("serve.decode.wait"):
            return jax.device_get(nxt)

    def release_slot(self, slot: int) -> None:
        """Host-only retirement: deref the lane's chain and return its
        unused reservation.  No device work — stale arena rows are masked
        by the next reader and overwritten by the next owner — so this is
        safe even while the device pool is broken (failed lanes must
        release their blocks, test_chaos)."""
        meta = self._meta[slot]
        if meta is None:
            return
        for j in range(meta.nblocks):
            self.pool.deref(int(self.tables[slot, j]))
        self.pool.unreserve(meta.resv)
        self.tables[slot, :] = 0
        self._meta[slot] = None

    # failed lanes use the same host-only path (no device call to explode)
    abandon_slot = release_slot

    def ensure_caches(self) -> bool:
        """Check the arenas after a failed jitted call; True if intact.
        Rebuilding resets the pool — every lane's state is gone, the
        caller must fail its active lanes (same contract as SlotEngine)."""
        leaves = jax.tree.leaves(self.paged)
        if not any(leaf.is_deleted() for leaf in leaves):
            return True
        for leaf in leaves:
            if not leaf.is_deleted():
                leaf.delete()      # release survivors before the rebuild
        self.paged = init_paged(self.model.cfg, self.slots, self.max_len,
                                self.num_blocks, self.block_size)
        self.pool.reset()
        self.tables[:] = 0
        self._meta = [None] * self.slots
        return False

    def stats(self) -> Dict[str, Any]:
        """Allocator + sharing scorecard (benchmarks record these)."""
        s = dict(self.pool.stats())
        s["tokens_cached"] = self.tokens_cached
        s["prefix_hit_rate"] = (self.pool.prefix_hits
                                / max(1, self.pool.prefix_queries))
        s["blocks_per_token"] = (self.pool.allocs
                                 / max(1, self.tokens_cached))
        return s


@dataclasses.dataclass
class _Lane:
    """One occupied slot: its request plus the decode cursor.  A lane with
    ``prefilling=True`` is mid chunked-prefill: it owns its slot and blocks
    but does not join the decode batch until admission completes."""
    req: Request
    pos: int                 # next cache position this lane writes
    last_tok: int
    tokens: List[int]
    prefilling: bool = False


# ---------------------------------------------------------------------------
# Step scheduler: admission / step / retirement loop
# ---------------------------------------------------------------------------
#: the spans in which the scheduler is blocked in the engine's device calls
#: (ServeReport's T3); they never nest
_DEVICE_CALL_SPANS = ("serve.admit", "serve.chunk", "serve.decode")


class StepScheduler:
    """Continuous-batching loop over a :class:`SlotEngine` (DESIGN.md §6).

    ``submit`` returns a future immediately; requests are admitted into free
    slots mid-flight and retire independently on their own EOS or
    ``max_new``.  Drive the loop synchronously (``step``/``drain``) or in
    the background (``start``/``stop``, or ``with sched:``).

    ``spans`` (shared with the engine) times each iteration ``serve.step``,
    each admission ``serve.admit``, each prefill chunk the loop runs
    ``serve.chunk`` and each decode step ``serve.decode`` (its parts
    ``serve.decode.prep``/``.call``/``.wait`` in the engine); the counter
    ``serve.decode_lanes`` sums the lanes of every decode step."""

    _seq = itertools.count(1)

    def __init__(self, engine: SlotEngine, temperature: float = 0.0,
                 seed: int = 0, policy: Optional[AdmissionPolicy] = None):
        self.engine = engine
        self.temperature = temperature
        self.policy = policy or AdmissionPolicy()
        self.rejected = 0        # submits refused at the QoS depth cap
        self.expired = 0         # queued requests aged out past max_delay
        self.name = f"slot-engine-{next(StepScheduler._seq)}"
        self._key = jax.random.PRNGKey(seed)
        self._queue: "collections.deque[Request]" = collections.deque()
        self._lanes: List[Optional[_Lane]] = [None] * engine.slots
        self._cond = threading.Condition()
        self._thread: Optional[threading.Thread] = None
        self._stop = False
        self._uid = 0
        self._beats = 0
        self._last_beat = time.monotonic()
        # held by callers that synchronously drive this scheduler end to end
        # (submit + drain) — enforces the single-stepper invariant when one
        # scheduler instance is shared (see ServeEngine.generate)
        self.drive_lock = threading.Lock()
        self.completed = 0
        self.spans = getattr(engine, "spans", None) or Spans()
        # scorecard (core.portability.ServeReport): the span totals as the
        # last iteration ended, read against this baseline
        self._totals = self._base = self.spans.snapshot()
        self._steps = 0
        self._tokens = 0

    # -- submission ----------------------------------------------------------
    def submit(self, prompt: List[int], max_new: int = 16, *,
               eos_id: Optional[int] = None, qos: str = "default",
               on_token: Optional[Callable[[int, int], None]] = None
               ) -> HaloFuture:
        """Enqueue a request; returns a future for its generated tokens.

        ``qos`` names an :class:`AdmissionPolicy` class: a full class queue
        rejects the submit with :class:`AdmissionError` (bounded queueing
        is the overload contract — DESIGN.md §14).  ``on_token(token,
        index)`` streams every token (including the one sampled from the
        prefill) from the stepping thread as it lands."""
        prompt = list(map(int, prompt))
        if not prompt:
            raise ValueError("empty prompt")
        if max_new < 1:
            raise ValueError("max_new must be >= 1")
        if len(prompt) + max_new > self.engine.max_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new ({max_new}) exceeds the "
                f"engine max_len ({self.engine.max_len})")
        cap = self.policy.qos(qos).max_depth
        with self._cond:
            if self._stop:
                raise RuntimeError(
                    "StepScheduler is stopped; start() it again to submit")
            if cap is not None:
                depth = sum(1 for r in self._queue if r.qos == qos)
                if depth >= cap:
                    self.rejected += 1
                    raise AdmissionError(
                        f"QoS class {qos!r} queue is full "
                        f"({depth}/{cap} queued); rejected")
            if not self._queue and not any(l is not None
                                           for l in self._lanes):
                # busy period starts now: the stall clock for liveness runs
                # from here, not from whenever the last request finished
                self._last_beat = time.monotonic()
            self._uid += 1
            fut = HaloFuture(uid=self._uid, alias="generate")
            self._queue.append(Request(self._uid, prompt, max_new,
                                       eos_id=eos_id, qos=qos, future=fut,
                                       submitted_at=time.monotonic(),
                                       on_token=on_token))
            self._cond.notify_all()
        return fut

    # -- introspection -------------------------------------------------------
    def pending(self) -> int:
        with self._cond:
            return len(self._queue)

    def active(self) -> int:
        with self._cond:
            return sum(l is not None for l in self._lanes)

    def busy(self) -> bool:
        with self._cond:
            return bool(self._queue) or any(l is not None
                                            for l in self._lanes)

    def heartbeat(self):
        """Liveness probe for :class:`~repro.core.agents.HealthMonitor`:
        ``(progress counter, busy, last activity)``.  Busy means queued or
        in-flight requests exist; the counter advances once per engine
        iteration, so a stepping thread wedged inside a device call (or a
        scheduler nobody is driving) stalls and gets flagged."""
        with self._cond:
            busy = bool(self._queue) or any(l is not None
                                            for l in self._lanes)
            return self._beats, busy, self._last_beat

    def _beat(self) -> None:
        with self._cond:
            self._beats += 1
            self._last_beat = time.monotonic()

    def attach_health(self, monitor) -> "StepScheduler":
        """Register with a :class:`~repro.core.agents.HealthMonitor`: when
        the monitor declares this scheduler DEAD (its stepping thread
        stopped advancing while work was pending), every queued and
        in-flight request fails with :class:`AgentDeadError` instead of
        leaving clients blocked on futures that will never resolve."""
        monitor.register(self)
        monitor.on_transition(self._on_health_transition)
        return self

    def _on_health_transition(self, target, old: str, new: str) -> None:
        if target is not self or new != AgentState.DEAD:
            return
        exc = AgentDeadError(
            f"{self.name} declared dead (engine loop stopped making "
            f"progress); queued and in-flight requests failed")
        log.error("%s", exc)
        with self._cond:
            dropped = list(self._queue)
            self._queue.clear()
        for r in dropped:
            if r.future is not None:
                r.future.set_exception(exc)
        self._fail_active(exc)

    def report(self) -> ServeReport:
        """T1/T3 since construction or ``reset_stats``, from the span
        totals as the last iteration ended (so a step in flight counts in
        neither): T3 is the time in ``serve.admit``, ``serve.chunk`` and
        ``serve.decode``, T1 the rest of ``serve.step`` (ServeReport says
        what each holds)."""
        spent = self.spans.since(self._base, self._totals)["seconds"]
        t3 = sum(spent.get(name, 0.0) for name in _DEVICE_CALL_SPANS)
        return ServeReport(t1_s=spent.get("serve.step", 0.0) - t3, t3_s=t3,
                           steps=self._steps, tokens=self._tokens)

    def reset_stats(self) -> None:
        self._base = self._totals
        self._steps = self._tokens = 0

    # -- engine iteration ----------------------------------------------------
    def _next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def _abandon(self, slot: int) -> None:
        """Release a failed lane's blocks.  Paged engines expose the
        host-only ``abandon_slot`` (refcount bookkeeping, safe even with a
        broken device pool); the dense engine's eviction is a device call,
        so it is skipped here — dense lane state is garbage the next
        ``insert_slot`` fully overwrites anyway."""
        release = getattr(self.engine, "abandon_slot", None)
        if release is None:
            return
        try:
            release(slot)
        except Exception:
            log.exception("abandon_slot(%d) failed", slot)

    def _fail_active(self, exc: BaseException) -> None:
        """Fail every occupied lane (their cache state is unrecoverable)."""
        with self._cond:
            lanes = [(i, l) for i, l in enumerate(self._lanes)
                     if l is not None]
            self._lanes = [None] * self.engine.slots
        for i, lane in lanes:
            self._abandon(i)
            if lane.req.future is not None:
                lane.req.future.set_exception(exc)

    def _finish(self, req: Request, tokens: List[int]) -> None:
        req.result = tokens
        req.finished_at = time.monotonic()
        self.completed += 1
        if req.future is not None:
            req.future.set_result(list(tokens))

    def _expire_queued(self) -> None:
        """Fail queued requests that aged past their QoS class max_delay."""
        now = time.monotonic()
        expired: List[Request] = []
        with self._cond:
            if not self._queue:
                return
            keep: "collections.deque[Request]" = collections.deque()
            for r in self._queue:
                limit = self.policy.qos(r.qos).max_delay
                if limit is not None and now - r.submitted_at > limit:
                    expired.append(r)
                else:
                    keep.append(r)
            self._queue = keep
        for r in expired:
            self.expired += 1
            if r.future is not None:
                r.future.set_exception(AdmissionError(
                    f"request {r.uid} waited > {self.policy.qos(r.qos).max_delay}s "
                    f"queued (QoS class {r.qos!r}); dropped"))

    def _admissible(self, req: Request) -> bool:
        """Free-memory gate: paged engines must cover the request's
        worst-case blocks and stay above the policy watermark; dense
        engines always admit (their memory is fixed per slot)."""
        can = getattr(self.engine, "can_admit", None)
        if can is None:
            return True
        return can(len(req.prompt), req.max_new,
                   watermark=self.policy.watermark)

    def _finish_admission(self, slot: int, req: Request, tok: int) -> bool:
        """Handle a completed prefill's first token; True if the request
        retired immediately (EOS or max_new == 1) and freed its slot."""
        self._tokens += 1
        req.stream(tok, 0)
        if (req.eos_id is not None and tok == req.eos_id) \
                or req.max_new == 1:
            with self._cond:
                self._lanes[slot] = None
            self.engine.release_slot(slot)
            self._finish(req, [tok])
            return True
        with self._cond:
            self._lanes[slot] = _Lane(req, pos=len(req.prompt),
                                      last_tok=tok, tokens=[tok])
        return False

    def step(self) -> bool:
        """One engine iteration: admit → prefill chunks → decode → retire.

        Returns True if any work was done.  Call from a single thread at a
        time (the background loop, or the caller when not started)."""
        try:
            with self.spans.span("serve.step"):
                return self._step()
        finally:
            self._totals = self.spans.snapshot()

    def _step(self) -> bool:
        worked = False
        self._beat()          # claim the iteration: a hang inside it stalls
        self._expire_queued()

        # (a) admission: prefill queued requests into free slots.  FCFS —
        # a head-of-queue request the watermark cannot cover yet blocks
        # later ones (no starvation of big prompts); it ages out via its
        # QoS max_delay if the arena never drains enough.
        begin = getattr(self.engine, "begin_admission", None)
        while True:
            with self._cond:
                free = [i for i, l in enumerate(self._lanes) if l is None]
                req = None
                if free and self._queue and self._admissible(self._queue[0]):
                    req = self._queue.popleft()
            if req is None:
                break
            slot = free[0]
            worked = True
            req.started_at = time.monotonic()
            try:
                with self.spans.span("serve.admit", uid=req.uid,
                                     prompt_len=len(req.prompt)):
                    if begin is not None:
                        with self._cond:
                            # hold the slot before the device call: a
                            # chunked admission spans iterations
                            self._lanes[slot] = _Lane(req, pos=0,
                                                      last_tok=-1, tokens=[],
                                                      prefilling=True)
                        tok = begin(slot, req.prompt, req.max_new,
                                    self._next_key(), self.temperature)
                    else:
                        tok = self.engine.prefill_into_slot(
                            slot, req.prompt, self._next_key(),
                            self.temperature)
            except Exception as exc:
                with self._cond:
                    self._lanes[slot] = None
                self._abandon(slot)
                if req.future is not None:
                    req.future.set_exception(exc)
                if not self.engine.ensure_caches():
                    # donated buffers died with the failed prefill: every
                    # in-flight lane lost its cache state
                    self._fail_active(exc)
                continue
            if tok is None:
                continue           # chunked prefill in flight on this lane
            with self._cond:
                self._lanes[slot] = None     # _finish_admission re-occupies
            self._finish_admission(slot, req, tok)

        # (a') chunked prefills: one chunk per prefilling lane per iteration,
        # so a long prompt interleaves with decode instead of stalling it
        with self._cond:
            prefilling = [(i, l) for i, l in enumerate(self._lanes)
                          if l is not None and l.prefilling]
        for i, lane in prefilling:
            worked = True
            try:
                meta = self.engine._meta[i]
                with self.spans.span(
                        "serve.chunk", uid=lane.req.uid, start=meta.pos,
                        chunk_len=min(self.engine.chunk_tokens,
                                      len(meta.prompt) - meta.pos)):
                    tok = self.engine.continue_admission(i)
            except Exception as exc:
                with self._cond:
                    self._lanes[i] = None
                self._abandon(i)
                if lane.req.future is not None:
                    lane.req.future.set_exception(exc)
                if not self.engine.ensure_caches():
                    self._fail_active(exc)
                continue
            if tok is None:
                continue                     # more chunks to go
            with self._cond:
                self._lanes[i] = None
            self._finish_admission(i, lane.req, tok)

        # (b) one batched decode step across all decoding slots
        with self._cond:
            occupied = [(i, l) for i, l in enumerate(self._lanes)
                        if l is not None and not l.prefilling]
        if occupied:
            worked = True
            b = self.engine.slots
            tok = np.zeros((b,), np.int32)
            pos = np.zeros((b,), np.int32)
            act = np.zeros((b,), bool)
            for i, lane in occupied:
                tok[i], pos[i], act[i] = lane.last_tok, lane.pos, True
            self.spans.count("serve.decode_lanes", len(occupied))
            try:
                with self.spans.span("serve.decode", lanes=len(occupied)):
                    nxt = self.engine.decode_step(tok, pos, act,
                                                  self._next_key(),
                                                  self.temperature)
            except Exception as exc:
                self._fail_active(exc)
                self.engine.ensure_caches()   # rebuild if donation consumed
                raise

            # (c) retirement: each slot checks its own EOS / max_new
            for i, lane in occupied:
                t = int(nxt[i])
                lane.tokens.append(t)
                lane.last_tok = t
                lane.pos += 1
                self._tokens += 1
                lane.req.stream(t, len(lane.tokens) - 1)
                if (lane.req.eos_id is not None and t == lane.req.eos_id) \
                        or len(lane.tokens) >= lane.req.max_new:
                    with self._cond:
                        self._lanes[i] = None
                    self.engine.release_slot(i)
                    self._finish(lane.req, lane.tokens)

        if worked:
            self._steps += 1
            self._beat()
        return worked

    def drain(self) -> None:
        """Synchronously step until no queued or in-flight work remains."""
        while self.busy():
            self.step()

    def cancel_pending(self) -> None:
        """Cancel queued (not yet admitted) requests — synchronous drivers
        use it to recover cleanly from a failed drain, so leftovers never
        leak into their next batch."""
        with self._cond:
            dropped = list(self._queue)
            self._queue.clear()
        for r in dropped:
            if r.future is not None:
                r.future.cancel()

    # -- background loop -----------------------------------------------------
    def start(self) -> "StepScheduler":
        if self._thread is None or not self._thread.is_alive():
            self._stop = False
            self._thread = threading.Thread(target=self._loop,
                                            name="slot-engine", daemon=True)
            self._thread.start()
        return self

    def stop(self, drain: bool = True) -> None:
        """Stop the loop; by default serve queued + in-flight work first."""
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if drain:
            self.drain()       # step() ignores _stop; only submit is gated
        else:
            with self._cond:
                dropped = list(self._queue)
                self._queue.clear()
                lanes = [(i, l) for i, l in enumerate(self._lanes)
                         if l is not None]
                self._lanes = [None] * self.engine.slots
            for r in dropped:
                if r.future is not None:
                    r.future.cancel()
            for i, lane in lanes:
                self._abandon(i)
                if lane.req.future is not None:
                    lane.req.future.cancel()

    __enter__ = start

    def __exit__(self, *exc_info) -> None:
        self.stop(drain=exc_info[0] is None)

    def _loop(self) -> None:
        while True:
            with self._cond:
                while not self._stop and not self._queue and \
                        not any(l is not None for l in self._lanes):
                    self._cond.wait()
                if self._stop:
                    return
            try:
                self.step()
            except Exception:
                # the failed iteration's futures already carry the error;
                # the loop must survive to serve later submissions
                log.exception("slot engine step failed; loop continues")


# ---------------------------------------------------------------------------
# Legacy whole-batch front (compat wrappers over the slot engine)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class ServeEngine:
    """Legacy batch front: ``generate`` is a thin wrapper over the slot
    engine — one request per prompt row, drained synchronously — kept so the
    pre-slot API, tests and examples continue to work.  Non-token frontends
    (patch/frame stubs) and ``batch_extra`` callers fall back to the
    original lockstep loop (`_generate_lockstep`)."""

    model: Model
    max_len: int = 256

    #: distinct batch widths kept warm by ``generate`` — each holds its own
    #: slot pool + compiled programs, so the compat path stays bounded even
    #: when a RequestQueue produces every live-batch width in 1..batch_size
    MAX_CACHED_WIDTHS = 4

    def __post_init__(self):
        self._prefill = jax.jit(self.model.prefill)
        self._decode = jax.jit(self.model.decode_step, donate_argnums=(1,))
        self._scheds: "collections.OrderedDict[int, StepScheduler]" = \
            collections.OrderedDict()
        self._scheds_lock = threading.Lock()      # guards the width cache

    def _sched_for(self, b: int, params) -> StepScheduler:
        """Width-``b`` scheduler from the LRU cache (dict access only — the
        caller takes the scheduler's own ``drive_lock`` before mutating or
        driving it, so different widths run concurrently)."""
        with self._scheds_lock:
            sched = self._scheds.get(b)
            if sched is None:
                sched = StepScheduler(SlotEngine(self.model, params, b,
                                                 self.max_len))
                self._scheds[b] = sched
                while len(self._scheds) > self.MAX_CACHED_WIDTHS:  # LRU evict
                    self._scheds.popitem(last=False)
            else:
                self._scheds.move_to_end(b)
        return sched

    def generate(self, params, prompts: jax.Array, max_new: int, *,
                 temperature: float = 0.0, key: Optional[jax.Array] = None,
                 batch_extra: Optional[Dict[str, jax.Array]] = None
                 ) -> jax.Array:
        """prompts (B, S0) int32 → (B, max_new) int32 generated tokens.

        Compat path: rows are submitted to a width-``B`` slot pool and
        drained synchronously, so admission prefills row by row (B small
        host-synced prefills instead of one batched one) — fine for tests
        and examples; latency-sensitive traffic should drive a long-lived
        :class:`StepScheduler` instead."""
        b, s0 = prompts.shape
        assert s0 + max_new <= self.max_len, "grow max_len"
        key = key if key is not None else jax.random.PRNGKey(0)
        if batch_extra or self.model.cfg.frontend != "none":
            return self._generate_lockstep(params, prompts, max_new,
                                           temperature=temperature, key=key,
                                           batch_extra=batch_extra)
        rows = np.asarray(jax.device_get(prompts))
        sched = self._sched_for(b, params)
        with sched.drive_lock:       # same-width calls serialize; different
            sched.engine.params = params       # widths proceed concurrently
            sched.temperature = temperature
            sched._key = key
            futs = [sched.submit(list(map(int, rows[i])), max_new=max_new)
                    for i in range(b)]
            sched.drain()
        return jnp.asarray([f.result() for f in futs], jnp.int32)

    def _generate_lockstep(self, params, prompts: jax.Array, max_new: int, *,
                           temperature: float = 0.0,
                           key: Optional[jax.Array] = None,
                           batch_extra: Optional[Dict[str, jax.Array]] = None
                           ) -> jax.Array:
        """The pre-slot whole-batch path: one batched prefill, then lockstep
        scalar-position decode.  Retained for stub frontends (patch/frame
        inputs via ``batch_extra``) and as the parity reference for the slot
        engine's tests."""
        b, s0 = prompts.shape
        assert s0 + max_new <= self.max_len, "grow max_len"
        key = key if key is not None else jax.random.PRNGKey(0)
        batch = {"tokens": prompts}
        if batch_extra:
            batch.update(batch_extra)
        logits, caches = self._prefill(params, batch)
        caches = pad_caches(self.model.cfg, caches, self.max_len)
        prefix = self.model.cfg.prefix_len if \
            self.model.cfg.frontend == "patch_embed" else 0
        pos = s0 + prefix                      # next cache slot to write
        out = []
        tok = sample_tokens(logits, key, temperature)[:, None]
        out.append(tok)
        for i in range(max_new - 1):
            key, sub = jax.random.split(key)
            logits, caches = self._decode(params, caches, tok,
                                          jnp.asarray(pos + i, jnp.int32))
            tok = sample_tokens(logits, sub, temperature)[:, None]
            out.append(tok)
        return jnp.concatenate(out, axis=1)


class RequestQueue:
    """Whole-batch compat front for the serving engine.

    ``submit`` enqueues and returns a future for the request's generated
    tokens.  Batches run either synchronously via ``flush`` or from the
    background drain loop (``start``/``stop``, or ``with queue:``), which
    flushes as soon as the batch is full or the oldest submission is
    ``max_delay`` seconds old.  Interim/compat semantics: requests still
    *join* only at batch boundaries, but each flush drives one dedicated
    ``batch_size``-wide slot pool (a single compiled decode program — no
    per-width retracing), so there are no pad lanes (the old path echoed
    ``batch[0]`` into every empty lane) and every request retires at its own
    ``max_new`` / ``eos_id`` instead of the batch max.  For mid-flight
    join/leave use :class:`StepScheduler` directly."""

    def __init__(self, engine: ServeEngine, params, batch_size: int,
                 prompt_len: int, max_delay: float = 0.05,
                 temperature: float = 0.0):
        self.engine = engine
        self.params = params
        self.batch_size = batch_size
        self.prompt_len = prompt_len
        self.max_delay = max_delay
        self.temperature = temperature
        self._queue: List[Request] = []
        self._cond = threading.Condition()
        self._drain: Optional[threading.Thread] = None
        self._stop = False
        self._uid = 0
        self._sched: Optional[StepScheduler] = None

    def _flush_sched(self) -> StepScheduler:
        """The queue's fixed-width slot pool, built once (one compile).
        Lazy-init under the queue lock; the caller mutates/drives the
        scheduler under its ``drive_lock``."""
        with self._cond:
            if self._sched is None:
                self._sched = StepScheduler(
                    SlotEngine(self.engine.model, self.params,
                               self.batch_size, self.engine.max_len))
            return self._sched

    def submit(self, prompt: List[int], max_new: int = 16,
               eos_id: Optional[int] = None) -> HaloFuture:
        if max_new < 1:
            raise ValueError("max_new must be >= 1")
        # flush frames every prompt to prompt_len, so that is the bound
        if self.prompt_len + max_new > self.engine.max_len:
            raise ValueError(
                f"prompt_len ({self.prompt_len}) + max_new ({max_new}) "
                f"exceeds the engine max_len ({self.engine.max_len})")
        with self._cond:
            if self._stop:
                raise RuntimeError(
                    "RequestQueue is stopped; start() it again to submit")
            self._uid += 1
            fut = HaloFuture(uid=self._uid, alias="generate")
            self._queue.append(Request(self._uid, prompt, max_new,
                                       eos_id=eos_id, future=fut,
                                       submitted_at=time.monotonic()))
            self._cond.notify_all()
        return fut

    def ready(self) -> bool:
        return len(self._queue) >= self.batch_size

    def pending(self) -> int:
        return len(self._queue)

    def flush(self) -> List[Request]:
        """Serve the oldest queued requests through the flush pool,
        completing their futures.  Only live rows are submitted — no pad
        lanes — and each row retires at its own ``max_new`` / ``eos_id``
        (prompts keep the legacy fixed ``prompt_len`` framing)."""
        with self._cond:
            live = self._queue[: self.batch_size]
            self._queue = self._queue[self.batch_size:]
        if not live:
            return []
        sched = self._flush_sched()
        try:
            with sched.drive_lock:   # client flush() vs background drain loop
                sched.engine.params = self.params
                sched.temperature = self.temperature
                futs = [sched.submit(
                    (r.prompt + [0] * self.prompt_len)[: self.prompt_len],
                    max_new=r.max_new, eos_id=r.eos_id) for r in live]
                sched.drain()
            outs = [f.result(timeout=1.0) for f in futs]
        except Exception as exc:
            # whole-batch failure semantics (as before the slot engine); the
            # pool self-heals — leftovers are cancelled and the caches only
            # rebuild if the failed call actually consumed the donation
            sched.cancel_pending()
            sched.engine.ensure_caches()
            for r in live:
                if r.future is not None and not r.future.done():
                    r.future.set_exception(exc)
            raise
        for r, out in zip(live, outs):
            r.result = out
            if r.future is not None:
                r.future.set_result(out)
        return live

    # -- background drain loop (continuous batching) -------------------------
    def start(self) -> "RequestQueue":
        if self._drain is None or not self._drain.is_alive():
            self._stop = False
            self._drain = threading.Thread(target=self._drain_loop,
                                           name="serve-drain", daemon=True)
            self._drain.start()
        return self

    def stop(self, drain: bool = True) -> None:
        """Stop the loop; by default serve whatever is still queued first."""
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        if self._drain is not None:
            self._drain.join()
            self._drain = None
        if drain:
            while self._queue:
                try:
                    self.flush()
                except Exception:   # that batch's futures carry the error
                    log.exception("flush failed during drain")
        else:
            with self._cond:
                dropped, self._queue = self._queue, []
            for r in dropped:
                if r.future is not None:
                    r.future.cancel()

    __enter__ = start

    def __exit__(self, *exc_info) -> None:
        self.stop(drain=exc_info[0] is None)

    def _drain_loop(self) -> None:
        while True:
            with self._cond:
                while not self._stop and not self._queue:
                    self._cond.wait()
                if self._stop:
                    return
                # deadline batching: run as soon as the batch is full or the
                # oldest request has waited long enough
                while not self._stop and len(self._queue) < self.batch_size:
                    left = (self._queue[0].submitted_at + self.max_delay
                            - time.monotonic()) if self._queue else None
                    if left is None or left <= 0:
                        break
                    self._cond.wait(timeout=left)
                if self._stop or not self._queue:
                    continue
            try:
                self.flush()
            except Exception:
                # the failed batch's futures already carry the exception; the
                # loop must survive to serve later submissions
                log.exception("flush failed; drain loop continues")
