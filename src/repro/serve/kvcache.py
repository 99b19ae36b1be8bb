"""KV/SSM cache utilities for the serving pool (DESIGN.md §6, §14).

Two storage models over the same model-produced cache tree:

* **Dense slots** — every leaf is stacked ``(R, B, ...)`` (leading R = scan
  dim over stacked layers) and a *slot* is a batch lane on axis 1:
  ``insert_slot`` / ``evict_slot`` / ``pad_caches``.
* **Block-paged** — sequence-bearing leaves are re-laid-out as one arena of
  fixed-size blocks per leaf, ``(R, num_blocks, block_size, F)`` (each
  position's ``F`` values one row), indexed through a per-slot block table:
  :class:`BlockPool` (host refcounted allocator with prefix-hash reuse),
  ``leaf_layout`` / ``init_paged`` (planning), ``gather_views`` (blocks →
  dense per-lane views of every layer, for the chunk path) and
  ``layer_view`` (one layer's, read inside the decode layer scan) for the
  unmodified attention math, ``write_entries`` / ``scatter_slots``
  (written entries → arena), ``copy_block`` (COW fork).

Block 0 of every arena is the *null block*: never allocated, kept all-zero
(inactive-lane scatters are value-zeroed and redirected to it), so padded
block-table entries always point at valid, masked-out storage.
"""
from __future__ import annotations

import collections
import dataclasses
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig

PyTree = Any


def insert_slot(full: PyTree, one: PyTree, slot) -> PyTree:
    """Write a padded single-request cache (batch=1 lanes) into lane ``slot``
    of the pooled slot-indexed cache.

    The whole lane is replaced, so whatever a retired occupant left behind
    (including masked decode garbage) never leaks into the new request.
    ``slot`` may be a traced scalar: one compiled insert program serves every
    slot of a given prompt-length bucket."""
    slot = jnp.asarray(slot, jnp.int32)
    return jax.tree.map(
        lambda f, o: jax.lax.dynamic_update_slice_in_dim(
            f, o.astype(f.dtype), slot, axis=1),
        full, one)


def evict_slot(full: PyTree, slot) -> PyTree:
    """Zero lane ``slot`` — retirement hygiene.  Correctness never depends on
    it (``insert_slot`` fully overwrites the lane and decode masks inactive
    lanes), but a freed slot holding no stale KV keeps cache dumps honest."""
    slot = jnp.asarray(slot, jnp.int32)
    return jax.tree.map(
        lambda f: jax.lax.dynamic_update_slice_in_dim(
            f, jnp.zeros((f.shape[0], 1) + f.shape[2:], f.dtype),
            slot, axis=1),
        full)


def _to_ring(k: jax.Array, window: int) -> jax.Array:
    """(R,B,H,S0,dh) prefill keys → (R,B,H,window,dh) ring buffer.

    Slot assignment: position p lives at slot p % window (matches the decode
    writer in models.attention)."""
    s0 = k.shape[3]
    if s0 <= window:
        return jnp.pad(k, ((0, 0),) * 3 + ((0, window - s0), (0, 0)))
    last = k[:, :, :, s0 - window:]
    return jnp.roll(last, s0 % window, axis=3)


def pad_caches(cfg: ArchConfig, caches: PyTree, target_len: int) -> PyTree:
    """Grow every attention cache's sequence axis to its serving length.

    Cache layouts (leading R = stacked scan dim):
      GQA:   (R,B,Hkv,S,dh) ×2  → pad axis 3 (ring-rolled for SWA layers)
      MLA:   (R,B,S,lat), (R,B,S,rdh) → pad axis 2
      Mamba: conv/ssm states → unchanged (O(1) state)
    """
    from ..models.transformer import ring_len

    out = []
    for i, st in enumerate(cfg.stages):
        blocks = []
        for j, spec in enumerate(st.pattern):
            c = caches[i][j]
            kind = spec.kind
            a = cfg.shared_attn if kind == "shared_attn" else spec.attn
            if kind == "mamba":
                blocks.append(c)
            elif a.kv_lora:
                cl, cr = c
                pad = target_len - cl.shape[2]
                blocks.append((
                    jnp.pad(cl, ((0, 0), (0, 0), (0, pad), (0, 0))),
                    jnp.pad(cr, ((0, 0), (0, 0), (0, pad), (0, 0)))))
            else:
                tgt = ring_len(cfg, a, target_len)
                ck, cv = c
                if tgt < target_len:               # SWA ring layer
                    blocks.append((_to_ring(ck, tgt), _to_ring(cv, tgt)))
                else:
                    pad = tgt - ck.shape[3]
                    blocks.append((
                        jnp.pad(ck, ((0, 0),) * 3 + ((0, pad), (0, 0))),
                        jnp.pad(cv, ((0, 0),) * 3 + ((0, pad), (0, 0)))))
        out.append(tuple(blocks))
    return out


# ---------------------------------------------------------------------------
# Block-paged layout planning
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class LeafSpec:
    """Paging metadata for one cache leaf.

    ``kind`` is ``"seq"`` for sequence-bearing leaves (GQA K/V, MLA latent
    and rope caches — paged into blocks along their sequence axis) or
    ``"lane"`` for O(1) per-lane state (Mamba conv/SSM — kept dense and
    slot-indexed).  ``seq_axis``/``length`` describe the stacked
    ``(R, B, ...)`` dense leaf; position ``p`` lives at ring slot
    ``p % length`` (identity for full-length leaves).  ``feat`` is the dense
    leaf's other per-position axes in order (``(kv, dh)`` for GQA,
    ``(width,)`` for MLA): one position's entry, flattened, is a row of
    ``F = prod(feat)`` values in its block."""
    kind: str
    seq_axis: int = 0
    length: int = 0
    feat: Tuple[int, ...] = ()


def _is_spec(x) -> bool:
    return isinstance(x, LeafSpec)


def leaf_layout(cfg: ArchConfig, max_len: int) -> PyTree:
    """A tree of :class:`LeafSpec` mirroring the model's cache tree."""
    from ..models.transformer import ring_len

    out = []
    for st in cfg.stages:
        blocks = []
        for spec in st.pattern:
            a = cfg.shared_attn if spec.kind == "shared_attn" else spec.attn
            if spec.kind == "mamba":
                blocks.append((LeafSpec("lane"),) * 3)   # conv_x, conv_bc, ssm
            elif a.kv_lora:
                blocks.append((LeafSpec("seq", 2, max_len, (a.kv_lora,)),
                               LeafSpec("seq", 2, max_len,
                                        (a.rope_head_dim,))))
            else:
                lr = ring_len(cfg, a, max_len)
                kv = LeafSpec("seq", 3, lr, (a.n_kv_heads, a.head_dim))
                blocks.append((kv, kv))
        out.append(tuple(blocks))
    return out


def ring_lengths(layout: PyTree, max_len: int) -> List[int]:
    """Distinct SWA ring lengths (< max_len) across all sequence leaves."""
    specs = jax.tree.leaves(layout, is_leaf=_is_spec)
    return sorted({s.length for s in specs
                   if s.kind == "seq" and s.length < max_len})


def init_paged(cfg: ArchConfig, slots: int, max_len: int, num_blocks: int,
               block_size: int) -> PyTree:
    """Zero-initialized paged cache tree: sequence leaves become
    ``(R, num_blocks, block_size, F)`` arenas, one row of ``F`` values per
    position, lane leaves stay the dense ``(R, slots, ...)`` slot-indexed
    state.

    Folding the features into one minor axis keeps it a multiple of the
    TPU's 128 lanes wherever ``F`` is (640 for h2o-danube-1.8b's 8 KV heads
    of 80; MLA's 64-wide rope key is not).  With ``head_dim`` 80 as the
    minor axis the device's default layout makes the block axis minor
    instead, and every program that gathers or scatters blocks relayouts
    the whole arena first."""
    from ..models.transformer import cache_specs

    specs = cache_specs(cfg, slots, max_len)
    layout = leaf_layout(cfg, max_len)

    def build(ls: LeafSpec, sp):
        if ls.kind == "seq":
            return jnp.zeros((sp.shape[0], num_blocks, block_size,
                              math.prod(ls.feat)), sp.dtype)
        return jnp.zeros(sp.shape, sp.dtype)

    return jax.tree.map(build, layout, specs, is_leaf=_is_spec)


# ---------------------------------------------------------------------------
# Paged device ops (traced inside the engine's jitted programs)
# ---------------------------------------------------------------------------
def _positions(ls: LeafSpec, rows: jax.Array, block_size: int) -> jax.Array:
    """Gathered blocks ``(..., m, block_size, F)`` → the first ``length``
    positions, ``(..., length, *feat)``."""
    lead = rows.shape[:-3]
    seq = rows.reshape(lead + (rows.shape[-3] * block_size,) + ls.feat)
    return jax.lax.slice_in_dim(seq, 0, ls.length, axis=len(lead))


def _write_rows(arena: jax.Array, bid: jax.Array, off: jax.Array,
                val: jax.Array) -> jax.Array:
    """Store ``val`` ``(R, n, F)``: entry ``i`` goes to block ``bid[i]`` at
    offset ``off[i]`` on every layer — one scatter indexed by layer, block
    and offset, whose window is the arena's minor axis alone, so the arena
    keeps its layout."""
    layers = jnp.arange(arena.shape[0])[:, None]
    return arena.at[layers, bid, off].set(val.astype(arena.dtype))


def gather_views(layout: PyTree, paged: PyTree, tables: jax.Array,
                 block_size: int) -> PyTree:
    """Blocks → dense per-lane views, ``(R, B, ..., length, ...)`` per leaf.

    ``tables`` is the (B, max_blocks) int32 block table.  Each leaf gathers
    the first ``ceil(length / block_size)`` table entries and unflattens
    their rows back into the dense leaf's axes, sliced to exactly the dense
    row length — so the unmodified chunk attention math runs on the view
    and never sees the block structure.  Unwritten positions read whatever
    their block holds (zeros from the null block, stale KV from a reused
    one); the per-lane position masks exclude them exactly, so attention
    over a gathered view is bit-identical to attention over the dense slot
    cache."""

    def g(ls: LeafSpec, arena):
        if ls.kind == "lane":
            return arena
        m = -(-ls.length // block_size)
        rows = jnp.take(arena, tables[:, :m], axis=1)   # (R,B,m,bs,F)
        return jnp.moveaxis(_positions(ls, rows, block_size), 2,
                            ls.seq_axis)

    return jax.tree.map(g, layout, paged, is_leaf=_is_spec)


def layer_view(ls: LeafSpec, arena: jax.Array, tables: jax.Array, layer,
               block_size: int) -> jax.Array:
    """One layer's dense per-lane view of a sequence arena,
    ``(B, ..., length, ...)``: what :func:`gather_views` gives at index
    ``layer`` of its stacked view, gathered straight from the arena (one
    gather over the layer and block axes, so nothing arena-sized is
    sliced or copied).  ``layer`` may be traced (the decode layer scan)."""
    m = -(-ls.length // block_size)
    rows = arena[layer, tables[:, :m]]                  # (B,m,bs,F)
    return jnp.moveaxis(_positions(ls, rows, block_size), 1,
                        ls.seq_axis - 1)


def written_entry(ls: LeafSpec, view: jax.Array, pos: jax.Array
                  ) -> jax.Array:
    """The entry each lane wrote at ring slot ``pos % length`` of its
    one-layer view: ``(B, *feat)``, the sequence axis dropped."""
    axis = ls.seq_axis - 1
    idx = jnp.mod(pos, ls.length).reshape((-1,) + (1,) * (view.ndim - 1))
    return jnp.squeeze(jnp.take_along_axis(view, idx, axis=axis), axis)


def write_entries(layout: PyTree, paged: PyTree, entries: PyTree,
                  tables: jax.Array, pos: jax.Array, active: jax.Array,
                  block_size: int) -> PyTree:
    """Write one decode step's entries back into the arenas, in place.

    ``entries`` mirrors the cache tree: a sequence leaf holds the ``(R, B,
    *feat)`` entries each lane wrote at ring slot ``pos % length``, a lane
    leaf the new ``(R, B, ...)`` state, which replaces the old wholesale
    (the model already kept inactive lanes').  Each sequence leaf takes one
    scatter into the donated arena.  Inactive lanes are redirected to the
    null block with a zero value, so block 0 stays all-zero and no shared
    block is ever touched (COW forking made every written block private
    before this runs)."""

    def w(ls: LeafSpec, arena, entry):
        if ls.kind == "lane":
            return entry
        slot = jnp.mod(pos, ls.length)
        bid = jnp.take_along_axis(tables, (slot // block_size)[:, None],
                                  axis=1)[:, 0]
        val = entry.reshape(entry.shape[:2] + (-1,))    # (R, B, F)
        val = jnp.where(active[None, :, None], val,
                        jnp.zeros((), val.dtype))
        return _write_rows(arena, jnp.where(active, bid, 0),
                           jnp.where(active, slot % block_size, 0), val)

    return jax.tree.map(w, layout, paged, entries, is_leaf=_is_spec)


def scatter_slots(ls: LeafSpec, arena: jax.Array, view: jax.Array,
                  table_row: jax.Array, slots: jax.Array,
                  block_size: int) -> jax.Array:
    """Scatter ring slots ``slots`` of a single-lane view into the arena.

    Admission building block: the whole-prompt path writes slots
    ``0..min(S0, length)`` of the padded prefill cache, the chunk path
    writes ``(p0 + arange(C)) % length`` (injective while C ≤ ring length,
    which the engine's chunk clamp guarantees)."""
    val = jnp.take(view[:, 0], slots, axis=ls.seq_axis - 1)
    val = jnp.moveaxis(val, ls.seq_axis - 1, 1)          # (R, n, *feat)
    return _write_rows(arena, jnp.take(table_row, slots // block_size),
                       jnp.mod(slots, block_size),
                       val.reshape(val.shape[:2] + (-1,)))


def copy_block(layout: PyTree, paged: PyTree, src: jax.Array,
               dst: jax.Array) -> PyTree:
    """COW fork: copy arena row ``src`` into ``dst`` on every sequence leaf
    (one block id indexes the same row across all arenas)."""

    def c(ls: LeafSpec, arena):
        if ls.kind == "lane":
            return arena
        row = jax.lax.dynamic_index_in_dim(arena, src, axis=1, keepdims=True)
        return jax.lax.dynamic_update_slice_in_dim(arena, row, dst, axis=1)

    return jax.tree.map(c, layout, paged, is_leaf=_is_spec)


# ---------------------------------------------------------------------------
# Host-side block allocator
# ---------------------------------------------------------------------------
class NoFreeBlocks(RuntimeError):
    """The arena has no free or evictable block left."""


def prefix_block_keys(tokens: Sequence[int], block_size: int,
                      limit: Optional[int] = None) -> List[Tuple[int, ...]]:
    """Content keys for each whole block of a token prefix.

    Key ``i`` is the exact token tuple covering blocks ``0..i`` — chained
    content addressing with no hash collisions (a block is reusable only
    when everything before it matched too).  ``limit`` caps the number of
    keys (admission never matches the *entire* prompt: at least one suffix
    token must run through prefill to produce the first sampled logits)."""
    n = len(tokens) // block_size
    if limit is not None:
        n = min(n, limit)
    return [tuple(tokens[:(i + 1) * block_size]) for i in range(n)]


class BlockPool:
    """Refcounted host allocator over a fixed arena of KV blocks
    (DESIGN.md §14).

    Block 0 is the null block — reserved at construction, never allocated.
    The remaining ids are partitioned into three disjoint states:

    * **free** — on the free list, content garbage;
    * **live** — refcount ≥ 1 (one reference per lane block-table entry);
    * **reusable** — refcount 0 but still registered in the prefix cache:
      an LRU of retired prompt blocks that a later ``match_prefix`` can
      revive without recomputing their KV, evicted on allocation pressure.

    ``reserve``/``alloc(reserved=True)`` implement admission-time
    worst-case accounting: a lane reserves ``ceil((S0 + max_new) / bs)``
    blocks up front (enough to cover every later tail allocation *and*
    every COW fork of a matched block), so decode can never hit
    :class:`NoFreeBlocks` mid-flight.  ``check()`` asserts the full
    invariant set — the property suite calls it after every operation."""

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is the null block)")
        if block_size < 1:
            raise ValueError("block_size must be >= 1")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.reset()

    def reset(self) -> None:
        """Drop all bookkeeping back to the empty-arena state."""
        self._free: List[int] = list(range(self.num_blocks - 1, 0, -1))
        self._ref: Dict[int, int] = {}
        self._reusable: "collections.OrderedDict[int, tuple]" = \
            collections.OrderedDict()
        self._key_of: Dict[int, tuple] = {}
        self._bid_of: Dict[tuple, int] = {}
        self.reserved = 0
        self.allocs = 0
        self.forks = 0
        self.evictions = 0
        self.prefix_hits = 0
        self.prefix_queries = 0

    # -- introspection -----------------------------------------------------
    @property
    def capacity(self) -> int:
        return self.num_blocks - 1

    def free_blocks(self) -> int:
        return len(self._free)

    def live_blocks(self) -> int:
        return len(self._ref)

    def available(self) -> int:
        """Blocks an allocation could obtain: free + evictable reusable."""
        return len(self._free) + len(self._reusable)

    def refcount(self, bid: int) -> int:
        return self._ref.get(bid, 0)

    def is_registered(self, bid: int) -> bool:
        return bid in self._key_of

    def stats(self) -> Dict[str, int]:
        return {"capacity": self.capacity, "free": len(self._free),
                "live": len(self._ref), "reusable": len(self._reusable),
                "reserved": self.reserved, "allocs": self.allocs,
                "forks": self.forks, "evictions": self.evictions,
                "prefix_hits": self.prefix_hits,
                "prefix_queries": self.prefix_queries}

    # -- reservations ------------------------------------------------------
    def can_reserve(self, n: int) -> bool:
        return self.available() - self.reserved >= n

    def reserve(self, n: int) -> None:
        if not self.can_reserve(n):
            raise NoFreeBlocks(
                f"cannot reserve {n} blocks ({self.available()} available, "
                f"{self.reserved} already reserved)")
        # Reservations are honored from the free list alone: a later
        # match_prefix may revive reusable blocks (moving them live without
        # an alloc), which must never strand a reservation.  Evict LRU
        # reusable blocks up front until the free list covers every unit.
        while len(self._free) - self.reserved < n:
            bid, _ = self._reusable.popitem(last=False)
            self._drop_registration(bid)
            self._free.append(bid)
            self.evictions += 1
        self.reserved += n

    def unreserve(self, n: int) -> None:
        if n > self.reserved:
            raise ValueError(f"unreserve({n}) exceeds reserved "
                             f"({self.reserved})")
        self.reserved -= n

    # -- allocation / refcounting -----------------------------------------
    def alloc(self, *, reserved: bool = False) -> int:
        """Take a block (refcount 1).  ``reserved=True`` draws down a prior
        ``reserve``; otherwise the allocation must fit beside every
        outstanding reservation."""
        if reserved:
            # reserve() pre-evicted into the free list: reserved <= free
            if self.reserved < 1:
                raise ValueError("alloc(reserved=True) with no reservation")
            self.reserved -= 1
            bid = self._free.pop()
        else:
            if self.available() - self.reserved < 1:
                raise NoFreeBlocks(
                    f"arena exhausted ({self.available()} available, "
                    f"{self.reserved} reserved)")
            # never dip the free list below the reserved floor — evict a
            # reusable block instead so reservations stay honorable
            if len(self._free) > self.reserved:
                bid = self._free.pop()
            else:
                bid, _ = self._reusable.popitem(last=False)   # evict LRU
                self._drop_registration(bid)
                self.evictions += 1
        self._ref[bid] = 1
        self.allocs += 1
        return bid

    def ref(self, bid: int) -> None:
        if bid not in self._ref:
            raise ValueError(f"ref of non-live block {bid}")
        self._ref[bid] += 1

    def deref(self, bid: int) -> None:
        """Drop one reference.  At zero the block parks on the reusable LRU
        if still prefix-registered, else returns to the free list."""
        c = self._ref.get(bid)
        if c is None:
            raise ValueError(f"double free of block {bid}")
        if c > 1:
            self._ref[bid] = c - 1
            return
        del self._ref[bid]
        key = self._key_of.get(bid)
        if key is not None:
            self._reusable[bid] = key
            self._reusable.move_to_end(bid)
        else:
            self._free.append(bid)

    def fork(self, bid: int, *, reserved: bool = False) -> int:
        """COW: allocate a private target for shared block ``bid`` and drop
        this lane's reference to the original.  The device copy
        (``copy_block``) is the caller's job."""
        if self.refcount(bid) < 2:
            raise ValueError(f"fork of unshared block {bid} "
                             f"(refcount {self.refcount(bid)})")
        new = self.alloc(reserved=reserved)
        self.deref(bid)
        self.forks += 1
        return new

    # -- prefix cache ------------------------------------------------------
    def _drop_registration(self, bid: int) -> None:
        key = self._key_of.pop(bid, None)
        if key is not None:
            self._bid_of.pop(key, None)

    def register_prefix(self, bid: int, key: tuple) -> bool:
        """Publish a live block as holding the prefix ``key``; False if the
        key (or block) is already registered."""
        if key in self._bid_of or bid in self._key_of:
            return False
        if bid not in self._ref:
            raise ValueError(f"register of non-live block {bid}")
        self._key_of[bid] = key
        self._bid_of[key] = bid
        return True

    def unregister(self, bid: int) -> None:
        """Withdraw a live block from the prefix cache — the engine calls
        this before writing a registered unshared block in place, since its
        content is about to stop matching its key."""
        self._drop_registration(bid)

    def match_prefix(self, keys: Sequence[tuple]) -> List[int]:
        """Longest resident chain matching ``keys``; every matched block
        gains a reference (revived off the reusable LRU when parked)."""
        out: List[int] = []
        for key in keys:
            self.prefix_queries += 1
            bid = self._bid_of.get(key)
            if bid is None:
                break
            self.prefix_hits += 1
            if bid in self._reusable:
                del self._reusable[bid]
                self._ref[bid] = 1
            else:
                self._ref[bid] += 1
            out.append(bid)
        return out

    # -- invariants --------------------------------------------------------
    def check(self) -> None:
        """Assert every allocator invariant; raises AssertionError on the
        first violation.  O(blocks) — cheap enough to run after every
        operation in the property suite."""
        def inv(cond: bool, msg: str) -> None:
            if not cond:
                raise AssertionError(f"BlockPool invariant violated: {msg}\n"
                                     f"  stats={self.stats()}")

        free, reuse, live = (set(self._free), set(self._reusable),
                             set(self._ref))
        inv(len(free) == len(self._free), "free list holds duplicates")
        inv(not free & reuse and not free & live and not reuse & live,
            "free/reusable/live states overlap")
        inv(free | reuse | live == set(range(1, self.num_blocks)),
            "blocks leaked or fabricated (partition != 1..N-1)")
        inv(0 not in free | reuse | live, "null block 0 entered circulation")
        inv(all(c >= 1 for c in self._ref.values()),
            "live block with refcount < 1")
        inv(0 <= self.reserved <= len(self._free),
            "reservations exceed the free list (a reserved alloc would "
            "have to evict or fail)")
        inv(len(self._key_of) == len(self._bid_of)
            and all(self._bid_of[k] == b for b, k in self._key_of.items()),
            "prefix registry is not a bijection")
        inv(all(b in self._key_of for b in reuse),
            "reusable block without a prefix registration")
