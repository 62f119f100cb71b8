"""Paged KV arena: fixed-capacity, block-table cache store for the slot
engine.

The dense cache path (``kvcache.merge`` / ``select_slots``) re-materializes
the whole live batch on every admission and changes the cache's batch axis
whenever the live count changes — so each admission copies O(live cache)
bytes and each batch-size change retraces the fused decode step under XLA.
``KVArena`` replaces that with an allocator-shaped API sized once from the
``ParallelPlan``:

* the **token axis is paged**: every unbounded KV sequence axis is stored
  as physical blocks of ``block_size`` tokens in a shared pool, and each
  slot owns a row of a ``(capacity, blocks_per_slot)`` **block table**
  mapping logical block -> physical block (a reserved trash block absorbs
  writes from unoccupied slots, so the fused step needs no branches);
* every pool is **stored in the layout the paged kernels read**
  (``kernels.paged_pool``: KV heads packed to 128-lane rows, int8 scales
  in unpadded lane rows), so a step hands the stored buffer to the kernel
  and scatters new rows into it in place;
* **admission writes pages in place** (``alloc`` + ``write_prefill``
  scatter exactly the new request's pages and per-slot state — the live
  batch is never touched);
* **eviction is a free-list operation** (``free`` returns the slot's
  blocks; no device work at all);
* **blocks are shareable across slots** (prefix cache): ``alloc`` can
  stitch already-resident blocks into a new slot's table
  (``shared=...``), per-block refcounts keep them alive across source
  evictions, ``register``/``unregister`` let a prefix index freeze
  blocks (writers ``cow_block`` first — copy-on-write on divergence),
  and ref-0 cached blocks park on an LRU the allocator reclaims before
  ever failing;
* the decode step always runs at the full static shape ``(capacity, ...)``
  with an occupancy mask, so it compiles exactly once per service.

Cache pytrees keep the shape convention documented in ``kvcache``:
``ndim >= 2`` leaves are ``(layers, batch, ...)`` batched state, small
integer leaves are sequence lengths.  The arena classifies each leaf ONCE
at construction by probing ``init_cache`` at two ``max_len`` values
(``jax.eval_shape`` — no allocation): axes that grow with ``max_len`` are
sequence axes and get paged; everything else (SSM/conv state, encoder
cross-KV, saturated sliding-window rings) is fixed-size per-slot state
held at ``(layers, capacity, ...)``.  This makes the arena family-agnostic
across all six model families.
"""
from __future__ import annotations

import functools
import math
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import paged_pool
from repro.kernels.quant import QuantPages
from repro.launch import mesh as meshlib

Cache = Any  # pytree of arrays

_LEN, _PAGED, _STATE = "len", "paged", "state"

VALID_KV_DTYPES = ("bf16", "int8")


def _is_len_leaf(shape: Tuple[int, ...], dtype) -> bool:
    return len(shape) <= 1 and jnp.issubdtype(dtype, jnp.integer)


class KVArena:
    """Fixed-capacity paged cache arena for one DP replica group.

    Host-side bookkeeping (free lists, block tables, occupancy) is plain
    numpy; device state is three pytrees of fixed-shape arrays — ``pages``
    (block pools for sequence leaves), ``state`` (per-slot fixed-size
    leaves) and ``lens`` (``(capacity,)`` int32) — threaded functionally
    through the jitted decode step via the pure helpers below.
    """

    def __init__(self, cfg, init_cache: Callable, *, capacity: int,
                 max_seq_len: int, block_size: int = 32,
                 pool_blocks: Optional[int] = None, dtype=None,
                 kv_dtype: str = "bf16", mesh=None):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        if kv_dtype not in VALID_KV_DTYPES:
            raise ValueError(f"kv_dtype must be one of {VALID_KV_DTYPES}, "
                             f"got {kv_dtype!r}")
        # "bf16" = keep the family's native KV dtype (the model config's
        # compute dtype — f32 in the toy configs); "int8" = quantized block
        # format: floating paged leaves become QuantPages pools (int8
        # values + per-token-per-head f32 scales travelling with the
        # blocks).  Fixed per-slot STATE leaves (SSM conv/SSD state,
        # encoder cross-KV, saturated ring windows) are never quantized.
        self.kv_dtype = kv_dtype
        self.cfg = cfg
        # under a model-parallel service mesh every buffer is born sharded
        # (``meshlib.arena_spec``): heads split over ``model``, pages and
        # slots replicated, so no device ever holds the whole pool
        self.mesh = mesh
        self.capacity = int(capacity)
        self.block_size = int(block_size)
        self.blocks_per_slot = max(1, math.ceil(max_seq_len / block_size))
        self.slot_tokens = self.blocks_per_slot * self.block_size  # S_max
        self.pool_blocks = (self.capacity * self.blocks_per_slot
                            if pool_blocks is None else int(pool_blocks))
        if self.pool_blocks < self.blocks_per_slot:
            raise ValueError("pool smaller than one slot's block budget")
        self.trash_block = self.pool_blocks       # reserved garbage block

        # -- classify the family's cache layout by probing init_cache ----
        probe = lambda s: jax.eval_shape(
            lambda: init_cache(cfg, 1, s, dtype) if dtype is not None
            else init_cache(cfg, 1, s))
        lo, hi = probe(self.slot_tokens), probe(self.slot_tokens
                                               + self.block_size)
        lo_leaves, self._treedef = jax.tree.flatten(lo)
        hi_leaves = jax.tree.leaves(hi)
        self._tags: List[str] = []
        self._paged_shapes: List[Tuple[int, ...]] = []
        self._state_shapes: List[Tuple[int, ...]] = []
        self._dtypes: List[Any] = []
        for a, b in zip(lo_leaves, hi_leaves):
            self._dtypes.append(a.dtype)
            if _is_len_leaf(a.shape, a.dtype):
                self._tags.append(_LEN)
                continue
            grown = [d for d in range(a.ndim) if a.shape[d] != b.shape[d]]
            if not grown:
                if a.ndim < 2 or a.shape[1] != 1:
                    raise ValueError(
                        f"state leaf {a.shape} lacks a batch axis at 1")
                self._tags.append(_STATE)
                self._state_shapes.append(a.shape)
            else:
                if grown != [2] or a.ndim < 3 or a.shape[1] != 1:
                    raise ValueError(
                        f"paged leaf must grow only along axis 2 "
                        f"(layers, batch, seq, ...); got {a.shape} vs "
                        f"{b.shape}")
                if a.shape[2] != self.slot_tokens:
                    raise ValueError(
                        f"seq axis {a.shape[2]} != arena slot_tokens "
                        f"{self.slot_tokens}")
                self._tags.append(_PAGED)
                self._paged_shapes.append(a.shape)

        # -- device state --------------------------------------------------
        # page pools are stored in the layout the paged kernels read
        # (``paged_pool``): (layers, Hkv/G, pages, block_size, W), G KV
        # heads of the per-device count to a row of whole 128-lane tiles
        self.pages: List[Any] = []
        self._quantized: List[bool] = []          # per paged leaf
        self._pool_dims: List[Tuple[int, int, int, int]] = []  # A0,Hkv,D,G
        self._paged_dtypes: List[Any] = []        # the leaves' own dtypes
        self.state: List[jnp.ndarray] = []
        n_model = meshlib.axis_size(mesh, "model") if mesh is not None else 1
        for i, tag in enumerate(self._tags):
            if tag == _PAGED:
                A0, _, _, *rest = lo_leaves[i].shape
                if len(rest) != 2:
                    raise ValueError(
                        f"paged leaf {lo_leaves[i].shape} is not "
                        f"(layers, batch, seq, kv_heads, head_dim)")
                Hkv, D = rest
                local = Hkv // n_model if Hkv % n_model == 0 else Hkv
                self._pool_dims.append(
                    (A0, Hkv, D, paged_pool.head_group(D, local)))
                self._quantized.append(
                    self.kv_dtype == "int8"
                    and jnp.issubdtype(self._dtypes[i], jnp.floating))
                self._paged_dtypes.append(self._dtypes[i])
                self.pages.append(jax.tree.map(   # +1 trash block
                    lambda a: self._zeros(a.shape, a.dtype, pool=True),
                    self._pool_specs(len(self.pages), self.pool_blocks + 1)))
            elif tag == _STATE:
                A0, _, *rest = lo_leaves[i].shape
                self.state.append(self._zeros((A0, self.capacity, *rest),
                                              self._dtypes[i]))
        self.lens = self._zeros((self.capacity,), jnp.int32)

        # -- host bookkeeping ----------------------------------------------
        self._block_tables = np.full(
            (self.capacity, self.blocks_per_slot), self.trash_block,
            np.int32)
        self._free_slots: List[int] = list(range(self.capacity))
        self._free_blocks: List[int] = list(range(self.pool_blocks))
        self._slot_blocks = {}
        self._occ = np.zeros((self.capacity,), bool)
        self._write_fns: Dict[int, Callable] = {}
        self._tables_dev: Optional[jnp.ndarray] = None
        self._occ_dev: Optional[jnp.ndarray] = None

        # -- cross-slot block sharing (prefix cache) -----------------------
        # A physical block may back several slots' block-table rows (shared
        # prompt prefixes) and/or be retained by a prefix index after every
        # referencing slot died.  ``_block_refs`` counts live slot
        # references; ``_cached`` marks blocks registered by a prefix index
        # (their content is immutable — any write COWs first); ref-0 cached
        # blocks park in ``_idle_cached`` (an LRU by last release) and are
        # reclaimed before the allocator ever fails, via ``evict_hook`` so
        # the index drops its entries.
        self._block_refs = np.zeros((self.pool_blocks,), np.int32)
        self._cached: set = set()
        self._idle_cached: "OrderedDict[int, None]" = OrderedDict()
        self.evict_hook: Optional[Callable[[int], None]] = None
        self.cache_retention: Optional[int] = None  # max idle cached blocks
        self.cached_evictions = 0     # idle cached blocks reclaimed
        self.parks = 0                # preemption block-table parks
        self.parked_blocks = 0        # blocks currently held by parked
        #                               requests (admission headroom lost
        #                               to frozen-but-resumable KV)
        self.cow_copies = 0           # copy-on-write block copies
        self.cow_calls = 0            # jitted COW dispatches (batching
        #                               coalesces a wave's copies into one)
        self._cow_many_fns: Dict[int, Callable] = {}

        # bytes one cache token occupies across all paged leaves, and the
        # fixed per-slot state footprint (allocator-style accounting).  A
        # quantized leaf counts 1 byte per value plus its f32 per-row scale
        self.token_bytes = 0
        for s, d, q in zip(self._paged_shapes, self._paged_dtypes,
                           self._quantized):
            if q:
                self.token_bytes += int(np.prod([s[0], *s[3:]]))      # int8
                self.token_bytes += int(np.prod([s[0], *s[3:-1]])) * 4
            else:
                self.token_bytes += (int(np.prod([s[0], *s[3:]]))
                                     * np.dtype(d).itemsize)
        self.state_slot_bytes = sum(
            int(np.prod([s[0], *s[2:]])) * np.dtype(d).itemsize
            for s, d in zip(self._state_shapes,
                            (self._dtypes[i] for i, t in
                             enumerate(self._tags) if t == _STATE)))

    def _zeros(self, shape, dtype, *, pool: bool = False):
        if self.mesh is None:
            return jnp.zeros(shape, dtype)
        spec = meshlib.arena_spec(self.mesh, tuple(shape), pool=pool)
        return jnp.zeros(shape, dtype,
                         device=jax.sharding.NamedSharding(self.mesh, spec))

    def _pool_specs(self, i: int, pages: int):
        """Paged leaf ``i``'s pool at ``pages`` physical pages, as
        ``ShapeDtypeStruct``s: one array, or a ``QuantPages`` of two."""
        A0, Hkv, D, G = self._pool_dims[i]
        dtype = jnp.int8 if self._quantized[i] else self._paged_dtypes[i]
        values = jax.ShapeDtypeStruct(
            paged_pool.value_shape(A0, pages, self.block_size, Hkv, D, G),
            dtype)
        if not self._quantized[i]:
            return values
        return QuantPages(values, jax.ShapeDtypeStruct(
            paged_pool.scale_shape(A0, pages, self.block_size, Hkv, G),
            jnp.float32))

    def pool_structs(self, pages: int) -> List[Any]:
        """The page pools at ``pages`` physical pages (trash page
        included), placed like the live pools: what a step compiled for
        another capacity takes, allocated nowhere."""
        return [jax.tree.map(lambda s, a: jax.ShapeDtypeStruct(
                    s.shape, s.dtype, sharding=a.sharding),
                    self._pool_specs(i, pages), pool)
                for i, pool in enumerate(self.pages)]

    def shardings(self) -> Optional[Tuple[Any, Any, Any]]:
        """``(pages, state, lens)`` placements under a mesh (``None``
        without one): a step that returns the arena's buffers pins them
        here, so they never reshard and the next call hits the same
        compile."""
        if self.mesh is None:
            return None
        return jax.tree.map(lambda a: a.sharding,
                            (self.pages, self.state, self.lens))

    def device_slot_bytes(self) -> int:
        """Bytes one more slot costs on each device holding the arena:
        its blocks' share of every page pool plus its state row."""
        def shard_bytes(a):
            return (math.prod(a.sharding.shard_shape(a.shape))
                    * a.dtype.itemsize)
        per_block = (sum(shard_bytes(p) for p in jax.tree.leaves(self.pages))
                     / (self.pool_blocks + 1))
        per_state = (sum(shard_bytes(s) for s in self.state) + shard_bytes(
            self.lens)) / self.capacity
        return math.ceil(per_block * self.blocks_per_slot + per_state)

    # ------------------------------------------------------------------
    # allocator surface
    # ------------------------------------------------------------------
    def blocks_for(self, total_tokens: int) -> int:
        return max(1, math.ceil(total_tokens / self.block_size))

    @property
    def free_capacity(self) -> int:
        """Blocks the allocator can hand out without failing: the free
        list plus every reclaimable (ref-0 cached) block."""
        return len(self._free_blocks) + len(self._idle_cached)

    def can_alloc(self, total_tokens: int, *, shared: Sequence[int] = (),
                  reserve: int = 0) -> bool:
        """Admission feasibility.  ``shared`` lists the cached blocks a
        prefix hit would stitch in (they reduce the fresh-block demand,
        but idle ones must be EXCLUDED from the reclaimable supply — the
        hit revives them); ``reserve`` asks for extra claimable headroom
        (e.g. the divergence-COW copy a partial-tail share will need)."""
        shared = list(shared)
        idle_shared = sum(1 for b in shared if b in self._idle_cached)
        claimable = (len(self._free_blocks) + len(self._idle_cached)
                     - idle_shared)
        return (bool(self._free_slots)
                and (self.blocks_for(total_tokens) - len(shared) + reserve
                     <= claimable)
                and total_tokens <= self.slot_tokens)

    def _reclaim_lru_block(self) -> None:
        """Evict the least-recently-released idle cached block back to the
        free list.  The append happens BEFORE the hook fires: the hook's
        ``unregister`` calls (subtree drops) must see this block as
        already freed, or they would double-append it."""
        blk, _ = self._idle_cached.popitem(last=False)
        self._cached.discard(blk)
        self.cached_evictions += 1
        self._free_blocks.append(blk)
        if self.evict_hook is not None:
            self.evict_hook(blk)

    def _claim_blocks(self, n: int) -> List[int]:
        """Pop ``n`` blocks from the free list, reclaiming idle cached
        blocks in LRU order when it runs short (``evict_hook`` lets the
        prefix index drop the evicted block's entries first)."""
        while len(self._free_blocks) < n and self._idle_cached:
            self._reclaim_lru_block()
        if len(self._free_blocks) < n:
            raise RuntimeError("arena out of blocks")
        return [self._free_blocks.pop(0) for _ in range(n)]

    def alloc(self, total_tokens: int, slot: Optional[int] = None, *,
              shared: Sequence[int] = ()) -> int:
        """Claim a slot and its token blocks for a request whose lifetime
        needs ``total_tokens`` (prompt + generation budget).  ``shared``
        stitches already-resident physical blocks (a cached prompt prefix)
        into the FRONT of the slot's block table instead of claiming fresh
        blocks for those positions — each one's refcount rises and idle
        cached blocks are revived off the LRU."""
        if total_tokens > self.slot_tokens:
            raise ValueError(
                f"request needs {total_tokens} tokens > arena slot budget "
                f"{self.slot_tokens} (raise max_seq_len)")
        n = self.blocks_for(total_tokens)
        shared = list(shared)
        if len(shared) > n:
            raise ValueError(
                f"{len(shared)} shared prefix blocks exceed the request's "
                f"{n}-block budget")
        # incref the shared prefix FIRST so a same-call reclaim sweep can
        # never evict a block the hit is about to use
        for b in shared:
            if self._block_refs[b] == 0:
                self._idle_cached.pop(b, None)
            self._block_refs[b] += 1
        try:
            fresh = self._claim_blocks(n - len(shared))
        except RuntimeError:
            for b in shared:          # undo the increfs; caller requeues
                self._release_block(b)
            raise
        if slot is None:
            if not self._free_slots:
                for b in shared:
                    self._release_block(b)
                self._free_blocks.extend(fresh)
                raise RuntimeError("arena out of slots")
            slot = self._free_slots.pop(0)
        else:
            self._free_slots.remove(slot)
        for b in fresh:
            self._block_refs[b] = 1
        blocks = shared + fresh
        self._slot_blocks[slot] = blocks
        row = np.full((self.blocks_per_slot,), self.trash_block, np.int32)
        row[:n] = blocks
        self._block_tables[slot] = row
        self._occ[slot] = True
        self._tables_dev = self._occ_dev = None
        return slot

    def reset_len(self, slot: int) -> None:
        """Zero a slot's device-side length.  Chunked admissions must call
        this after ``alloc``: the first chunk reads its start offset from
        ``lens`` (one-shot ``write_prefill`` overwrites it, chunk writes
        only advance it — a recycled slot would otherwise resume at the
        previous tenant's length)."""
        self.set_len(slot, 0)

    def set_len(self, slot: int, n: int) -> None:
        """Set a slot's device-side length — a prefix-cache hit admits with
        ``lens[slot] = hit_tokens`` so chunked prefill resumes past the
        shared prefix."""
        self.lens = self.lens.at[slot].set(n)

    def _release_block(self, block: int) -> None:
        """Drop one slot reference; a ref-0 block parks on the cached LRU
        if a prefix index still wants it, else returns to the free list."""
        self._block_refs[block] -= 1
        if self._block_refs[block] > 0:
            return
        self._block_refs[block] = 0
        if block in self._cached:
            self._idle_cached.pop(block, None)
            self._idle_cached[block] = None       # most-recently released
        else:
            self._free_blocks.append(block)

    def free(self, slot: int) -> None:
        """Release a slot: pure free-list bookkeeping, zero device work.
        Blocks shared with other slots (or retained by a prefix index)
        survive; only the last reference returns a block to circulation."""
        if not self._occ[slot]:
            return
        for b in self._slot_blocks.pop(slot):
            self._release_block(b)
        self._block_tables[slot] = self.trash_block
        self._occ[slot] = False
        self._free_slots.append(slot)
        self._tables_dev = self._occ_dev = None
        self._enforce_retention()

    # ------------------------------------------------------------------
    # preemption surface: block-table parking
    # ------------------------------------------------------------------
    @property
    def parkable(self) -> bool:
        """Preemption by parking freezes only the slot's BLOCKS; per-slot
        state leaves (SSM conv/recurrent state, ring windows) live in
        slot-indexed buffers that the next tenant overwrites, so layouts
        that carry any cannot park."""
        return not self._state_shapes

    def park(self, slot: int) -> List[int]:
        """Freeze a live slot's blocks and free the SLOT without releasing
        the blocks: the caller now owns one reference per block (exactly
        the references the slot held) and the physical KV stays resident.
        Resume hands them back through ``alloc(total, shared=blocks)``
        (which re-increfs) followed by ``release_parked`` (dropping the
        parked hold) — net refcounts unchanged, bit-identical content."""
        if not self._occ[slot]:
            raise ValueError(f"slot {slot} is not occupied")
        if not self.parkable:
            raise ValueError(
                "arena carries per-slot state leaves; parking would "
                "destroy them on slot reuse")
        blocks = self._slot_blocks.pop(slot)
        self._block_tables[slot] = self.trash_block
        self._occ[slot] = False
        self._free_slots.append(slot)
        self._tables_dev = self._occ_dev = None
        self.parks += 1
        self.parked_blocks += len(blocks)
        return blocks

    def release_parked(self, blocks: Sequence[int]) -> None:
        """Drop a parked hold — after a resume's ``alloc(shared=blocks)``
        re-increfed them, or to abandon an expired parked request (then
        cached blocks fall to the idle LRU, private ones to the free
        list)."""
        for b in blocks:
            self._release_block(b)
        self.parked_blocks -= len(blocks)
        self._enforce_retention()

    # ------------------------------------------------------------------
    # prefix-cache surface: registration, retention, copy-on-write
    # ------------------------------------------------------------------
    def register(self, block: int) -> None:
        """Mark a block as held by a prefix index: its content is frozen
        (writers COW) and it outlives its slots, parked on the LRU until
        reclaimed or re-shared."""
        self._cached.add(block)

    def unregister(self, block: int) -> None:
        """Prefix index dropped its entry: an idle block goes straight
        back to the free list, a live one merely loses immutability once
        its refs drain.  (Every indexed ref-0 block is on the idle list —
        a ref-0 uncached block is already free — so this is O(1).)"""
        self._cached.discard(block)
        if block in self._idle_cached:
            del self._idle_cached[block]
            self._free_blocks.append(block)

    def _enforce_retention(self) -> None:
        """Cap the idle cached pool at ``cache_retention`` blocks (the
        category knob: latency plans keep a bounded prefix cache,
        frequency plans retain aggressively)."""
        if self.cache_retention is None:
            return
        while len(self._idle_cached) > self.cache_retention:
            self._reclaim_lru_block()

    def block_ref(self, block: int) -> int:
        return int(self._block_refs[block])

    def is_cached(self, block: int) -> bool:
        return block in self._cached

    def cow_block(self, slot: int, logical: int) -> bool:
        """Copy-on-write: give ``slot`` a private copy of its ``logical``-th
        block if the physical block is shared with another slot or frozen
        by a prefix index.  Returns True when a copy happened (one block of
        device copy; the table row changes, so the device table re-uploads
        on next use)."""
        return self.cow_blocks([(slot, logical)]) > 0

    def cow_blocks(self, pairs: Sequence[Tuple[int, int]]) -> int:
        """Batched copy-on-write: coalesce several pending single-block
        COWs — e.g. the divergence copies of one admission wave whose
        members share a prompt template — into ONE jitted gather/scatter
        over (srcs, dsts) index vectors instead of one jit dispatch per
        block.  ``pairs`` lists (slot, logical) targets; blocks a slot
        already owns exclusively are skipped.  The copy vectors pad to the
        next power of two (padding copies the trash block onto itself) so
        the dispatch count stays O(log capacity) shapes, not one per wave
        size.  Returns the number of real blocks copied."""
        # phase 1 — decide, without mutating: which pairs actually need a
        # private copy (two sharers of the same source both do)
        needed: List[Tuple[int, int, int]] = []   # (slot, logical, phys)
        for slot, logical in pairs:
            phys = int(self._block_tables[slot][logical])
            if phys == self.trash_block:
                raise ValueError(f"slot {slot} logical block {logical} is "
                                 f"unallocated")
            if self._block_refs[phys] <= 1 and phys not in self._cached:
                continue
            needed.append((slot, logical, phys))
        if not needed:
            return 0
        # phase 2 — claim EVERY destination up front, before any table
        # mutation: if the arena is exhausted this raises with all
        # bookkeeping still consistent (the sources have live slot refs,
        # so the claim sweep can never reclaim them)
        fresh_blocks = self._claim_blocks(len(needed))
        todo: List[Tuple[int, int]] = []          # (phys, fresh)
        for (slot, logical, phys), fresh in zip(needed, fresh_blocks):
            self._block_refs[fresh] = 1
            blocks = self._slot_blocks[slot]
            blocks[blocks.index(phys)] = fresh
            self._block_tables[slot][logical] = fresh
            todo.append((phys, fresh))
        n = 1
        while n < len(todo):
            n *= 2
        src = np.full((n,), self.trash_block, np.int32)
        dst = np.full((n,), self.trash_block, np.int32)
        for i, (s, d) in enumerate(todo):
            src[i], dst[i] = s, d
        fn = self._cow_many_fns.get(n)
        if fn is None:
            def cow_copy_blocks(pages, src, dst):
                # a QuantPages pool copies its scales with the int8 values
                return [paged_pool.copy_pages(p, src, dst, dims[2], dims[1])
                        for p, dims in zip(pages, self._pool_dims)]
            fn = jax.jit(cow_copy_blocks, donate_argnums=(0,),
                         out_shardings=(None if self.mesh is None
                                        else self.shardings()[0]))
            self._cow_many_fns[n] = fn
        self.pages = fn(self.pages, jnp.asarray(src), jnp.asarray(dst))
        self._tables_dev = None
        for phys, _ in todo:
            self._release_block(phys)  # sole-ref cached sources go idle...
        self._enforce_retention()      # ...so the knob's bound applies here
        self.cow_copies += len(todo)
        self.cow_calls += 1
        return len(todo)

    def ensure_writable(self, slot: int, start: int, n_tokens: int = 1
                        ) -> int:
        """COW every block the write ``[start, start + n_tokens)`` touches
        that the slot does not exclusively own.  Cheap host check in the
        common case; multi-block writes coalesce their copies into one
        batched ``cow_blocks`` dispatch.  Returns the blocks copied."""
        if not self._cached and not (self._block_refs > 1).any():
            return 0
        lo = max(0, start) // self.block_size
        hi = max(0, start + n_tokens - 1) // self.block_size
        pairs = [(slot, logical)
                 for logical in range(lo, min(hi, self.blocks_per_slot - 1)
                                      + 1)
                 if self._block_tables[slot][logical] != self.trash_block]
        return self.cow_blocks(pairs)

    def block_tables(self) -> np.ndarray:
        """(capacity, blocks_per_slot) logical->physical block map."""
        return self._block_tables.copy()

    def occupancy(self) -> np.ndarray:
        return self._occ.copy()

    def device_block_tables(self) -> jnp.ndarray:
        """Device-resident block table, re-uploaded only after an alloc or
        free — steady-state decode steps pay no host copy or transfer."""
        if self._tables_dev is None:
            self._tables_dev = jnp.asarray(self._block_tables)
        return self._tables_dev

    def device_occupancy(self) -> jnp.ndarray:
        if self._occ_dev is None:
            self._occ_dev = jnp.asarray(self._occ)
        return self._occ_dev

    @property
    def live(self) -> int:
        return int(self._occ.sum())

    def slot_bytes(self, prompt_len: int) -> int:
        """Bytes an admission actually writes: the prompt's pages (block-
        granular — whole blocks are the scatter unit) plus the slot's
        fixed state — NOT the live batch (which is never copied)."""
        blocks = self.blocks_for(max(1, prompt_len))
        return (blocks * self.block_size * self.token_bytes
                + self.state_slot_bytes)

    def chunk_bytes(self, n_tokens: int) -> int:
        """Bytes one chunked-prefill call writes: exactly the chunk's
        token rows (the multi-token ``append_rows`` scatter is row-
        granular, not block-granular) plus the slot's fixed state row."""
        return n_tokens * self.token_bytes + self.state_slot_bytes

    # ------------------------------------------------------------------
    # admission write path
    # ------------------------------------------------------------------
    def write_prefill(self, slot: int, cache: Cache,
                      prompt_len: int) -> int:
        """Scatter one freshly prefilled single-request cache (batch 1,
        seq padded to ``slot_tokens``) into the slot's pages and state row.
        Only the blocks the prompt occupies are written — positions past
        the prompt are garbage until ``append_rows`` reaches them, and the
        per-slot ``len`` masks them everywhere.  Returns the bytes written
        (admission-copy accounting); one compile per distinct block count.
        """
        n_blocks = self.blocks_for(max(1, prompt_len))
        fn = self._write_fns.get(n_blocks)
        if fn is None:
            fn = jax.jit(functools.partial(self._scatter_prefill_blocks,
                                           n_blocks=n_blocks),
                         donate_argnums=(0, 1, 2),
                         out_shardings=self.shardings())
            self._write_fns[n_blocks] = fn
        self.pages, self.state, self.lens = fn(
            self.pages, self.state, self.lens, cache,
            jnp.asarray(slot, jnp.int32),
            jnp.asarray(self._block_tables[slot][:n_blocks], jnp.int32),
            jnp.asarray(prompt_len, jnp.int32))
        return self.slot_bytes(prompt_len)

    def _scatter_prefill_blocks(self, pages, state, lens, cache, slot,
                                bt_row, plen, *, n_blocks):
        leaves = jax.tree.leaves(cache)
        new_pages, new_state = list(pages), list(state)
        pi = si = 0
        cache_len = None
        for leaf, tag in zip(leaves, self._tags):
            if tag == _LEN and cache_len is None:
                # trust the model's own emitted length (e.g. VLM prefills
                # count their image prefix on top of the text prompt)
                cache_len = jnp.asarray(leaf, jnp.int32).reshape(-1)[0]
            if tag == _PAGED:
                A0, _, S, *rest = leaf.shape
                blocks = leaf[:, 0, :n_blocks * self.block_size].reshape(
                    A0, n_blocks, self.block_size, *rest)
                new_pages[pi] = paged_pool.write_pages(pages[pi], blocks,
                                                       bt_row)
                pi += 1
            elif tag == _STATE:
                new_state[si] = state[si].at[:, slot].set(
                    leaf[:, 0].astype(state[si].dtype))
                si += 1
        if cache_len is None:
            cache_len = plen
        return new_pages, new_state, lens.at[slot].set(cache_len)

    # ------------------------------------------------------------------
    # pure helpers for the fused decode step (jit-safe, no host state)
    # ------------------------------------------------------------------
    def dense_view(self, pages: Sequence[jnp.ndarray],
                   block_tables: jnp.ndarray) -> List[jnp.ndarray]:
        """Gather each page pool through the block table into a contiguous
        ``(layers, B, slot_tokens, ...)`` view (``B`` = the table's row
        count).  NOT the hot path anymore: the attention families' paged-
        NATIVE steps (``decode_step_paged`` / ``prefill_chunk_paged``)
        read K/V in place through the table, so this full materialization
        survives only as (a) the fallback for families/configs without a
        paged-native step (pure-SSM state caches, ring sliding-window
        layouts) and (b) the test/benchmark oracle the zero-gather path is
        verified bit-identical against.  A QuantPages pool gathers values
        and scales through the same table and dequantizes to the leaf's
        original dtype — the fallback sees exactly the float view the
        quantized kernels compute in-register."""
        return [paged_pool.gather(p, block_tables, dims[2], dims[1],
                                  dtype=dt)
                for p, dt, dims in zip(pages, self._paged_dtypes,
                                       self._pool_dims)]

    def assemble(self, dense: Sequence[jnp.ndarray],
                 state: Sequence[jnp.ndarray],
                 lens: jnp.ndarray) -> Cache:
        """Rebuild the family's cache pytree (per-slot lens everywhere)."""
        leaves, di, si = [], iter(dense), iter(state)
        for tag, dt in zip(self._tags, self._dtypes):
            if tag == _LEN:
                leaves.append(lens.astype(dt))
            elif tag == _PAGED:
                leaves.append(next(di))
            else:
                leaves.append(next(si))
        return jax.tree.unflatten(self._treedef, leaves)

    def disassemble(self, cache: Cache) -> Tuple[List[jnp.ndarray],
                                                 List[jnp.ndarray]]:
        # QuantPages pools ride the paged-native steps as single cache
        # leaves, so flatten with them intact (a bare jax.tree.leaves would
        # split them into values + scales and misalign the tag zip)
        leaves = jax.tree.flatten(
            cache, is_leaf=lambda x: isinstance(x, QuantPages))[0]
        dense, state = [], []
        for leaf, tag in zip(leaves, self._tags):
            if tag == _PAGED:
                dense.append(leaf)
            elif tag == _STATE:
                state.append(leaf)
        return dense, state

    def append_rows(self, pages: Sequence[jnp.ndarray],
                    dense_new: Sequence[jnp.ndarray], lens: jnp.ndarray,
                    live: jnp.ndarray, block_tables: jnp.ndarray, *,
                    n_tokens: int = 1,
                    valid_tokens: Optional[jnp.ndarray] = None
                    ) -> List[jnp.ndarray]:
        """``arena.append``: write each live slot's newly produced cache
        tokens back to its physical pages, in place.

        Generalizes from the fused decode step's single-token append
        (``n_tokens=1``: one row per slot at position ``lens``) to the
        chunked-prefill multi-token append: ``n_tokens`` consecutive rows
        per slot starting at ``lens``, of which only the first
        ``valid_tokens`` (per slot, defaults to all) are real — this is
        ``write_prefill``'s offset/partial mode, keyed off the block table
        so chunk starts need no block alignment.  Rows of dead slots and
        padding rows past ``valid_tokens`` route to the trash block, so
        the scatter stays branch-free and shape-stable.
        """
        cap = lens.shape[0]
        bs = self.block_size
        offs = jnp.arange(n_tokens)                       # (T,)
        pos = jnp.clip(lens[:, None] + offs[None], 0,
                       self.slot_tokens - 1)              # (cap, T)
        blk = jnp.take_along_axis(block_tables, pos // bs, axis=1)
        ok = live[:, None]
        if valid_tokens is not None:
            ok = ok & (offs[None] < valid_tokens[:, None])
        blk = jnp.where(ok, blk, self.trash_block).reshape(-1)
        off = jnp.where(ok, pos % bs, 0).reshape(-1)
        out = []
        for p, d in zip(pages, dense_new):
            A0, _, _, *rest = d.shape
            idx = pos.reshape(1, cap, n_tokens, *([1] * len(rest)))
            row = jnp.take_along_axis(d, idx, axis=2)     # (A0, cap, T, ...)
            # a QuantPages pool quantizes the fresh float rows on insert
            out.append(paged_pool.write_rows(
                p, row.reshape(A0, cap * n_tokens, *rest), blk, off))
        return out

    def merge_state(self, state: Sequence[jnp.ndarray],
                    state_new: Sequence[jnp.ndarray],
                    live: jnp.ndarray) -> List[jnp.ndarray]:
        """Commit updated per-slot state only for live slots (dead slots
        must not absorb the masked step's garbage)."""
        out = []
        for old, new in zip(state, state_new):
            mask = live.reshape(1, live.shape[0],
                                *([1] * (old.ndim - 2)))
            out.append(jnp.where(mask, new.astype(old.dtype), old))
        return out
