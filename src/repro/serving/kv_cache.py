"""Paged KV cache: fixed-size blocks + per-request block tables.

The serving analogue of the paper's programmable strided memory access
(SMA): instead of one dense (slots, S_max, H, D) buffer that pins worst-case
memory per slot, K/V live in a shared pool of `num_blocks` blocks of
`block_size` tokens each, and every request addresses its tokens through a
block table — a programmable stride pattern over the pool.  Slot memory is
decoupled from `max_seq`: idle slots hold zero blocks, and a slot refilled
with a new request reuses freed blocks without re-initializing the pool.

Layout (per attention layer):

  k_pool / v_pool : (num_blocks, H_kv, block_size, D)
  block_tables    : (slots, max_blocks_per_slot) int32, entries index blocks

Heads sit outside positions so that one pool block, all heads included, is
one contiguous tile-aligned slab: the flash-decode kernel fetches it as a
(1, H_kv, block_size, D) block whose last two dims are whole array dims,
which the TPU's (8, 128) tiling rule accepts for any H_kv and block size.

Block id 0 is a reserved *null* block: unallocated table entries point at
it, and writes from idle slots or masked positions land there.  It is never
handed out by the allocator, so garbage in it is never attended (the causal
length mask excludes every position a table does not really cover).

The device side is pure array math (`write_kv` / `gather_kv`), jit-safe and
scanned over layer groups; the host side (`BlockAllocator`, `BlockTables`)
makes allocation decisions between steps, exactly like the paper's RISC-V
core programs the streamer strides between GeMM calls.
"""

from __future__ import annotations

import math
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NULL_BLOCK = 0


class PagedKVCache(NamedTuple):
    """Block-pooled decode cache for one attention layer (or a stacked group).

    Mirrors ``attention.KVCache``'s (k, v) fields so the two cache kinds are
    interchangeable pytree leaves; ``isinstance`` distinguishes them where
    the addressing differs.

    int8 residency (``Engine(kv_precision="int8")``): the pools hold int8
    codes and ``k_scale``/``v_scale`` hold per-(block, position, kv-head)
    f32 dequant scales — per *position* rather than per block because decode
    appends one token at a time, and a shared per-block scale could not
    absorb a new outlier token without requantizing the block's committed
    bytes.  At D=64 the scale overhead is 4/256 of the f32 pool, so the
    pool shrinks ~3.8x (~4x more blocks per byte).  Float pools leave the
    scale fields None — both layouts are valid pytrees of one NamedTuple.
    """

    k: jax.Array  # (num_blocks, H_kv, block_size, D)
    v: jax.Array  # (num_blocks, H_kv, block_size, D)
    k_scale: Optional[jax.Array] = None  # (num_blocks, H_kv, block_size) f32
    v_scale: Optional[jax.Array] = None

    @property
    def num_blocks(self) -> int:
        return self.k.shape[-4]

    @property
    def block_size(self) -> int:
        return self.k.shape[-2]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None


def init_paged_kv(
    num_blocks: int, block_size: int, n_kv_heads: int, head_dim: int, dtype,
    *, kv_precision: str = "float",
) -> PagedKVCache:
    shape = (num_blocks, n_kv_heads, block_size, head_dim)
    if kv_precision == "int8":
        sshape = shape[:-1]
        return PagedKVCache(
            k=jnp.zeros(shape, jnp.int8), v=jnp.zeros(shape, jnp.int8),
            k_scale=jnp.ones(sshape, jnp.float32),
            v_scale=jnp.ones(sshape, jnp.float32))
    if kv_precision != "float":
        raise ValueError(
            f"unknown kv_precision {kv_precision!r}; known: float, int8")
    return PagedKVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def quantize_kv_tokens(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric int8 per (token, kv-head): (B, S, H, D) float ->
    ((B, S, H, D) int8 codes, (B, S, H) f32 scales).  Zero rows quantize to
    zero codes at scale 1 (no special-casing on dequant)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _block_positions(block_tables: jax.Array, start, S: int, block_size: int
                     ) -> Tuple[jax.Array, jax.Array]:
    """Pool (block, offset) write indices for S tokens starting at `start`
    per slot.

    block_tables: (B, max_blocks); start: scalar or (B,).  Returns two (B, S)
    arrays: the pool block id and the position inside it.  Positions beyond
    a slot's table capacity resolve to the null block — without the explicit
    mask, take_along_axis would clamp to the table's *last* entry and
    silently overwrite a live block.
    """
    start = jnp.asarray(start, jnp.int32)
    if start.ndim == 0:
        start = jnp.broadcast_to(start, (block_tables.shape[0],))
    pos = start[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]   # (B, S)
    pos = jnp.maximum(pos, 0)
    table_cap = block_tables.shape[1] * block_size
    blk = jnp.take_along_axis(
        block_tables, jnp.minimum(pos, table_cap - 1) // block_size, axis=1)
    blk = jnp.where(pos < table_cap, blk, NULL_BLOCK)
    return blk, pos % block_size


def write_kv(
    cache: PagedKVCache,
    block_tables: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
    start,
) -> PagedKVCache:
    """Scatter S new tokens per slot into the pool at positions start..start+S-1.

    k_new/v_new: (B, S, H, D).  Distinct live slots own distinct blocks, so
    real writes never collide; idle-slot writes collapse onto the null block.
    """
    H, D = cache.k.shape[1], cache.k.shape[3]
    blk, off = _block_positions(block_tables, start, k_new.shape[1],
                                cache.block_size)
    blk, off = blk.reshape(-1), off.reshape(-1)
    # Advanced indices split by a slice: the updates are (B * S, H[, D]).
    k_scale, v_scale = cache.k_scale, cache.v_scale
    if cache.quantized:
        # Quantize on write (per token x kv-head); the pool never sees floats.
        k_new, ks = quantize_kv_tokens(k_new)
        v_new, vs = quantize_kv_tokens(v_new)
        k_scale = k_scale.at[blk, :, off].set(ks.reshape(-1, H), mode="drop")
        v_scale = v_scale.at[blk, :, off].set(vs.reshape(-1, H), mode="drop")
    k_pool = cache.k.at[blk, :, off].set(
        k_new.astype(cache.k.dtype).reshape(-1, H, D), mode="drop")
    v_pool = cache.v.at[blk, :, off].set(
        v_new.astype(cache.v.dtype).reshape(-1, H, D), mode="drop")
    return PagedKVCache(k=k_pool, v=v_pool, k_scale=k_scale, v_scale=v_scale)


def copy_blocks(
    cache: PagedKVCache, src: jax.Array, dst: jax.Array
) -> PagedKVCache:
    """Device-side block copy: pool[dst[i]] = pool[src[i]] for K and V.

    The write half of copy-on-write divergence: when a request must mutate a
    block whose refcount is > 1 (see ``BlockTables.make_writable``), the host
    allocates a fresh destination block and this op clones the shared
    contents into it before any write lands.  `src`/`dst` are (n,) int32;
    jit-safe for a fixed n (the engine batches one divergence wave per step).
    """
    k = cache.k.at[dst].set(cache.k[src])
    v = cache.v.at[dst].set(cache.v[src])
    k_scale, v_scale = cache.k_scale, cache.v_scale
    if cache.quantized:
        k_scale = k_scale.at[dst].set(k_scale[src])
        v_scale = v_scale.at[dst].set(v_scale[src])
    return PagedKVCache(k=k, v=v, k_scale=k_scale, v_scale=v_scale)


def gather_kv(
    cache: PagedKVCache, block_tables: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Per-slot contiguous K/V views (B, max_blocks * block_size, H, D).

    A gather through the block table — the strided-access read pattern.
    Entries past a slot's true length read the null block; callers mask by
    position, so that garbage is never attended.  int8 pools are dequantized
    here (f32 out) — this path materializes the view anyway, so there is no
    byte saving to preserve; the flash-decode kernel dequantizes in-register
    instead (kernels/flash_decode.py).  Any (B, C) slice of table columns
    works too: the bounded decode fallback reads C columns per iteration.
    """
    _, H, bs, D = cache.k.shape
    B, C = block_tables.shape

    def view(pool, scale):
        x = jnp.take(pool, block_tables.reshape(-1), axis=0)  # (B*C, H, bs, D)
        if scale is not None:
            s = jnp.take(scale, block_tables.reshape(-1), axis=0)
            x = x.astype(jnp.float32) * s[..., None]
        return x.reshape(B, C, H, bs, D).transpose(0, 1, 3, 2, 4).reshape(
            B, C * bs, H, D)

    return view(cache.k, cache.k_scale), view(cache.v, cache.v_scale)


def pool_bytes(cache: PagedKVCache) -> int:
    """Resident bytes of this pool (codes + scales) — the capacity metric
    ``EngineMetrics.summary()`` reports per engine."""
    total = cache.k.size * cache.k.dtype.itemsize \
        + cache.v.size * cache.v.dtype.itemsize
    if cache.quantized:
        total += cache.k_scale.size * cache.k_scale.dtype.itemsize
        total += cache.v_scale.size * cache.v_scale.dtype.itemsize
    return total


def swap_out_blocks(caches, ids) -> List[dict]:
    """Serialize pool blocks ``ids`` to host memory, one dict of numpy
    arrays per cache kind — the device->host half of KV-swap preemption.

    The engine stacks per-layer pools with a leading group axis, so the
    block axis is located from the right: ``k``/``v`` are
    (..., num_blocks, H_kv, block_size, D) and scales (when int8-resident)
    are (..., num_blocks, H_kv, block_size).  Payload arrays keep the pool
    dtype (int8 codes swap as int8 — half the host traffic), and restoring
    into a *different* set of block ids later is fine: block contents are
    position-independent, only the table rows carry ordering.
    """
    ids = np.asarray(ids, np.int32)
    out: List[dict] = []
    for c in caches:
        if not isinstance(c, PagedKVCache):
            raise TypeError(
                "swap_out_blocks requires paged (attention) cache kinds; "
                "recurrent state is not block-addressable — gate preemption "
                "to attention-only stacks")
        entry = {"k": np.asarray(jnp.take(c.k, ids, axis=c.k.ndim - 4)),
                 "v": np.asarray(jnp.take(c.v, ids, axis=c.v.ndim - 4))}
        if c.quantized:
            sax = c.k_scale.ndim - 3
            entry["k_scale"] = np.asarray(jnp.take(c.k_scale, ids, axis=sax))
            entry["v_scale"] = np.asarray(jnp.take(c.v_scale, ids, axis=sax))
        out.append(entry)
    return out


def swap_in_blocks(caches, ids, saved: List[dict]):
    """Restore a ``swap_out_blocks`` payload into pool blocks ``ids``
    (freshly allocated — not necessarily the ids swapped out) and return
    the new cache tuple.  Runs un-jitted between ticks: scatter dispatch
    cost is the preemption price, measured by benchmarks/sched_bench.py."""
    ids = np.asarray(ids, np.int32)
    out = []
    for c, entry in zip(caches, saved):
        def put(arr, vals, axis):
            idx = (slice(None),) * axis + (ids,)
            return arr.at[idx].set(jnp.asarray(vals))

        k = put(c.k, entry["k"], c.k.ndim - 4)
        v = put(c.v, entry["v"], c.v.ndim - 4)
        ks, vs = c.k_scale, c.v_scale
        if c.quantized:
            sax = c.k_scale.ndim - 3
            ks = put(ks, entry["k_scale"], sax)
            vs = put(vs, entry["v_scale"], sax)
        out.append(PagedKVCache(k=k, v=v, k_scale=ks, v_scale=vs))
    return tuple(out)


# ---------------------------------------------------------------------------
# Host side: allocation decisions between steps
# ---------------------------------------------------------------------------


def blocks_for(tokens: int, block_size: int) -> int:
    return -(-tokens // block_size) if tokens > 0 else 0


class BlockAllocator:
    """Free-list allocator over pool blocks 1..num_blocks-1 (0 is the null
    block) with admission-time reservations and per-block refcounts.

    A request reserves its worst-case block count (ceil((prompt + max_new) /
    block_size)) when admitted, then draws blocks lazily as its length
    crosses block boundaries — so admission control guarantees a request
    never starves mid-decode, while resident usage tracks actual length.

    Refcounts make blocks *shareable*: ``ref()`` (or the ``fork_blocks``
    helper) lets a second owner — another request reusing a prefilled prompt
    prefix, or the prefix cache itself — hold the same physical block, and
    ``free()`` only returns a block to the free list when its last owner
    lets go.  Shared (refcount > 1) blocks are read-only by convention: the
    engine aligns prefix sharing to block boundaries so KV writes only ever
    land in refcount-1 blocks, and ``BlockTables.make_writable`` +
    ``copy_blocks`` provide explicit copy-on-write divergence for any caller
    that must write into a shared region.
    """

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 2:
            raise ValueError("need at least 2 blocks (block 0 is reserved)")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))  # pop() -> 1 first
        self._refs: Dict[int, int] = {}
        self._reserved = 0
        # Lifetime traffic counters (repro.obs): cumulative draws/returns and
        # the high-water mark — cheap int adds, always on.
        self.total_allocated = 0
        self.total_freed = 0
        self.peak_in_use = 0

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def reserved(self) -> int:
        """Blocks promised to admitted requests but not yet drawn."""
        return self._reserved

    @property
    def available(self) -> int:
        """Blocks neither allocated nor promised to an admitted request."""
        return len(self._free) - self._reserved

    @property
    def in_use(self) -> int:
        return (self.num_blocks - 1) - len(self._free)

    def occupancy(self) -> float:
        return self.in_use / max(1, self.num_blocks - 1)

    def can_reserve(self, n: int) -> bool:
        return n <= self.available

    def reserve(self, n: int) -> bool:
        if not self.can_reserve(n):
            return False
        self._reserved += n
        return True

    def alloc(self, n: int, *, reserved: bool = True) -> List[int]:
        if n > len(self._free):
            raise RuntimeError(
                f"block pool exhausted: want {n}, free {len(self._free)}")
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._refs[b] = 1
        if reserved:
            self._reserved = max(0, self._reserved - n)
        self.total_allocated += n
        if self.in_use > self.peak_in_use:
            self.peak_in_use = self.in_use
        return out

    def ref(self, ids: List[int]) -> None:
        """Add one owner to each (already-allocated) block."""
        for b in ids:
            if b not in self._refs:
                raise ValueError(f"block {b} is not allocated; cannot share it")
            self._refs[b] += 1

    def refcount(self, b: int) -> int:
        return self._refs.get(b, 0)

    def free(self, ids: List[int], *, unreserve: int = 0,
             rereserve: bool = False) -> int:
        """Drop one owner per block; a block returns to the free list only
        when its last owner frees it (shared blocks just lose a ref).

        ``rereserve`` puts every block that actually reached the free list
        back under the caller's admission reservation — the KV-rewind case:
        a request returning blocks drawn for rejected speculative positions
        must still be able to redraw them later without re-admission, or the
        allocator's no-mid-decode-starvation guarantee breaks.  Shared
        blocks (refcount > 1) only lose a ref and are NOT re-reserved — the
        free list did not grow, so a reservation against it would be a lie.
        Returns the number of blocks that reached the free list."""
        returned = 0
        for b in ids:
            if b == NULL_BLOCK:
                raise ValueError("cannot free the null block")
            rc = self._refs.get(b, 0)
            if rc <= 0:
                raise ValueError(f"double free of block {b}")
            if rc == 1:
                del self._refs[b]
                self._free.append(b)
                returned += 1
            else:
                self._refs[b] = rc - 1
        self._reserved = max(0, self._reserved - unreserve)
        if rereserve:
            self._reserved += returned
        self.total_freed += returned
        return returned

    def check(self) -> None:
        """Allocator invariant: free list and refcounted blocks partition
        the pool exactly, every refcount is positive, and the null block is
        owned by neither.  Raises AssertionError on any leak/double-free."""
        free = set(self._free)
        live = set(self._refs)
        assert len(free) == len(self._free), "duplicate ids on the free list"
        assert not (free & live), f"blocks both free and live: {free & live}"
        assert NULL_BLOCK not in free and NULL_BLOCK not in live, \
            "the null block escaped into the allocator"
        every = set(range(1, self.num_blocks))
        assert free | live == every, \
            f"leaked blocks: {sorted(every - free - live)}"
        assert all(rc > 0 for rc in self._refs.values()), "non-positive refcount"
        assert 0 <= self._reserved <= len(self._free), \
            f"reservations ({self._reserved}) exceed the free list ({len(self._free)})"

    def stats(self) -> dict:
        """Counter snapshot for metrics export / trace annotation."""
        return {
            "in_use": self.in_use,
            "reserved": self._reserved,
            "free": len(self._free),
            "total_allocated": self.total_allocated,
            "total_freed": self.total_freed,
            "peak_in_use": self.peak_in_use,
        }


def fork_blocks(alloc: BlockAllocator, ids: List[int]) -> List[int]:
    """Copy-on-write fork: share `ids` with a new owner (refcount + 1 each)
    and return the same physical ids.  No KV bytes move — both owners read
    the same pool blocks; a write requires divergence first (see
    ``BlockTables.make_writable`` / ``copy_blocks``).  The engine only forks
    *full* blocks at block-aligned prefix boundaries, so its writes — which
    always start at the first un-shared position — never touch a forked
    block and the copy half of CoW stays off the hot path."""
    alloc.ref(ids)
    return list(ids)


class BlockTables:
    """Host mirror of the device block tables: (slots, max_blocks) int32.

    Tracks per-slot allocated block lists and materializes the device array
    on demand.  The engine pushes `.array()` into the decode state whenever
    a table row changed (admission, growth, release).
    """

    def __init__(self, slots: int, max_blocks: int):
        self.slots = slots
        self.max_blocks = max_blocks
        self.table = np.zeros((slots, max_blocks), np.int32)
        self.blocks: List[List[int]] = [[] for _ in range(slots)]
        self.dirty = True

    def covered_tokens(self, slot: int, block_size: int) -> int:
        return len(self.blocks[slot]) * block_size

    def ensure(self, slot: int, length: int, alloc: BlockAllocator) -> bool:
        """Grow slot's table to cover `length` tokens; returns True if changed."""
        need = blocks_for(length, alloc.block_size) - len(self.blocks[slot])
        if need <= 0:
            return False
        if len(self.blocks[slot]) + need > self.max_blocks:
            raise RuntimeError(
                f"slot {slot}: {length} tokens exceed max_blocks {self.max_blocks}")
        for b in alloc.alloc(need):
            self.table[slot, len(self.blocks[slot])] = b
            self.blocks[slot].append(b)
        self.dirty = True
        return True

    def seed(self, slot: int, ids: List[int]) -> None:
        """Install already-owned blocks (a forked prefix) at the head of an
        *empty* slot row.  The caller has taken its refs (fork_blocks);
        release() later drops them like any other row entry."""
        if self.blocks[slot]:
            raise RuntimeError(
                f"slot {slot} is not empty; seed only a fresh slot")
        if len(ids) > self.max_blocks:
            raise RuntimeError(
                f"seed of {len(ids)} blocks exceeds max_blocks {self.max_blocks}")
        for i, b in enumerate(ids):
            self.table[slot, i] = b
        self.blocks[slot] = list(ids)
        self.dirty = True

    def make_writable(
        self, slot: int, block_idx: int, alloc: BlockAllocator
    ) -> Optional[Tuple[int, int]]:
        """Copy-on-write divergence for one table entry: if the block at
        `block_idx` is shared (refcount > 1), allocate a private replacement,
        swap it into the row, drop this slot's ref on the original, and
        return ``(src, dst)`` for the caller to clone on device via
        ``copy_blocks``.  Returns None when the block is already exclusive.
        """
        b = self.blocks[slot][block_idx]
        if alloc.refcount(b) <= 1:
            return None
        [fresh] = alloc.alloc(1, reserved=False)
        alloc.free([b])                      # drop this slot's share
        self.blocks[slot][block_idx] = fresh
        self.table[slot, block_idx] = fresh
        self.dirty = True
        return b, fresh

    def rewind(
        self, slot: int, length: int, alloc: BlockAllocator, *,
        rereserve: bool = True,
    ) -> Tuple[int, Optional[Tuple[int, int]]]:
        """KV rewind: shrink slot's table to cover exactly `length` tokens,
        returning blocks past the boundary to the pool.

        The rollback half of speculative decoding: blocks drawn to hold
        drafted-token KV are handed back when the draft is (partially)
        rejected, and — with ``rereserve`` (default) — return to the
        request's admission reservation so later growth cannot starve.
        Freed blocks are not zeroed: the causal length mask never exposes a
        position the table does not cover, and every block is fully
        re-written by its next owner before its positions become visible
        (the same invariant slot release relies on).

        Composes with CoW sharing: when the new tail block is *partial*
        (future writes will land inside it) and shared (refcount > 1 — e.g.
        a forked prefix block), it is diverged via ``make_writable`` so the
        rewound slot never mutates bytes another owner is reading —
        copy-then-rewind, never rewind-in-place.  Returns
        ``(blocks_freed, copy_pair)`` where ``copy_pair`` is the (src, dst)
        to clone on device via ``copy_blocks`` (None when no divergence was
        needed).  A block-aligned `length` needs no divergence: the next
        write starts a fresh block.
        """
        keep = blocks_for(length, alloc.block_size)
        ids = self.blocks[slot]
        if keep > len(ids):
            raise ValueError(
                f"slot {slot}: cannot rewind to {length} tokens "
                f"({keep} blocks) — only {len(ids)} blocks held")
        dropped = ids[keep:]
        if dropped:
            alloc.free(dropped, rereserve=rereserve)
            del ids[keep:]
            self.table[slot, keep:] = NULL_BLOCK
            self.dirty = True
        pair = None
        if keep and length % alloc.block_size:
            pair = self.make_writable(slot, keep - 1, alloc)
        return len(dropped), pair

    def release(self, slot: int, alloc: BlockAllocator, *, unreserve: int = 0) -> int:
        """Free all of slot's blocks back to the pool; returns count freed."""
        ids = self.blocks[slot]
        n = len(ids)
        alloc.free(ids, unreserve=unreserve)
        self.blocks[slot] = []
        self.table[slot, :] = NULL_BLOCK
        self.dirty = True
        return n

    def array(self) -> jax.Array:
        self.dirty = False
        return jnp.asarray(self.table)


def default_pool_blocks(
    slots: int, max_seq: int, block_size: int, *, headroom: float = 1.0
) -> int:
    """Pool sizing: null block + headroom * worst-case concurrent demand."""
    per_slot = blocks_for(max_seq, block_size)
    return 1 + max(1, math.ceil(headroom * slots * per_slot))
