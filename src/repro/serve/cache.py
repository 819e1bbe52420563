"""KV-cache management for continuous batching: slot stripes and paged blocks.

Two cache layouts share this module:

* **slots** (PR 2): the pooled decode cache is the ordinary
  ``transformer.init_cache`` pytree with ``batch == num_slots`` — every
  request reserves a worst-case ``max_len`` stripe for its whole lifetime;
* **paged** (PR 3): K/V live in a global pool of fixed-size blocks
  (``transformer.init_paged_cache`` leaves ``(L, num_blocks, block_size,
  Hkv * hd)``, head-major in the last dimension) handed out by
  ``BlockPool``; each request holds only the blocks its *actual* context
  occupies, recorded in a fixed-width
  per-request block table (``(num_slots, max_len // block_size)`` int32,
  unallocated entries == ``num_blocks``).  Mixed context lengths then share
  HBM instead of each reserving the worst case.

Slot-layout cache ops (pure tree ops, jit-friendly):

* ``scatter_rows``  — batched admission (the scheduler's production path):
  write A request rows into their (distinct) slots in one scatter, with
  invalid rows degenerating to exact no-ops so a fixed-width program admits
  any number <= A of requests;
* ``evict_slot``    — zero slot ``s`` (optional hygiene: stale rows above a
  slot's ``cur_len`` are already invisible, because ``decode_attention``
  masks keys past ``kv_len`` and overwrites position ``cur_len`` before
  attending over it);
* ``insert_slot`` / ``slot_view`` / ``insert_prefill_kv`` — the single-slot
  primitives (scatter_rows restricted to A=1). The scheduler admits through
  scatter_rows only; these exist for per-slot manipulation by tooling and
  the ROADMAP sharded-slots follow-on (where a slot migrates between hosts
  one at a time), and are pinned by tests/test_scheduler.py.

All three take the slot index as a *traced* scalar, so one compiled program
serves every slot — no shape depends on which slot is being filled.  The
paged layout's device ops are ``scatter_prompt_blocks`` here plus
``models.attention.paged_decode_attention``; block ids are likewise traced
data, so one compiled program serves any block-table contents.

``merge_admit_carry`` is the async host loop's primitive: it scatters an
admission batch's first sampled tokens and PRNG keys into the
device-resident decode carry, letting the scheduler compose admit-program
futures into the next chunk's inputs without a host sync (see
``scheduler.ServeSession`` and docs/serving.md).

Host-side bookkeeping lives in ``SlotPool`` (decode-row free list),
``BlockPool`` (KV-block free list — both min-heaps with O(1) membership)
and ``PromptBuckets`` (fixed prompt-length buckets so prefill compiles once
per bucket, never per request length).

**Partial-table invariants (chunked prefill, PR 10).**  A block table is
valid at ANY prefix of its final contents: entries ``[0, ceil(pos / bs))``
map real blocks holding the first ``pos`` written positions, everything
after is the ``num_blocks`` sentinel.  Three properties make a partially
built table safe to serve and to keep extending, all pinned by
tests/test_chunked_prefill.py:

* **sentinel writes drop** — every K/V scatter routes through
  ``where(blk < W, phys, num_blocks)``-style clamping, so a write whose
  position falls past the allocated prefix lands in the pool's dump row
  ``num_blocks`` and is never read;
* **reads never cross ``kv_len``** — attention masks keys at the caller's
  ``cur_len``/``kv_len``, so sentinel-tailed entries (and any garbage
  between a chunk's end and the next write) are invisible: a table with a
  sentinel tail serves reads identically to a truncated context;
* **scatter-before-gather** — a chunk writes its own K/V before attending,
  so position ``pos`` is readable the moment ``kv_len`` reaches it, and
  the next chunk (or decode step) may immediately read through the same
  table row it just extended.

The scheduler grows a mid-prefill row's table one chunk at a time
(``_ensure_blocks`` up to the chunk's last write) and scrubs that row to
all-sentinel in every decode dispatch until the prefill completes — decode
ticks write unconditionally at ``cur_len``, and the scrub is what keeps
those writes off the row's already-written prompt K/V.
"""
from __future__ import annotations

import bisect
import heapq
from collections import OrderedDict
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "insert_slot",
    "insert_prefill_kv",
    "scatter_rows",
    "scatter_prompt_blocks",
    "copy_block",
    "pool_bytes_per_device",
    "merge_admit_carry",
    "merge_spec_len",
    "evict_slot",
    "slot_view",
    "PromptBuckets",
    "SlotPool",
    "BlockPool",
    "PrefixCache",
]


# ---------------------------------------------------------------------------
# Pure cache-tree ops (jit-friendly, slot index traced)
# ---------------------------------------------------------------------------


def insert_slot(cache: Any, slot_cache: Any, slot: jax.Array) -> Any:
    """Write a batch-1 cache pytree (leaves (L, 1, ...)) into slot ``slot``
    of the pooled cache (leaves (L, B, ...))."""
    return jax.tree.map(
        lambda full, one: jax.lax.dynamic_update_slice_in_dim(
            full, one.astype(full.dtype), slot, axis=1
        ),
        cache,
        slot_cache,
    )


def slot_view(cache: Any, slot: jax.Array) -> Any:
    """Batch-1 view of one slot (leaves (L, 1, ...))."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=1), cache
    )


def evict_slot(cache: Any, slot: jax.Array) -> Any:
    """Zero one slot's rows across every leaf. Correctness never requires
    this (see module docstring); it exists for hygiene/debugging and is
    exercised by the scheduler's ``zero_on_evict`` option."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_update_slice_in_dim(
            a, jnp.zeros((a.shape[0], 1) + a.shape[2:], a.dtype), slot, axis=1
        ),
        cache,
    )


@jax.named_scope("kv_write")
def scatter_rows(
    full: jax.Array,
    part: jax.Array,
    slots: jax.Array,
    valid: jax.Array,
    s_cap: Optional[int] = None,
) -> jax.Array:
    """Write ``part`` (lead, A, [S,] ...) into batch rows ``slots`` of
    ``full`` (lead, B, [Smax,] ...) — the batched-admission primitive.

    ``slots`` must hold distinct row ids (the scheduler passes a permutation
    of range(B)); rows with ``valid == False`` rewrite the values they
    gathered — an exact no-op — which is how ONE fixed-width compiled
    program admits any number <= A of requests.  ``s_cap`` restricts the
    write to sequence positions [0, s_cap) (fused-prefill K/V, where
    ``part`` covers only the prompt bucket)."""
    vb = valid.reshape((1, -1) + (1,) * (full.ndim - 2))
    if s_cap is None:
        cur = full[:, slots]
        part = jnp.where(vb, part.astype(full.dtype), cur)
        return full.at[:, slots].set(part)
    cur = full[:, slots, :s_cap]
    part = jnp.where(vb, part.astype(full.dtype), cur)
    return full.at[:, slots, :s_cap].set(part)


def merge_admit_carry(
    last_token: jax.Array,
    slot_keys: jax.Array,
    slots: jax.Array,
    tok0s: jax.Array,
    keys: jax.Array,
    valid: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Scatter an admission batch's first sampled tokens ``tok0s`` (A,) and
    per-request PRNG keys ``keys`` (A, 2) into the device-resident decode
    carry ``last_token`` (N,) / ``slot_keys`` (N, 2) at rows ``slots``.

    The async serve loop keeps the decode carry on device between chunks;
    this merge lets freshly admitted rows join the next chunk without the
    host ever fetching the admit program's outputs.  ``slots`` must hold
    distinct ids (the scheduler passes acquired slots padded with distinct
    unused ids); rows with ``valid == False`` rewrite the values they
    gathered — an exact no-op — so one fixed-width compiled program merges
    any number <= A of admissions."""
    lt = last_token.at[slots].set(
        jnp.where(valid, tok0s.astype(last_token.dtype), last_token[slots])
    )
    sk = slot_keys.at[slots].set(
        jnp.where(valid[:, None], keys.astype(slot_keys.dtype), slot_keys[slots])
    )
    return lt, sk


def merge_spec_len(
    cur_len: jax.Array,
    slots: jax.Array,
    lens: jax.Array,
    valid: jax.Array,
) -> jax.Array:
    """Scatter an admission batch's prompt lengths ``lens`` (A,) into the
    device-resident ``cur_len`` carry (N,) at rows ``slots``.

    Speculative decoding advances rows by data-dependent accepted counts,
    so the async serve loop keeps ``cur_len`` on device alongside the
    decode carry.  Same no-op discipline as :func:`merge_admit_carry`:
    rows with ``valid == False`` rewrite the values they gathered."""
    return cur_len.at[slots].set(
        jnp.where(valid, lens.astype(cur_len.dtype), cur_len[slots])
    )


def insert_prefill_kv(cache: Any, kvs: Tuple[jax.Array, jax.Array], slot: jax.Array) -> Any:
    """Write fused-prefill K/V stacks (each (L, 1, S_bucket, Hkv, hd), from
    ``forward(..., return_kv=True)`` on a batch-1 prompt) into positions
    [0, S_bucket) of slot ``slot``.  Attention-family caches only."""
    k, v = kvs
    zeros = (0,) * (cache["k"].ndim - 2)
    start = (0, slot) + zeros

    def write(full, part):
        return jax.lax.dynamic_update_slice(full, part.astype(full.dtype), start)

    return dict(cache, k=write(cache["k"], k), v=write(cache["v"], v))


# ---------------------------------------------------------------------------
# Paged-layout cache ops
# ---------------------------------------------------------------------------


@jax.named_scope("kv_write")
def scatter_prompt_blocks(
    cache: Any,
    kvs: Tuple[jax.Array, jax.Array],
    block_ids: jax.Array,
    block_size: int,
) -> Any:
    """Write fused-prefill K/V stacks (each (L, A, S_bucket, Hkv, hd)) into
    the paged cache (leaves (L, num_blocks, block_size, Hkv * hd)).

    ``block_ids`` is (A, nb) int32 with ``nb == ceil(S_bucket / block_size)``:
    row ``i``'s ``j``-th entry is the physical block receiving positions
    ``[j*block_size, (j+1)*block_size)`` of prompt ``i``.  Entries ``>=
    num_blocks`` (the host's sentinel for unallocated / padding rows) are
    DROPPED by jit scatter semantics — that is how one fixed-width compiled
    program admits any number of requests holding any number of blocks, with
    no ``valid`` mask needed.  Bucket positions past the last allocated block
    hold only right-pad garbage, so dropping them is exact."""
    k, v = kvs
    A, nb = block_ids.shape
    L = k.shape[0]
    pad = nb * block_size - k.shape[2]
    if pad:
        widths = [(0, 0), (0, 0), (0, pad), (0, 0), (0, 0)]
        k = jnp.pad(k, widths)
        v = jnp.pad(v, widths)
    ids = block_ids.reshape(-1)

    def write(full, part):
        # heads merge into the pool's head-major last dim: a reshape of the
        # prompt's K/V, never of the pool
        part = part.reshape(L, A * nb, block_size, full.shape[3])
        return full.at[:, ids].set(part.astype(full.dtype))

    return dict(cache, k=write(cache["k"], k), v=write(cache["v"], v))


def pool_bytes_per_device(cache: Any) -> int:
    """Bytes of KV pool resident on EACH device.

    Under tensor-parallel serving the pool shards its head-major last dim
    (whole KV heads), so every device holds ``1/tp`` of each leaf;
    ``Sharding.shard_shape`` gives the per-device shard shape for sharded
    and single-device placements alike, which makes this the bench/stats
    primitive for the ``1/tp`` KV-bytes claim (see
    benchmarks/serve_tp.py)."""
    total = 0
    for leaf in jax.tree.leaves(cache):
        shard = leaf.sharding.shard_shape(leaf.shape)
        total += int(np.prod(shard)) * leaf.dtype.itemsize
    return total


def copy_block(cache: Any, src: jax.Array, dst: jax.Array) -> Any:
    """Copy one physical block's K/V rows from block ``src`` to block ``dst``
    — the copy-on-write fork primitive.  Both indices are *traced* scalars,
    so ONE compiled program forks any (src, dst) pair; ``dst`` is always a
    freshly acquired (valid) block id, so the clamping semantics of
    ``dynamic_update_slice`` never engage.  Every layer's block moves at
    once, whole rows of ``Hkv * hd`` lanes."""

    def cp(full):
        row = jax.lax.dynamic_slice_in_dim(full, src, 1, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(full, row, dst, axis=1)

    return dict(cache, k=cp(cache["k"]), v=cp(cache["v"]))


# ---------------------------------------------------------------------------
# Host-side bookkeeping
# ---------------------------------------------------------------------------


class PromptBuckets:
    """Fixed prompt-length buckets: prefill compiles once per bucket size,
    so no request length ever triggers a new compile."""

    def __init__(self, sizes: Sequence[int]):
        if not sizes:
            raise ValueError("need at least one prompt bucket")
        self.sizes: Tuple[int, ...] = tuple(sorted(set(int(s) for s in sizes)))
        if self.sizes[0] < 1:
            raise ValueError(f"bucket sizes must be >= 1, got {self.sizes}")

    @property
    def max_size(self) -> int:
        return self.sizes[-1]

    def bucket(self, prompt_len: int) -> int:
        """Smallest bucket >= prompt_len (binary search over the sorted
        bucket list)."""
        i = bisect.bisect_left(self.sizes, prompt_len)
        if i == len(self.sizes):
            raise ValueError(
                f"prompt_len={prompt_len} exceeds largest bucket {self.sizes[-1]}"
            )
        return self.sizes[i]

    def pad(self, prompt: np.ndarray, pad_id: int = 0) -> np.ndarray:
        """(S0,) -> (1, bucket) int32, zero-padded on the right.  Pad tokens
        sit at positions >= prompt_len: causality keeps them out of every
        real position's receptive field, and decode masks/overwrites their
        cache rows before ever attending over them."""
        n = int(prompt.shape[0])
        b = self.bucket(n)
        out = np.full((1, b), pad_id, np.int32)
        out[0, :n] = prompt
        return out


class _IdPool:
    """Min-heap free list over ``count`` integer ids with an O(1) membership
    set: ``acquire`` is O(log n) (was O(n) ``list.pop(0)``), ``release`` is
    O(log n) with O(1) double-free detection (was a linear scan + sort).
    Lowest free id first keeps allocation deterministic for tests/replay."""

    _what = "id"

    def __init__(self, count: int):
        if count < 1:
            raise ValueError(f"need at least one {self._what}, got {count}")
        self._count = count
        self._heap: List[int] = list(range(count))   # range is already a heap
        self._free_set = set(self._heap)

    @property
    def free_count(self) -> int:
        return len(self._heap)

    @property
    def busy_count(self) -> int:
        return self._count - len(self._heap)

    def acquire(self) -> Optional[int]:
        if not self._heap:
            return None
        i = heapq.heappop(self._heap)
        self._free_set.discard(i)
        return i

    def acquire_many(self, n: int) -> Optional[List[int]]:
        """All-or-nothing: ``n`` ids, or None (pool untouched) if fewer free."""
        if n > len(self._heap):
            return None
        return [self.acquire() for _ in range(n)]

    def release(self, i: int) -> None:
        if not 0 <= i < self._count:
            raise ValueError(f"{self._what} {i} out of range")
        if i in self._free_set:
            raise ValueError(f"{self._what} {i} double-released")
        heapq.heappush(self._heap, i)
        self._free_set.add(i)

    def _validate_release_many(self, ids: Sequence[int]) -> None:
        seen: set = set()
        for i in ids:
            if not 0 <= i < self._count:
                raise ValueError(f"{self._what} {i} out of range")
            if i in self._free_set or i in seen:
                raise ValueError(f"{self._what} {i} double-released")
            seen.add(i)

    def release_many(self, ids: Sequence[int]) -> None:
        """Atomic batch release: the whole batch is validated before any id
        mutates the pool, so a double-free/out-of-range id raises with
        ``free_count`` (and every invariant a caller might roll back against)
        untouched."""
        self._validate_release_many(ids)
        for i in ids:
            self.release(i)


class SlotPool(_IdPool):
    """Free list over ``num_slots`` decode slots (batch rows of the decode
    program)."""

    _what = "slot"

    def __init__(self, num_slots: int):
        super().__init__(num_slots)
        self.num_slots = num_slots


class BlockPool(_IdPool):
    """Refcounted free list over ``num_blocks`` physical KV blocks — the
    paged layout's global memory allocator.

    ``acquire`` hands out a block with refcount 1; ``share`` takes an extra
    reference on a live block (prefix sharing: several requests' block tables
    — plus the scheduler's prefix cache — point at the same physical block);
    ``release`` drops one reference and only returns the block to the free
    heap when the count hits zero.  ``free_count`` / ``busy_count`` keep
    counting *physical* blocks, so capacity math is unchanged.  The host-side
    block table maps a request's logical block slots to its physical blocks,
    and the sentinel id ``num_blocks`` marks unallocated table entries
    (device writes there are dropped)."""

    _what = "block"

    def __init__(self, num_blocks: int):
        super().__init__(num_blocks)
        self.num_blocks = num_blocks
        self._ref: List[int] = [0] * num_blocks

    @property
    def sentinel(self) -> int:
        return self.num_blocks

    def refcount(self, i: int) -> int:
        if not 0 <= i < self._count:
            raise ValueError(f"block {i} out of range")
        return self._ref[i]

    def acquire(self) -> Optional[int]:
        i = super().acquire()
        if i is not None:
            self._ref[i] = 1
        return i

    def share(self, i: int) -> int:
        """Take one extra reference on a live block; returns the new count."""
        if not 0 <= i < self._count:
            raise ValueError(f"block {i} out of range")
        if self._ref[i] < 1:
            raise ValueError(f"block {i} is free; cannot share")
        self._ref[i] += 1
        return self._ref[i]

    def release(self, i: int) -> None:
        if not 0 <= i < self._count:
            raise ValueError(f"block {i} out of range")
        if self._ref[i] < 1:
            raise ValueError(f"block {i} double-released")
        self._ref[i] -= 1
        if self._ref[i] == 0:
            heapq.heappush(self._heap, i)
            self._free_set.add(i)

    def _validate_release_many(self, ids: Sequence[int]) -> None:
        # Atomicity with refcounts: each id may appear up to refcount(i)
        # times in one batch, so validate per-id multiplicity, not set
        # membership.
        mult: dict = {}
        for i in ids:
            if not 0 <= i < self._count:
                raise ValueError(f"block {i} out of range")
            mult[i] = mult.get(i, 0) + 1
        for i, n in mult.items():
            if n > self._ref[i]:
                raise ValueError(
                    f"block {i}: batch releases {n} refs but only "
                    f"{self._ref[i]} held"
                )


class PrefixCache:
    """Host-side map from prompt-prefix content to the physical block that
    already holds its K/V, enabling copy-on-write prefix sharing.

    Keys are *structural rolling keys*: the key for block ``j`` of a prompt
    is ``intern((key of block j-1, tokens in block j))`` with ``ROOT`` (-1)
    as the zeroth parent — a collision-free stand-in for a rolling hash over
    the token ids (interning compares exact token tuples, so two prefixes
    share a key iff their token contents are identical).  Keys are content-
    bound, not block-bound, so chains self-heal across eviction: evicting a
    mid-chain entry only un-publishes that block; re-inserting the same
    content later re-uses the same key id.

    The cache itself never touches the :class:`BlockPool` — the scheduler
    takes one pool reference per published block (the cache's +1) and drops
    it on eviction, keeping all refcount traffic in one place.  Entries are
    kept in LRU order; ``lru_blocks`` exposes eviction candidates for
    reclaim-under-pressure."""

    ROOT = -1

    def __init__(self) -> None:
        self._intern: dict = {}           # (parent_key, tokens) -> key_id
        self._entries: "OrderedDict[int, int]" = OrderedDict()  # key -> block
        self._by_block: dict = {}         # block -> key_id

    def __len__(self) -> int:
        return len(self._entries)

    def key(self, parent: int, tokens: Sequence[int]) -> int:
        """Intern the rolling key for a block holding ``tokens`` whose
        predecessor block has key ``parent`` (``ROOT`` for block 0)."""
        k = (int(parent), tuple(int(t) for t in tokens))
        kid = self._intern.get(k)
        if kid is None:
            kid = len(self._intern)
            self._intern[k] = kid
        return kid

    def lookup(self, key_id: int) -> Optional[int]:
        """Physical block published under ``key_id`` (-> MRU), else None."""
        blk = self._entries.get(key_id)
        if blk is not None:
            self._entries.move_to_end(key_id)
        return blk

    def insert(self, key_id: int, block: int) -> None:
        """Publish ``block`` under ``key_id``.  The caller must hold a pool
        reference on ``block`` on the cache's behalf (and must have checked
        ``lookup`` first — double publication is a bug)."""
        if key_id in self._entries:
            raise ValueError(f"prefix key {key_id} already published")
        if block in self._by_block:
            raise ValueError(f"block {block} already published")
        self._entries[key_id] = block
        self._by_block[block] = key_id

    def holds_block(self, block: int) -> bool:
        return block in self._by_block

    def drop_block(self, block: int) -> bool:
        """Un-publish the entry pointing at ``block`` (before the block
        mutates, or to reclaim it).  Returns True if an entry was dropped;
        the caller then releases the cache's pool reference."""
        kid = self._by_block.pop(block, None)
        if kid is None:
            return False
        del self._entries[kid]
        return True

    def lru_blocks(self) -> List[int]:
        """Published blocks, least-recently-used first (snapshot)."""
        return list(self._entries.values())
