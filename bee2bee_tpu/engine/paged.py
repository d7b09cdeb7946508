"""Paged KV cache: block pool, free-list allocator, block-level prefix sharing.

The rectangular shared cache ``[L, bsz, max_seq, Hkv, hd]`` makes every
row — idle or short — stream its full ``max_seq`` slice through HBM each
decode step (the scheduler measured 4x decode cost at bsz=8 with one
active row). This module replaces the row-owns-capacity model with the
vLLM/"Ragged Paged Attention" (PAPERS.md, arxiv 2604.15464) pool model:

- **One pool** ``[L, num_blocks, block_size, Hkv, hd]`` holds every
  row's K/V. Block 0 is the reserved null block (padding target; never
  allocated).
- **Per-row block tables** map logical position ``p`` to pool slot
  ``(table[p // block_size], p % block_size)``. The map is
  order-preserving, so masks and position biases apply unchanged over
  the gathered view (models/core.forward's ``block_tables`` path).
- **Host-side free-list allocator with refcounts**: blocks are allocated
  lazily as decode crosses block boundaries and freed at retirement.
  Refcounts make blocks shareable — the block-level prefix cache pins a
  prompt's blocks and a matching request references the full ones
  copy-on-write (only the final partial block is ever copied, because
  the borrower will write into it from the match point).

All allocator state is host-side python/numpy owned by the scheduler
thread (single-owner rule); the only device arrays are the pool itself
and the jitted single-block copy for CoW.

Why sharing whole blocks is sound: a cache entry claims validity for
positions ``[0, n)`` of its prompt. Slots ``>= n`` in the entry's final
partial block may later receive the donor's decode tokens — but a
borrower matching ``m <= n-1`` tokens copies that partial block and only
depends on slots ``< m`` (prompt K/V, immutable once written); slots
``>= m`` are overwritten by the borrower's own prefill or causally
masked. Chunked-prefill re-anchoring can re-feed tokens below the match
point; the prefill's write floor (core.forward ``paged_write_floor``)
drops those scatter writes so shared donor blocks are strictly read-only
— recomputed K/V under a different chunk geometry is not guaranteed
bit-identical, and a rewrite would perturb co-borrowers mid-decode.
"""

from __future__ import annotations

from collections.abc import Iterable

import numpy as np

from ..metrics import get_registry

# block-pool occupancy for /metrics (one engine per serving node, so
# unlabeled gauges suffice; the last-constructed allocator owns them)
_G_BLOCKS_USED = get_registry().gauge(
    "engine.paged_blocks_in_use", "paged KV pool blocks currently referenced"
)
_G_BLOCKS_FREE = get_registry().gauge(
    "engine.paged_blocks_free", "paged KV pool blocks on the free list"
)
_G_BLOCKS_TOTAL = get_registry().gauge(
    "engine.paged_blocks_total", "paged KV pool size (incl. the null block)"
)


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def pow2_at_least(n: int) -> int:
    """Smallest power of two >= max(n, 1) — buckets the block-table width
    so the decode program compiles O(log) shapes, not one per length."""
    return 1 << max(0, (max(n, 1) - 1).bit_length())


def best_prefix_key(keys, ids) -> tuple[tuple | None, int]:
    """THE prefix-cache match scan (PagedPrefixCache, and any other
    longest-prefix lookup): the key with the longest usable prefix of ``ids``
    (usable length = min(len(key), len(ids) - 1) — the final prompt
    token always prefills so admission gets its first-sample logits; an
    entry only matches when its WHOLE usable prefix equals the prompt's).

    Element-wise with early exits: the first mismatching token abandons
    the entry, and entries that cannot beat the current best are skipped
    outright — the old form built a tuple(ids[:m]) and sliced key[:m]
    per entry per admission, O(entries * prompt_len) churn that long
    prompts paid even on guaranteed misses. Ties keep the first
    (oldest-inserted) entry, matching the old `m > best_m` scan order.
    """
    cap = len(ids) - 1
    best_key, best_m = None, 0
    for key in keys:
        m = min(len(key), cap)
        if m <= best_m:
            continue
        for i in range(m):
            if key[i] != ids[i]:
                break
        else:
            best_key, best_m = key, m
    return best_key, best_m


class RecurrentStateUnsupported(ValueError):
    """An engine feature that assumes "a row's cache is its K/V pages" was
    asked for with a model whose rows also own recurrent state (falcon-h1's
    Mamba-2 mixer). ``feature`` names it. Raised when the engine is built:
    rollback is not free for a recurrence (spec verify), pinned blocks do
    not hold the state at a prefix's end (prefix cache), and the state is
    not sharded over a mesh yet — none of these may be silently wrong."""

    def __init__(self, feature: str, model: str, why: str):
        self.feature = feature
        super().__init__(
            f"{feature} is not supported for {model!r}: its rows carry "
            f"recurrent state beside their K/V pages, and {why}"
        )


def prefill_chunk_positions(n: int, start: int, bucket: int, S: int) -> list[int]:
    """THE chunk walk of admission prefill: start positions of each
    [pos, pos+bucket) window covering prompt tokens [start, n), with the
    capacity re-anchor (a window that would write past S is re-anchored
    to end exactly at S — re-feeding earlier tokens rather than letting a
    clamped/dropped write corrupt K/V rows). One implementation, two
    consumers — the rectangular walk and the paged walk (whose write
    ceil drops every scatter at/past n, so the paged block-sufficiency
    precheck is simply ceil(n / block_size) no matter how the windows
    land). Terminates: each window consumes min(bucket, n - pos) >= 1 tokens
    (after a re-anchor, n <= S <= pos + bucket, so the window reaches n).
    """
    out, pos = [], start
    while True:
        if pos + bucket > S:
            pos = max(0, S - bucket)
        out.append(pos)
        pos += min(bucket, n - pos)
        if pos >= n:
            return out


class BlockAllocator:
    """Free-list + refcount allocator over pool blocks 1..num_blocks-1
    (block 0 is the reserved null block and is never handed out)."""

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError(f"paged pool needs >= 2 blocks, got {num_blocks}")
        self.num_blocks = num_blocks
        # pop() hands out low ids first — keeps early pool pages hot
        self._free: list[int] = list(range(num_blocks - 1, 0, -1))
        self._refs = np.zeros((num_blocks,), np.int32)
        self.hwm = 0  # high-water mark of blocks in use (observability)
        _G_BLOCKS_TOTAL.set(num_blocks)
        self._set_gauges()

    def _set_gauges(self):
        _G_BLOCKS_USED.set(self.used_count)
        _G_BLOCKS_FREE.set(self.free_count)

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        return self.num_blocks - 1 - len(self._free)

    def alloc(self, n: int) -> list[int] | None:
        """n fresh blocks (refcount 1), or None when the pool can't cover
        the whole request — partial allocations would leak on the caller's
        retry path."""
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._refs[b] = 1
        self.hwm = max(self.hwm, self.used_count)
        self._set_gauges()
        return out

    def ref(self, blocks: Iterable[int]) -> None:
        for b in blocks:
            assert self._refs[b] > 0, f"ref of free block {b}"
            self._refs[b] += 1

    def deref(self, blocks: Iterable[int]) -> int:
        """Drop one reference per block; blocks reaching zero return to
        the free list. Returns how many were freed."""
        freed = 0
        for b in blocks:
            assert self._refs[b] > 0, f"deref of free block {b}"
            self._refs[b] -= 1
            if self._refs[b] == 0:
                self._free.append(b)
                freed += 1
        self._set_gauges()
        return freed

    def refcount(self, block: int) -> int:
        return int(self._refs[block])


class PagedPrefixCache:
    """Block-level prompt prefix cache: key = token-id tuple, value = the
    pool block ids covering positions [0, len(key)). Entries PIN their
    blocks via allocator refcounts — a put costs zero HBM (the deleted
    rectangular cache snapshotted a full batch-1 row per entry); the cost
    is pool blocks staying out of the free list until eviction.

    Match contract: longest usable prefix, capped at len(prompt) - 1 so
    the final token always prefills for its first-sample logits. The
    scheduler thread owns all access."""

    def __init__(self, capacity: int, allocator: BlockAllocator):
        self.capacity = capacity
        self.allocator = allocator
        # key -> tuple of block ids (insertion-ordered = LRU order)
        self._entries: dict[tuple, tuple[int, ...]] = {}

    def __len__(self) -> int:
        return len(self._entries)

    def match(self, ids: list[int]):
        """-> (m, blocks | None): longest usable cached prefix and the
        entry's FULL block list (the caller slices per its match length)."""
        best_key, best_m = best_prefix_key(self._entries, ids)
        if best_key is None:
            return 0, None
        blocks = self._entries.pop(best_key)  # LRU touch
        self._entries[best_key] = blocks
        return best_m, blocks

    def has(self, ids: list[int]) -> bool:
        return tuple(ids) in self._entries

    def put(self, ids: list[int], blocks: Iterable[int]) -> None:
        key = tuple(ids)
        if key in self._entries:
            return
        blocks = tuple(blocks)
        self.allocator.ref(blocks)  # pin
        self._entries[key] = blocks
        while len(self._entries) > self.capacity:
            self._evict_one()

    def _evict_one(self) -> bool:
        if not self._entries:
            return False
        key = next(iter(self._entries))  # LRU = oldest insertion
        self.allocator.deref(self._entries.pop(key))
        return True

    def evict_for_pressure(self, blocks_needed: int) -> bool:
        """Free pinned blocks until the allocator can cover
        `blocks_needed`. Returns True when it can. Eviction only drops the
        CACHE's pins — blocks also referenced by an active row (or by a
        caller that pre-ref'd them for a CoW copy) survive."""
        while self.allocator.free_count < blocks_needed:
            if not self._evict_one():
                return False
        return True

    def clear(self) -> None:
        while self._evict_one():
            pass
