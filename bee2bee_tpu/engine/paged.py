"""Paged KV cache: block pool, free-interval allocator, block-level prefix sharing.

The rectangular shared cache ``[L, bsz, max_seq, Hkv, hd]`` makes every
row — idle or short — stream its full ``max_seq`` slice through HBM each
decode step (the scheduler measured 4x decode cost at bsz=8 with one
active row). This module replaces the row-owns-capacity model with the
vLLM/"Ragged Paged Attention" (PAPERS.md, arxiv 2604.15464) pool model:

- **One pool** ``[L, num_blocks, 2, Hkv, block_size, hd]`` holds every
  row's K beside its V (core.init_paged_pool). Block 0 is the reserved null block (padding target; never
  allocated).
- **Per-row block tables** map logical position ``p`` to pool slot
  ``(table[p // block_size], p % block_size)``. The map is
  order-preserving, so masks and position biases apply unchanged over
  the gathered view (models/core.forward's ``block_tables`` path).
- **Host-side free-interval allocator with refcounts**: blocks are allocated
  lazily as decode crosses block boundaries and freed at retirement.
  Refcounts make blocks shareable — the block-level prefix cache pins a
  prompt's blocks and a matching request references the full ones
  copy-on-write (only the final partial block is ever copied, because
  the borrower will write into it from the match point).

``RowCache`` is the one owner of all of it: the allocator, the tables, the
prefix pins, the pool arrays, the recurrent state of models that have one
and the small jitted programs over them. The scheduler decides WHEN a row
is covered, released, moved or exported; where its cache lives is decided
here. Everything is touched by the scheduler thread alone (single-owner
rule), and the host side is plain python/numpy.

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

import bisect
import collections
import functools
from collections.abc import Iterable

import jax
import jax.numpy as jnp
import numpy as np

from ..metrics import get_registry
from ..models import core, support
from ..tracing import prog_scope

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
_C_KV_PAGES_WRITTEN = get_registry().counter(
    "engine.kv_pages_written",
    "pool pages the page-write kernel copied in and out: batch rows x the "
    "pages a chunk can touch x the write calls (one a layer: a page holds K "
    "beside V; every attention call of the dispatch); 0 on the scatter paths",
)
_C_KV_TILES = get_registry().counter(
    "engine.kv_tiles",
    "grid steps of the ragged read: one layer's call x the dispatched "
    "window's attention calls (kind label: live = steps with a work item "
    "| stepped = all; live / stepped = the share of the grid that does work)",
)
_C_KV_PAGES_READ = get_registry().counter(
    "engine.kv_pages_read",
    "pool pages the ragged read's work items bring, counted as engine.kv_tiles "
    "is (one layer's call x the dispatch's attention calls; kind label: in_run "
    "= pages that arrive R at a time in ONE copy of a run of adjacent pool "
    "blocks | single = pages copied one by one; in_run / all = how much of the "
    "read the allocator's runs serve)",
)
_G_KV_TOKENS_HELD = get_registry().gauge(
    "engine.kv_tokens_held",
    "layer-tokens the live rows hold in the pool: their contexts x all "
    "layers (set once a dispatched decode window or verify step; a model "
    "with a sliding-window layer only)",
)
_G_KV_TOKENS_BEHIND_WINDOW = get_registry().gauge(
    "engine.kv_tokens_behind_window",
    "of those, the layer-tokens a sliding-window layer holds and no later "
    "read can see (positions at or below context - window, x the window "
    "layers): what per-layer-kind block tables with below-window release "
    "would free; 0 for a model whose window never binds",
)
_G_STATE_ROWS = get_registry().gauge(
    "engine.state_rows",
    "row slots of recurrent state allocated (= the batch bucket; recurrent "
    "models only)",
)
_G_STATE_BYTES = get_registry().gauge(
    "engine.state_bytes",
    "device bytes of the rows' recurrent state (ssm + conv; recurrent "
    "models only)",
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


class PoolExhausted(RuntimeError):
    """Paged block pool has no free blocks (after reclaiming prefix pins).
    Admission backpressure, not a crash — callers requeue or fail the one
    request, never the whole scheduler."""


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
    """Free-interval + refcount allocator over pool blocks 1..num_blocks-1
    (block 0 is the reserved null block and is never handed out).

    The free blocks are kept as disjoint intervals ``[start, end)``, sorted,
    merged whenever a freed block touches one: the pool is page-major, so a
    RUN of adjacent blocks is one stretch of a layer's memory and the ragged
    read brings it with one copy (ops/ragged._tile_plan's ``R``). ``alloc``
    therefore hands out ascending ids in the fewest runs the free space
    allows. Nothing is reserved ahead: every free block is anyone's."""

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError(f"paged pool needs >= 2 blocks, got {num_blocks}")
        self.num_blocks = num_blocks
        # one interval: low ids go out first — keeps early pool pages hot
        self._starts: list[int] = [1]
        self._ends: list[int] = [num_blocks]
        self._n_free = num_blocks - 1
        self._refs = np.zeros((num_blocks,), np.int32)
        self.hwm = 0  # high-water mark of blocks in use (observability)
        _G_BLOCKS_TOTAL.set(num_blocks)
        self._set_gauges()

    def _set_gauges(self):
        _G_BLOCKS_USED.set(self.used_count)
        _G_BLOCKS_FREE.set(self.free_count)

    @property
    def free_count(self) -> int:
        return self._n_free

    @property
    def used_count(self) -> int:
        return self.num_blocks - 1 - self._n_free

    def free_runs(self) -> list[tuple[int, int]]:
        """The free intervals ``(start, end)``, ascending (tests, debugging)."""
        return list(zip(self._starts, self._ends))

    def _take(self, i: int, n: int) -> range:
        """The first n blocks of free interval i."""
        start = self._starts[i]
        if start + n == self._ends[i]:
            del self._starts[i], self._ends[i]
        else:
            self._starts[i] = start + n
        return range(start, start + n)

    def alloc(self, n: int, after: int = 0) -> list[int] | None:
        """n fresh blocks (refcount 1), or None when the pool can't cover
        the whole request — partial allocations would leak on the caller's
        retry path. Ascending ids in the FEWEST runs the free intervals
        allow: the smallest interval that holds all n (the lowest of equals:
        a fresh pool goes out from block 1 up), else the largest intervals
        whole and the smallest that holds the rest. With ``after`` (a row's
        last block: decode growth) the blocks that follow it come first, as
        far as they are free, so the row's run goes on."""
        if n > self._n_free:
            return None
        out: list[int] = []
        if after:
            i = bisect.bisect_left(self._starts, after + 1)
            if i < len(self._starts) and self._starts[i] == after + 1:
                out.extend(self._take(i, min(n, self._ends[i] - after - 1)))
        grown = len(out)
        while len(out) < n:
            need = n - len(out)
            sizes = [e - s for s, e in zip(self._starts, self._ends)]
            fit = min((size for size in sizes if size >= need), default=0)
            if not fit:  # no interval holds the rest: the largest goes whole
                need = fit = max(sizes)
            out.extend(self._take(sizes.index(fit), need))
        out[grown:] = sorted(out[grown:])
        self._refs[out] = 1
        self._n_free -= n
        self.hwm = max(self.hwm, self.used_count)
        self._set_gauges()
        return out

    def ref(self, blocks: Iterable[int]) -> None:
        for b in blocks:
            assert self._refs[b] > 0, f"ref of free block {b}"
            self._refs[b] += 1

    def deref(self, blocks: Iterable[int]) -> int:
        """Drop one reference per block; blocks reaching zero return to
        the free intervals, merged with the neighbours they touch. Returns
        how many were freed."""
        freed = []
        for b in blocks:
            assert self._refs[b] > 0, f"deref of free block {b}"
            self._refs[b] -= 1
            if self._refs[b] == 0:
                freed.append(b)
        freed.sort()
        i = 0
        while i < len(freed):
            j = i + 1
            while j < len(freed) and freed[j] == freed[j - 1] + 1:
                j += 1
            self._release(freed[i], freed[j - 1] + 1)
            i = j
        self._n_free += len(freed)
        self._set_gauges()
        return len(freed)

    def _release(self, start: int, end: int) -> None:
        """[start, end) joins the free intervals."""
        i = bisect.bisect_left(self._starts, start)
        if i and self._ends[i - 1] == start:  # grows the interval below it
            i -= 1
            self._ends[i] = end
        else:
            self._starts.insert(i, start)
            self._ends.insert(i, end)
        if i + 1 < len(self._starts) and self._starts[i + 1] == end:
            self._ends[i] = self._ends.pop(i + 1)
            del self._starts[i + 1]

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


# ---- the jitted programs over the pool and the state. Every leaf's slot
# dim is axis 1: the pool's block axis (the [L, NB, 2, Hkv, BS, hd] pages,
# the int8 pool's [L, NB, 2, Hkv] scales and a latent pool's
# [L, NB, 1, BS, W] rows line up, so one program moves pages and their
# scales together), the state's row ([L, B, ...]). All but the gather (a
# pure read: the pool keeps serving) donate what they update. Each body
# runs under the root scope "prog.pool" (tracing.prog_scope).

_donating = functools.partial(jax.jit, donate_argnums=(0,))

# the block export format (RowCache.export_row, meshnet/migrate.py) is
# HEAD-major with the block axis at 2, K and V (and their scales) apart:
# {"k", "v"} [L, Hkv, nb, BS, hd] (+ {"k_scale", "v_scale"} [L, Hkv, nb]),
# or {"latent"} [L, 1, nb, BS, W]. It is older than the stored layout and
# did not change with it (a peer may run either): the two halves of a
# stored leaf's pages split and join at this edge
_WIRE_HALVES = {"kv": ("k", "v"), "kv_scale": ("k_scale", "v_scale")}


@_donating
@prog_scope("prog.pool")
def _copy_slot(tree, src, dst):
    """Slot ``src`` of every leaf copied over slot ``dst``: a pool block
    (the CoW copy) or a state row (compaction). Scalar ids: one trace a
    tree, ever."""
    return jax.tree.map(
        lambda big: jax.lax.dynamic_update_slice_in_dim(
            big, jax.lax.dynamic_slice_in_dim(big, src, 1, axis=1),
            dst, axis=1), tree)


@functools.partial(jax.jit, static_argnums=(0,))
@prog_scope("prog.pool")
def _gather_blocks(hd, pool, idx):
    """Blocks ``idx`` of every leaf in the export format, pages cut to the
    model's head size ``hd``: a lane-aligned pool's pad lanes
    (core.init_paged_pool) do not travel."""
    out = {}
    for name, arr in pool.items():
        got = arr[:, idx]
        halves = _WIRE_HALVES.get(name)
        parts = {name: got} if halves is None else {
            wire: got[:, :, i] for i, wire in enumerate(halves)}
        for wire, part in parts.items():
            part = jnp.swapaxes(part, 1, 2)  # blocks behind the heads
            out[wire] = part[..., :hd] if part.ndim == 5 else part
    return out


@_donating
@prog_scope("prog.pool")
def _scatter_blocks(pool, new, idx):
    """Write blocks ``new`` (the export format) at ``idx``; pages narrower
    than the pool's (head size vs lane-aligned) get their pad lanes
    zeroed."""
    def stored(name, arr):
        def part(wire):
            blocks = jnp.swapaxes(new[wire], 1, 2)  # blocks in front again
            pad = arr.shape[-1] - blocks.shape[-1] if blocks.ndim == 5 else 0
            return jnp.pad(blocks, ((0, 0),) * (blocks.ndim - 1) + ((0, pad),))

        halves = _WIRE_HALVES.get(name)
        if halves is None:
            return part(name)
        return jnp.stack([part(wire) for wire in halves], axis=2)

    return {
        name: arr.at[:, idx].set(stored(name, arr))
        for name, arr in pool.items()
    }


@_donating
@prog_scope("prog.pool")
def _reset_scales(pool, idx):
    return dict(pool, kv_scale=pool["kv_scale"].at[:, idx].set(0.0))


@_donating
@prog_scope("prog.pool")
def _state_insert(st, row, b):
    return jax.tree.map(
        lambda big, r: jax.lax.dynamic_update_slice_in_dim(
            big, r.astype(big.dtype), b, axis=1), st, row)


@_donating
@prog_scope("prog.pool")
def _state_scatter(st, rows, slots):
    """``rows`` [L, n, ...] into the slots ``slots`` [n] of every leaf; a
    slot past the bucket (a dead row of the group) is dropped."""
    return jax.tree.map(
        lambda big, r: big.at[:, slots].set(r.astype(big.dtype), mode="drop"),
        st, rows)


@functools.partial(jax.jit, static_argnums=(1,))
@prog_scope("prog.pool")
def _state_shrink(st, n):
    return jax.tree.map(lambda a: a[:, :n], st)


class RowCache:
    """Everything a row keeps between steps, and where it lives.

    - ONE block pool for every row + host-side tables; the pool never
      resizes with the batch bucket (row identity lives in the block
      table), so grow/shrink/compaction cost zero device copies and
      per-step cache traffic follows the table width.
    - The OTHER kind of row state (recurrent models, falcon-h1): one slot
      a row of the batch bucket beside the pool — [L, bsz, ...], re-shaped
      WITH the bucket (the pool is not: a block table gives a row its
      pages, nothing gives it another state). Zeroed at admission, moved by
      compaction. None for every other model.

    ``pool`` and ``state`` are plain attributes: the scheduler hands them
    to a jit root that donates them and stores the root's result back."""

    def __init__(self, engine, max_batch: int):
        self.engine = engine
        self.max_batch = max_batch
        self.block_size = engine.engine_cfg.kv_block_size
        self.blocks_per_row = engine.blocks_per_row
        self.recurrent = engine.model_cfg.has_ssm
        self.windowed = any(engine.model_cfg.layer_windows)  # a layer reads behind a window
        # what a token stores, a layer (core.pool_layout): the page
        # counters, the export format and the bytes a token follow from it
        self.layout = core.pool_layout(engine.model_cfg)
        ic = engine.introspect
        # the CoW copy is scalar-arg'd (one trace ever): un-predicated,
        # repeats storm
        self._copy_block = ic.sentinel.watch(
            "cow_copy", _copy_slot, key_fn=lambda pool, src, dst: ()
        )
        # engine.hbm_bytes{component}: a latent pool goes under its own name
        ic.ledger.register(
            "latent" if "latent" in self.layout else "kv_pool", lambda: self.pool)
        if self.recurrent:
            ic.ledger.register("state", lambda: self.state)
        self.rebuild()

    def rebuild(self):
        """An empty cache at batch bucket 1: the constructor's path, and the
        recovery after a device-side failure — the pool was donated through
        the failed call and may hold poisoned buffers, so allocator, tables,
        prefix pins, pool and state are all made anew."""
        e = self.engine
        self.alloc = BlockAllocator(e.pool_blocks)
        self.tables = np.zeros((self.max_batch, self.blocks_per_row), np.int32)
        self.row_blocks: list[list[int]] = [[] for _ in range(self.max_batch)]
        self._deferred: list[int] = []  # release(in_flight=True)
        entries = e.engine_cfg.prefix_cache_entries
        self.prefix = (
            PagedPrefixCache(entries, self.alloc) if entries > 0 else None
        )
        self.pool = e.new_pool()
        self.state = e.new_state(1)
        self._set_state_gauges(1)

    def _set_state_gauges(self, rows: int):
        self.state_rows = rows
        if self.recurrent:
            _G_STATE_ROWS.set(rows)
            _G_STATE_BYTES.set(
                sum(a.nbytes for a in jax.tree.leaves(self.state))
            )

    # ---- widths: ONE rule for tables and for block-index arguments

    def _width(self, nblocks: int) -> int:
        """Pow2-bucketed width (bounds compile variants to O(log)) — never
        below ``nblocks``, never past the physical table."""
        return min(pow2_at_least(nblocks), self.blocks_per_row)

    def declared_table_width(self, w) -> bool:
        """Is ``w`` a width ``_width`` can emit? The decode roots' declared
        compile space (None = a table-less call, also legal)."""
        if w is None:
            return True
        limit = self.blocks_per_row
        return w == limit or (w & (w - 1) == 0 and 0 < w <= limit)

    def _padded_index(self, blocks) -> np.ndarray:
        """``blocks`` as a device index argument at the bucketed width; pad
        entries name the null block 0, which dead-row decode scribbles on by
        design anyway."""
        idx = np.zeros((self._width(len(blocks)),), np.int32)
        idx[:len(blocks)] = blocks
        return idx

    # ---- blocks

    def _alloc_fresh(self, n: int, after: int = 0) -> list[int]:
        """n fresh blocks (those behind block ``after`` first, where free:
        BlockAllocator.alloc), reclaiming LRU prefix pins under pressure;
        raises PoolExhausted when even that can't cover it. On an int8
        pool the fresh blocks' scale entries reset to zero here — the
        quantize-on-write running max would otherwise inherit the PREVIOUS
        tenant's amax and serve the new row at an inflated step forever.
        Every allocation path (admission prefill, decode growth, CoW copy
        targets, KV imports) funnels through this method (the CoW copy and
        the import scatter then overwrite with the real scales)."""
        fresh = self.alloc.alloc(n, after)
        if fresh is None and self.prefix is not None:
            if self.prefix.evict_for_pressure(n):
                fresh = self.alloc.alloc(n, after)
        if fresh is None:
            raise PoolExhausted(
                f"paged KV pool exhausted: need {n} blocks, "
                f"{self.alloc.free_count} free of {self.alloc.num_blocks}"
            )
        if self.engine.kv_quantized and fresh:
            self.pool = _reset_scales(
                self.pool, self._padded_index(fresh)
            )
        return fresh

    def cover(self, b: int, upto: int):
        """Grow row b's block table to cover positions [0, upto) — the
        lazy allocation that makes short rows cheap. Raises PoolExhausted
        (with row state untouched beyond already-owned blocks)."""
        need = ceil_div(upto, self.block_size)
        have = len(self.row_blocks[b])
        if need <= have:
            return
        assert need <= self.blocks_per_row, (need, upto)
        # growth goes on behind the row's last block where that is free: a
        # row's pages stay a run, which the ragged read brings in one copy
        fresh = self._alloc_fresh(
            need - have, after=self.row_blocks[b][-1] if have else 0)
        self.row_blocks[b].extend(fresh)
        self.tables[b, have:need] = fresh

    def growth_fits(self, growth: Iterable[tuple[int, int]]) -> bool:
        """Would covering every ``(row, upto)`` fit the free list outright,
        with no prefix pin reclaimed? (Look-ahead dispatch asks: it must
        never be destructive.)"""
        need = sum(
            max(0, ceil_div(upto, self.block_size) - len(self.row_blocks[b]))
            for b, upto in growth
        )
        return need <= self.alloc.free_count

    def release(self, b: int, in_flight: bool = False):
        """Drop row b's block references (shared blocks survive via their
        other refs — prefix pins, CoW donors) and null its table row so
        dead-row decode writes land in the null block. Decode windows still
        ``in_flight`` keep dead-row-scattering into the blocks, so their
        deref waits for ``flush_deferred`` (reallocating them early would
        let an in-flight write corrupt another row's fresh block)."""
        if self.row_blocks[b]:
            if in_flight:
                self._deferred.extend(self.row_blocks[b])
            else:
                self.alloc.deref(self.row_blocks[b])
            self.row_blocks[b] = []
        self.tables[b, :] = 0

    def flush_deferred(self):
        """Free the blocks of rows released while windows were in flight —
        the caller's ring is empty now."""
        if self._deferred:
            self.alloc.deref(self._deferred)
            self._deferred = []

    # ---- the batch bucket: compaction and resize

    def move(self, src: int, dst: int):
        """Row ``src`` becomes row ``dst`` (a free slot): its pages move by
        table alone; its state slot is the one device copy a compaction
        costs."""
        self.tables[dst] = self.tables[src]
        self.tables[src] = 0
        self.row_blocks[dst] = self.row_blocks[src]
        self.row_blocks[src] = []
        if self.recurrent:
            self.state = _copy_slot(self.state, np.int32(src), np.int32(dst))

    def resize(self, bsz: int):
        """Follow the batch bucket to ``bsz`` rows: the state is re-shaped,
        the pool is not. Active rows live in [0, active), so the leading
        slots carry them all — grown, they lead a new zeroed bucket."""
        if not self.recurrent:
            return
        if bsz > self.state_rows:
            self.state = _state_insert(
                self.engine.new_state(bsz), self.state, np.int32(0)
            )
        else:
            self.state = _state_shrink(self.state, bsz)
        self._set_state_gauges(bsz)

    def put_state(self, rows, group_state):
        """A prefilled group's states ([L, n, ...]) into the slots ``rows``
        ([n]; -1 = a dead row of the group, whose state goes nowhere): one
        program a group."""
        slots = np.asarray(rows, np.int32)
        self.state = _state_scatter(
            self.state, group_state,
            np.where(slots < 0, np.int32(self.state_rows), slots))

    # ---- tables for a device call

    def rows_table(self, rows, chunk: int = 0) -> np.ndarray:
        """[n, tw] — the tables of the rows ``rows`` of one prefill group
        (-1 = a dead row: the null block throughout). tw covers the longest
        of them, and never less than a fresh prompt filling ``chunk``
        positions would need, so that the groups of one bucket share ONE
        width whatever their prompts' lengths."""
        rows = np.asarray(rows, np.int64)
        tw = self._width(max(
            [ceil_div(chunk, self.block_size)]
            + [len(self.row_blocks[b]) for b in rows if b >= 0]))
        table = self.tables[np.maximum(rows, 0), :tw]  # a copy
        table[rows < 0] = 0
        return table

    def window_table(self, live_rows: list[int], bsz: int):
        """-> ([bsz, tw] table of the batch bucket, blocks the live rows
        map): tw covers the longest live row. ``table.size`` is what
        attention visits, the second value what is actually mapped (tests
        and the page counters assert they track each other)."""
        live = [len(self.row_blocks[b]) for b in live_rows]
        tw = self._width(max(live))
        return np.ascontiguousarray(self.tables[:bsz, :tw]), sum(live)

    def count_pages_written(self, rows: int, chunk: int, calls: int = 1):
        """engine.kv_pages_written for one dispatch of ``calls`` forwards
        over [rows, chunk] tokens — only where core.forward writes through
        the page-write kernel."""
        e = self.engine
        if e.kv_in_place:
            from ..ops.ragged import chunk_pages  # loaded with the attn_fn

            _C_KV_PAGES_WRITTEN.inc(
                rows * chunk_pages(chunk, self.block_size)
                * calls * e.model_cfg.cache_layers
            )

    def count_tiles(self, tables, offsets, chunk: int, calls: int = 1):
        """engine.kv_tiles and engine.kv_pages_read for one dispatch of
        ``calls`` attention calls a layer over ``tables`` [rows, tw],
        ``chunk`` tokens a row from ``offsets``: the grid steps of ONE
        layer's ragged read, those with a work item, and the pages the items
        bring in a run copy / one by one — ops/ragged.read_counts, the call's
        own tile plan, live-tile and run arithmetic on host integers, at the
        shapes one shard of the pool sees. A model whose layers are of
        several kinds (full beside windowed) counts each kind with its own
        window and adds the mean over its layers, rounded down: the MEAN
        layer's call. Only where the ragged kernel reads."""
        e, cfg = self.engine, self.engine.model_cfg
        if e.engine_cfg.attention != "flash":
            return
        from ..ops.ragged import read_counts  # loaded with the attn_fn

        pages = next(iter(self.pool.values()))  # K beside V, or latent rows
        *_, heads, block, head_dim = pages.sharding.shard_shape(pages.shape)
        total = np.zeros(4, np.int64)  # live, stepped, in_run, single
        for window, n in collections.Counter(cfg.layer_windows).items():
            total += n * np.array(read_counts(
                tables, offsets[: len(tables)], window, heads=heads,
                # query heads a stored head: all of them read a latent row
                group=cfg.n_heads // next(iter(self.layout.values()))[0],
                chunk=chunk,
                head_dim=head_dim, block_size=block, itemsize=e.dtype.itemsize,
                quantized=e.kv_quantized, latent="latent" in self.layout,
            ))
        live, stepped, in_run, single = (
            int(x) for x in total * calls // cfg.cache_layers)
        _C_KV_TILES.inc(live, kind="live")
        _C_KV_TILES.inc(stepped, kind="stepped")
        _C_KV_PAGES_READ.inc(in_run, kind="in_run")
        _C_KV_PAGES_READ.inc(single, kind="single")

    def note_tokens_held(self, contexts):
        """The gauges engine.kv_tokens_held / engine.kv_tokens_behind_window
        from the live rows' ``contexts`` (tokens cached a row): a window
        layer's read at the next position p sees the keys above p - window,
        so a row holds max(0, context - window + 1) tokens a window layer
        that nothing will read again (one table a row: they stay mapped)."""
        windows = self.engine.model_cfg.layer_windows
        ctx = np.asarray(contexts, np.int64)
        _G_KV_TOKENS_HELD.set(int(ctx.sum()) * len(windows))
        _G_KV_TOKENS_BEHIND_WINDOW.set(sum(
            n * int(np.maximum(ctx - w + 1, 0).sum())
            for w, n in collections.Counter(windows).items() if w))

    # ---- prefix sharing

    def match_prefix(self, ids: list[int]):
        """-> (start, blocks | None): the longest cached prefix of ``ids``
        and its entry's block list; (0, None) on a miss or without a
        prefix cache."""
        return (0, None) if self.prefix is None else self.prefix.match(ids)

    def adopt(self, b: int, n: int, start: int, cached) -> bool:
        """Wire row b's table for an ``n``-token prefill that resumes at
        ``start`` on the blocks ``cached`` (match_prefix; None = from
        scratch): share the matched prefix's FULL blocks, CoW-copy at most
        its final partial block — the borrower writes into it from
        ``start``, which is therefore also the prefill's write floor.
        Returns whether that copy ran. Raises PoolExhausted BEFORE any
        device work when the pool cannot hold the whole prompt; the caller
        releases the row."""
        BS = self.block_size
        row: list[int] = []
        self.row_blocks[b] = row
        self.tables[b, :] = 0
        full = start // BS
        partial: int | None = None
        try:
            if cached is not None:
                shared = list(cached[:full])
                # take our refs FIRST: the eviction below may reclaim
                # prefix entries — including the donor — and must not free
                # blocks this row is about to depend on
                self.alloc.ref(shared)
                row.extend(shared)
                self.tables[b, :full] = shared
                if start % BS:
                    partial = int(cached[full])
                    self.alloc.ref([partial])
            # sufficiency precheck: the write ceil drops every scatter
            # at/past position n, so prefill claims exactly the blocks
            # covering the prompt — ceil(n / BS) — regardless of bucket
            # padding (fresh blocks = that minus the shared fulls; the CoW
            # copy target is the full-th block and is counted)
            fresh_needed = ceil_div(n, BS) - full
            if fresh_needed > self.alloc.free_count and not (
                self.prefix is not None
                and self.prefix.evict_for_pressure(fresh_needed)
            ):
                raise PoolExhausted(
                    f"paged KV pool exhausted: admission needs "
                    f"{fresh_needed} blocks, {self.alloc.free_count} free "
                    f"of {self.alloc.num_blocks}"
                )
            if partial is None:
                return False
            fresh = self._alloc_fresh(1)
            # the ONE CoW device copy
            self.pool = self._copy_block(
                self.pool, np.int32(partial), np.int32(fresh[0])
            )
            row.append(fresh[0])
            self.tables[b, full] = fresh[0]
            return True
        finally:
            if partial is not None:
                self.alloc.deref([partial])

    def publish_prefix(self, b: int, ids: list[int]):
        """Pin row b's blocks covering exactly the positions of ``ids`` as
        a prefix entry. Pinning is free (refcounts, no snapshot); a capacity
        eviction inside may free other entries' blocks."""
        if self.prefix is not None:  # put() keeps an entry it already has
            self.prefix.put(
                ids, self.row_blocks[b][:ceil_div(len(ids), self.block_size)]
            )

    # ---- migration: a row's pages as host arrays

    def export_row(self, b: int, upto: int):
        """-> (nb, {leaf: [L, Hkv, nb, ...]} | None): the pages holding
        positions [0, upto) of row b at the model's head size, with the
        int8 pool's scales under their own keys. Pure read."""
        # a recurrent row's blocks are NOT its complete state
        support.require(self.engine.model_cfg, "kv_export")
        nb = ceil_div(upto, self.block_size)
        if not nb:
            return 0, None
        idx = self._padded_index(self.row_blocks[b][:nb])
        hd = next(iter(self.layout.values()))[1]  # the published width
        got = jax.device_get(_gather_blocks(hd, self.pool, idx))
        return nb, {
            name: np.asarray(arr[:, :, :nb]) for name, arr in got.items()
        }

    def import_row(self, b: int, upto: int, kv: dict):
        """Row b from shipped pages covering [0, upto): fresh blocks, the
        table, one scatter (``kv`` is the export format of this pool —
        engine.import_generation validated it). Raises PoolExhausted
        with nothing taken."""
        self.cover(b, upto)  # b is a free row: all of it is fresh
        idx = self._padded_index(self.row_blocks[b])
        # every leaf padded to the index width; pad columns are zero data
        # aimed at the null block
        pad = len(idx) - len(self.row_blocks[b])
        new = {
            name: np.pad(arr, [(0, 0), (0, 0), (0, pad)]
                         + [(0, 0)] * (np.ndim(arr) - 3))
            for name, arr in kv.items()
        }
        self.pool = _scatter_blocks(self.pool, new, idx)
