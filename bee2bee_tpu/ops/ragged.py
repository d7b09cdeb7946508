"""Ragged paged-attention pallas kernel: one decode path for paged +
flash + spec.

The dense paged path (models/core.forward's ``block_tables`` branch)
gathers every mapped block into a rectangular [B, S, Hkv, hd] view and
materializes [B, H, T, S] scores — the block pool saved cache HBM but
attention still paid the dense rectangle. This kernel (after "Ragged
Paged Attention" — PAPERS.md, arxiv 2604.15464) reads K/V straight from
the pool:

- **Pool-direct gather, a tile a step, a page ONE copy** (PR 44): the
  pool is page-major with K beside V, ``[NB, 2, Hkv, BS, hd]`` (one layer
  of core.init_paged_pool's ``kv`` leaf ``[L, NB, 2, Hkv, BS, hd]``: a
  slice, or the stacked leaf with the layer as a prefetched scalar —
  "Layouts" below), so everything a block holds of a layer is adjacent.
  One grid step carries ``Th`` KV heads x ``Tp`` consecutive table entries
  of one row: the pool is passed as ``Tp`` operands, operand p blocked
  ``(None, 2, Th, BS, hd)`` with a scalar-prefetched pool block id in its
  index_map — ``(pages[step*Tp + p], 0, h, 0, 0)`` — so each is one copy
  of K AND V of Th heads of one pool block (with ``Th == Hkv``, every
  decode shape the engine issues, one contiguous run of
  2 x Hkv x BS x hd numbers); the body takes ``k = r[0]``, ``v = r[1]``.
  The pipeline double-buffers all of them and fetches the next step's
  tile while this one computes. No gathered view, no [T, S] score
  materialization. What a step pays is copies ISSUED before bytes moved
  (~0.15 us a page OPERAND of the pipeline; PERF.md Findings, PR 44 and
  PR 53): with K and V two head-major arrays a page of 4 GQA heads was 2
  operands of 4 strided 4 KB pieces, and the read ran at 12 % of its
  roofline. A latent pool
  (``[NB, 1, BS, W]``, MLA) is the same operand with a unit axis where the
  two halves of heads stand, and no V.
  Every tensor operand's trailing block dims are ``(rows, hd)`` —
  Mosaic-tileable (a layout with the head axis between BS and hd would
  put a 1-blocked head axis second-to-last and fail to lower, and a
  bool-mask operand blocked per 16-lane page would violate the same rule
  — the constraint that shaped ops/flash.py's head-major layout).
- **Whole pages: the kernel starts its own copies, a RUN of adjacent
  pages is ONE copy, and a page no query of the step can see is none**
  (PR 53 for pages under 128 KB; every such pool since PR 59). Where a step
  takes WHOLE pages of a float pool on the 128 lanes — K beside V of every
  KV head (4 GQA heads x 128: 32 KB; ouro's 16 MHA heads: 128 KB; phi-3's
  32 in its lane-aligned pool: 256 KB) or a latent row's page (640 = 5 x
  128 lanes: 20 KB) — a page is one aligned stretch of HBM, so the pool is
  ONE operand left where it lies (``pl.ANY``) and the kernel brings a tile
  with ``pltpu.make_async_copy`` into one of two VMEM tile buffers
  ``[Tp, 2, Th, BS, hd]`` (``[Tp, 1, BS, W]`` latent), the NEXT step's
  copies started before this step's products (the work list holds every
  step's pages in SMEM). The pool is page-major, so table entries whose
  pool blocks are p, p+1, .. are one stretch of a layer: _work_list marks,
  a (step, copy group of ``R`` entries — _tile_plan: the whole tile),
  whether the group is such a run, and the kernel brings a marked group
  with one copy and any other page by page (engine/paged.BlockAllocator
  hands a row ascending runs, so nearly all are). An entry that names the
  null block is not copied at all, and _work_list names the null block
  for every entry that lies wholly past its row's causal frontier
  (_visible_pages: the 1-2 blocks a row owns AHEAD of its offset for the
  decode window): the copies follow the pages a query can SEE, where a
  page operand copies whatever the table names. The items, their order
  and the arithmetic are the page operands' to the bit. What keeps the
  page operands is what Mosaic or the data forces: the int8 pool (its
  scales are a page's, dequantized from the operand), a head size off the
  128 lanes (Mosaic refuses to slice an HBM ref there — phi-3's 96, gpt2's
  64 as the dense readers' and the int8 pool's slices keep them — and
  takes those as block shapes) and a step that takes some of the KV heads
  (its share of a page is pieces): which form runs follows from the shapes
  (_tile_plan's ``R``), never from a page's size, a flag or a model's
  name. Us a layer call, page operands -> the kernel's own copies (my chip
  runs, PR 59; chip_smoke case ``own_copies``, rows owning 0-2 blocks
  ahead): ouro's decode call (16 rows of 40-250 tokens) 52.5 -> 39.9,
  joyai's latent one (64 rows of 60-480) 162.6 -> 120.3, phi-3's 108.2 ->
  107.1 and 162.0 -> 162.5 at 1,560-1,850 tokens: operands of 256 KB were
  at the bytes' rate, those of 128 KB and of 20 KB were not; without the
  frontier's mask ouro reads 40.6, and st's whole-tile runs 453.9 against
  454.3 with it (a tile that holds the frontier is rarely a whole run).
- **The grid walks a compacted work list** (PR 31), not the table: the
  grid is ``(Hkv/Th, B x q blocks x table width/Tp)`` — head groups,
  then ONE sequential axis of the table's static length (so the compile
  space stays one program per (T, table width)). Before the call,
  _work_list builds from the scalars the kernel prefetches anyway (the
  tables, the offsets, the window; a handful of XLA ops on [B]- and
  [steps]-sized int32, which the compiler hoists out of the layer loop
  where the window is one constant) the list of LIVE items ``(row, q
  block, tile)`` in row order — phi-3-mini's short decode step has ~21
  of 64, falcon-h1's wide one ~80 of 128 — with each item's ``Tp`` pool
  block ids and a flag word (work, first / last item of its (row, q
  block): where the f32 softmax state is reset and the output block
  written). Step s < n_live does item s; the steps past the end repeat the
  last item's indices with no flag and all sit at the END of the grid. So
  consecutive steps are live items: the next row's first tile is in
  flight while this row's last computes (on the rectangular (row, tile)
  grid the dead steps TRAILED every row, and a row's first tile was
  waited for in full behind them), and an index map is one SMEM load.
- **The tile follows from the shapes** (_tile_plan: heads a shard holds,
  group size, chunk length, head size, page size, table width, dtypes,
  against a fixed VMEM budget), never from an argument, a flag or a
  model's name: MHA-32 x 96 takes all heads and 4 pages (1 MB of K and
  V), a 4-head GQA group 32 pages, a 2048-row prefill chunk 4 heads, 256
  q rows and 32 pages.
- **One kernel, every chunk shape**: queries fold to ``[B, Hkv, G*T,
  hd]`` rows (GQA group g major, chunk position t minor), so [B, 1]
  decode, [B, K+1] spec verify and ragged prefill chunks are all just
  different row counts of the same program. Rows tile over a q grid
  dimension so long prefill chunks bound VMEM.
- **Scalar-compact semantics**: no mask array at all. Causality and
  per-row ragged lengths derive from the prefetched per-row ``offset``;
  the sliding window (and the gemma-2/3 per-layer local/global
  alternation) arrives as ONE prefetched int32 ``window`` (0 = full
  causal) that core.forward selects per layer with the SAME
  is_sliding_layer rule the dense mask builder uses; logit softcap and
  the gemma score-scale override are scalar params. Null-block table
  entries past a row's live extent are beyond ``offset + T`` and
  therefore causally masked by construction.
- **Live tiles only**: a row's live extent is known from the same
  scalars (_live_tiles: the tiles between the window's start and the
  causal frontier of THIS q block). A tile outside it — the pow2,
  batch-wide table's padding, the pages a window has left behind, the
  upper triangle of a prefill chunk's q blocks — is no item of the work
  list, and neither is any tile of a row that maps no page (a retired row
  of the sticky batch keeps its stale offset; its table is the null
  block): no grid step between live ones, no copy, no compute. A (row, q
  block) without an item is never visited, so its output block is zeroed
  after the call (fused into the cut of the pad rows). Both the compute
  and the cache traffic follow the row's live pages rounded up to a
  tile, while the table (and with it the compile space) stays as it
  was. Dead entries INSIDE a live tile (the null block's, past a row's
  last page; a block the row owns past its frontier) are still copied as
  page operands; the kernel's own copies skip them, and the tile buffers
  start as zeros so that what no copy has reached is finite behind its
  zero weight. ALiBi
  stays dense-only (the bias needs absolute key positions per head; the
  engine validates).
- **Online softmax** over the tile iterations with f32 m/l/acc VMEM
  scratch (a leading Th), f32 MXU accumulation, storage dtype out —
  exactly ops/flash.py's numerics, so greedy parity with the dense path
  holds token-for-token; the Th heads of a step go through one batched
  dot, their chains interleaved by the compiler.

- **Int8 pool dequant in the tile**: with ``scale`` [NB, 2, Hkv] f32
  (the per-layer slice of core.init_paged_pool's per-page-per-head
  quantization scales, K's beside V's as the pages lie), the pool blocks
  arrive int8 and each grid step dequantizes ITS tile in VMEM — every
  page's K and V each with its own scale, into two [Th, Tp*BS, hd]
  scratches the two dots then read — so
  the precision change rides the existing gather: HBM cache traffic
  halves and nothing wider than one tile ever materializes (a tile of
  pages is also what makes int8's (32, 128) minimum tile meet a 16-slot
  page). The scales ride the SAME scalar-prefetch channel as the block
  tables — pre-gathered through the tables to ``[2, Hkv, B, MB]`` outside
  the kernel, so the kernel reads 2 x Th x Tp f32 a step at
  ``[half, h, b, j]`` from SMEM (a (1, 1)-blocked VMEM operand would
  violate the trailing-dims tiling rule above) and the SMEM footprint is
  table-sized — 2 * Hkv/shard * B * MB * 4 bytes, bounded by the
  pow2-bucketed LIVE width like every per-step operand, never by pool
  capacity. The f32 m/l/acc scratch already isolates accumulation from
  storage precision, so the quantized path changes no softmax math.

- **Layouts: the float pool is written and read IN PLACE, by Mosaic only**
  (PR 29). On the serving path the stacked leaf ``[L, NB, 2, Hkv, BS, hd]``
  is the layer loop's carry, and core.forward touches it with two calls of
  this module alone: ``paged_kv_write`` (aliased pool -> pool, ONE call a
  layer: a whole page — K beside V of all the shard's KV heads — a grid
  step) and ``ragged_paged_attention`` with a ``layer`` (the page index
  maps lead with the prefetched layer). Both address it row-major,
  ``(BS, hd)`` minor, so the carry stays put and a decode step moves B
  pages a layer instead of the pool. The pool must never meet, inside the
  layer loop, the other layouts the TPU compiler would pick for it: XLA's
  scatter wants head size minor, then the axes it scatters along, and the
  device's default for the stored array puts another axis minor-most when
  the head size pads to the 128 lanes (at phi-3's 96). Whenever XLA
  writes, slices, transposes or selects what Mosaic reads, layout
  assignment gives the carry XLA's layout and re-lays a layer's slice (or,
  with an XLA write straight into the stacked pool, the WHOLE pool) for
  the kernel in every layer: eight passes over 38-50 MB a layer, 40-52 %
  of phi-3's device time before PR 29 (tests/test_tpu_compile.py keeps
  that shut, at phi-3's and smallthinker's shapes). So that the STORED
  array's default layout is row-major too, and entering and leaving a
  program re-lays nothing either, the engine allocates this path's pool
  with the head at the lane width (core.init_paged_pool ``lane_aligned``):
  both calls take a pool wider than the head, pad what they store and the
  queries with zeros, and cut the output back. (Pinning the layout of the
  96-wide array with ``jax.experimental.layout.Format`` compiles to the
  same program, but an executable with a pinned parameter layout comes
  back from the persistent compilation cache expecting the default one:
  PERF.md, Findings PR 29.) The int8 pool's requantising write is XLA's
  and stays on the per-layer slices (5-D operands, no ``layer``), as do
  the dense readers, whose reads are XLA's too. A caller that still holds
  K and V as two head-major pools (make_ragged_attn_fn's attn with a
  ``v``: checks and tests) pays a stack and a transpose to this layout
  inside its own jit and reads through the same kernel; the served hook
  hands the leaf through and never meets that copy.

On devices that are not TPUs the kernel runs in pallas interpret mode
(ops/flash.interpret_off_tpu), so the CPU test suite exercises the exact
kernel code path; on a TPU it compiles or raises.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..compat import shard_map
from .flash import NEG_INF, _LANES, interpret_off_tpu, validate_flash_mesh


# what the tile choice plans VMEM for (the q and o blocks, the f32
# softmax state, the pipeline's two buffers a page operand, one
# head's score temporaries) and the limit handed to Mosaic: the plan is
# an estimate, the limit leaves room for what the compiler adds (v5e's
# scoped default is 16 MiB of 128). The constants are the chip's (my chip
# runs, PR 44: PERF.md Findings): a page is ONE copy, and what a step pays
# is copies issued before bytes, so a step takes as many pages as 1 MB,
# 512 keys and the score temporaries allow — 32 pages of 4 GQA heads
# (32 KB each), 4 of 32 MHA heads (256 KB each)
_VMEM_BUDGET = 14 * 2**20
_VMEM_LIMIT = 32 * 2**20
_TILE_BYTES = 2**20  # K+V bytes one page tile aims to move
_TILE_TOKENS = 512  # most key positions a tile may span
_TILE_PAGES = 32  # most table entries a step: each is one page operand
_SCORE_ELEMS = 128 * 1024  # most [bq, Tp*BS] f32 score elements a head
_RUN_BYTES = 2**20  # most bytes ONE copy of a run of adjacent pages moves


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _tile_plan(Hkv, G, T, hd, BS, MB, itemsize, quantized, block_q=256,
               latent=False):
    """(Th, Tp, bq, R): KV heads, table entries and q rows of one grid step,
    and the pages of one COPY GROUP —
    a pure function of the call's shapes (``Hkv`` is the heads THIS shard
    holds; ``itemsize`` the compute dtype's, the pool's is 1 when
    ``quantized``). ``Th`` is the largest divisor of Hkv whose per-head
    VMEM (double-buffered q and o blocks, f32 m/l/acc, the f32 score
    temporaries of a 128-key tile) fits half the budget. ``Tp`` is the
    largest power of two, at most 32, that keeps a tile's K+V at 1 MB,
    its span at 512 positions, one head's scores at 128 K elements and the
    whole plan inside the budget, and does not pass the table (the pow2
    ceiling of MB when MB is smaller; the wrapper pads a table whose
    width ``Tp`` does not divide). A page operand is K beside V of the
    step's ``Th`` heads (a latent row is reckoned as if it had a V: its
    plan is what it was). So 32 MHA heads of 96 take the whole head axis
    and 4 pages a step, a 4-head GQA group 32 pages, a 2048-row prefill
    chunk 4 heads, 256 q rows and 32 pages.

    ``R`` is how many consecutive table entries ONE copy may bring when
    their pool blocks are adjacent (a run: the pool is page-major, so
    blocks p .. p+R-1 of a layer are one stretch of memory): the largest
    power of two, at most ``Tp``, that keeps such a copy at 1 MB — which is
    the tile itself wherever it applies (32 pages of 4 GQA heads x 128, 16
    of granite's 8 heads, 8 of ouro's 16 MHA heads, 4 of phi-3's 32, a
    latent row's 16: my chip runs, PR 53, a whole tile in one copy read as
    fast as 4 copies of 256 KB at decode and 7 % faster in the 2,048 chunk,
    and tiles copied page by page 4 % faster than groups of 8 were).
    ``R`` > 1 wherever a page can be copied WHOLE, whatever its size (my
    chip runs, PR 59: against the page operands ouro's decode call of
    128 KB pages read 24 % faster, joyai's latent one of 20 KB pages 26 %,
    phi-3's of 256 KB pages the same to 1 %; copy groups that keep a run
    whole across the frontier read no faster than masking every page past
    it): ``R`` = 1, the page-operand program, is what Mosaic or the data
    forces — an int8 pool (its scales are a page's), a head size off
    the 128 lanes (Mosaic slices no HBM ref there; every served pool is
    lane-aligned) and a step that takes some of the KV heads only (``Th`` <
    ``Hkv``, the 2,048 bucket of 32 MHA heads, the 128 bucket of ouro's 16:
    its share of a page is pieces, and adjacent pages do not join them)."""
    nq = G * T
    bq = min(block_q, max(nq, 8))
    lanes = _round_up(hd, _LANES)
    pool_item = 1 if quantized else itemsize
    state = (
        4 * _round_up(bq, 32 // itemsize) * lanes * itemsize
        + bq * (2 * _LANES + lanes) * 4
    )

    def scores(keys):  # s, p and the masks' worth of f32 [bq, keys] a head
        return bq * keys * 16

    Th = max(
        d for d in range(1, Hkv + 1)
        if Hkv % d == 0
        and (d == 1 or d * (state + scores(_LANES)) <= _VMEM_BUDGET // 2)
    )
    # one head's share of a page operand as VMEM holds it: K and V, the
    # pipeline's two buffers each (+ an int8 page's dequantized copy)
    moved = 4 * _round_up(BS, 32 // pool_item) * lanes * pool_item
    page = moved + (2 * BS * lanes * itemsize if quantized else 0)

    def fits(tp):
        keys = tp * BS
        return (
            tp <= _TILE_PAGES
            and keys <= _TILE_TOKENS
            and bq * keys <= _SCORE_ELEMS
            and tp * Th * moved <= 2 * _TILE_BYTES
            and Th * (state + scores(keys) + tp * page) <= _VMEM_BUDGET
        )

    Tp = 1
    while Tp < MB and fits(2 * Tp):
        Tp *= 2
    R = 1
    # what one copy of a page moves: K beside V of the heads, or a latent row
    page_bytes = (1 if latent else 2) * Th * BS * hd * itemsize
    if not (quantized or hd % _LANES or Th != Hkv):
        while 2 * R <= Tp and 2 * R * page_bytes <= _RUN_BYTES:
            R *= 2
    return Th, Tp, bq, R


def _live_tiles(off, win, i, *, chunk, block_q, tile_tokens, n_tiles, xp=jnp):
    """[lo, hi): the page tiles of one row that hold a key some query row
    of q block ``i`` can see. Visible keys are the positions
    (qlo - win, qhi] (from 0 when no window binds), qlo/qhi the block's
    first/last query position: a q block that is a run of one chunk
    (block_q divides T) has its own, any other spans the chunk. A tile
    outside [lo, hi) is wholly past the causal frontier or wholly below
    the window. ``xp`` is the array module: jnp for the call's traced
    scalars, numpy for the scheduler's host integers (read_counts)."""
    if chunk % block_q == 0:
        qlo = off + (i * block_q) % chunk
        qhi = qlo + block_q - 1
    else:
        qlo, qhi = off, off + chunk - 1
    hi = xp.minimum(qhi // tile_tokens + 1, n_tiles)
    lo = xp.where(win > 0, xp.maximum(qlo - win + 1, 0) // tile_tokens, 0)
    return lo, hi


def _item_counts(tables, off, win, *, chunk, block_q, n_qblocks, tile_pages,
                 block_size, xp=jnp):
    """(lo, n), both [B, q blocks]: the first live tile of every (row, q
    block) and how many work items it contributes — its live tiles
    (_live_tiles), or none at all for a row that maps no page: the table
    entry of its first visible key is the null block (a retired row of the
    sticky batch keeps its offset, its table is nulled)."""
    width = tables.shape[1]
    lo, hi = _live_tiles(
        off[:, None], win, xp.arange(n_qblocks, dtype=xp.int32)[None, :],
        chunk=chunk, block_q=block_q, tile_tokens=tile_pages * block_size,
        n_tiles=width // tile_pages, xp=xp,
    )
    first = xp.where(win > 0, xp.maximum(off - win + 1, 0), 0) // block_size
    mapped = xp.take_along_axis(
        tables, xp.minimum(first, width - 1)[:, None], axis=1
    ) != 0
    # (a q block that spans the chunk has no `i` in its range: broadcast)
    shape = (tables.shape[0], n_qblocks)
    n = xp.where(mapped, xp.maximum(hi - lo, 0), 0)
    return xp.broadcast_to(lo, shape), xp.broadcast_to(n, shape)


_WORK, _FIRST, _LAST = 1, 2, 4  # bits of a work item's flags


def _run_bits(pages, run_pages, xp=jnp):
    """One int32 word a table tile, out of ``pages`` [..., Tp]: bit g says
    that copy group g — entries g*R .. g*R+R-1 — is a RUN, pool blocks p,
    p+1, .. p+R-1 with p not the null block (one copy brings it), bit 16+g
    that none of its entries is the null block (its copies, however many,
    move R pages: what the wait expects). Tp / R <= 16 groups. The word
    comes out of a reduction (_work_list: why)."""
    R = run_pages
    grp = pages.reshape(*pages.shape[:-1], -1, R)
    ramp = xp.arange(R, dtype=xp.int32)
    run = (grp[..., 0] != 0) & xp.all(grp == grp[..., :1] + ramp, axis=-1)
    whole = xp.all(grp != 0, axis=-1)
    g = xp.arange(grp.shape[-2], dtype=xp.int32)
    return xp.sum(
        (run.astype(xp.int32) << g) + (whole.astype(xp.int32) << (16 + g)),
        axis=-1, dtype=xp.int32,
    )


def _visible_pages(tables, off, *, chunk, block_size, xp=jnp):
    """``tables`` [B, width] with the null block in every entry that lies
    wholly past its row's causal frontier — its first position is above the
    row's last query position ``off + chunk - 1``: the blocks a row owns
    AHEAD of its offset (the decode window's, engine.blocks_per_row) hold no
    key a query of this step can see, and the kernel's own copies start none
    for the null block. The page operands (R = 1) take the table as it is."""
    first = xp.arange(tables.shape[1], dtype=xp.int32) * block_size
    return xp.where(first[None, :] <= (off + chunk - 1)[:, None], tables, 0)


def _work_list(tables, off, win, *, chunk, block_q, n_qblocks, tile_pages,
               block_size, run_pages=1):
    """((seg, tile, flags, pages), visited): the call's compacted work
    list, one entry a step of the sequential grid axis — three [S] int32
    with S = B x q blocks x tiles and ``pages`` [S * Tp], the Tp pool blocks
    of the step's tile, in step order; ``seg`` is ``row * q blocks + q
    block`` — and ``visited`` [B, q blocks] bool: the (row, q block)s that
    have an item at all. The live items (row, q block, tile) come first,
    rows ascending, then q blocks, then tiles ascending — the online
    softmax's order; ``flags`` marks an item as work and as the first / last
    of its (row, q block). The steps past the last item repeat ITS seg, tile
    and pages with no flag: no block index changes there, so the pipeline
    starts no copy, and the body computes nothing. Built from what the
    kernel prefetches anyway (tables, offsets, the window). With
    ``run_pages`` R > 1 the tuple ends with ``runs`` [S]: the step's copy
    groups that are runs of adjacent pool blocks (_run_bits).

    Inside a layer loop the list is loop-invariant wherever the window is
    one constant, and the TPU compiler hoists it — all but an operand of
    the kernel whose LAST op is a cheap elementwise one, which it
    recomputes in every layer (~2 us each, my chip runs, PR 31). So ``seg``
    and ``visited`` come straight out of reductions."""
    B, width = tables.shape
    Tp, n_tiles = tile_pages, width // tile_pages
    lo, n = _item_counts(
        tables, off, win, chunk=chunk, block_q=block_q, n_qblocks=n_qblocks,
        tile_pages=Tp, block_size=block_size,
    )
    lo, n = lo.reshape(-1), n.reshape(-1)  # (row, q block) major
    ends = jnp.cumsum(n)
    n_live = ends[-1]
    step = jnp.arange(B * n_qblocks * n_tiles, dtype=jnp.int32)
    item = jnp.minimum(step, n_live - 1)  # the tail repeats the last item
    # the (row, q block) an item belongs to: the first whose end is past
    # it (item < n_live = ends[-1], so there is one)
    seg = jnp.sum(ends[None, :] <= item[:, None], axis=1, dtype=jnp.int32)
    lo_, n_, end_ = (x[seg] for x in (lo, n, ends))
    k = item - (end_ - n_)  # the item's place among its (row, q block)'s
    tile = jnp.clip(lo_ + k, 0, n_tiles - 1).astype(jnp.int32)
    flags = jnp.where(
        step < n_live, _WORK + _FIRST * (k == 0) + _LAST * (k == n_ - 1), 0
    ).astype(jnp.int32)
    if run_pages > 1:
        tables = _visible_pages(tables, off, chunk=chunk, block_size=block_size)
    pages = tables.reshape(B, n_tiles, Tp)[seg // n_qblocks, tile]
    runs = (_run_bits(pages, run_pages),) if run_pages > 1 else ()
    pages = pages.reshape(-1)
    visited = jnp.any(
        (seg[None, :] == jnp.arange(B * n_qblocks)[:, None]) & (flags[None, :] != 0),
        axis=1,
    )
    return (seg, tile, flags, pages, *runs), visited.reshape(B, n_qblocks)


def read_counts(tables, offsets, window, *, heads, group, chunk, head_dim,
                block_size, itemsize, quantized=False, latent=False):
    """(live, stepped, in_run, single) of ONE layer's call on host integers:
    the work items the read's grid does and the grid steps it takes, head
    groups included, and the pages (table entries that are not the null
    block, nor — under the kernel's own copies — past the row's frontier:
    _visible_pages) its items bring in a run copy / one by one — the same
    _tile_plan, _live_tiles and _run_bits arithmetic the call runs on the device, on
    numpy ``tables`` [B, MB] and ``offsets`` [B]. ``heads`` is the KV heads
    a shard holds, ``head_dim`` the pool's. For the scheduler's
    engine.kv_tiles and engine.kv_pages_read counters."""
    import numpy as np

    tables = np.asarray(tables, np.int32)
    offsets = np.asarray(offsets, np.int32)
    B, MB = tables.shape
    Th, Tp, bq, R = _tile_plan(
        heads, group, chunk, head_dim, block_size, MB, itemsize, quantized,
        latent=latent,
    )
    if MB % Tp:
        tables = np.pad(tables, ((0, 0), (0, -MB % Tp)))
    n_qblocks = _round_up(group * chunk, bq) // bq
    lo, n = _item_counts(
        tables, offsets, np.int32(window), chunk=chunk,
        block_q=bq, n_qblocks=n_qblocks, tile_pages=Tp, block_size=block_size,
        xp=np,
    )
    groups = heads // Th
    if R > 1:  # the copies follow the pages a query can see (_visible_pages)
        tables = _visible_pages(
            tables, offsets, chunk=chunk, block_size=block_size, xp=np)
    tiles = tables.reshape(B, -1, Tp)
    # a tile's mapped pages, and those of its copy groups that are runs
    a_tile = np.zeros((B, tiles.shape[1], 2), np.int64)
    a_tile[..., 0] = (tiles != 0).sum(axis=2)
    if R > 1:
        bits = _run_bits(tiles, R, xp=np) & 0xFFFF
        a_tile[..., 1] = R * (bits[..., None] >> np.arange(Tp // R) & 1).sum(axis=2)
    # summed over every item's tile: tiles [first, first + n) of a (row, q block)
    upto = np.zeros((B, tiles.shape[1] + 1, 2), np.int64)
    np.cumsum(a_tile, axis=1, out=upto[:, 1:])
    first = np.minimum(lo, tiles.shape[1])  # (an item-less row's may lie past)
    row = np.arange(B)[:, None]
    mapped, in_run = (upto[row, first + n] - upto[row, first]).sum(axis=(0, 1))
    return (groups * int(n.sum()), groups * B * n_qblocks * tiles.shape[1],
            groups * int(in_run), groups * int(mapped - in_run))


def _ragged_kernel(
    # scalar-prefetch (SMEM int32). The work list, one entry a grid step
    # (_work_list); the index maps read pages and seg, the body the rest:
    seg_ref,  # [S] the step's batch row * q blocks + its q block
    tile_ref,  # [S] its page tile
    flag_ref,  # [S] _WORK | _FIRST | _LAST (0: a step past the last item)
    pages_ref,  # [S * Tp] the pool block of entry p of step s's tile
    off_ref,  # [B] position of q[:, 0]
    win_ref,  # [1] sliding window (0 = none)
    lay_ref,  # [1] the stacked pool's layer (the K/V index maps read it;
    #           0 and unread for a 4-D slice)
    *refs,
    # quantized=True prepends one more scalar-prefetch ref:
    #   scale_ref   SMEM [2, Hkv, B, MBp] f32: K's and V's page scales,
    #               pre-gathered through the block tables per row
    # run_pages > 1 likewise:
    #   runs_ref    SMEM [S] the step's copy groups that are runs (_run_bits)
    # then the tensor operands either way:
    #   q_ref        [1, Th, BQ, hd]  q rows: GQA group g major, chunk pos t minor
    #   page_refs[p] [2, Th, BS, hd]  Tp operands: K beside V of Th heads of the
    #                pool block at entry p of the step's table tile ([Th, BS, W]
    #                of a latent pool: Th is its unit axis)
    #                — or, run_pages > 1, ONE operand: the pool where it lies
    #                (pl.ANY), its tiles brought by the kernel's own copies:
    #                whole pages of a float pool on the lanes (_tile_plan)
    #   o_ref        [1, Th, BQ, hd]
    #   m_ref        VMEM [Th, BQ, 128] f32 running max
    #   l_ref        VMEM [Th, BQ, 128] f32 running sum
    #   acc_ref      VMEM [Th, BQ, hd] f32
    # and, quantized, the tile's dequantized keys and values:
    #   kdq_ref, vdq_ref  VMEM [Th, Tp*BS, hd] compute dtype
    # or, run_pages > 1, where the copies land and what they signal:
    #   buf_ref      VMEM [2, Tp, 2, Th, BS, hd] this step's tile, the next's
    #                ([2, Tp, 1, BS, W] of a latent pool: pages as they lie)
    #   sem_ref      DMA semaphores [2], one a buffer
    sm_scale: float,
    softcap: float,
    block_size: int,
    block_q: int,
    chunk: int,  # T: query positions per row (row r is chunk position r % T)
    n_qblocks: int,
    tile_heads: int,
    tile_pages: int,
    quantized: bool = False,
    v_width: int = 0,  # latent rows (MLA): a page holds no V, a fetched tile
    #                    is the keys as it is and the values by its first
    #                    v_width columns; o_ref / acc_ref are v_width wide
    run_pages: int = 1,  # R: table entries one copy brings when their pool
    #                      blocks are adjacent (_tile_plan); 1 = page operands
    stacked: bool = False,  # run_pages > 1: the pool operand leads with L
):
    Th, Tp, BS, R = tile_heads, tile_pages, block_size, run_pages
    if quantized:
        scale_ref, *refs = refs
    if R > 1:
        runs_ref, q_ref, pool_ref, o_ref, m_ref, l_ref, acc_ref, buf_ref, sem_ref = refs
        dq_refs = ()
    else:
        q_ref, *refs = refs
        page_refs = refs[:Tp]
        o_ref, m_ref, l_ref, acc_ref, *dq_refs = refs[Tp:]
    tile_tokens = Tp * BS
    h0 = pl.program_id(0) * Th
    step = pl.program_id(1)
    flags = flag_ref[step]

    def tile_copies(t, slot, wait):
        """Start the copies that bring step t's tile into buffer ``slot``,
        or wait for them: a copy group that is a run arrives as ONE copy of
        R adjacent pool blocks, any other page by page, and an entry that
        names the null block is not copied at all (it lies past the row's
        last token: its keys are masked, whatever the buffer holds there).
        Every copy of a buffer signals its one semaphore, which counts what
        arrived: a group with no null entry is waited for as a whole,
        however it was started. (A page past the row's causal frontier
        arrives here AS a null entry: _visible_pages.)"""
        src = pool_ref.at[lay_ref[0]] if stacked else pool_ref
        word = runs_ref[t]

        def move(entry, page, n):  # whole pages: Th == Hkv (_tile_plan)
            copy = pltpu.make_async_copy(
                src.at[pl.ds(page, n)],
                buf_ref.at[slot, pl.ds(entry, n)],
                sem_ref.at[slot],
            )
            copy.wait() if wait else copy.start()

        for g in range(Tp // R):
            first = t * Tp + g * R
            whole = (word >> (g + 16 * wait)) & 1

            @pl.when(whole != 0)
            def _run():
                move(g * R, pages_ref[first], R)

            @pl.when(whole == 0)
            def _pages():
                for r in range(R):
                    page = pages_ref[first + r]

                    @pl.when(page != 0)
                    def _page():
                        move(g * R + r, page, 1)

    if R > 1:
        n_steps = pl.num_programs(1)
        slot = step % 2  # two tile buffers: this step's and the next's

        @pl.when(step == 0)
        def _prime():
            # a buffer's entries that no copy has reached yet meet the
            # values' product behind a zero weight: they must be finite
            buf_ref[...] = jnp.zeros_like(buf_ref)

            @pl.when(flags & _WORK != 0)
            def _first_tile():
                tile_copies(step, slot, wait=False)

        # the NEXT step's tile is in flight while this one computes
        nxt = jnp.minimum(step + 1, n_steps - 1)

        @pl.when((step + 1 < n_steps) & (flag_ref[nxt] & _WORK != 0))
        def _prefetch():
            tile_copies(nxt, 1 - slot, wait=False)

    @pl.when(flags & _FIRST != 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # a step past the last item copied nothing and computes nothing
    @pl.when(flags & _WORK != 0)
    def _attend():
        seg, j = seg_ref[step], tile_ref[step]
        b, i = seg // n_qblocks, seg % n_qblocks
        off = off_ref[b]
        win = win_ref[0]
        q = q_ref[0]  # [Th, BQ, hd]
        if R > 1:  # the tile's pages where the copies put them
            tile_copies(step, slot, wait=True)
            tile = [buf_ref.at[slot, p] for p in range(Tp)]
        else:
            tile = page_refs
        if quantized:
            # every key (value) row of a page shares ONE scale per kv head:
            # the wrapper pre-gathered the per-page scales through the
            # block tables to [2, Hkv, B, MBp], so the item's row and tile
            # index them directly, and nothing wider than the tile dequantizes
            def dequant(h, _):
                for p in range(Tp):
                    rows = pl.ds(p * BS, BS)
                    for half, out_ref in enumerate(dq_refs):
                        out_ref[h, rows] = (
                            tile[p][half, h].astype(jnp.float32)
                            * scale_ref[half, h0 + h, b, j * Tp + p]
                        ).astype(out_ref.dtype)

            jax.lax.fori_loop(0, Th, dequant, None)
            k, v = (r[...] for r in dq_refs)
        elif v_width:
            k = jnp.concatenate([r[...] for r in tile], axis=1)
            v = k[:, :, :v_width]
        else:
            k, v = (jnp.concatenate([r[half] for r in tile], axis=1)
                    for half in (0, 1))
        # all Th heads in one batched dot: their dot -> softmax -> dot
        # chains are independent, and the compiler interleaves them
        s = (
            jnp.einsum("hqd,hkd->hqk", q, k, preferred_element_type=jnp.float32)
            * sm_scale
        )  # [Th, BQ, Tp*BS]
        if softcap:  # gemma-2: tanh cap BEFORE masking, like core._attention
            s = jnp.tanh(s / softcap) * softcap
        # visibility from scalars: query row r sits at chunk position
        # (i*BQ + r) % T, key column c at pool position j*Tp*BS + c
        row = jax.lax.broadcasted_iota(jnp.int32, (block_q, tile_tokens), 0)
        qpos = off + (i * block_q + row) % chunk
        kvpos = j * tile_tokens + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, tile_tokens), 1
        )
        msk = kvpos <= qpos
        msk = (msk & ((win <= 0) | (kvpos > qpos - win)))[None]
        s = jnp.where(msk, s, NEG_INF)

        m_prev = m_ref[...][:, :, :1]
        l_prev = l_ref[...][:, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # a fully-masked ROW would otherwise contribute exp(-1e30+1e30)=1
        p = jnp.where(msk, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum(
            "hqk,hkd->hqd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(flags & _LAST != 0)
    def _finalize():
        # l == 0 only for q rows with nothing visible (the pad rows of a q
        # block past G*T never are: they alias chunk positions): emit 0,
        # not 0/0 = NaN
        l = l_ref[...][:, :, :1]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def ragged_paged_attention(
    q,  # [B, T, H, hd]
    pool,  # [NB, 2, Hkv, BS, hd], one layer's slice of the paged pool's ``kv``
    #        leaf (K beside V) — or, with ``layer``, the stacked leaf
    #        [L, NB, 2, Hkv, BS, hd] itself; a latent pool's [(L,) NB, 1, BS, W]
    block_tables,  # [B, MB] int32: pool block ids per row (0 = null block)
    offset,  # [] or [B] int32: global position of q[:, 0]
    window=None,  # [] or [1] int32 (traced ok) or python int: sliding
    #               window for THIS call's layer; None/0 = full causal
    sm_scale: float | None = None,
    logit_softcap: float = 0.0,
    block_q: int = 256,
    interpret: bool | None = None,
    scale=None,  # [NB, 2, Hkv] f32: an int8 pool's per-page-per-head scales
    #              (K's beside V's, as the pages lie); dequant in-kernel
    layer=None,  # [] or [1] int32 (traced ok): the pool is STACKED and
    #              this is the layer to read, in place (module docstring)
    v_width: int | None = None,  # latent rows (MLA): the pool holds no V and
    #              the values are the first v_width columns of the key rows
):
    """Causal attention for a [B, T] chunk over the paged pool; returns
    [B, T, H*hd] (core._attention ABI). T=1 is decode, T=K+1 spec verify,
    T=bucket a ragged prefill chunk — one compiled program per (T, table
    width) pair, both already bucketed by the engine; the tile a grid
    step carries follows from the shapes (_tile_plan). With ``scale`` the
    pool is int8 (core.init_paged_pool's quantized layout) and each
    fetched page dequantizes in VMEM before its dot — same tiles, same
    softmax math, half the pool HBM traffic. With ``layer`` the pool
    operands are the stacked leaf and the page index maps lead with the
    prefetched layer: the same copies from the same bytes, and no slice
    of the pool exists outside the kernel.

    With ``v_width`` the pool holds LATENT rows (core.pool_layout: one row
    a token, a unit axis where K/V pages have their two halves of heads):
    every query head reads the same rows, a page tile serves as keys (the
    whole row) and as values (its first ``v_width`` columns), and the
    result is [B, T, H*v_width]. ``q`` is the absorbed query beside its
    rotated part, as wide as a row."""
    B, T, H, hd = q.shape
    latent = v_width is not None
    if latent and scale is not None:
        raise ValueError("latent rows take no int8 scales")
    stacked = layer is not None
    # the axes in front of a page's (BS, width): (2, Hkv), or a latent
    # row's unit axis
    rank = (4 if latent else 5) + stacked
    if pool.ndim != rank or pool.shape[stacked + 1] != (1 if latent else 2):
        raise ValueError(
            f"pool of shape {pool.shape}: a layer's slice is [NB, 2, Hkv, BS, "
            "hd] (latent rows [NB, 1, BS, W]) and takes no `layer`, the "
            "stacked leaf leads with L and needs one"
        )
    NB, *parts, BS, _ = pool.shape[stacked:]
    Hkv = parts[-1]
    MB = block_tables.shape[1]
    G = H // Hkv
    sm_scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(hd)
    hd_q, hd = hd, pool.shape[-1]
    if hd != hd_q:
        # a lane-aligned pool (core.init_paged_pool): its pad lanes hold
        # zeros, so zero lanes on q leave every score as it was, and the
        # output's pad lanes, cut off below, are zero
        q = jnp.pad(q, ((0, 0),) * 3 + ((0, hd - hd_q),))
    interpret = interpret_off_tpu() if interpret is None else interpret
    quantized = scale is not None

    nq = G * T
    Th, Tp, bq, R = _tile_plan(
        Hkv, G, T, hd, BS, MB, q.dtype.itemsize, quantized, block_q, latent
    )
    while R > NB:  # (a test's pool: smaller than a copy group, it holds no such run)
        R //= 2
    nqp = _round_up(nq, bq)
    n_qblocks = nqp // bq
    # [B, T, H, hd] -> [B, Hkv, G*T, hd]: head h = kvh*G + g attends kv
    # head kvh = h // G, so heads of one group are contiguous rows
    qT = q.reshape(B, T, Hkv, G, hd).transpose(0, 2, 3, 1, 4).reshape(B, Hkv, nq, hd)
    if nqp != nq:
        qT = jnp.pad(qT, ((0, 0), (0, 0), (0, nqp - nq), (0, 0)))

    tables = jnp.asarray(block_tables, jnp.int32)
    if MB % Tp:
        # a width the tile does not divide (the engine's are powers of
        # two, so never on the serving path): null entries past the
        # table's end, causally dead like the pow2 padding itself
        tables = jnp.pad(tables, ((0, 0), (0, _round_up(MB, Tp) - MB)))
    n_tiles = tables.shape[1] // Tp
    off = jnp.broadcast_to(
        jnp.asarray(offset if offset is not None else 0, jnp.int32).reshape(-1),
        (B,),
    )
    win = jnp.asarray(window if window is not None else 0, jnp.int32).reshape(-1)[:1]
    lay = jnp.asarray(layer if stacked else 0, jnp.int32).reshape(-1)[:1]
    work, visited = _work_list(
        tables, off, win[0], chunk=T, block_q=bq, n_qblocks=n_qblocks,
        tile_pages=Tp, block_size=BS, run_pages=R,
    )

    hd_o = v_width if latent else hd  # the output block's width
    kernel = functools.partial(
        _ragged_kernel,
        sm_scale=sm_scale,
        softcap=float(logit_softcap or 0.0),
        block_size=BS,
        block_q=bq,
        chunk=T,
        n_qblocks=n_qblocks,
        tile_heads=Th,
        tile_pages=Tp,
        quantized=quantized,
        v_width=v_width or 0,
        run_pages=R,
        stacked=stacked,
    )

    # index maps take the grid indices (head group, step) and the
    # scalar-prefetch refs as trailing args (7 of them, or 8 with the
    # quantization scales — the variadic tail keeps one lambda serving
    # both). The page maps ARE the gather: entry p of the step's tile reads
    # K and V of Th heads of pool block pages[s*Tp + p] (of layer lay[0]
    # when the pool is stacked) in ONE copy — one SMEM load a map; a step
    # whose pages are the step before's (the tail) starts no copy. Where a
    # copy may bring a RUN of pages (R > 1) the pool is ONE operand left
    # where it lies, and the kernel starts its copies itself (tile_copies)
    def page_map(p):
        def index(h, s, seg_, tile_, flag_, pages_, off_, win_, lay_, *_):
            page = (pages_[s * Tp + p], *(0,) * (len(parts) - 1), h, 0, 0)
            return (lay_[0], *page) if stacked else page

        return index

    def qo_spec(width):
        return pl.BlockSpec(
            (1, Th, bq, width),
            lambda h, s, seg_, *_: (
                seg_[s] // n_qblocks, h, seg_[s] % n_qblocks, 0),
        )

    page_block = (None,) * (stacked + 1) + (*parts[:-1], Th, BS, hd)
    if R > 1:
        pool_specs = [pl.BlockSpec(memory_space=pl.ANY)]
        tile_scratch = [
            pltpu.VMEM((2, Tp, *parts[:-1], Th, BS, hd), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ]
    else:
        pool_specs = [pl.BlockSpec(page_block, page_map(p)) for p in range(Tp)]
        tile_scratch = [pltpu.VMEM((Th, Tp * BS, hd), q.dtype)] * (2 * quantized)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(work) + 3 + quantized,
        grid=(Hkv // Th, B * n_qblocks * n_tiles),
        in_specs=[qo_spec(hd)] + pool_specs,
        out_specs=qo_spec(hd_o),
        scratch_shapes=[
            pltpu.VMEM((Th, bq, _LANES), jnp.float32),
            pltpu.VMEM((Th, bq, _LANES), jnp.float32),
            pltpu.VMEM((Th, bq, hd_o), jnp.float32),
        ] + tile_scratch,
    )
    # pre-gather the per-page scales through the block tables OUTSIDE the
    # kernel: the SMEM operand is then [2, Hkv, B, MBp] — bounded by the
    # pow2-bucketed LIVE table width like every other per-step operand —
    # instead of the pool-sized [NB, 2, Hkv], which scales with total
    # capacity and would overflow SMEM on production-sized pools. The
    # gather itself is B*MB*2*Hkv f32 per call — noise next to one tile's
    # page traffic — and the kernel then indexes (half, h, b, j) directly.
    scales = (
        (jnp.asarray(scale, jnp.float32)[tables].transpose(2, 3, 0, 1),)
        if quantized
        else ()
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, nqp, hd_o), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # the steps walk the work list in order (a row's softmax state
            # lives across them); the head groups are independent
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(*work[:4], off, win, lay, *scales, *work[4:], qT, *[pool] * len(pool_specs))
    # a (row, q block) with no item was never visited: its block of `out`
    # holds whatever the buffer held. Zero it (fused into the cut below)
    out = jnp.where(
        jnp.repeat(visited, bq, axis=1)[:, None, :, None], out, 0
    )
    # [B, Hkv, nqp, hd] -> [B, T, H*hd]
    hd_q = v_width if latent else hd_q
    out = out[:, :, :nq, :hd_q].reshape(B, Hkv, G, T, hd_q).transpose(0, 3, 1, 2, 4)
    return out.reshape(B, T, H * hd_q)


# ----------------------------------------------------------- page write


def chunk_pages(T: int, BS: int) -> int:
    """Most pages a chunk of T positions can touch, whatever its start."""
    return (T + BS - 2) // BS + 1


def _write_limits(floor, ceil, rows: int):
    """[2, rows] int32 (floor, ceil) of each row's written positions; a
    scalar holds for every row, None = no limit."""
    return jnp.stack([
        jnp.broadcast_to(jnp.asarray(x, jnp.int32).reshape(-1), (rows,))
        for x in (0 if floor is None else floor,
                  jnp.iinfo(jnp.int32).max if ceil is None else ceil)
    ])


def _written_span(off, lim_ref, b, chunk):
    """[lo, hi): the positions of row b's chunk that may be written — the
    chunk itself, cut by the row's write floor and ceil."""
    return (jnp.maximum(off, lim_ref[0, b]),
            jnp.minimum(off + chunk, lim_ref[1, b]))


def _page_write_kernel(
    tables_ref,  # SMEM [B, MB] int32 (the pool's index map reads it)
    off_ref,  # SMEM [B] int32: position of the chunk's first token
    lay_ref,  # SMEM [1] int32: layer of the stacked pool (index map)
    lim_ref,  # SMEM [2, B] int32: each row's write floor, write ceil
    new_ref,  # [2, Hkv, BS, hd] the chunk's rows laid out as THIS page's
    #           slots ([2, Hkv, 1, hd] for a one-token chunk: its one row);
    #           a latent pool's page is [1, BS, W]
    page_ref,  # the page as the pool holds it, K beside V
    out_ref,  # the same block of the same buffer (aliased)
    *,
    block_size: int,
    chunk: int,
):
    b = pl.program_id(0)
    off = off_ref[b]
    lo, hi = _written_span(off, lim_ref, b, chunk)
    page = page_ref[...]
    pos = (off // block_size + pl.program_id(1)) * block_size + (
        jax.lax.broadcasted_iota(jnp.int32, page.shape, page.ndim - 2)
    )
    out_ref[...] = jnp.where(
        (pos >= lo) & (pos < hi), jnp.broadcast_to(new_ref[...], page.shape), page
    )


def paged_kv_write(
    pool,  # [L, NB, 2, Hkv, BS, hd]: the stacked ``kv`` leaf, written in place
    #        (a latent pool's [L, NB, 1, BS, W] likewise)
    new,  # [B, T, 2, Hkv, hd]: the chunk's K beside its V, any float dtype
    #       ([B, T, 1, W] latent rows)
    block_tables,  # [B, MB] int32
    offset,  # [] or [B] int32: position of new[:, 0]
    layer,  # [] or [1] int32 (traced ok)
    floor=None,  # [] or [B] int32: a row's positions below it are not written
    ceil=None,  # [] or [B] int32: a row's positions at / over it are not written
    interpret: bool | None = None,
):
    """Store a chunk's K and V into the pages its positions map to, IN
    PLACE in the stacked pool, in ONE call; returns the pool (the same
    buffer: the operand is aliased to the result). Row b's position ``p`` in
    ``[offset_b, offset_b + T)`` and in ``[floor_b, ceil_b)`` goes to slot
    ``p % BS`` of block ``tables[b, p // BS]`` of ``layer``, K and V of
    every KV head; every other byte of every block a row owns keeps its
    value.

    Grid ``(row, page of the chunk)``, a whole page a step — the axes in
    front of a page's ``(BS, width)`` are read off the pool's shape: K and
    V of ALL the shard's KV heads, or a latent row's unit axis — as one
    contiguous block: the step copies the page in, replaces the slots the
    chunk owns, copies it out (2 x 2 x Hkv x BS x hd a page; a decode step
    of B rows moves B pages a layer). A chunk need not start on a page
    edge, so it may touch ``(T + BS - 2) // BS + 1`` pages; the chunk is
    laid out in page coordinates beforehand (a chunk-sized XLA gather,
    nothing pool-sized). A grid page that holds no position to write —
    past the chunk, past the table, wholly under the floor or over the
    ceil, a dead row's — names the null block 0 and rewrites it unchanged.
    Rows own disjoint blocks (the allocator's invariant), so no two steps
    write one live page.

    This is the write half of the pool's in-place contract (module
    docstring, "Layouts"): it must be a Mosaic call, like the read."""
    *parts, BS, hd = pool.shape[2:]  # (2, Hkv), or a latent row's (1,)
    B, T = new.shape[:2]
    MB = block_tables.shape[1]
    interpret = interpret_off_tpu() if interpret is None else interpret
    tables = jnp.asarray(block_tables, jnp.int32)
    off = jnp.broadcast_to(jnp.asarray(offset, jnp.int32).reshape(-1), (B,))
    lay = jnp.asarray(layer, jnp.int32).reshape(-1)[:1]
    lim = _write_limits(floor, ceil, B)
    n_pages = chunk_pages(T, BS)
    new = new.astype(pool.dtype)
    if new.shape[-1] != hd:  # a lane-aligned pool: its pad lanes hold zeros
        new = jnp.pad(new, ((0, 0),) * (new.ndim - 1) + ((0, hd - new.shape[-1]),))
    if T == 1:
        # [B, 1, 2, Hkv, 1, hd]: the candidate of every slot of both halves
        rows = new[..., None, :]
    else:
        # slot s of the chunk's page p holds chunk position p*BS + s - off % BS
        src = jnp.arange(n_pages * BS, dtype=jnp.int32)[None] - (off % BS)[:, None]
        rows = jnp.moveaxis(
            jnp.take_along_axis(
                new, jnp.clip(src, 0, T - 1).reshape(B, -1, *(1,) * (new.ndim - 2)),
                axis=1,
            ).reshape(B, n_pages, BS, *parts, hd),
            2, -2,
        )

    zeros = (0,) * (len(parts) + 2)

    def page_index(b, p, tb, off_, lay_, lim_):
        page = off_[b] // BS + p
        lo, hi = _written_span(off_[b], lim_, b, T)
        live = (page * BS < hi) & (page * BS + BS > lo) & (page < MB)
        return (
            lay_[0], jnp.where(live, tb[b, jnp.minimum(page, MB - 1)], 0), *zeros,
        )

    page_spec = pl.BlockSpec((None, None, *parts, BS, hd), page_index)
    return pl.pallas_call(
        functools.partial(_page_write_kernel, block_size=BS, chunk=T),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, n_pages),
            in_specs=[
                pl.BlockSpec(
                    (None, None, *parts, rows.shape[-2], hd),
                    lambda b, p, *_: (b, p, *zeros),
                ),
                page_spec,
            ],
            out_specs=page_spec,
        ),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={5: 0},  # the pool, after 4 scalars and the rows
        compiler_params=pltpu.CompilerParams(
            # in order: two steps may name the null block, never a live one
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(tables, off, lay, lim, rows, pool)


# ----------------------------------------------------- TP/mesh wrapper


def make_ragged_attn_fn(mesh=None, interpret: bool | None = None):
    """Build an attn_fn (core.transformer_block ABI) that reads the paged
    pool directly. core.forward marks it via the ``ragged`` attribute: on
    the block-tables path forward partials in the block tables, and the
    per-layer mask argument becomes the compact [1] int32 window selector
    (core.make_layer_window) instead of a bool mask — nothing S-wide is
    ever built. Over a float pool the kv_hook stores the chunk's K and V
    through ONE ``attn.write`` (paged_kv_write under this mesh) and hands
    the STACKED ``kv`` leaf through as ``k`` (``v`` None) with ``layer=``
    partialled in: written and read in place (module docstring,
    "Layouts"). On an int8 pool the hook hands the (pool slice,
    [NB, 2, Hkv] scale slice) pair through and the kernel dequantizes per
    gathered block. Handed a ``v`` as well, ``k`` and ``v`` are head-major
    pools ``[(L,) Hkv, NB, BS, hd]`` (the form the tree stored before
    PR 44; checks and tests build them by hand): attn lays them page-major
    itself and reads them through the same kernel.

    Under a non-trivial mesh the kernel runs per-shard via shard_map
    (pallas_call has no SPMD partitioning rule): q heads and the pool's
    kv-head dim shard over `model` (replicated for MQA — the flash
    kernel's head-layout rules, enforced by validate_flash_mesh),
    batch/tables/offsets over `data` when it divides; the window scalar
    replicates. The pool's block/slot dims never shard here — any row
    gathers arbitrary blocks (partition.paged_cache_spec: kv heads are
    axis 3 of the stacked leaf).

    ``interpret=None`` resolves from the MESH's devices
    (interpret_off_tpu), once, here. Called WITHOUT block tables it
    raises: the serving path always passes them (every engine root runs
    over the paged pool), and a quiet dense stand-in would let a run
    that asked for the kernel pass without it.
    """
    from jax.sharding import PartitionSpec as P

    if interpret is None:
        interpret = interpret_off_tpu(mesh)

    def mesh_axes(B, Hkv):
        """(batch, q-head, kv-head) axis names under this mesh, or None
        for a single device (no shard_map at all)."""
        if mesh is None or all(n == 1 for n in mesh.shape.values()):
            return None
        tp = mesh.shape.get("model", 1)
        data = mesh.shape.get("data", 1)
        return (
            "data" if data > 1 and B % data == 0 else None,
            "model" if tp > 1 else None,
            "model" if tp > 1 and Hkv % tp == 0 else None,
        )

    def scalars(B, offset, *rest):
        off = jnp.broadcast_to(
            jnp.asarray(offset if offset is not None else 0, jnp.int32).reshape(-1),
            (B,),
        )
        return (off,) + tuple(
            jnp.asarray(x if x is not None else 0, jnp.int32).reshape(-1)[:1]
            for x in rest
        )

    def attn(q, k, v, mask, cfg, positions=None, block_tables=None, layer=None):
        if block_tables is None:
            raise ValueError(
                "the ragged paged-attention attn_fn needs block tables "
                "(a paged pool); pass attn_fn=None for a cache-less or "
                "rectangular-cache forward"
            )
        # what the kv_hook hands through as ``k`` (``v`` is None): the
        # stacked ``kv`` leaf, or an int8 pool's (layer slice, scale slice)
        # pair — unpack it here so the kernel dequants in-loop
        pool, scale = k if isinstance(k, tuple) else (k, None)
        if v is not None:
            # the old form at the door: a head-major K pool and V pool
            # ([(L,) Hkv, NB, BS, hd] each; checks and tests build them by
            # hand). Laid page-major here, inside the caller's jit, and read
            # by the same kernel; the served hook never takes this branch
            pool = jnp.moveaxis(jnp.stack([pool, v], axis=-5), -3, -5)
        stacked = layer is not None  # the stacked leaf, read in place
        window = mask  # the ragged path's per-layer [1] int32 selector
        offset = positions[:, 0] if positions is not None else None
        sm_scale = 1.0 / math.sqrt(cfg.attn_scale or cfg.head_dim)
        softcap = float(cfg.attn_logit_softcap or 0.0)
        axes = mesh_axes(q.shape[0], pool.shape[-3])
        if axes is None:
            return ragged_paged_attention(
                q, pool, block_tables, offset, window,
                sm_scale=sm_scale, logit_softcap=softcap, interpret=interpret,
                scale=scale, layer=layer,
            )
        batch_ax, head_ax, kv_ax = axes
        off, win, lay = scalars(q.shape[0], offset, window, layer)
        # ONE shard_map for both pool precisions: the int8 scales shard
        # exactly like the pool's kv-head dim (their block dim, like the
        # pool's, never shards) and simply extend the operand tuple
        scale_args = () if scale is None else (scale,)
        pool_spec = P(*(None,) * (stacked + 2), kv_ax)

        def body(q_, p_, t_, o_, w_, l_, *sc):
            return ragged_paged_attention(
                q_, p_, t_, o_, w_,
                sm_scale=sm_scale, logit_softcap=softcap, interpret=interpret,
                scale=sc[0] if sc else None,
                layer=l_ if stacked else None,
            )

        mapped = shard_map(
            body,
            mesh=mesh,
            in_specs=(
                P(batch_ax, None, head_ax, None),
                pool_spec,
                P(batch_ax),
                P(batch_ax),
                P(),
                P(),
            ) + (P(None, None, kv_ax),) * len(scale_args),
            out_specs=P(batch_ax, None, head_ax),
            check_vma=False,
        )
        return mapped(
            q, pool, jnp.asarray(block_tables, jnp.int32), off, win, lay,
            *scale_args,
        )

    def write(pool, new, block_tables, offset, layer, floor=None, ceil=None):
        """paged_kv_write under this attn_fn's mesh: per shard of the
        pool's kv heads, like the read. The batch is NOT split over
        `data`: the pool is replicated there (partition.paged_cache_spec),
        so every replica must store every row."""
        axes = mesh_axes(new.shape[0], pool.shape[-3])
        if axes is None:
            return paged_kv_write(
                pool, new, block_tables, offset, layer, floor, ceil,
                interpret=interpret,
            )
        kv_ax = axes[2]
        off, lay = scalars(new.shape[0], offset, layer)
        lim = _write_limits(floor, ceil, new.shape[0])
        mapped = shard_map(
            lambda p_, n_, t_, o_, l_, m_: paged_kv_write(
                p_, n_, t_, o_, l_, m_[0], m_[1], interpret=interpret
            ),
            mesh=mesh,
            in_specs=(
                P(None, None, None, kv_ax), P(None, None, None, kv_ax),
                P(), P(), P(), P(),
            ),
            out_specs=P(None, None, None, kv_ax),
            check_vma=False,
        )
        return mapped(
            pool, new, jnp.asarray(block_tables, jnp.int32), off, lay, lim
        )

    def latent(q, pool, _v, mask, cfg, positions=None, block_tables=None,
               layer=None):
        """attn's ABI over LATENT rows (core._mla_attention): ``q`` [B, T,
        H, W] the absorbed query beside its rotated part, ``pool`` the
        stacked [L, NB, 1, BS, W] latent pool, read in place at ``layer``;
        no V. Every head reads the one row a token; -> [B, T, H*kv_rank].
        A single device only: the engine refuses a mesh for such a model."""
        if mesh_axes(q.shape[0], 1) is not None:
            raise ValueError("the latent read is not partitioned over a mesh")
        return ragged_paged_attention(
            q, pool, block_tables,
            positions[:, 0] if positions is not None else None, mask,
            sm_scale=1.0 / math.sqrt(cfg.mla_nope_dim + cfg.mla_rope_dim),
            interpret=interpret, layer=layer, v_width=cfg.mla_kv_rank,
        )

    attn.write = write
    attn.latent = latent
    attn.ragged = True
    return attn


def validate_ragged_mesh(cfg, mesh) -> None:
    """Head-layout rules for the pool-direct kernel — identical to the
    rectangular flash kernel's (q heads divide `model`; GQA KV must shard,
    only MQA may replicate), so the one validator serves both."""
    validate_flash_mesh(cfg, mesh)
