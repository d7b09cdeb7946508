"""Ragged paged-attention kernel (ops/ragged.py) parity suite.

Correctness bar: the kernel reading K/V straight from the block pool
must match models/core._attention over the gathered view across ragged
per-row lengths (block-boundary straddles included), null-block table
tails, GQA ratios down to MQA, sliding-window + logit-softcap +
score-scale configs, and the [B, K+1] spec-verify shape — all in
interpret mode on the CPU mesh, so the exact kernel code path runs in
tier-1. The engine-level acceptance test at the bottom mixes paged
prefill, paged decode and a spec-verify row in a single batch through
``attention="flash"`` and pins greedy token parity vs the dense engine.
"""

from __future__ import annotations

import functools
import threading
from dataclasses import replace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bee2bee_tpu.models import core
from bee2bee_tpu.models.config import get_config
from bee2bee_tpu.ops import ragged_paged_attention
from bee2bee_tpu.ops.ragged import chunk_pages, make_ragged_attn_fn, paged_kv_write

CFG = get_config("tiny-llama")  # only shape-free code paths used


def _pool_case(offs, T, H, Hkv, hd, BS=8, extra_tables=0, seed=0,
               dtype=jnp.float32):
    """Build a pool + per-row tables covering lengths offs[b] + T, plus
    the gathered dense view and the causal serving mask. ``extra_tables``
    appends null-block (0) table entries past every row's live extent —
    the engine's pow2 table-width bucketing does exactly that."""
    rng = np.random.default_rng(seed)
    B = len(offs)
    offs = np.asarray(offs, np.int32)
    need = [-(-(int(o) + T) // BS) for o in offs]
    MB = max(need) + extra_tables
    tables = np.zeros((B, MB), np.int32)
    nxt = 1
    for b in range(B):
        for i in range(need[b]):
            tables[b, i] = nxt
            nxt += 1
    NB = nxt + 1
    # one layer of core.init_paged_pool's ``kv`` leaf: K beside V, page-major
    kv = jnp.asarray(rng.standard_normal((NB, 2, Hkv, BS, hd)), dtype)
    q = jnp.asarray(rng.standard_normal((B, T, H, hd)), dtype)
    kg, vg = _gathered(kv, tables)
    s_idx = np.arange(MB * BS)[None, None, :]
    q_pos = (offs[:, None] + np.arange(T)[None, :])[:, :, None]
    mask = jnp.asarray(s_idx <= q_pos)  # [B, T, S] — for the dense ref
    return q, kv, jnp.asarray(tables), jnp.asarray(offs), mask, kg, vg


def _gathered(kv, tables):
    """The gathered views [B, S, Hkv, hd] of K and of V — what the dense
    path attends over — of a page-major pool slice [NB, 2, Hkv, BS, hd]."""
    B, MB = tables.shape
    _, _, Hkv, BS, hd = kv.shape
    g = jnp.transpose(kv[tables], (2, 0, 1, 4, 3, 5)).reshape(2, B, MB * BS, Hkv, hd)
    return g[0], g[1]


def _head_major(kv):
    """A page-major leaf [(L,) NB, 2, Hkv, BS, hd] as the head-major K pool
    and V pool [(L,) Hkv, NB, BS, hd] the tree stored before PR 44 (and
    that make_ragged_attn_fn's attn still takes at the door)."""
    both = jnp.moveaxis(kv, -5, -3)  # [(L,) 2, Hkv, NB, BS, hd]
    return both[..., 0, :, :, :, :], both[..., 1, :, :, :, :]


def _dense_ref(q, kg, vg, mask, cfg=CFG):
    return core._attention(q, kg, vg, mask[:, None, :, :], cfg)


def _assert_close(got, want, atol=2e-5):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=atol)


def test_ragged_decode_lengths_across_block_boundaries():
    """T=1 decode rows whose lengths sit just below, at, and past block
    boundaries (BS=8): the per-row page walk must mask the exact ragged
    extent."""
    q, kv, tb, off, mask, kg, vg = _pool_case(
        offs=[0, 7, 8, 21], T=1, H=4, Hkv=2, hd=16
    )
    out = ragged_paged_attention(q, kv, tb, off)
    _assert_close(out, _dense_ref(q, kg, vg, mask))


def test_ragged_null_block_tail_is_masked():
    """Table entries past the live extent map to null block 0 (the
    engine's pow2-bucketed width padding): they must contribute exactly
    nothing, matching the dense reference over the same padded view."""
    q, kv, tb, off, mask, kg, vg = _pool_case(
        offs=[3, 12], T=1, H=4, Hkv=2, hd=16, extra_tables=3, seed=1
    )
    assert int((np.asarray(tb) == 0).sum()) >= 6  # tails really padded
    out = ragged_paged_attention(q, kv, tb, off)
    assert np.isfinite(np.asarray(out)).all()
    _assert_close(out, _dense_ref(q, kg, vg, mask))


def test_ragged_dead_row_all_null_is_finite():
    """A dead batch row (retired mid-batch) has its whole table nulled:
    output is garbage-but-finite, and live rows are untouched."""
    q, kv, tb, off, mask, kg, vg = _pool_case(
        offs=[9, 4], T=1, H=4, Hkv=2, hd=16, seed=2
    )
    tb = tb.at[1].set(0)
    out = ragged_paged_attention(q, kv, tb, off)
    assert np.isfinite(np.asarray(out)).all()
    want = _dense_ref(q[:1], kg[:1], vg[:1], mask[:1])
    _assert_close(out[:1], want)


@pytest.mark.parametrize("H,Hkv", [(4, 4), (8, 2), (4, 1)],
                         ids=["mha", "gqa4", "mqa"])
def test_ragged_gqa_ratios(H, Hkv):
    q, kv, tb, off, mask, kg, vg = _pool_case(
        offs=[5, 18], T=2, H=H, Hkv=Hkv, hd=8, seed=3
    )
    out = ragged_paged_attention(q, kv, tb, off)
    _assert_close(out, _dense_ref(q, kg, vg, mask))


def test_ragged_sliding_window_softcap_and_scale():
    """The gemma-2 stack: the sliding window arrives as the prefetched
    scalar (0 = full causal; a traced value works — the per-layer
    alternation selects it with jnp.where), softcap and the score-scale
    override as scalar params — all must match the dense path, which is
    the ModelConfig-coverage contract."""
    cfg = replace(CFG, attn_logit_softcap=30.0, attn_scale=13)
    q, kv, tb, off, mask, kg, vg = _pool_case(
        offs=[6, 19, 33], T=2, H=4, Hkv=2, hd=16, seed=4
    )
    w = 9
    S = mask.shape[-1]
    q_pos = np.asarray(off)[:, None] + np.arange(2)[None, :]
    win = jnp.asarray(
        np.arange(S)[None, None, :] > (q_pos[:, :, None] - w)
    )
    maskw = mask & win
    import math

    for window in (w, jnp.full((1,), w, jnp.int32)):  # python int + traced
        out = ragged_paged_attention(
            q, kv, tb, off, window=window,
            sm_scale=1.0 / math.sqrt(13), logit_softcap=30.0,
        )
        _assert_close(out, _dense_ref(q, kg, vg, maskw, cfg))
    # a window wider than any offset never masks: must equal full causal
    out = ragged_paged_attention(q, kv, tb, off, window=10_000)
    _assert_close(out, _dense_ref(q, kg, vg, mask))


def test_ragged_spec_verify_shape():
    """[B, K+1] — the speculative-decode verify chunk: per-row offsets,
    rows at different depths, causality within the chunk."""
    K = 5
    q, kv, tb, off, mask, kg, vg = _pool_case(
        offs=[2, 15, 24], T=K + 1, H=4, Hkv=2, hd=16, seed=5
    )
    out = ragged_paged_attention(q, kv, tb, off)
    _assert_close(out, _dense_ref(q, kg, vg, mask))


def test_ragged_prefill_chunk_rows():
    """A bucket-wide chunk (T=16) at ragged per-row offsets — chunked
    prefill re-anchoring lands rows at arbitrary positions; q-row tiling
    (block_q below the row count) must not change the math."""
    q, kv, tb, off, mask, kg, vg = _pool_case(
        offs=[0, 11], T=16, H=4, Hkv=2, hd=16, seed=6
    )
    out = ragged_paged_attention(q, kv, tb, off, block_q=8)
    _assert_close(out, _dense_ref(q, kg, vg, mask))


def test_ragged_under_jit():
    q, kv, tb, off, mask, kg, vg = _pool_case(
        offs=[4, 9], T=1, H=4, Hkv=2, hd=16, seed=7
    )
    f = jax.jit(lambda *a: ragged_paged_attention(*a))
    _assert_close(f(q, kv, tb, off), _dense_ref(q, kg, vg, mask))


def _quantize_pool(kv):
    """f32 pool [NB, 2, Hkv, BS, hd] → (int8 pool, [NB, 2, Hkv] scales), the
    per-page-per-head symmetric amax recipe core._quantized_page_write
    applies on write."""
    p = np.asarray(kv, np.float32)
    s = np.max(np.abs(p), axis=(3, 4)) / 127.0
    safe = np.where(s > 0, s, 1.0)
    q = np.clip(np.rint(p / safe[..., None, None]), -127, 127).astype(np.int8)
    return jnp.asarray(q), jnp.asarray(s.astype(np.float32))


def test_ragged_int8_pool_dequant_matches_dense_on_dequantized_view():
    """ISSUE 12 kernel contract: with an int8 pool + [NB, 2, Hkv] scales the
    kernel dequantizes K before QK^T and V before PV per gathered block —
    it must match the dense reference attending over the HOST-dequantized
    gathered view exactly (same values enter both softmaxes, so the only
    tolerance is the usual online-softmax reordering). Covers ragged
    decode lengths, null-block tails, and the [B, K+1] verify shape."""
    for offs, T, extra in ([0, 7, 8, 21], 1, 0), ([3, 12], 1, 3), ([2, 15, 24], 6, 0):
        q, kv, tb, off, mask, _kg, _vg = _pool_case(
            offs=offs, T=T, H=4, Hkv=2, hd=16, extra_tables=extra, seed=11
        )
        kvq, sc = _quantize_pool(kv)
        out = ragged_paged_attention(q, kvq, tb, off, scale=sc)
        # dense view over the DEQUANTIZED pool (what the engine's int8
        # dense fallback builds), gathered exactly like _pool_case does
        kg, vg = _gathered(
            jnp.asarray(kvq, jnp.float32) * sc[..., None, None], np.asarray(tb))
        _assert_close(out, _dense_ref(q, kg, vg, mask))


def test_ragged_attn_fn_needs_block_tables_and_reads_interpret_from_mesh():
    """No quiet stand-ins on the kernel path: called without block tables
    the ragged attn_fn raises (it used to become the dense reference), and
    interpret mode is decided from the devices the call runs on — the
    mesh's — never from an exception swallowed while asking."""
    import types

    from bee2bee_tpu.ops.flash import interpret_off_tpu
    from bee2bee_tpu.ops.ragged import make_ragged_attn_fn

    attn = make_ragged_attn_fn(None)
    q = jnp.zeros((1, 1, 4, 16))
    with pytest.raises(ValueError, match="needs block tables"):
        attn(q, q, q, None, get_config("tiny-llama"))

    def fake_mesh(platform):
        return types.SimpleNamespace(
            devices=np.array([types.SimpleNamespace(platform=platform)])
        )

    assert interpret_off_tpu(fake_mesh("cpu")) is True
    assert interpret_off_tpu(fake_mesh("tpu")) is False
    assert interpret_off_tpu() is True  # the suite's default backend is the CPU


def test_ragged_int8_scales_lie_as_the_pages_do():
    """One scale array for K and V, [NB, 2, Hkv] beside the pages' leading
    axes: the kernel reads K's half and V's half of it, each its own
    (swapping the halves of the scales swaps what the two dots see)."""
    q, kv, tb, off, mask, *_ = _pool_case(offs=[4, 19], T=1, H=4, Hkv=2, hd=16)
    kvq, sc = _quantize_pool(kv.at[:, 1].multiply(3.0))  # V's scales are not K's
    assert sc.shape == kvq.shape[:3]
    want = ragged_paged_attention(q, kvq, tb, off, scale=sc)
    kg, vg = _gathered(jnp.asarray(kvq, jnp.float32) * sc[..., None, None], np.asarray(tb))
    _assert_close(want, _dense_ref(q, kg, vg, mask))
    swapped = ragged_paged_attention(q, kvq, tb, off, scale=sc[:, ::-1])
    assert not np.allclose(np.asarray(swapped), np.asarray(want), atol=1e-3)


def test_ragged_bf16_storage_f32_accumulation():
    q, kv, tb, off, mask, kg, vg = _pool_case(
        offs=[10], T=1, H=4, Hkv=2, hd=16, seed=8, dtype=jnp.bfloat16
    )
    out = ragged_paged_attention(q, kv, tb, off)
    assert out.dtype == jnp.bfloat16
    want = _dense_ref(
        q.astype(jnp.float32), kg.astype(jnp.float32),
        vg.astype(jnp.float32), mask,
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want), atol=0.08, rtol=0.08
    )


# --------------------------------------- tiles of heads x tiles of live pages

# One grid step carries Th KV heads x Tp table entries (ops/ragged._tile_plan
# decides from the shapes). The cases walk what the plan can choose and what
# the shapes can force: MHA-32 / GQA / MQA head layouts at head sizes 96 and
# 128, table widths below, at and far above a tile, rows whose live pages end
# mid-tile, exactly at a tile edge, and nowhere (a dead row), a window that
# kills leading tiles, decode / spec-verify / prefill-chunk row counts, the
# bf16 and the int8 pool, eager and under jit. `offs` are q[:, 0]'s
# positions: a row holds offs + T tokens in 16-slot pages.
TILE_CASES = {
    # 13 pages (ends mid-tile of 8), 8 pages (a tile edge), 3 pages, dead
    "mha32-hd96-mb32-mid-edge-dead": dict(
        Hkv=32, G=1, hd=96, MB=32, T=1, offs=[200, 127, 40, 9], dead=[3]),
    "mha32-hd96-mb128-long": dict(
        Hkv=32, G=1, hd=96, MB=128, T=1, offs=[1800, 2047, 1023]),
    "mha32-hd96-mb2-below-a-tile": dict(
        Hkv=32, G=1, hd=96, MB=2, T=1, offs=[0, 15, 16, 31]),
    "gqa8x4-hd128-mb32": dict(
        Hkv=8, G=4, hd=128, MB=32, T=1, offs=[255, 256, 300, 2]),
    "gqa2x4-hd128-mb128-window-kills-leading-tiles": dict(
        Hkv=2, G=4, hd=128, MB=128, T=1, offs=[1900, 1500, 255], window=300),
    "gqa2x4-hd128-mb5-table-narrower-than-tile": dict(
        Hkv=2, G=4, hd=128, MB=5, T=1, offs=[70, 33, 5], dead=[1]),
    "mqa1x8-hd128-mb4": dict(
        Hkv=1, G=8, hd=128, MB=4, T=1, offs=[63, 31, 0]),
    "gqa8x4-hd96-spec-verify-t5": dict(
        Hkv=8, G=4, hd=96, MB=32, T=5, offs=[123, 124, 250, 300]),
    "gqa2x4-hd128-spec-verify-t5-window-softcap": dict(
        Hkv=2, G=4, hd=128, MB=32, T=5, offs=[400, 129, 60], window=130,
        softcap=30.0),
    "gqa2x4-hd128-prefill-chunk-t256": dict(
        Hkv=2, G=4, hd=128, MB=32, T=256, offs=[0, 250]),
    "mha32-hd96-prefill-chunk-t256-window": dict(
        Hkv=32, G=1, hd=96, MB=32, T=256, offs=[256], window=200),
    "mha32-hd96-mb32-bf16": dict(
        Hkv=32, G=1, hd=96, MB=32, T=1, offs=[200, 127, 40], dtype="bfloat16"),
    "mha32-hd96-mb32-int8-dead-tiles": dict(
        Hkv=32, G=1, hd=96, MB=32, T=1, offs=[200, 127, 40, 9], dead=[3],
        int8=True),
    "gqa2x4-hd128-int8-t5-window": dict(
        Hkv=2, G=4, hd=128, MB=32, T=5, offs=[400, 129, 60], window=130,
        int8=True),
    "mha32-hd96-mb32-under-jit": dict(
        Hkv=32, G=1, hd=96, MB=32, T=1, offs=[200, 127, 40, 9], dead=[3],
        jit=True),
    "gqa2x4-hd128-mb128-window-under-jit": dict(
        Hkv=2, G=4, hd=128, MB=128, T=1, offs=[1900, 1500, 255], window=300,
        jit=True),
    # the work list's edges (PR 31): the grid walks a compacted list of live
    # (row, q block, tile) items, so what matters is where a row's items
    # start and end in it. Retired rows of the sticky batch (table nulled,
    # offset stale) between live ones contribute nothing
    "mha32-hd96-mb32-retired-rows-stale-offsets": dict(
        Hkv=32, G=1, hd=96, MB=32, T=1, offs=[200, 300, 40, 9, 500, 130],
        dead=[1, 4]),
    "gqa2x4-hd128-mb32-first-and-last-row-retired": dict(
        Hkv=2, G=4, hd=128, MB=32, T=1, offs=[400, 300, 17, 511], dead=[0, 3]),
    # every row's init, work and finalize fall on ONE step (tiles of 256 / 128)
    "gqa2x4-hd128-mb32-rows-of-exactly-one-tile": dict(
        Hkv=2, G=4, hd=128, MB=32, T=1, offs=[255, 0, 100, 17]),
    "mha32-hd96-mb32-rows-of-exactly-one-tile": dict(
        Hkv=32, G=1, hd=96, MB=32, T=1, offs=[127, 0, 64]),
    "mha32-hd96-mb32-every-row-dead": dict(
        Hkv=32, G=1, hd=96, MB=32, T=1, offs=[200, 127, 40], dead=[0, 1, 2]),
    # the list of a windowed row starts at its first live tile, not at 0
    "gqa2x4-hd128-mb128-window-retired-row-t5": dict(
        Hkv=2, G=4, hd=128, MB=128, T=5, offs=[1900, 1200, 700, 300],
        window=260, dead=[1]),
    # several q blocks a row, each with its own live range (the skipped
    # upper triangle), a row starting mid-table and a retired one
    "mha8-hd96-prefill-t512-two-q-blocks": dict(
        Hkv=8, G=1, hd=96, MB=64, T=512, offs=[0, 300, 77], dead=[2]),
    "gqa2x4-hd128-prefill-t256-four-q-blocks-window": dict(
        Hkv=2, G=4, hd=128, MB=64, T=256, offs=[700, 0], window=300),
    # smallthinker's shapes (PR 43): a table of 1,024 pages, a window of 4,096
    # that BINDS — the list starts 8-19 tiles into a row — beside a row inside it
    "gqa4x7-hd128-mb1024-window-binds": dict(
        Hkv=4, G=7, hd=128, MB=1024, T=1, offs=[6300, 4100, 9000, 700], window=4096),
    "gqa4x7-hd128-mb1024-prefill-chunk-t256-window-binds": dict(
        Hkv=4, G=7, hd=128, MB=1024, T=256, offs=[6144], window=4096),
    "gqa8x4-hd96-spec-verify-t5-retired-rows": dict(
        Hkv=8, G=4, hd=96, MB=32, T=5, offs=[123, 124, 250, 300, 11],
        dead=[0, 3]),
    "gqa2x4-hd128-int8-retired-and-one-tile-rows": dict(
        Hkv=2, G=4, hd=128, MB=32, T=1, offs=[255, 300, 17, 500], dead=[1],
        int8=True),
    # a table the tile does not divide: the wrapper pads it with null entries
    "gqa2x4-hd128-mb20-tile-does-not-divide": dict(
        Hkv=2, G=4, hd=128, MB=20, T=1, offs=[300, 17, 250], dead=[1]),
    "gqa2x4-hd128-mb20-tile-does-not-divide-t5-int8": dict(
        Hkv=2, G=4, hd=128, MB=20, T=5, offs=[300, 17, 250], int8=True),
}


@pytest.mark.parametrize("case", sorted(TILE_CASES))
def test_ragged_tiles_match_dense(case):
    c = TILE_CASES[case]
    Hkv, G, hd, MB, T = c["Hkv"], c["G"], c["hd"], c["MB"], c["T"]
    offs, window = c["offs"], c.get("window", 0)
    BS = 16
    dtype = jnp.dtype(c.get("dtype", "float32"))
    need = max(-(-(o + T) // BS) for o in offs)
    assert need <= MB, "the case's rows must fit its table"
    q, kv, tb, off, mask, kg, vg = _pool_case(
        offs=offs, T=T, H=Hkv * G, Hkv=Hkv, hd=hd, BS=BS,
        extra_tables=MB - need, seed=len(case), dtype=dtype,
    )
    assert tb.shape == (len(offs), MB)
    live = [b for b in range(len(offs)) if b not in c.get("dead", ())]
    mapped = np.asarray(tb)  # the tables the dense view was gathered through
    for b in c.get("dead", ()):  # retired mid-batch: the whole table nulled
        tb = tb.at[b].set(0)
    if window:
        S = mask.shape[-1]
        q_pos = np.asarray(off)[:, None] + np.arange(T)[None, :]
        mask = mask & jnp.asarray(
            np.arange(S)[None, None, :] > (q_pos[:, :, None] - window)
        )
    cfg = replace(CFG, attn_logit_softcap=c.get("softcap", 0.0))
    kw = dict(logit_softcap=c.get("softcap", 0.0))
    if c.get("int8"):
        kv, sc = _quantize_pool(kv)
        kw.update(scale=sc)
        # the dense view over the DEQUANTIZED pool, gathered like _pool_case
        kg, vg = _gathered(
            jnp.asarray(kv, jnp.float32) * sc[..., None, None], mapped)

    def run(q, kv, tb, off, win):
        return ragged_paged_attention(q, kv, tb, off, window=win, **kw)

    if c.get("jit"):
        run = jax.jit(run)
    out = run(q, kv, tb, off, jnp.full((1,), window, jnp.int32))
    assert out.shape == (len(offs), T, Hkv * G * hd) and out.dtype == dtype
    assert np.isfinite(np.asarray(out, np.float32)).all()
    # a retired row contributes no work item: its output block is zeroed
    assert not np.asarray(out, np.float32)[list(c.get("dead", ()))].any()
    want = _dense_ref(
        q.astype(jnp.float32), kg.astype(jnp.float32), vg.astype(jnp.float32),
        mask, cfg,
    )
    tol = dict(atol=0.08, rtol=0.08) if dtype == jnp.bfloat16 else dict(atol=5e-5)
    np.testing.assert_allclose(
        np.asarray(out, np.float32)[live], np.asarray(want)[live], **tol
    )


def test_tile_plan_follows_shapes_within_vmem_budget():
    """(Th, Tp, bq) is a pure function of the call's shapes: the issue's
    three examples, divisibility, the table-width cap, and the plan's own
    VMEM arithmetic under the budget for every shape the engine issues."""
    from bee2bee_tpu.ops import ragged

    def plan(*shapes):  # the copy group R has a test of its own, below
        return ragged._tile_plan(*shapes)[:3]

    # phi-3-mini decode: all 32 MHA heads, 4 pages a step (a page operand is
    # K beside V of every head: 256 KB, 1 MB a step; my chip runs, PR 44), int8
    # pages the same; its 2048-row prefill chunk: fewer heads so that q, scores
    # and accumulators fit, q rows 256, and as many pages as 512 keys hold
    assert plan(32, 1, 1, 96, 16, 32, 2, False) == (32, 4, 8)
    assert plan(32, 1, 1, 96, 16, 32, 2, True) == (32, 4, 8)
    assert plan(32, 1, 2048, 96, 16, 128, 2, False) == (4, 32, 256)
    # a mistral-7b shard under model:4 (2 KV heads, groups of 4, hd 128):
    # both heads and a LARGER page tile than the MHA plan's
    assert plan(2, 4, 1, 128, 16, 64, 2, False) == (2, 32, 8)
    # smallthinker (GQA 28/4 x 128, a 1,024-page table): 32 pages of 32 KB a
    # step, decode and the 2,048 chunk alike; falcon-h1's 20/4 the same;
    # joyai's latent row (reckoned as if it had a V) keeps 16
    assert plan(4, 7, 1, 128, 16, 1024, 2, False) == (4, 32, 8)
    assert plan(4, 7, 2048, 128, 16, 1024, 2, False) == (4, 32, 256)
    assert plan(4, 5, 1, 128, 16, 32, 2, False) == (4, 32, 8)
    assert plan(1, 32, 1, 640, 16, 32, 2, False) == (1, 16, 32)
    # a table narrower than a tile takes the table (pow2 ceiling)
    assert plan(32, 1, 1, 96, 16, 2, 2, False)[1] == 2
    assert plan(32, 1, 1, 96, 16, 4, 2, False)[1] == 4
    assert plan(2, 4, 1, 128, 16, 5, 2, False)[1] == 8
    assert plan(2, 4, 1, 128, 16, 20, 2, False)[1] == 32
    assert plan(8, 1, 1, 256, 16, 1, 2, False)[1] == 1
    for Hkv, G, hd in [(32, 1, 96), (8, 4, 128), (2, 4, 128), (1, 8, 256),
                       (12, 1, 64), (16, 2, 256)]:
        for T in (1, 5, 7, 64, 256, 2048):
            for MB in (1, 2, 4, 32, 128, 256):
                for itemsize, quantized in ((2, False), (4, False), (2, True)):
                    Th, Tp, bq = plan(Hkv, G, T, hd, 16, MB, itemsize, quantized)
                    assert Hkv % Th == 0 and Th >= 1
                    assert Tp & (Tp - 1) == 0 and 1 <= Tp <= ragged._TILE_PAGES
                    assert Tp < 2 * MB  # never past the table's pow2 ceiling
                    assert bq == min(256, max(G * T, 8))
                    lanes = -(-hd // 128) * 128
                    pool_item = 1 if quantized else itemsize
                    vmem = (
                        Th * 2 * 2 * max(bq, 32 // itemsize) * lanes * itemsize  # q, o
                        + Th * bq * (2 * 128 + lanes) * 4  # m, l, acc
                        + Th * bq * Tp * 16 * 16  # f32 score temporaries
                        + 2 * 2 * Tp * Th * max(16, 32 // pool_item) * lanes * pool_item
                        + quantized * 2 * Th * Tp * 16 * lanes * itemsize  # dequantized
                    )
                    assert vmem <= ragged._VMEM_BUDGET or (Th == 1 and Tp == 1), (
                        Hkv, G, T, hd, MB, itemsize, quantized, Th, Tp, bq, vmem
                    )


def test_dead_tile_starts_no_copy():
    """The grid walks a compacted work list (_work_list): the steps flagged
    as work are exactly the live tiles - the tiles with a key some query of
    the q block can see, brute-forced from the visibility rule itself - of
    the rows that map a page, in (row, q block, tile) order, the first and
    the last of every (row, q block) flagged; every later step repeats the
    last item's row, q block, tile AND pages with no flag, so no block index
    changes there and the pipeline starts no copy."""
    from bee2bee_tpu.ops.ragged import _FIRST, _LAST, _WORK, _work_list

    BS, Tp, n_tiles = 16, 8, 16
    tt, width = BS * Tp, Tp * n_tiles
    rng = np.random.default_rng(0)
    offs_all = (0, 1, 127, 128, 129, 1000, 1500, 5000)
    for chunk, block_q in ((1, 8), (5, 20), (256, 256), (512, 256), (64, 256)):
        n_qblocks = max(1, chunk // block_q)
        for win in (0, 1, 100, 300, 5000):
            offs = np.array(offs_all + (2047 - chunk,), np.int32)
            B = len(offs)
            tables = rng.integers(1, 900, size=(B, width)).astype(np.int32)
            retired = [2, B - 1] if win != 100 else list(range(B))
            tables[retired] = 0  # nulled table, stale offset
            work, visited = _work_list(
                jnp.asarray(tables), jnp.asarray(offs), jnp.int32(win),
                chunk=chunk, block_q=block_q, n_qblocks=n_qblocks,
                tile_pages=Tp, block_size=BS,
            )
            seg, tile, flags, pages = (np.asarray(x) for x in work)
            row, qblk = seg // n_qblocks, seg % n_qblocks
            assert len(row) == B * n_qblocks * n_tiles  # the grid's static length
            want = []  # (row, q block, tile, flags) of every item, in order
            kv = np.arange(n_tiles * tt)
            for b in range(B):
                if b in retired:
                    continue
                for i in range(n_qblocks):
                    rows = np.arange(i * block_q, (i + 1) * block_q)
                    qpos = offs[b] + rows % chunk
                    vis = kv[None, :] <= qpos[:, None]
                    if win > 0:
                        vis &= kv[None, :] > qpos[:, None] - win
                    tiles = sorted(set((kv[vis.any(axis=0)] // tt).tolist()))
                    want += [
                        (b, i, t, _WORK | _FIRST * (t == tiles[0])
                         | _LAST * (t == tiles[-1]))
                        for t in tiles
                    ]
            n = len(want)
            got = list(zip(row.tolist(), qblk.tolist(), tile.tolist(), flags.tolist()))
            assert got[:n] == want, (chunk, win)
            last = want[-1][:3] if want else got[0][:3]
            assert all(g == (*last, 0) for g in got[n:]), (chunk, win)
            assert (0 <= tile).all() and (tile < n_tiles).all()
            # the pages a step names are its tile's table entries; in the
            # tail they are the last item's, so no index changes
            pages = pages.reshape(-1, Tp)
            np.testing.assert_array_equal(
                pages, tables.reshape(B, n_tiles, Tp)[row, tile])
            assert (pages[max(n, 1):] == pages[max(n, 1) - 1]).all()
            # and the (row, q block)s with an item are the ones written
            seen = np.zeros((B, n_qblocks), bool)
            for b, i, _, _ in want:
                seen[b, i] = True
            np.testing.assert_array_equal(np.asarray(visited), seen)


def test_scheduler_counts_visited_and_live_pages():
    """engine.kv_pages_visited / engine.kv_pages_live grow, once a
    dispatched window, by rows x table width x steps and by the rows'
    mapped pages x steps."""
    from bee2bee_tpu.engine import EngineConfig, InferenceEngine
    from bee2bee_tpu.metrics import get_registry

    reg = get_registry()

    def counters():
        return (
            reg.counter("engine.kv_pages_visited").value(),
            reg.counter("engine.kv_pages_live").value(),
        )

    eng = InferenceEngine("tiny-llama", engine_config=EngineConfig(
        max_seq_len=128, max_batch=2, decode_chunk=4, kv_block_size=8,
        attention="flash",
    ))
    sched = eng.scheduler
    seen = []
    orig = sched._prepare_window_tables

    def spy(extra, calls):
        before = counters()
        tables = orig(extra, calls)
        if tables is not None:
            live = sum(
                len(sched.cache.row_blocks[b])
                for b, r in enumerate(sched._rows) if r is not None
            )
            seen.append((counters()[0] - before[0], counters()[1] - before[1],
                         tables.shape[0] * tables.shape[1] * calls, live * calls))
        return tables

    sched._prepare_window_tables = spy
    v0, l0 = counters()
    try:
        eng.generate(list(range(3, 23)), max_new_tokens=12, temperature=0.0)
    finally:
        eng.close()
    assert seen, "no decode window was dispatched"
    for dv, dl, want_v, want_l in seen:
        assert (dv, dl) == (want_v, want_l)
        assert 0 < dl <= dv
    v1, l1 = counters()
    assert v1 - v0 == sum(s[2] for s in seen)
    assert l1 - l0 == sum(s[3] for s in seen)


def test_scheduler_counts_live_and_stepped_tiles():
    """engine.kv_tiles{kind}: once a dispatched window, the read's grid
    steps of one layer's call x the window's calls - all of them
    (``stepped``: head groups x rows x q blocks x tiles of the tile plan)
    and those with a work item (``live``: the rows' live tiles), by the
    call's own arithmetic (ops/ragged.read_counts)."""
    from bee2bee_tpu.engine import EngineConfig, InferenceEngine
    from bee2bee_tpu.metrics import get_registry
    from bee2bee_tpu.ops.ragged import _tile_plan, read_counts

    tiles = get_registry().counter("engine.kv_tiles")

    def counters():
        return tiles.value(kind="live"), tiles.value(kind="stepped")

    BS = 8
    eng = InferenceEngine("tiny-llama", engine_config=EngineConfig(
        max_seq_len=128, max_batch=4, decode_chunk=4, kv_block_size=BS,
        attention="flash",
    ))
    cfg, sched = eng.model_cfg, eng.scheduler
    seen = []
    orig = sched._prepare_window_tables

    def spy(extra, calls):
        before = counters()
        tables = orig(extra, calls)
        if tables is not None:
            B, MB = tables.shape
            Th, Tp, _, _ = _tile_plan(
                cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, 1, cfg.head_dim,
                BS, MB, eng.dtype.itemsize, False)
            tt = Tp * BS
            # a decode row at offset o sees keys 0..o: tiles 0..o // tt
            live = sum(
                min(int(sched._offsets[b]) // tt + 1, -(-MB // Tp))
                for b, r in enumerate(sched._rows) if r is not None
            )
            groups = cfg.n_kv_heads // Th
            after = counters()
            seen.append((after[0] - before[0], after[1] - before[1],
                         groups * live * calls, groups * B * -(-MB // Tp) * calls))
        return tables

    sched._prepare_window_tables = spy
    try:
        eng.generate(list(range(3, 40)), max_new_tokens=12, temperature=0.0)
    finally:
        eng.close()
    assert seen, "no decode window was dispatched"
    for d_live, d_stepped, want_live, want_stepped in seen:
        assert (d_live, d_stepped) == (want_live, want_stepped)
        assert 0 < d_live <= d_stepped
    # the same arithmetic, where rows are retired or windowed
    tables = np.zeros((4, 32), np.int32)
    tables[0, :13], tables[2, :3] = 1, 2  # rows 1 and 3 map no page
    kw = dict(heads=32, group=1, chunk=1, head_dim=128, block_size=16, itemsize=2)
    # 32 MHA heads: tiles of 4 pages = 64 keys, 8 of them across the table
    assert read_counts(tables, [200, 999, 40, 9], 0, **kw)[:2] == (4 + 1, 4 * 8)
    assert read_counts(tables, [200, 999, 40, 9], 64, **kw)[:2] == (2 + 1, 4 * 8)
    tables[:] = 0
    assert read_counts(tables, [200, 999, 40, 9], 0, **kw)[:2] == (0, 4 * 8)


# (Hkv, G, T, hd, MB, offs, window): the cells' shapes at the plan PR 44's
# chip runs chose (a page ONE operand: 32 pages a step of 4 GQA heads, 4 of
# 32 MHA heads, a latent row's 16)
WORK_COUNT_CASES = {
    "st-decode-window-binds": (4, 7, 1, 128, 1024, [6300, 4100, 9000, 700, 4095], 4096),
    "st-decode-full-layer": (4, 7, 1, 128, 1024, [6300, 4100, 9000, 700, 4095], 0),
    "st-chunk-2048-crosses-the-window": (4, 7, 2048, 128, 1024, [4096], 4096),
    "h1-decode": (4, 5, 1, 128, 32, [100, 499, 31, 250, 0, 64], 0),
    "phi3-decode": (32, 1, 1, 128, 32, [200, 127, 40, 9, 63, 64], 0),
    "phi3-long-decode": (32, 1, 1, 128, 128, [1800, 2047, 1023], 0),
    "phi3-bucket-2048": (32, 1, 2048, 128, 128, [0], 0),
    "joyai-latent-decode": (1, 32, 1, 640, 32, [100, 499, 31, 250], 0),
}


@pytest.mark.parametrize("case", sorted(WORK_COUNT_CASES))
def test_work_counts_equal_the_devices_item_count_at_the_new_plan(case):
    """ops/ragged.read_counts (host integers, for engine.kv_tiles) against the
    work list the call itself builds on the device, under the same tile plan:
    the items flagged as work and the grid's steps, head groups included; one
    row of every case is retired (table nulled, offset stale)."""
    from bee2bee_tpu.ops.ragged import _WORK, _round_up, _tile_plan, _work_list, read_counts

    Hkv, G, T, hd, MB, offs, window = WORK_COUNT_CASES[case]
    BS, B = 16, len(offs)
    rng = np.random.default_rng(len(case))
    tables = np.zeros((B, MB), np.int32)
    for b, o in enumerate(offs):
        n = -(-(o + T) // BS)
        tables[b, :n] = rng.integers(1, 5000, n)
    if B > 1:
        tables[1] = 0
    Th, Tp, bq, _ = _tile_plan(Hkv, G, T, hd, BS, MB, 2, False)
    n_qblocks = _round_up(G * T, bq) // bq
    work, _ = _work_list(
        jnp.asarray(tables), jnp.asarray(offs, jnp.int32), jnp.int32(window),
        chunk=T, block_q=bq, n_qblocks=n_qblocks, tile_pages=Tp, block_size=BS)
    flags = np.asarray(work[2])
    groups = Hkv // Th
    got = read_counts(tables, offs, window, heads=Hkv, group=G, chunk=T,
                      head_dim=hd, block_size=BS, itemsize=2)[:2]
    assert got == (groups * int((flags & _WORK != 0).sum()), groups * len(flags))
    assert 0 < got[0] < got[1]


# ------------------------- a run of adjacent pages is ONE copy (PR 53)
#
# Where a step takes whole lane-aligned pages of a float pool (under 128 KB
# until PR 59, of any size since) the read takes the pool as ONE operand and
# starts its own copies: a copy group of R table entries whose pool blocks
# are adjacent arrives as one copy, any other page by page, a null entry not
# at all. The cases below are small (16 pages a row, 2-3 rows) and share six
# compiled programs (a table's CONTENTS are an input).

RUN_BS, RUN_MB, RUN_HD = 16, 16, 128
# (rows' lengths, how each row's table is filled): the lengths leave null
# entries inside the last live tile — mid-group (37 tokens = 3 pages), at a
# group's edge (128 tokens = 8 pages) and none (256 = the whole table)
RUN_TABLES = {
    "all-runs": "run",
    "none-descending-ids": "descending",
    "mixed-a-run-broken-mid-group": "broken",
    "null-tail-inside-a-live-tile": "short",
    "a-run-that-starts-off-alignment": "offset",
}
RUN_CHUNKS = {"decode": 1, "verify-k4": 5, "prefill-2-q-blocks": 64}


def _run_tables(kind, lengths, rng):
    """[B, RUN_MB] tables for rows of ``lengths`` tokens, and the pool size."""
    tables = np.zeros((len(lengths), RUN_MB), np.int32)
    nxt = 8  # all-runs: every row's run starts on a multiple of the group
    for b, n in enumerate(lengths):
        need = -(-n // RUN_BS)
        if kind == "offset":
            # three blocks from elsewhere, then a run from pool block 8k+3 on:
            # off the group in the table AND in the pool
            lead = min(3, need)
            ids = np.r_[nxt + 40 - np.arange(lead), nxt + 3 + np.arange(need - lead)]
        else:
            ids = nxt + np.arange(need)
        if kind == "descending":
            ids = ids[::-1]
        if kind == "broken" and need > 6:
            ids[[5, 6]] = ids[[6, 5]]  # entries 5, 6 swapped inside group 0
        tables[b, :need] = ids
        nxt += 48
    return tables, nxt + 48


@functools.lru_cache(maxsize=None)
def _run_read(T, budget):  # (the budget is read at trace time: a jit each)
    return jax.jit(lambda q, kv, tables, off, win: ragged_paged_attention(
        q, kv, tables, off, window=win))


# st's heads with the copy group the chip chose (the whole 16-page tile), h1's
# with a budget that cuts a tile into four groups (the general path)
@pytest.mark.parametrize("heads", [(28, 4, None), (20, 4, 256 * 1024)],
                         ids=["gqa-28-4-a-tile-a-group", "gqa-20-4-four-groups"])
@pytest.mark.parametrize("window", [0, 48], ids=["no-window", "window-binds"])
@pytest.mark.parametrize("chunk", sorted(RUN_CHUNKS))
@pytest.mark.parametrize("tables", sorted(RUN_TABLES))
def test_run_copies_match_dense_and_the_page_by_page_side_bit_for_bit(
        tables, chunk, window, heads, monkeypatch):
    """The run path under interpret mode at st's and h1's heads x 128: equal
    to the dense reference over the gathered view, and BIT-equal to the same
    call on a permuted copy of the pool and tables (every group broken: the
    same items in the same order, copied page by page)."""
    from bee2bee_tpu.ops import ragged
    from bee2bee_tpu.ops.ragged import _tile_plan

    H, Hkv, budget = heads
    if budget:
        monkeypatch.setattr(ragged, "_RUN_BYTES", budget)
    T = RUN_CHUNKS[chunk]
    kind = RUN_TABLES[tables]
    lengths = [256, 37 + T, 128 + T] if kind == "short" else [256, 200 + T, 120 + T]
    lengths = [min(n, RUN_MB * RUN_BS) for n in lengths]
    rng = np.random.default_rng(len(tables) + T)
    tb, NB = _run_tables(kind, lengths, rng)
    Th, Tp, bq, R = _tile_plan(Hkv, H // Hkv, T, RUN_HD, RUN_BS, RUN_MB, 4, False)
    assert (R, Tp // R) == ((4, 4) if budget else (16, 1))
    assert T < 64 or H // Hkv * T > bq  # the prefill chunk: several q blocks
    kv = jnp.asarray(rng.standard_normal((NB, 2, Hkv, RUN_BS, RUN_HD)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((len(lengths), T, H, RUN_HD)), jnp.float32)
    off = np.asarray(lengths, np.int32) - T
    args = (jnp.asarray(off), jnp.int32(window))
    got = _run_read(T, budget)(q, kv, jnp.asarray(tb), *args)
    kg, vg = _gathered(kv, tb)
    qpos = (off[:, None] + np.arange(T)[None, :])[:, :, None]
    kvpos = np.arange(RUN_MB * RUN_BS)[None, None, :]
    mask = kvpos <= qpos
    if window:
        mask &= kvpos > qpos - window
    cfg = replace(CFG, attn_scale=RUN_HD)  # the scores scaled by the head size
    want = _dense_ref(q, kg, vg, jnp.asarray(mask), cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-4)
    perm = np.r_[0, 1 + rng.permutation(NB - 1)].astype(np.int32)
    kv_perm = kv[np.argsort(perm)]  # block perm[i] holds what block i held
    permuted = _run_read(T, budget)(q, kv_perm, jnp.asarray(perm[tb]), *args)
    assert np.array_equal(np.asarray(got), np.asarray(permuted))


@pytest.mark.parametrize("tables", sorted(RUN_TABLES))
def test_host_page_counts_equal_the_work_lists_run_bits(tables, monkeypatch):
    """ops/ragged.read_counts' pages in a run copy / copied one by one (the
    host's numpy, for engine.kv_pages_read) against the bits the call's own
    work list marks on the same tables: R pages a marked group of a step
    with a work item, the rest of its non-null entries single."""
    from bee2bee_tpu.ops.ragged import (
        _WORK, _round_up, _tile_plan, _work_list, read_counts)

    from bee2bee_tpu.ops import ragged

    H, Hkv, T = 28, 4, 1
    kind = RUN_TABLES[tables]
    lengths = [256, 38, 129] if kind == "short" else [256, 201, 121]
    tb, _ = _run_tables(kind, lengths, np.random.default_rng(0))
    off = np.asarray(lengths, np.int32) - T
    for window, budget in ((0, None), (48, None), (0, 128 * 1024), (48, 128 * 1024)):
        if budget:  # four groups a tile
            monkeypatch.setattr(ragged, "_RUN_BYTES", budget)
        Th, Tp, bq, R = _tile_plan(Hkv, H // Hkv, T, RUN_HD, RUN_BS, RUN_MB, 2, False)
        assert (R, Tp // R) == ((4, 4) if budget else (16, 1))
        work, _ = _work_list(
            jnp.asarray(tb), jnp.asarray(off), jnp.int32(window), chunk=T,
            block_q=bq, n_qblocks=_round_up(H // Hkv * T, bq) // bq,
            tile_pages=Tp, block_size=RUN_BS, run_pages=R)
        _, _, flags, pages, runs = (np.asarray(x) for x in work)
        live = flags & _WORK != 0
        marked = (runs[live, None] >> np.arange(Tp // R) & 1).sum()
        mapped = (pages.reshape(-1, Tp)[live] != 0).sum()
        got = read_counts(tb, off, window, heads=Hkv, group=H // Hkv, chunk=T,
                          head_dim=RUN_HD, block_size=RUN_BS, itemsize=2)
        assert got[2:] == (R * marked, mapped - R * marked)
        want_runs = {"run": True, "descending": False}.get(kind)
        if want_runs is not None and not window:
            assert (got[2] > 0) == want_runs
    assert got[2] + got[3] > 0


# Since PR 59 the kernel's own copies serve every lane-aligned bf16 pool whose
# step takes whole pages — ouro's 128 KB pages (16 MHA heads x 128: the tile
# of 8 is the copy group) and joyai's latent rows (640 = 5 x 128 lanes, the
# tile of 16) — and start no copy for a page past the row's causal frontier:
# the blocks a row owns AHEAD of its offset for the decode window.
OWN_COPY_POOLS = {
    "ouro-mha-16x128": dict(H=16, Hkv=16, hd=128, v_width=None, R=8),
    "joyai-latent-640": dict(H=32, Hkv=1, hd=640, v_width=512, R=16),
}
# (how a row's table is filled, blocks it owns ahead of its offset)
OWN_COPY_TABLES = {
    "runs-null-tail": ("run", 0),
    "runs-pages-owned-ahead": ("run", 2),
    "descending-pages-owned-ahead": ("descending", 2),
    "broken-mid-group-owned-ahead": ("broken", 1),
}


def _own_copy_case(pool, tables):
    """(q, pool [NB, parts.., BS, hd], tables, off, blocks owned ahead): one
    decode query a row of 38, 128 and 201 tokens (null entries mid-tile, at a
    tile's edge, a tile and a half) whose tables map ``ahead`` more blocks
    than their tokens fill."""
    c, (kind, ahead) = OWN_COPY_POOLS[pool], OWN_COPY_TABLES[tables]
    lengths = np.asarray([38, 128, 201], np.int32)
    rng = np.random.default_rng(len(pool) + len(tables))
    tb, NB = _run_tables(kind, [int(n) + ahead * RUN_BS for n in lengths], rng)
    parts = (1,) if c["v_width"] else (2, c["Hkv"])
    kv = jnp.asarray(rng.standard_normal((NB, *parts, RUN_BS, c["hd"])), jnp.float32)
    q = jnp.asarray(rng.standard_normal((3, 1, c["H"], c["hd"])), jnp.float32)
    owned_ahead = [
        int(blk) for b, n in enumerate(lengths)
        for blk in tb[b, -(-int(n) // RUN_BS):] if blk]
    assert len(owned_ahead) == 3 * ahead
    return q, kv, tb, lengths - 1, owned_ahead


@pytest.mark.parametrize("tables", sorted(OWN_COPY_TABLES))
@pytest.mark.parametrize("pool", sorted(OWN_COPY_POOLS))
def test_own_copies_of_large_pages_and_latent_rows_equal_the_page_operands(
        pool, tables, monkeypatch):
    """ouro's and joyai's decode call under the kernel's own copies
    (interpret mode) against the SAME call as page operands (a copy budget
    of 0: R = 1, the form both had before PR 59): bit for bit, with null
    entries inside a live tile, blocks owned ahead of the offset and a
    descending table. And no copy is started for a block past the frontier:
    NaN in those blocks never reaches the output (the page operands would
    bring them, behind a zero weight that NaN survives)."""
    from bee2bee_tpu.ops import ragged

    c = OWN_COPY_POOLS[pool]
    q, kv, tb, off, owned_ahead = _own_copy_case(pool, tables)
    plan = dict(latent=bool(c["v_width"]))
    G = c["H"] // c["Hkv"]

    def read(kv):  # (the budget is read at trace time: no jit to key on it)
        return np.asarray(ragged_paged_attention(
            q, kv, jnp.asarray(tb), jnp.asarray(off), v_width=c["v_width"]))

    Th, Tp, _, R = ragged._tile_plan(c["Hkv"], G, 1, c["hd"], RUN_BS, RUN_MB, 4, False, **plan)
    assert (Th, R, Tp) == (c["Hkv"], c["R"] // 2, c["R"] // 2)  # float32: half the bf16 group
    got = read(kv)
    poisoned = read(kv.at[np.asarray(owned_ahead, np.int32)].set(jnp.nan)
                    if owned_ahead else kv)
    assert np.isfinite(poisoned).all() and np.array_equal(got, poisoned)
    monkeypatch.setattr(ragged, "_RUN_BYTES", 0)
    assert ragged._tile_plan(c["Hkv"], G, 1, c["hd"], RUN_BS, RUN_MB, 4, False, **plan)[3] == 1
    assert np.array_equal(got, read(kv))


@pytest.mark.parametrize("pool", sorted(OWN_COPY_POOLS))
def test_read_counts_count_no_page_past_the_frontier(pool):
    """engine.kv_pages_read follows the copies: of a row's mapped blocks
    only those that hold a position at or under its last query's are
    counted, in a run or singly; the blocks owned ahead are not. The page
    operands (an int8 pool's here) bring every mapped entry of a live tile."""
    from bee2bee_tpu.ops.ragged import read_counts

    c = OWN_COPY_POOLS[pool]
    kw = dict(heads=c["Hkv"], group=c["H"] // c["Hkv"], chunk=1,
              head_dim=c["hd"], block_size=RUN_BS, itemsize=2,
              latent=bool(c["v_width"]))
    R = c["R"]
    tb = np.zeros((3, 32), np.int32)
    tb[0, :R] = 8 + np.arange(R)  # a whole run, every page visible
    tb[1, :R] = 64 + np.arange(R)  # a whole run, its last 2 pages ahead
    tb[2, :5] = 128 + np.arange(5)[::-1]  # 5 single pages, 2 of them ahead
    off = np.asarray([R * RUN_BS - 1, (R - 2) * RUN_BS - 1, 3 * RUN_BS - 1])
    live, stepped, in_run, single = read_counts(tb, off, 0, **kw)
    assert (live, stepped) == (3, 3 * 32 // R)
    assert (in_run, single) == (R, (R - 2) + 3)
    # one position on, each row's next block comes into sight
    assert read_counts(tb, off + 1, 0, **kw)[2:] == (R, (R - 1) + 4)
    if not c["v_width"]:
        assert read_counts(tb, off, 0, **{**kw, "quantized": True})[2:] == (0, 2 * R + 5)


# (heads a shard holds, group, head size, quantized, latent) -> R at 16-token
# bf16 pages and a wide table: what decides is whether a page can be copied
# WHOLE (a bf16 stretch on the lanes, every KV head in the step), never its
# size (PR 59) and never a name
RUN_GROUPS = {
    "st-gqa-28-4-32KB": ((4, 7, 128, False, False), 32),
    "h1-gqa-20-4-32KB": ((4, 5, 128, False, False), 32),
    "granite-8-kv-heads-64KB": ((8, 4, 128, False, False), 16),
    "mistral-shard-2-kv-heads-16KB": ((2, 4, 128, False, False), 32),
    "phi3-mha-32-lane-aligned-256KB": ((32, 1, 128, False, False), 4),
    "ouro-mha-16-128KB": ((16, 1, 128, False, False), 8),
    "phi3-head-96-off-the-lanes": ((32, 1, 96, False, False), 1),
    "gpt2-head-64-off-the-lanes": ((12, 1, 64, False, False), 1),
    "int8-pool": ((4, 7, 128, True, False), 1),
    "joyai-latent-rows": ((1, 32, 640, False, True), 16),
}


@pytest.mark.parametrize("case", sorted(RUN_GROUPS))
def test_copy_group_follows_the_pages_bytes(case):
    from bee2bee_tpu.ops.ragged import _RUN_BYTES, _tile_plan

    (Hkv, G, hd, quantized, latent), want = RUN_GROUPS[case]
    for T in (1, 2048):
        Th, Tp, _, R = _tile_plan(Hkv, G, T, hd, 16, 1024, 2, quantized,
                                  latent=latent)
        if T == 1:
            assert R == want
        halves = 1 if latent else 2  # a latent page holds no V
        assert R == 1 or (
            Th == Hkv and halves * Th * 16 * hd * 2 * R <= _RUN_BYTES)
        assert R in (1, Tp)  # what the chip chose: the whole tile, or a page
        assert want > 1 or R == 1
        # the 2,048 chunk of 16 or 32 MHA heads takes SOME heads a step: a
        # page is pieces there, and the page operands stay
        assert (R == 1) == (want == 1 or Th < Hkv)



# ------------------------------------- the pool written and read in place


def _scatter_write(pool, new, tables, off, layer, floor, ceil):
    """core.forward's kv_hook scatter (the dense readers' write, and the
    page-write's specification), on one layer of a stacked pool: ``pool``
    [L, NB, *parts, BS, hd] and ``new`` [B, T, *parts, hd], parts (2, Hkv)
    or a latent row's (1,)."""
    BS = pool.shape[-2]
    positions = off[:, None] + jnp.arange(new.shape[1], dtype=jnp.int32)[None]
    blk = jnp.take_along_axis(tables, positions // BS, axis=1)
    slot = positions % BS
    if floor is not None:
        blk = jnp.where(positions >= floor, blk, 0)
    if ceil is not None:
        blk = jnp.where(positions < ceil, blk, 0)
    parts = (slice(None),) * (pool.ndim - 4)
    return pool.at[layer].set(
        pool[layer].at[(blk, *parts, slot)].set(new.astype(pool.dtype))
    )


# offs, T (BS = 16, tables 8 wide); floor / ceil as core.forward takes them.
# ONE call stores K and V (PR 44): `new` is the chunk's K beside its V
PAGE_WRITE_CASES = {
    "decode-slot-mid-page": dict(offs=[5, 16, 31, 100], T=1),
    "spec-verify-straddles-a-page-edge": dict(offs=[5, 12, 31, 90], T=7),
    "prefill-chunk-unaligned-tail": dict(offs=[37], T=64, ceil=37 + 50),
    "prefill-chunk-on-a-page-edge": dict(offs=[32], T=64, ceil=32 + 64),
    "floor-above-off": dict(offs=[32], T=64, floor=53, ceil=32 + 60),
    "floor-above-the-whole-chunk": dict(offs=[0, 16], T=32, floor=64),
    "ceil-inside-the-chunk": dict(offs=[0, 16], T=32, ceil=21),
    "dead-row": dict(offs=[5, 16, 31, 100], T=1, dead=(2,)),
    "chunk-runs-off-the-table": dict(offs=[125, 120], T=7),
    "gqa-20-4-x128": dict(offs=[3, 47, 64, 1], T=1, Hkv=4, hd=128),
    "gqa-28-4-x128-chunk-off-a-page-edge": dict(offs=[37], T=64, Hkv=4, hd=128),
    "mha-x96": dict(offs=[3, 47], T=7, Hkv=32, hd=96, dtype=jnp.bfloat16),
    # a lane-aligned pool (core.init_paged_pool): 96 stored in 128 lanes
    "mha-x96-in-128-lanes": dict(offs=[3, 47], T=7, Hkv=8, hd=96, lanes=128),
    "decode-x64-in-128-lanes": dict(offs=[5, 16, 31, 100], T=1, hd=64, lanes=128),
    # the latent pool (MLA): ONE 576-wide row a token on a unit axis,
    # stored in 640 lanes; every other layer's slice keeps its bits
    "latent-x576-in-640-lanes": dict(
        offs=[5, 16, 31, 100], T=1, latent=True, hd=576, lanes=640, dtype=jnp.bfloat16),
    "latent-x576-prefill-chunk": dict(
        offs=[37], T=64, ceil=37 + 50, latent=True, hd=576, lanes=640,
        dtype=jnp.bfloat16),
}


@pytest.mark.parametrize("case", sorted(PAGE_WRITE_CASES))
def test_page_write_matches_scatter_bit_for_bit(case):
    """The ONE Mosaic page-write a layer stores exactly what kv_hook's
    scatter stores, K's half and V's half of every block a row owns, and
    touches no other layer. (The null block 0 is garbage by contract: the
    scatter dumps refused positions there, the page-write only what a dead
    row's all-null table sends.)"""
    c = dict(PAGE_WRITE_CASES[case])
    offs, T = c.pop("offs"), c.pop("T")
    hd = c.pop("hd", 64)
    parts = (1,) if c.pop("latent", False) else (2, c.pop("Hkv", 4))
    dtype, dead = c.pop("dtype", jnp.float32), c.pop("dead", ())
    floor, ceil, lanes = c.get("floor"), c.get("ceil"), c.get("lanes", hd)
    B, MB, BS, L, layer = len(offs), 8, 16, 3, 1
    rng = np.random.default_rng(7)
    pool = jnp.asarray(rng.standard_normal((L, 1 + B * MB, *parts, BS, lanes)), dtype)
    pool = pool.at[..., hd:].set(0)  # pad lanes hold zeros, and keep them
    new = jnp.asarray(rng.standard_normal((B, T, *parts, hd)), jnp.float32)
    tables = np.arange(1, 1 + B * MB, dtype=np.int32).reshape(B, MB)
    for b in dead:
        tables[b] = 0
    tables, off = jnp.asarray(tables), jnp.asarray(offs, jnp.int32)

    want = _scatter_write(
        pool, jnp.pad(new, ((0, 0),) * (new.ndim - 1) + ((0, lanes - hd),)),
        tables, off, layer, floor, ceil,
    )
    got = jax.jit(paged_kv_write)(
        pool, new, tables, off, jnp.int32(layer), floor, ceil
    )
    want, got, was = (np.asarray(x, np.float32) for x in (want, got, pool))
    assert np.array_equal(got[:, 1:], want[:, 1:])
    if not dead:  # a dead row's table IS the null block: it writes there
        assert np.array_equal(got[:, 0], was[:, 0])
    if not dead and (floor is None or floor < max(offs) + T):
        assert not np.array_equal(got[layer], was[layer]), "nothing was written"
        if len(parts) == 2:  # K's half and V's half both took their own rows
            for half in (0, 1):
                assert not np.array_equal(got[layer][:, half], was[layer][:, half])
            assert not np.array_equal(got[layer][:, 0], got[layer][:, 1])
    assert chunk_pages(T, BS) == max(
        (o % BS + T - 1) // BS + 1 for o in range(BS)
    )


@pytest.mark.parametrize("layer", [0, 2], ids=["layer-0", "layer-L-1"])
def test_stacked_pool_read_matches_sliced_read(layer):
    """The kernel handed the stacked pool and a layer index makes the
    copies it makes from that layer's slice: same bits out."""
    q, kv, tb, off, *_ = _pool_case(
        offs=[0, 7, 8, 21], T=2, H=4, Hkv=2, hd=16, extra_tables=2
    )
    rng = np.random.default_rng(11)
    stacked = jnp.asarray(
        rng.standard_normal((3, *kv.shape)), kv.dtype).at[layer].set(kv)
    want = ragged_paged_attention(q, kv, tb, off)
    got = jax.jit(ragged_paged_attention)(q, stacked, tb, off, layer=jnp.int32(layer))
    assert np.array_equal(np.asarray(got), np.asarray(want))
    with pytest.raises(ValueError, match="stacked leaf"):
        ragged_paged_attention(q, stacked, tb, off)
    with pytest.raises(ValueError, match="stacked leaf"):
        ragged_paged_attention(q, kv, tb, off, layer=0)
    # the same pool stored lane-aligned (16 -> 128 lanes, zeros beyond):
    # q is padded with zeros, the output cut back - the same numbers
    aligned = jnp.pad(stacked, ((0, 0),) * 5 + ((0, 112),))
    got = jax.jit(ragged_paged_attention)(q, aligned, tb, off, layer=jnp.int32(layer))
    assert got.shape == want.shape
    _assert_close(got, want, atol=1e-6)


# GQA 28/4 x 128 with a window of 24 keys (binding and not), MHA x 96 on a
# lane-aligned pool; offs are q[:, 0]'s positions, pages of 8
OLD_FORM_CASES = {
    "gqa28x4-hd128-decode-window-binds-and-not": dict(
        H=28, Hkv=4, hd=128, T=1, offs=[70, 9, 23, 24], window=24),
    "gqa28x4-hd128-chunk-crosses-the-window": dict(
        H=28, Hkv=4, hd=128, T=16, offs=[20, 0], window=24),
    "mha8-hd96-in-128-lanes": dict(
        H=8, Hkv=8, hd=96, T=3, offs=[40, 5], window=0, lanes=128),
}


@pytest.mark.parametrize("case", sorted(OLD_FORM_CASES))
def test_head_major_pair_at_the_door_reads_what_the_kv_leaf_reads(case):
    """make_ragged_attn_fn's attn still takes a stacked head-major K pool and
    V pool with ``layer=`` (the benchmark's window_read and older callers
    build them by hand): it lays them page-major inside the caller's jit and
    reads them through the SAME kernel — bit for bit what the served form
    (the ``kv`` leaf as ``k``, no ``v``) gives, and both equal the dense
    float32 reference."""
    c = OLD_FORM_CASES[case]
    H, Hkv, hd, T, window = c["H"], c["Hkv"], c["hd"], c["T"], c["window"]
    lanes = c.get("lanes", hd)
    q, kv, tb, off, mask, kg, vg = _pool_case(
        offs=c["offs"], T=T, H=H, Hkv=Hkv, hd=hd, extra_tables=3, seed=len(case))
    L, layer = 3, 1
    rng = np.random.default_rng(5)
    stacked = jnp.asarray(rng.standard_normal((L, *kv.shape)), kv.dtype).at[layer].set(kv)
    stacked = jnp.pad(stacked, ((0, 0),) * 5 + ((0, lanes - hd),))
    cfg = replace(CFG, n_heads=H, n_kv_heads=Hkv, head_dim_override=hd)
    attn = make_ragged_attn_fn(None)
    positions = off[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
    win = jnp.full((1,), window, jnp.int32)

    @jax.jit
    def served(q, pool):
        return attn(q, pool, None, win, cfg, positions=positions, block_tables=tb,
                    layer=jnp.int32(layer))

    @jax.jit
    def door(q, k_pool, v_pool):
        return attn(q, k_pool, v_pool, win, cfg, positions=positions,
                    block_tables=tb, layer=jnp.int32(layer))

    want = served(q, stacked)
    k_pool, v_pool = _head_major(stacked)
    assert k_pool.shape == (L, Hkv, kv.shape[0], 8, lanes)
    got = door(q, k_pool, v_pool)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    if window:
        S = mask.shape[-1]
        q_pos = np.asarray(positions)
        mask = mask & jnp.asarray(
            np.arange(S)[None, None, :] > (q_pos[:, :, None] - window))
        # the window binds for some queries (key 0 is behind it) and not
        # for others
        assert (q_pos >= window).any() and not (q_pos >= window).all()
    _assert_close(want, _dense_ref(q, kg, vg, mask, cfg), atol=5e-5)


def test_forward_writes_and_reads_the_stacked_pool_in_place():
    """core.forward with the ragged attn_fn over a float pool: a prefill
    chunk with a floor and a ceil leaves layer 0 of the pool bit-equal to
    the scatter path's (its K/V depend on no attention), deeper layers and
    the logits within the two readers' float difference."""
    cfg = replace(CFG, n_layers=3)
    params = core.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    BS, MB, T = 8, 6, 16
    pool0 = jax.tree.map(
        lambda a: jnp.asarray(
            np.random.default_rng(3).standard_normal(a.shape), a.dtype),
        core.init_paged_pool(cfg, 2 * MB + 1, BS, jnp.float32),
    )
    assert set(pool0) == {"kv"} and pool0["kv"].shape == (
        3, 2 * MB + 1, 2, cfg.n_kv_heads, BS, cfg.head_dim)
    tables = jnp.arange(1, 2 * MB + 1, dtype=jnp.int32).reshape(2, MB)
    ids = jnp.asarray(np.random.default_rng(4).integers(3, 200, (2, T)), jnp.int32)
    off = jnp.asarray([11, 16], jnp.int32)

    def run(attn_fn):
        return jax.jit(lambda p, c: core.forward(
            p, cfg, ids, c, off, attn_fn=attn_fn, block_tables=tables,
            paged_write_floor=jnp.int32(13), paged_write_ceil=jnp.int32(30),
        ))(params, pool0)

    (lg_d, pool_d), (lg_r, pool_r) = run(None), run(make_ragged_attn_fn())
    d, r = np.asarray(pool_d["kv"]), np.asarray(pool_r["kv"])
    assert np.array_equal(r[0][1:], d[0][1:])
    np.testing.assert_allclose(r[:, 1:], d[:, 1:], atol=1e-4)
    np.testing.assert_allclose(np.asarray(lg_r), np.asarray(lg_d), atol=2e-4)


def test_forward_issues_one_page_write_call_a_layer():
    """The served program as traced: ONE aliased page-write and one read a
    layer (the two Mosaic calls), where K and V were written by two."""
    cfg = replace(CFG, n_layers=2)
    params = jax.eval_shape(
        lambda: core.init_params(cfg, jax.random.PRNGKey(0), jnp.float32))
    pool = jax.eval_shape(lambda: core.init_paged_pool(cfg, 9, 8, jnp.float32))
    tables = jnp.arange(1, 9, dtype=jnp.int32).reshape(2, 4)
    ids = jnp.zeros((2, 1), jnp.int32)
    text = str(jax.make_jaxpr(lambda p, c: core.forward(
        p, cfg, ids, c, jnp.asarray([3, 9], jnp.int32),
        attn_fn=make_ragged_attn_fn(), block_tables=tables,
    ))(params, pool))
    # the layer loop is one scan body: its two kernel calls are the program's
    assert text.count("pallas_call[") == 2, text.count("pallas_call[")
    assert text.count("input_output_aliases=((5, 0),)") == 1  # the write


def test_scheduler_counts_written_pages():
    """engine.kv_pages_written: rows x the pages a chunk can touch x the ONE
    write call of every layer of every forward a dispatch runs (a page holds
    K beside V); nothing on the dense reader's scatter path."""
    from bee2bee_tpu.engine import EngineConfig, InferenceEngine
    from bee2bee_tpu.metrics import get_registry

    written = get_registry().counter("engine.kv_pages_written")
    kw = dict(max_seq_len=128, max_batch=2, decode_chunk=4, kv_block_size=8)

    def serve(attention):
        eng = InferenceEngine(
            "tiny-llama", engine_config=EngineConfig(attention=attention, **kw))
        sched, want = eng.scheduler, []
        windows, prefill = sched._prepare_window_tables, eng._prefill

        def spy_window(extra, calls):
            tables = windows(extra, calls)
            if tables is not None:
                want.append(tables.shape[0] * chunk_pages(extra // calls, 8) * calls)
            return tables

        def spy_prefill(params, tokens, *a, **k):
            want.append(tokens.shape[0] * chunk_pages(tokens.shape[1], 8))
            return prefill(params, tokens, *a, **k)

        sched._prepare_window_tables, eng._prefill = spy_window, spy_prefill
        before = written.value()
        try:
            eng.generate(list(range(3, 23)), max_new_tokens=12, temperature=0.0)
        finally:
            eng.close()
        return written.value() - before, sum(want) * eng.model_cfg.n_layers

    got, want = serve("flash")
    assert got == want > 0
    assert serve("dense")[0] == 0


def test_pool_is_lane_aligned_only_where_the_kernels_own_it_on_a_tpu():
    """core.init_paged_pool(lane_aligned=True) stores the head at the 128-lane
    width (so the TPU's default layout is the kernels' own); the engine asks
    for it only on the in-place path on a TPU - never on this CPU mesh, where
    every pool keeps the model's head size."""
    from bee2bee_tpu.engine import EngineConfig, InferenceEngine

    for model, lanes in (("phi-3-mini", 128), ("falcon-h1-34b-6l", 128), ("gemma-2b", 256)):
        cfg = replace(get_config(model), n_layers=1)
        shapes = jax.eval_shape(
            lambda cfg=cfg: core.init_paged_pool(cfg, 4, 16, lane_aligned=True))
        assert set(shapes) == {"kv"}  # ONE leaf: K beside V, page-major
        assert shapes["kv"].shape == (1, 4, 2, cfg.n_kv_heads, 16, lanes)
        plain = jax.eval_shape(lambda cfg=cfg: core.init_paged_pool(cfg, 4, 16))
        assert plain["kv"].shape[-1] == cfg.head_dim
    kw = dict(max_seq_len=128, max_batch=2, decode_chunk=4, kv_block_size=8)
    for attention in ("flash", "dense"):
        eng = InferenceEngine("tiny-llama", engine_config=EngineConfig(
            attention=attention, **kw))
        try:
            assert eng.new_pool()["kv"].shape[-1] == eng.model_cfg.head_dim
        finally:
            eng.close()


# ------------------------------------------------- engine-level acceptance


def test_single_batch_mixes_prefill_decode_and_spec_verify():
    """THE acceptance bar (ISSUE 8): one engine, attention='flash',
    --spec on, serving a long chunk-prefilled prompt, a plain decoding
    prompt, and a repetitive prompt whose rows spec-verify [B, K+1]
    chunks — concurrently, through the ragged kernel — with greedy
    token-for-token parity vs the dense engine, and speculation must
    actually have engaged."""
    from bee2bee_tpu.engine import EngineConfig, InferenceEngine

    kw = dict(
        max_seq_len=128, dtype="float32", cache_dtype="float32",
        decode_chunk=4, prefill_buckets=(16, 32, 64), max_batch=4,
        prefill_chunk=16, prefix_cache_entries=4,
    )
    rng = np.random.default_rng(9)
    long_prompt = list(rng.integers(3, 500, size=50))  # chunked prefill
    plain_prompt = list(rng.integers(3, 500, size=12))
    rep_prompt = [5, 6, 7, 8, 9] * 3 + [5, 6, 7]  # drafts from step one

    jobs = [(long_prompt, 10), (plain_prompt, 12), (rep_prompt, 24)]

    ref = InferenceEngine("tiny-llama", engine_config=EngineConfig(**kw))
    want = [
        ref.generate(p, max_new_tokens=n, temperature=0.0).token_ids
        for p, n in jobs
    ]
    ref.close()

    eng = InferenceEngine(
        "tiny-llama",
        engine_config=EngineConfig(attention="flash", spec_tokens=6, **kw),
    )
    try:
        results: list = [None] * len(jobs)

        def run(i):
            p, n = jobs[i]
            results[i] = eng.generate(p, max_new_tokens=n, temperature=0.0)

        threads = [
            threading.Thread(target=run, args=(i,)) for i in range(len(jobs))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i in range(len(jobs)):
            assert results[i].token_ids == want[i], f"row {i} diverged"
        st = eng.scheduler.stats
        assert st.peak_active >= 2, "rows never actually batched"
        assert st.spec_steps > 0 and st.spec_drafted > 0, (
            "speculation never engaged — the mixed-batch claim is untested"
        )
        # CoW prefix sharing under the kernel: a repeat of the long prompt
        # admits from pinned blocks (at most one partial-block copy) and
        # the kernel reads the shared donor blocks bit-identically
        again = eng.generate(long_prompt, max_new_tokens=10, temperature=0.0)
        assert again.token_ids == want[0]
        assert st.prefix_hits >= 1
        # row refs all released; only the prefix cache's pins remain (the
        # three distinct prompts pin disjoint block sets, and the repeat
        # de-duplicates on its exact key instead of re-pinning)
        pinned = sum(
            len(blocks)
            for blocks in eng.scheduler.cache.prefix._entries.values()
        )
        assert st.paged_blocks_in_use == pinned
    finally:
        eng.close()


def test_int8_batch_mixes_prefill_decode_and_spec_verify():
    """ISSUE 12 engine-level acceptance: one int8-pool engine with
    attention='flash' and --spec on serves a chunk-prefilled prompt, a
    plain decoding prompt, and a spec-verifying repetitive prompt
    concurrently — all three chunk shapes riding the QUANTIZED kernel —
    with token-for-token parity vs the int8 DENSE engine under the same
    spec setting (identical write sequences → identical pages and
    scales, so the two READ paths see the same quantized bytes and any
    divergence is a kernel-dequant bug), and speculation must actually
    have engaged. Quantization tolerance vs full precision is pinned by
    the test_paged_cache family sweep."""
    from bee2bee_tpu.engine import EngineConfig, InferenceEngine

    kw = dict(
        max_seq_len=128, dtype="float32", cache_dtype="int8",
        decode_chunk=4, prefill_buckets=(16, 32, 64), max_batch=4,
        prefill_chunk=16, prefix_cache_entries=4,
    )
    rng = np.random.default_rng(9)
    long_prompt = list(rng.integers(3, 500, size=50))  # chunked prefill
    plain_prompt = list(rng.integers(3, 500, size=12))
    rep_prompt = [5, 6, 7, 8, 9] * 3 + [5, 6, 7]  # drafts from step one

    jobs = [(long_prompt, 10), (plain_prompt, 12), (rep_prompt, 24)]

    ref = InferenceEngine(
        "tiny-llama", engine_config=EngineConfig(spec_tokens=6, **kw)
    )
    want = [
        ref.generate(p, max_new_tokens=n, temperature=0.0).token_ids
        for p, n in jobs
    ]
    ref.close()

    eng = InferenceEngine(
        "tiny-llama",
        engine_config=EngineConfig(attention="flash", spec_tokens=6, **kw),
    )
    try:
        results: list = [None] * len(jobs)

        def run(i):
            p, n = jobs[i]
            results[i] = eng.generate(p, max_new_tokens=n, temperature=0.0)

        threads = [
            threading.Thread(target=run, args=(i,)) for i in range(len(jobs))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i in range(len(jobs)):
            assert results[i].token_ids == want[i], f"row {i} diverged"
        st = eng.scheduler.stats
        assert st.peak_active >= 2, "rows never actually batched"
        assert st.spec_steps > 0 and st.spec_drafted > 0, (
            "speculation never engaged through the quantized kernel"
        )
        assert st.paged_blocks_in_use >= 0  # released below
        # every row retired: only prefix pins (scales included) remain
        pinned = sum(
            len(blocks)
            for blocks in eng.scheduler.cache.prefix._entries.values()
        )
        assert st.paged_blocks_in_use == pinned
    finally:
        eng.close()


def test_flash_engine_spec_parity_sequential():
    """Spec-on ragged decode == spec-off dense decode, token-for-token,
    on the repetitive workload (the drafter engages every few steps)."""
    from bee2bee_tpu.engine import EngineConfig, InferenceEngine

    kw = dict(
        max_seq_len=128, dtype="float32", cache_dtype="float32",
        decode_chunk=4, prefill_buckets=(16, 32, 64),
    )
    rep = [5, 6, 7, 8, 9] * 3 + [5, 6, 7]
    ref = InferenceEngine("tiny-llama", engine_config=EngineConfig(**kw))
    want = ref.generate(rep, max_new_tokens=40, temperature=0.0).token_ids
    ref.close()
    eng = InferenceEngine(
        "tiny-llama",
        engine_config=EngineConfig(attention="flash", spec_tokens=6, **kw),
    )
    try:
        got = eng.generate(rep, max_new_tokens=40, temperature=0.0).token_ids
        st = eng.scheduler.stats
        assert got == want
        assert st.spec_drafted > 0 and st.spec_steps > 0
    finally:
        eng.close()


# ---------------- latent rows (PR 39, MLA): one fetched tile, keys AND values


@pytest.mark.parametrize("T,offs,stacked,dims", [
    (1, [0, 7, 8, 30], False, (40, 24, 5)), (1, [5, 0, 21], True, (40, 24, 5)),
    (13, [0, 9], True, (40, 24, 5)), (7, [16, 3], False, (40, 24, 5)),
    (1, [5, 41], True, (576, 512, 32)),
], ids=["decode", "decode-stacked", "prefill-chunk-stacked", "spec-shape",
        "decode-stacked-576-wide-32-heads"])
def test_latent_read_matches_the_dense_path(T, offs, stacked, dims):
    """The latent read against core._latent_attention over the gathered rows,
    at widths off the 128 lanes (row 40 = 24 + 16, values its first 24
    columns), 5 heads sharing each row, rows of unequal length, null-block
    table tails; on one layer's slice and on the stacked pool; and at the
    published widths (576-wide rows, values the first 512, 32 heads)."""
    (W, R, H), BS = dims, 8
    q, kv, tables, offs_, mask, kg, _ = _pool_case(
        offs, T, H, 1, W, BS=BS, extra_tables=2, seed=11)
    rows = kv[:, :1, 0]  # [NB, 1, BS, W]: a layer of the latent leaf
    scale = 0.21
    want = core._latent_attention(q, kg[:, :, 0], mask[:, None], R, scale)
    if stacked:
        pool = jnp.stack([jnp.zeros_like(rows), rows, jnp.ones_like(rows)])
        got = ragged_paged_attention(q, pool, tables, offs_, sm_scale=scale,
                                     v_width=R, layer=jnp.int32(1))
    else:
        got = ragged_paged_attention(q, rows, tables, offs_, sm_scale=scale, v_width=R)
    assert got.shape == (len(offs), T, H * R)
    _assert_close(got.reshape(want.shape), want)


def test_latent_read_takes_no_kv_leaf_and_no_scales():
    q, kv, tables, offs_, *_ = _pool_case([3], 1, 2, 1, 16)
    with pytest.raises(ValueError, match="latent rows"):
        ragged_paged_attention(q, kv, tables, offs_, v_width=8)
    with pytest.raises(ValueError, match="latent rows"):
        ragged_paged_attention(q, kv[:, :1, 0], tables, offs_, v_width=8,
                               scale=jnp.ones(kv.shape[:1] + (1,)))
