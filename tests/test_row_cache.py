"""engine/paged.RowCache on its own: where a row's cache lives, driven
without a scheduler thread (the engine is built, its scheduler never is).

Each case ends with every allocator refcount back at zero — the invariant
whose loss shows, in serving, only as a pool that exhausts days later.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bee2bee_tpu.engine import EngineConfig, InferenceEngine, FeatureUnsupported
from bee2bee_tpu.engine.paged import PoolExhausted, RowCache
from bee2bee_tpu.models import core

BS = 16
KW = dict(
    max_seq_len=128, dtype="float32", cache_dtype="float32", decode_chunk=4,
    prefill_buckets=(16, 32, 64), kv_block_size=BS,
)


def _cache(model="tiny-llama", max_batch=4, **over):
    eng = InferenceEngine(
        model, engine_config=EngineConfig(max_batch=max_batch, **{**KW, **over})
    )
    assert eng._scheduler is None  # nothing here starts the thread
    return eng, RowCache(eng, max_batch)


def _assert_all_free(rc: RowCache):
    assert rc.alloc.used_count == 0
    assert not rc.alloc._refs.any(), np.flatnonzero(rc.alloc._refs)
    assert not rc.tables.any() and not any(rc.row_blocks)


def _fill(rc: RowCache, seed=0):
    """Distinct values in every pool element, so a block's content names it."""
    keys = jax.random.split(jax.random.key(seed), len(rc.pool))
    rc.pool = {
        name: jax.random.normal(k, arr.shape, jnp.float32).astype(arr.dtype)
        for k, (name, arr) in zip(keys, rc.pool.items())
    }


def _block(rc: RowCache, b: int) -> dict:
    return {name: np.asarray(arr[:, b]) for name, arr in rc.pool.items()}


def test_cover_release_and_release_deferred_while_in_flight():
    eng, rc = _cache()
    try:
        rc.cover(0, BS + 1)  # 17 positions: two blocks
        rc.cover(0, 10)  # already covered: nothing new
        rc.cover(1, 1)
        assert [len(r) for r in rc.row_blocks] == [2, 1, 0, 0]
        assert rc.tables[0, :2].tolist() == rc.row_blocks[0]
        assert 0 not in rc.row_blocks[0] + rc.row_blocks[1]  # never the null block
        assert rc.alloc.used_count == 3 == rc.alloc.hwm
        held = list(rc.row_blocks[0])
        # a window still in flight dead-row-scatters into row 0's blocks:
        # the table row is nulled at once, the blocks are not reusable yet
        rc.release(0, in_flight=True)
        assert not rc.tables[0].any() and rc.row_blocks[0] == []
        assert rc.alloc.used_count == 3
        rc.cover(2, 2 * BS)
        assert not set(rc.row_blocks[2]) & set(held)
        rc.flush_deferred()
        assert rc.alloc.used_count == 3 and all(rc.alloc.refcount(b) == 0 for b in held)
        rc.flush_deferred()  # idempotent
        rc.release(1)
        rc.release(2)
        _assert_all_free(rc)
    finally:
        eng.close()


@pytest.mark.parametrize("model", ["tiny-llama", "tiny-falcon-h1"])
def test_compaction_move_and_bucket_resize(model):
    """A row moves by table alone; a recurrent model's state slot moves with
    it and follows the batch bucket up and down. The pool never resizes."""
    eng, rc = _cache(model)
    try:
        pool_shapes = {k: v.shape for k, v in rc.pool.items()}
        rc.resize(4)
        rc.cover(0, 5)
        rc.cover(3, 40)
        moved = list(rc.row_blocks[3])
        if rc.recurrent:
            marked = jax.tree.map(lambda a: a + 3, eng.new_state(1))
            rc.put_state([3], marked)
        rc.move(3, 1)
        assert rc.row_blocks[1] == moved and rc.row_blocks[3] == []
        assert rc.tables[1, :3].tolist() == moved and not rc.tables[3].any()
        rc.resize(2)
        rc.resize(4)
        if rc.recurrent:
            for name, leaf in rc.state.items():
                assert leaf.shape[1] == 4
                got = np.asarray(leaf)
                assert (got[:, 1] == 3).all(), name  # the row's state, moved + kept
                assert not got[:, 2:].any(), name  # regrown slots are zero
            reg = eng.introspect.ledger.snapshot()["components"]
            assert reg["state"] == sum(a.nbytes for a in rc.state.values())
        else:
            assert rc.state is None
        assert {k: v.shape for k, v in rc.pool.items()} == pool_shapes
        rc.release(0)
        rc.release(1)
        _assert_all_free(rc)
    finally:
        eng.close()


def test_adopt_shares_full_blocks_copies_the_partial_one_and_sets_the_floor():
    eng, rc = _cache(prefix_cache_entries=2)
    try:
        donor = list(range(3, 43))  # 40 tokens: two full blocks + 8 slots
        assert rc.match_prefix(donor) == (0, None)
        assert rc.adopt(0, len(donor), 0, None) is False
        rc.cover(0, len(donor))
        _fill(rc)
        rc.publish_prefix(0, donor)
        d0, d1, d2 = rc.row_blocks[0]
        assert [rc.alloc.refcount(b) for b in (d0, d1, d2)] == [2, 2, 2]

        longer = donor + [7] * 8
        start, cached = rc.match_prefix(longer)
        assert start == 40 and tuple(cached) == (d0, d1, d2)
        before = {b: _block(rc, b) for b in (d0, d1, d2)}
        assert rc.adopt(1, len(longer), start, cached) is True
        s0, s1, own = rc.row_blocks[1]
        # the write floor: every position below `start` sits in a block the
        # borrower must not write — the donor's two full ones, shared ...
        assert (s0, s1) == (d0, d1) and own not in (d0, d1, d2)
        assert [rc.alloc.refcount(b) for b in (d0, d1, d2, own)] == [3, 3, 2, 1]
        # ... and its own copy of the partial one, identical so far
        for name, want in before[d2].items():
            np.testing.assert_array_equal(_block(rc, own)[name], want)
        for b, want in before.items():  # donor blocks: bit-identical
            for name in want:
                np.testing.assert_array_equal(_block(rc, b)[name], want[name])
        rc.cover(1, len(longer))  # 48 positions: the three blocks suffice
        assert len(rc.row_blocks[1]) == 3
        rc.release(0)
        rc.release(1)
        assert rc.alloc.used_count == 3  # the pins alone
        rc.prefix.clear()
        _assert_all_free(rc)
    finally:
        eng.close()


def test_adopt_refuses_before_any_copy_when_the_prompt_cannot_fit():
    eng, rc = _cache(prefix_cache_entries=2, kv_pool_blocks=5)  # 4 usable
    try:
        donor = list(range(3, 27))  # 24 tokens: one full block + 8 slots
        rc.adopt(0, len(donor), 0, None)
        rc.cover(0, len(donor))
        rc.publish_prefix(0, donor)
        longer = donor + [9] * 40  # 64 tokens: 3 fresh blocks, 2 free
        start, cached = rc.match_prefix(longer)
        pool_before = rc.pool
        with pytest.raises(PoolExhausted):
            rc.adopt(1, len(longer), start, cached)
        assert rc.pool is pool_before  # no device work happened
        rc.release(1)  # the caller's move (scheduler._plan_row)
        # the pins were given up trying (they could not cover it); the
        # donor ROW's blocks, and the refs adopt took for row 1, are intact
        d0, d1 = rc.row_blocks[0]
        assert len(rc.prefix) == 0
        assert [rc.alloc.refcount(b) for b in (d0, d1)] == [1, 1]
        rc.release(0)
        _assert_all_free(rc)
    finally:
        eng.close()


def test_prefix_pins_yield_under_pressure_and_a_rows_blocks_do_not():
    eng, rc = _cache(prefix_cache_entries=4, kv_pool_blocks=6)  # 5 usable
    try:
        ids = list(range(3, 35))
        rc.cover(0, len(ids))
        rc.publish_prefix(0, ids)
        rc.release(0)
        assert rc.alloc.used_count == 2 and len(rc.prefix) == 1  # pinned, rowless
        assert rc.growth_fits([(1, 3 * BS)]) and not rc.growth_fits([(1, 4 * BS)])
        rc.cover(1, 4 * BS)  # 4 blocks, 3 free: the pin is reclaimed
        assert len(rc.prefix) == 0 and rc.alloc.used_count == 4
        with pytest.raises(PoolExhausted):
            rc.cover(2, 2 * BS)  # row 1's blocks are not for the taking
        assert rc.row_blocks[2] == [] and len(rc.row_blocks[1]) == 4
        assert rc.match_prefix(ids + [1]) == (0, None)
        rc.release(1)
        _assert_all_free(rc)
    finally:
        eng.close()


def test_int8_pool_zeroes_the_scale_of_a_recycled_block():
    """The quantize-on-write running max must not inherit the previous
    tenant's amax: a block handed out again starts at scale zero, and no
    other block's scale is touched."""
    eng, rc = _cache(cache_dtype="int8")
    try:
        rc.cover(0, BS)
        rc.cover(1, BS)
        (used,), (other,) = rc.row_blocks[0], rc.row_blocks[1]
        rc.pool = dict(
            rc.pool,
            kv_scale=rc.pool["kv_scale"].at[:, used].set(2.0).at[:, other].set(3.0),
        )
        rc.release(0)
        rc.cover(2, BS)
        assert rc.row_blocks[2] == [used]  # the free list hands it straight back
        scale = np.asarray(rc.pool["kv_scale"])  # [L, NB, 2, Hkv]: K's and V's
        assert scale.shape[2] == 2
        assert not scale[:, used].any()
        assert (scale[:, other] == 3.0).all()
        rc.release(1)
        rc.release(2)
        _assert_all_free(rc)
    finally:
        eng.close()


def test_export_import_round_trip_between_lane_aligned_and_plain_pools():
    """Pages travel at the model's head size whichever way the pool stores
    them (core.init_paged_pool lane_aligned: 16 in 128 lanes here)."""
    eng, plain = _cache()
    aligned = RowCache(eng, 4)
    hd = eng.model_cfg.head_dim
    try:
        aligned.pool = jax.jit(functools.partial(
            core.init_paged_pool, eng.model_cfg, eng.pool_blocks, BS,
            jnp.float32, lane_aligned=True,
        ))()
        assert aligned.pool["kv"].shape[-1] == 128 != hd
        n = 2 * BS + 5  # 37 positions: three blocks, a 4-wide index
        plain.cover(0, n)
        _fill(plain)
        nb, sent = plain.export_row(0, n)
        assert nb == 3 and sent["k"].shape == (
            eng.model_cfg.n_layers, eng.model_cfg.n_kv_heads, 3, BS, hd)
        aligned.import_row(2, n, sent)
        assert len(aligned.row_blocks[2]) == 3
        assert aligned.tables[2, :3].tolist() == aligned.row_blocks[2]
        # the stored leaf is page-major, K beside V; the wire is head-major
        got = np.asarray(aligned.pool["kv"])[:, aligned.row_blocks[2]]
        for half, name in enumerate(("k", "v")):
            np.testing.assert_array_equal(
                got[:, :, half, :, :, :hd].swapaxes(1, 2), sent[name])
        assert not got[..., hd:].any()  # pad lanes stay zero
        nb2, back = aligned.export_row(2, n)  # and out again, cut to size
        assert nb2 == 3
        plain.import_row(1, n, back)
        for half, name in enumerate(("k", "v")):
            np.testing.assert_array_equal(
                np.asarray(plain.pool["kv"])[:, plain.row_blocks[1], half]
                .swapaxes(1, 2), sent[name]
            )
        assert plain.export_row(3, 0) == (0, None)  # nothing written yet
        for rc, rows in ((plain, (0, 1)), (aligned, (2,))):
            for b in rows:
                rc.release(b)
            _assert_all_free(rc)
    finally:
        eng.close()


def test_import_refuses_with_nothing_taken_and_recurrent_export_is_typed():
    eng, rc = _cache(kv_pool_blocks=3)  # 2 usable
    try:
        rc.cover(0, 2 * BS)
        _, kv = rc.export_row(0, 2 * BS)
        with pytest.raises(PoolExhausted):
            rc.import_row(1, 2 * BS, kv)
        assert rc.row_blocks[1] == [] and rc.alloc.used_count == 2
        rc.release(0)
        _assert_all_free(rc)
    finally:
        eng.close()
    eng, rc = _cache("tiny-falcon-h1")
    try:
        rc.cover(0, BS)
        with pytest.raises(FeatureUnsupported) as err:
            rc.export_row(0, BS)  # its pages are not its whole state
        assert err.value.feature == "kv_export"
        rc.release(0)
        _assert_all_free(rc)
    finally:
        eng.close()


def test_window_tables_are_bucketed_and_count_what_is_mapped():
    eng, rc = _cache()
    try:
        rc.cover(0, 3 * BS)  # 3 blocks -> width 4
        rc.cover(2, BS)
        table, live = rc.window_table([0, 2], 4)
        assert table.shape == (4, 4) and live == 4 and table.flags.c_contiguous
        assert table[0, :3].tolist() == rc.row_blocks[0] and not table[1].any()
        assert rc.rows_table([2]).shape == (1, 1)
        # a prefill group: its rows' tables at ONE width, never under a fresh
        # prompt's that fills the chunk; a dead row (-1) maps the null block
        group = rc.rows_table([2, -1, 0], chunk=2 * BS)
        assert group.shape == (3, 4) and not group[1].any()
        assert group[2, :3].tolist() == rc.row_blocks[0] and group[0, 0] == rc.row_blocks[2][0]
        assert rc.rows_table([2, -1], chunk=2 * BS).shape == (2, 2)
        assert rc.tables[0, :3].tolist() == rc.row_blocks[0]  # a copy: the table itself is whole
        rc.cover(0, eng.max_seq_len + 4)  # the whole row: the physical width
        table, live = rc.window_table([0, 2], 4)
        assert table.shape[1] == eng.blocks_per_row == 9 and live == 10
        assert all(rc.declared_table_width(w) for w in (None, 1, 2, 4, 8, 9))
        assert not any(rc.declared_table_width(w) for w in (0, 3, 10, 16))
        rc.release(0)
        rc.release(2)
        _assert_all_free(rc)
    finally:
        eng.close()


@pytest.mark.parametrize("model", ["tiny-llama", "tiny-falcon-h1"])
def test_rebuild_after_a_device_failure_starts_from_nothing(model):
    over = {} if model == "tiny-falcon-h1" else {"prefix_cache_entries": 2}
    eng, rc = _cache(model, **over)
    try:
        rc.resize(4)
        rc.cover(0, 40)
        rc.cover(3, 5)
        rc.release(3, in_flight=True)
        if rc.prefix is not None:
            rc.publish_prefix(0, list(range(3, 43)))
        _fill(rc)
        old_alloc = rc.alloc
        rc.rebuild()
        assert rc.alloc is not old_alloc and rc.alloc.hwm == 0
        _assert_all_free(rc)
        assert rc.prefix is None or (
            len(rc.prefix) == 0 and rc.prefix.allocator is rc.alloc
        )
        assert not any(np.asarray(a).any() for a in rc.pool.values())
        rc.flush_deferred()  # the deferred blocks went with the old allocator
        if rc.recurrent:
            assert all(leaf.shape[1] == 1 for leaf in rc.state.values())
        # the HBM ledger follows the new arrays
        parts = eng.introspect.ledger.snapshot()["components"]
        assert parts["kv_pool"] == sum(a.nbytes for a in rc.pool.values())
        rc.cover(0, BS)  # and it serves again
        rc.release(0)
        _assert_all_free(rc)
    finally:
        eng.close()


@pytest.mark.parametrize("cache_dtype", ["float32", "int8"])
def test_export_import_keeps_the_head_major_wire_format(cache_dtype):
    """The stored leaf is page-major with K beside V (PR 44); the pages a row
    exports are what they were: ``{"k", "v"}`` [L, Hkv, nb, BS, hd] (+
    ``{"k_scale", "v_scale"}`` [L, Hkv, nb] of an int8 pool), block axis 2,
    so a peer on either layout takes them. Out, into another cache, and out
    again: the same tensors, and the importer's stored pages are the
    exporter's."""
    eng, src = _cache(cache_dtype=cache_dtype)
    dst = RowCache(eng, 4)
    cfg = eng.model_cfg
    L, Hkv, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    try:
        n = 2 * BS + 5
        src.cover(1, n)
        dst.cover(0, BS)  # the importer's blocks are not the exporter's
        keys = jax.random.split(jax.random.key(3), len(src.pool))
        src.pool = {
            name: (jax.random.randint(k, arr.shape, -100, 100).astype(arr.dtype)
                   if arr.dtype == jnp.int8
                   else jax.random.uniform(k, arr.shape, jnp.float32, 0.5, 2.0))
            for k, (name, arr) in zip(keys, src.pool.items())
        }
        assert src.pool["kv"].shape == (L, eng.pool_blocks, 2, Hkv, BS, hd)
        nb, sent = src.export_row(1, n)
        names = {"k", "v"} | ({"k_scale", "v_scale"} if cache_dtype == "int8" else set())
        assert nb == 3 and set(sent) == names
        assert sent["k"].shape == sent["v"].shape == (L, Hkv, 3, BS, hd)
        if cache_dtype == "int8":
            assert sent["k_scale"].shape == sent["v_scale"].shape == (L, Hkv, 3)
            assert sent["k"].dtype == np.int8 and sent["k_scale"].dtype == np.float32
        # the wire's K is the stored pages' first half, its V the second
        stored = np.asarray(src.pool["kv"])[:, src.row_blocks[1]]
        np.testing.assert_array_equal(sent["k"], stored[:, :, 0].swapaxes(1, 2))
        np.testing.assert_array_equal(sent["v"], stored[:, :, 1].swapaxes(1, 2))
        assert not np.array_equal(sent["k"], sent["v"])
        dst.import_row(2, n, sent)
        assert dst.row_blocks[2] != src.row_blocks[1]
        for name in src.pool:
            np.testing.assert_array_equal(
                np.asarray(dst.pool[name])[:, dst.row_blocks[2]],
                np.asarray(src.pool[name])[:, src.row_blocks[1]])
        nb2, back = dst.export_row(2, n)
        assert nb2 == 3 and set(back) == names
        for name in names:
            np.testing.assert_array_equal(back[name], sent[name])
        src.release(1)
        dst.release(0)
        dst.release(2)
        _assert_all_free(src)
        _assert_all_free(dst)
    finally:
        eng.close()


def test_growth_goes_on_behind_the_rows_last_block_and_pages_read_are_counted():
    """A prompt's blocks are one ascending run; decode growth takes the
    block behind the row's last while it is free, any other once a neighbour
    holds it; and count_tiles books engine.kv_pages_read by the read's own
    host arithmetic (ops/ragged.read_counts) on the dispatched tables."""
    from bee2bee_tpu.metrics import get_registry
    from bee2bee_tpu.ops.ragged import read_counts

    eng, rc = _cache(attention="flash")
    try:
        rc.cover(0, 3 * BS)
        rc.cover(1, 2 * BS)
        first, second = list(rc.row_blocks[0]), list(rc.row_blocks[1])
        assert first == [first[0], first[0] + 1, first[0] + 2]
        assert second == [first[-1] + 1, first[-1] + 2]
        rc.cover(1, 3 * BS)  # behind row 1's last: free
        assert rc.row_blocks[1] == second + [second[-1] + 1]
        rc.cover(0, 4 * BS)  # behind row 0's last sits row 1: any other
        assert rc.row_blocks[0][-1] not in first + rc.row_blocks[1]
        rc.release(1)
        rc.cover(0, 6 * BS)  # the freed blocks merged: a run of two
        assert rc.row_blocks[0][-1] == rc.row_blocks[0][-2] + 1

        pages = get_registry().counter("engine.kv_pages_read")
        before = [pages.value(kind=k) for k in ("in_run", "single")]
        tables, mapped = rc.window_table([0], 2)
        offsets = np.asarray([6 * BS - 1, 0], np.int32)
        rc.count_tiles(tables, offsets, 1, calls=3)
        cfg = eng.model_cfg
        want = read_counts(
            tables, offsets, 0, heads=cfg.n_kv_heads,
            group=cfg.n_heads // cfg.n_kv_heads, chunk=1, head_dim=cfg.head_dim,
            block_size=BS, itemsize=eng.dtype.itemsize)
        got = [pages.value(kind=k) - b for k, b in zip(("in_run", "single"), before)]
        assert got == [3 * want[2], 3 * want[3]]
        assert sum(got) == 3 * mapped  # every mapped page of the one live row
        rc.release(0)
        _assert_all_free(rc)
    finally:
        eng.close()
