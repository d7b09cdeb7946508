"""An admission burst as a few ``[n, bucket]`` prefill programs (ISSUE 42).

The program: a group of n rows of unequal lengths gives, row for row, the
logits, the pool's real positions, the recurrent state and the first token
of n single calls; pad and dead rows write nothing outside the null block; a
row's write floor protects its donor's blocks inside a group. The scheduler:
which groups a burst of queued requests becomes, who goes alone, what a
request that cannot be admitted costs the others, and that after the boot
warm-up no served burst compiles."""

from __future__ import annotations

import time

import jax
import numpy as np
import pytest

import bee2bee_tpu.engine.scheduler  # noqa: F401 — registers the counters read below
from bee2bee_tpu.engine import EngineConfig, InferenceEngine
from bee2bee_tpu.engine.engine import (
    PREFILL_GROUP_MAX_BUCKET, PREFILL_GROUP_ROWS, PREFILL_GROUP_TOKENS,
)
from bee2bee_tpu.engine.paged import PoolExhausted
from bee2bee_tpu.metrics import get_registry

BS = 8
KW = dict(max_seq_len=128, dtype="float32", cache_dtype="float32", decode_chunk=4,
          max_batch=8, prefill_buckets=(16, 32, 64), kv_block_size=BS, rng_seed=7)
MODELS = [("tiny-llama", "dense"), ("tiny-llama", "flash"), ("tiny-falcon-h1", "dense"),
          ("tiny-joyai", "dense"), ("tiny-joyai", "flash")]


def _engine(model: str = "tiny-llama", **over) -> InferenceEngine:
    return InferenceEngine(model, engine_config=EngineConfig(**{**KW, **over}))


def _prompt(seed: int, n: int) -> list[int]:
    return [1] + [int(t) for t in np.random.RandomState(seed).randint(3, 259, n - 1)]


def _value(name: str, **labels) -> float:
    return get_registry().get(name).value(**labels)


def _counts() -> dict:
    return {**{f"calls{b}": _value("engine.prefill_calls", bucket=str(b)) for b in (16, 32, 64)},
            "live": _value("engine.prefill_rows", kind="live"),
            "dead": _value("engine.prefill_rows", kind="dead"),
            "real": _value("engine.prefill_tokens", kind="real"),
            "pad": _value("engine.prefill_tokens", kind="pad")}


def _grew(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


def _submit_together(eng, specs, **kw):
    """[(prompt, budget)] queued before the loop can pop the first."""
    sch = eng.scheduler
    with sch._cond:
        return [sch.submit(eng._make_request(p, n, 0.0, 0, 1.0, None, stream=False, **kw))
                for p, n in specs]


def _done(req, timeout=120.0) -> dict:
    while True:
        ev = req.events.get(timeout=timeout)
        if ev.get("done"):
            return ev


def _idle(eng):
    sch = eng.scheduler
    deadline = time.monotonic() + 30.0
    while (sch._undelivered or sch._inflight or sch.active) and time.monotonic() < deadline:
        time.sleep(0.01)
    time.sleep(0.05)


# ---------------------------------------------------------------- the program


def _call(eng, pool, rows, tables, bucket, floors=None):
    """One prefill program over ``rows`` = [(tokens, offset) | None (dead)]."""
    n = len(rows)
    tokens = np.zeros((n, bucket), np.int32)
    true_len, offset, ceil = (np.zeros((n,), np.int32) for _ in range(3))
    for i, row in enumerate(rows):
        if row is not None:
            seq, off = row
            tokens[i, :len(seq)] = seq
            true_len[i], offset[i], ceil[i] = len(seq), off, off + len(seq)
    state = eng.new_state(n)
    out = eng._prefill(
        eng.params, tokens, pool, true_len, offset, tables,
        np.zeros((n,), np.int32) if floors is None else np.asarray(floors, np.int32),
        ceil, **({"state": state} if state is not None else {}))
    pool, logits, *extras = out
    extras = dict(extras[0]) if extras else {}
    extras.pop("moe_stats", None)
    return pool, np.asarray(logits), jax.tree.map(np.asarray, extras)


@pytest.mark.parametrize("model,attention", MODELS, ids=[f"{m}-{a}" for m, a in MODELS])
def test_a_group_is_row_for_row_its_single_calls(model, attention):
    """Three rows of 5, 16 and 11 tokens and a dead row in ONE [4, 16] call
    against three [1, 16] calls over the same tables: logits, first token,
    every block of the pool but the null block, and the recurrent state."""
    eng = _engine(model, attention=attention)
    try:
        rc = eng.scheduler.cache
        lens = [5, 16, 11]
        prompts = [_prompt(i, n) for i, n in enumerate(lens)]
        for b, n in enumerate(lens):
            rc.cover(b, n)
        group_rows = [0, 1, -1, 2]  # the dead row in the middle
        pool_g, logits_g, state_g = _call(
            eng, eng.new_pool(),
            [(prompts[0], 0), (prompts[1], 0), None, (prompts[2], 0)],
            rc.rows_table(group_rows, 16), 16)
        pool_s = eng.new_pool()
        singles = []
        for b in range(3):
            pool_s, logits, state = _call(
                eng, pool_s, [(prompts[b], 0)], rc.rows_table([b], 16), 16)
            singles.append((logits, state))
        for g, b in ((0, 0), (1, 1), (3, 2)):
            logits, state = singles[b]
            np.testing.assert_allclose(logits_g[g], logits[0], rtol=2e-4, atol=2e-5)
            assert int(logits_g[g].argmax()) == int(logits[0].argmax())  # the first token
            for name in state:  # [L, n, ...]: the row's slot of the group's state
                np.testing.assert_allclose(
                    state_g[name][:, g], state[name][:, 0], rtol=2e-4, atol=2e-5)
        for name in pool_g:
            got, want = np.asarray(pool_g[name]), np.asarray(pool_s[name])
            # block axis 1; block 0 is the null block, where every pad
            # position's and the dead row's writes went
            np.testing.assert_allclose(got[:, 1:], want[:, 1:], rtol=2e-4, atol=2e-5)
            owned = sorted(blk for b in range(3) for blk in rc.row_blocks[b])
            free = np.setdiff1d(np.arange(1, got.shape[1]), owned)
            assert not got[:, free].any(), "a write outside the rows' own blocks"
        if state_g:  # the dead row's slot came back as it went in: zero
            assert not any(np.asarray(a)[:, 2].any() for a in state_g.values())
    finally:
        eng.close()


@pytest.mark.parametrize("attention", ["dense", "flash"])
def test_a_rows_floor_keeps_its_donors_blocks_inside_a_group(attention):
    """Row 1 re-feeds its whole prompt from position 0 over a table whose
    first two blocks are row 0's (a prefix share): with its floor at 16 those
    blocks keep their bytes while the group's other row, floor 0, writes from
    its first position on — and row 1's own block is written."""
    eng = _engine(attention=attention)
    try:
        rc = eng.scheduler.cache
        donor, other = _prompt(1, 2 * BS), _prompt(2, 2 * BS + 4)
        rc.cover(0, 2 * BS)
        pool, _, _ = _call(eng, eng.new_pool(), [(donor, 0)], rc.rows_table([0], 32), 32)
        before = {k: np.asarray(v).copy() for k, v in pool.items()}
        borrower = donor + _prompt(3, 5)
        rc.cover(1, 3 * BS)
        rc.tables[1, :2] = rc.tables[0, :2]  # shares the donor's full blocks
        own = int(rc.tables[1, 2])
        rc.cover(2, len(other))
        # perturbed inputs below the floor: were they written, the blocks would change
        refed = [t + 1 for t in borrower[:2 * BS]] + borrower[2 * BS:]
        pool, _, _ = _call(
            eng, pool, [(refed, 0), (other, 0)], rc.rows_table([1, 2], 32), 32,
            floors=[2 * BS, 0])
        for name, was in before.items():
            got = np.asarray(pool[name])
            shared = rc.row_blocks[0]
            np.testing.assert_array_equal(got[:, shared], was[:, shared])
            assert got[:, own].any() and not was[:, own].any()
            assert got[:, rc.row_blocks[2]].any()
    finally:
        eng.close()


def test_the_ladder_is_the_declared_compile_space():
    """The nine grouped programs a model gains at the default buckets, and
    what an engine declares of them inside its context (tiny-llama's 256)."""
    eng = _engine(prefill_buckets=(64, 128, 256, 512, 1024), max_seq_len=256)
    try:
        assert [eng.prefill_group_rows(b) for b in (64, 128, 256, 512, 1024, 2048)] == [
            (1, 2, 4, 8), (1, 2, 4, 8), (1, 2, 4), (1, 2), (1,), (1,)]
        assert (PREFILL_GROUP_ROWS, PREFILL_GROUP_TOKENS, PREFILL_GROUP_MAX_BUCKET) == (
            (1, 2, 4, 8), 1024, 512)
        assert eng._declared_prefill_shapes == {
            (1, 64), (2, 64), (4, 64), (8, 64), (1, 128), (2, 128), (4, 128), (8, 128),
            (1, 256), (2, 256), (4, 256)}
    finally:
        eng.close()


def test_a_group_off_the_ladder_is_a_typed_retrace_storm():
    eng = _engine()
    try:
        eng.generate(_prompt(0, 9), max_new_tokens=3)
        rc = eng.scheduler.cache
        rc.pool, _, _ = _call(eng, rc.pool, [None] * 3, rc.rows_table([-1] * 3, 16), 16)
        assert eng.introspect.sentinel.snapshot()["prefill"]["storms"] == 1
    finally:
        eng.close()


# ---------------------------------------------------------------- the scheduler


@pytest.fixture(scope="module")
def engine():
    eng = _engine()
    eng.scheduler.warm_prefill()
    eng.generate(_prompt(9, 20), max_new_tokens=6)  # the decode shapes compile here
    yield eng
    eng.close()


def test_the_boot_warm_up_calls_every_declared_shape_on_dead_rows():
    eng = _engine()
    try:
        before = _counts()
        eng.scheduler.warm_prefill()
        # the three buckets and the context (128) x n in 1, 2, 4, 8 = 16 shapes
        grown = _grew(_counts(), before)
        assert grown == {"calls16": 4, "calls32": 4, "calls64": 4, "live": 0,
                         "dead": 4 * 15, "real": 0, "pad": 15 * (16 + 32 + 64 + 128)}
        assert _value("engine.prefill_calls", bucket="128") >= 4
        # resident as loaded executables (engine/programs.py): no trace of
        # the jit root, then or when a burst asks for one
        assert {k[:2] for k in eng._prefill._resident} == eng._declared_prefill_shapes
        assert eng.introspect.sentinel.snapshot()["prefill"] == {"traces": 0, "storms": 0}
        rc = eng.scheduler.cache
        assert rc.alloc.used_count == 0
        for leaf in rc.pool.values():  # nothing outside the null block
            assert not np.asarray(leaf)[:, 1:].any()
    finally:
        eng.close()


def test_a_recurrent_models_warm_up_leaves_its_state_as_it_found_it():
    """... and has run the state insert of every group size at the batch
    bucket a loaded node holds."""
    eng = _engine("tiny-falcon-h1")
    try:
        before = _counts()
        eng.scheduler.warm_prefill()
        grown = _grew(_counts(), before)
        assert (grown["calls16"], grown["live"], grown["dead"]) == (4, 0, 4 * 15)
        rc = eng.scheduler.cache
        assert rc.state_rows == 1
        assert not any(np.asarray(a).any() for a in jax.tree.leaves(rc.state))
        out = eng.generate(_prompt(3, 20), max_new_tokens=6, temperature=0.0)
        assert out.new_tokens == 6
    finally:
        eng.close()


def test_a_burst_runs_as_the_widest_groups_its_buckets_fill(engine):
    """Seven requests queued together: five of bucket 16 are 4 + 1, two of
    bucket 32 one program; tokens as their single runs gave them."""
    specs = [(_prompt(i, n), 5) for i, n in enumerate((5, 9, 12, 16, 7, 20, 30))]
    want = [engine.generate(p, max_new_tokens=n, temperature=0.0).token_ids for p, n in specs]
    _idle(engine)
    traces = engine.introspect.sentinel.snapshot()["prefill"]["traces"]
    before = _counts()
    bursts0 = get_registry().get("engine.admit_burst_requests").totals()
    reqs = _submit_together(engine, specs)
    got = [_done(r)["result"].token_ids for r in reqs]
    _idle(engine)
    assert got == want
    assert _grew(_counts(), before) == {
        "calls16": 2, "calls32": 1, "calls64": 0, "live": 7, "dead": 0,
        "real": 99, "pad": 5 * 16 + 2 * 32 - 99}
    count, total = get_registry().get("engine.admit_burst_requests").totals()
    assert (count - bursts0[0], total - bursts0[1]) == (1, 7)
    # after the boot warm-up no served burst compiles, and no undeclared key fires
    snap = engine.introspect.sentinel.snapshot()["prefill"]
    assert snap["traces"] == traces and snap["storms"] == 0


def test_the_largest_group_is_dispatched_first(engine, monkeypatch):
    shapes = []
    prefill = engine._prefill

    def recorded(params, tokens, *a, **k):
        shapes.append(tokens.shape)
        return prefill(params, tokens, *a, **k)

    monkeypatch.setattr(engine, "_prefill", recorded)
    reqs = _submit_together(
        engine, [(_prompt(i, n), 3) for i, n in enumerate((20, 6, 7, 8, 9, 40, 25))])
    for r in reqs:
        _done(r)
    _idle(engine)
    assert shapes == [(4, 16), (2, 32), (1, 64)]


def test_sampled_rows_share_a_groups_one_sample(engine):
    sch = engine.scheduler
    with sch._cond:
        reqs = [sch.submit(engine._make_request(
            _prompt(i, 10), 6, 0.9, 20, 0.95, None, stream=False)) for i in range(4)]
    before = _counts()
    outs = [_done(r)["result"].token_ids for r in reqs]
    assert all(len(o) == 6 and all(0 <= t < engine.model_cfg.vocab_size for t in o)
               for o in outs)
    assert _grew(_counts(), before)["calls16"] in (0, 1)  # counted before or after the read
    _idle(engine)


def test_a_cancelled_and_an_unadmittable_request_cost_only_themselves(engine):
    specs = [(_prompt(i, 10 + i), 4) for i in range(6)]
    want = [engine.generate(p, max_new_tokens=n, temperature=0.0).token_ids for p, n in specs]
    _idle(engine)
    before = _counts()
    sch = engine.scheduler
    with sch._cond:
        reqs = [sch.submit(engine._make_request(p, n, 0.0, 0, 1.0, None, stream=False))
                for p, n in specs]
        reqs[1].cancelled = True
        reqs[3].adapter = "ghost"  # no such adapter: a typed failure at admission
    events = [_done(r) for r in reqs]
    _idle(engine)
    assert events[1]["result"].finish_reason == "cancelled"
    assert events[3].get("error_kind") == "unknown_adapter" and events[3]["result"] is None
    for i in (0, 2, 4, 5):
        assert events[i]["result"].token_ids == want[i]
    # the four left are one group of bucket 16
    assert _grew(_counts(), before) == {
        "calls16": 1, "calls32": 0, "calls64": 0, "live": 4, "dead": 0,
        "real": 10 + 12 + 14 + 15, "pad": 64 - 51}


def test_pool_backpressure_requeues_its_request_and_the_rest_of_the_burst_runs():
    """A pool of 13 blocks: four 20-token prompts (3 blocks each) fit, the
    fifth is requeued behind them (no failure), the four run as one group
    and the fifth is admitted once rows end."""
    eng = _engine(kv_pool_blocks=14)
    try:
        specs = [(_prompt(i, 20), 3) for i in range(5)]
        want = [eng.generate(p, max_new_tokens=n, temperature=0.0).token_ids for p, n in specs]
        _idle(eng)
        before, waits = _counts(), eng.scheduler.stats.paged_alloc_waits
        reqs = _submit_together(eng, specs)
        got = [_done(r)["result"].token_ids for r in reqs]
        _idle(eng)
        assert got == want
        assert eng.scheduler.stats.paged_alloc_waits > waits
        grown = _grew(_counts(), before)
        assert (grown["calls32"], grown["live"], grown["dead"]) == (2, 5, 0)  # 4, then 1
        assert eng.scheduler.cache.alloc.used_count == 0
    finally:
        eng.close()


def test_a_prompt_no_pool_can_hold_fails_alone_inside_a_burst():
    eng = _engine(kv_pool_blocks=6)  # 5 usable blocks = 40 tokens
    try:
        reqs = _submit_together(
            eng, [(_prompt(0, 10), 3), (_prompt(1, 60), 3), (_prompt(2, 12), 3)])
        events = [_done(r) for r in reqs]
        assert events[1]["result"] is None and "kv_pool_blocks" in events[1]["error"]
        assert all(len(events[i]["result"].token_ids) == 3 for i in (0, 2))
    finally:
        eng.close()


def test_a_multi_chunk_prompt_and_a_penalized_row_go_alone(monkeypatch):
    eng = _engine(prefill_chunk=16)
    try:
        eng.generate(_prompt(0, 9), max_new_tokens=2)
        shapes = []
        prefill = eng._prefill

        def recorded(params, tokens, *a, **k):
            shapes.append(tokens.shape)
            return prefill(params, tokens, *a, **k)

        monkeypatch.setattr(eng, "_prefill", recorded)
        sch = eng.scheduler
        with sch._cond:
            reqs = [sch.submit(eng._make_request(p, 3, 0.0, 0, 1.0, None, stream=False, **kw))
                    for p, kw in ((_prompt(1, 40), {}), (_prompt(2, 10), {}),
                                  (_prompt(3, 12), {"repetition_penalty": 1.3}),
                                  (_prompt(4, 14), {}))]
        for r in reqs:
            assert len(_done(r)["result"].token_ids) == 3
        # the pair of bucket 16 first; the walker's three windows and the
        # penalized row are programs of one row each
        assert shapes[0] == (2, 16) and sorted(shapes[1:]) == [(1, 16)] * 4
    finally:
        eng.close()


def test_a_burst_of_equal_prompts_still_shares_its_prefix():
    """The second of two equal prompts queued together waits a round for the
    first's blocks (published with its dispatch) and adopts them, as when
    the burst ran a request at a time."""
    eng = _engine(prefix_cache_entries=4)
    try:
        prompt = _prompt(5, 30)
        want = eng.generate(prompt, max_new_tokens=4, temperature=0.0).token_ids
        eng.scheduler.cache.prefix.clear()
        _idle(eng)
        stats = eng.scheduler.stats
        hits, saved = stats.prefix_hits, stats.prefix_tokens_saved
        reqs = _submit_together(eng, [(prompt, 4), (prompt, 4), (_prompt(6, 30), 4)])
        got = [_done(r)["result"].token_ids for r in reqs]
        assert got[0] == got[1] == want
        assert stats.prefix_hits == hits + 1 and stats.prefix_tokens_saved == saved + 29
    finally:
        eng.close()


def test_planning_raises_before_any_prefill_and_releases_the_row(engine):
    sch = engine.scheduler
    _idle(engine)
    req = engine._make_request(_prompt(0, 100), 2, 0.0, 0, 1.0, None, stream=False)
    free = sch.cache.alloc.free_count
    held = sch.cache.alloc.alloc(free - 3)  # leave 3 blocks: 100 tokens need 13
    before = _counts()
    try:
        with pytest.raises(PoolExhausted):
            sch._plan_row(req, 0, req.ids)
    finally:
        sch.cache.alloc.deref(held)
    assert _counts() == before and sch.cache.row_blocks[0] == []
    assert sch.cache.alloc.free_count == free
