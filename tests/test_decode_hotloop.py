"""Decode hot-loop tests (docs/PERF.md "Decode hot loop"): the readback
ring, the one decode root that carries the penalty counts, and the
grow-only batch bucket.

The acceptance pins live here:

- the decode root serves a mixed penalized/plain batch with every row
  token-for-token identical to its SOLO run (a row alone shares no window,
  ring slot or bucket with another);
- a penalized row does not park the whole batch: its window runs the ONE
  ``decode`` root (the sentinel knows no other decode root), and the
  batch-level speculation gate does not veto on penalized rows;
- look-ahead changes NO tokens under retirement churn, admission
  queueing, or re-admission — and removes host-sync stalls on the
  uniform-budget steady state it is designed for;
- the batch bucket holds its width through retirement churn (zero fresh
  decode traces), grows only under HBM-ledger headroom, and releases the
  bucket on idle;
- the ring's chain keeps its compile space pinned: repeat steady-state
  batches — including ring-empty re-entries from the host mirrors,
  which carry different arg shardings than chained device outputs —
  trigger zero new decode compiles (the sharding-keyed double-compile
  regression).
"""

from __future__ import annotations

import threading
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bee2bee_tpu.engine import EngineConfig, InferenceEngine
from bee2bee_tpu.engine.introspect import _C_HOST_SYNCS, _C_SYNC_STALLS
from bee2bee_tpu.engine.sampling import apply_penalties, sample_batched

ROWS = 4
PROMPTS = [[1 + (i * 37 + j) % 500 for j in range(32)] for i in range(ROWS)]


def _cfg(**knobs) -> EngineConfig:
    base = dict(
        max_seq_len=256,
        max_batch=ROWS,
        prefill_buckets=(32,),
        dtype="float32",
        cache_dtype="float32",
        decode_chunk=4,
        spec_tokens=0,
        rng_seed=7,
    )
    base.update(knobs)
    return EngineConfig(**base)


def _engine(**knobs) -> InferenceEngine:
    return InferenceEngine("tiny-llama", engine_config=_cfg(**knobs))


@pytest.fixture(scope="module")
def engine():
    eng = _engine()
    yield eng
    eng.close()


def _solo(eng, budgets, penalize_last=False):
    """Each row's rollout ALONE on the engine, one after another: the
    reference a batched row is held to."""
    out = []
    for i, budget in enumerate(budgets):
        kw = {"max_new_tokens": budget, "temperature": 0.0}
        if penalize_last and i == len(budgets) - 1:
            kw["repetition_penalty"] = 1.3
        out.append(eng.generate(PROMPTS[i % ROWS], **kw).token_ids)
    return out


def _run_batch(eng, budgets, penalize_last=False):
    """Concurrent batch through the scheduler; returns per-row token_ids
    in submission order. Greedy rows (+ optional repetition penalty on
    the last row) keep the outputs deterministic for parity checks."""
    results: list = [None] * len(budgets)

    def run(i):
        kw = {"max_new_tokens": budgets[i], "temperature": 0.0}
        if penalize_last and i == len(budgets) - 1:
            kw["repetition_penalty"] = 1.3
        results[i] = eng.generate(PROMPTS[i % ROWS], **kw)

    threads = [threading.Thread(target=run, args=(i,))
               for i in range(len(budgets))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(r is not None for r in results)
    return [r.token_ids for r in results]


def _decode_traces(eng) -> int:
    return eng.introspect.sentinel.snapshot().get(
        "decode", {"traces": 0}
    )["traces"]


# ------------------------------------------------- fused sampling root


def test_sample_batched_counts_none_is_the_prefusion_graph():
    """``counts=None`` must lower to the counts-free trace: identical
    tokens to the explicit two-stage apply_penalties → sample path, and
    all-off penalty values must be a no-op against the None graph."""
    key = jax.random.key(0)
    logits = jax.random.normal(jax.random.key(1), (3, 64), jnp.float32)
    counts = jnp.zeros((3, 2, 64), jnp.int32)
    counts = counts.at[0, 1, 5].set(3).at[0, 0, 9].set(1).at[2, 1, 11].set(2)
    temp = jnp.zeros((3,), jnp.float32)  # greedy rows: parity is exact
    top_k = jnp.zeros((3,), jnp.int32)
    top_p = jnp.ones((3,), jnp.float32)
    rep = jnp.asarray([1.7, 1.0, 1.3], jnp.float32)
    pres = jnp.asarray([0.5, 0.0, 0.0], jnp.float32)
    freq = jnp.asarray([0.1, 0.0, 0.9], jnp.float32)

    fused = sample_batched(logits, key, temp, top_k, top_p,
                           counts=counts, repetition=rep,
                           presence=pres, frequency=freq)
    staged = sample_batched(
        apply_penalties(logits, counts, rep, pres, freq),
        key, temp, top_k, top_p,
    )
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(staged))

    ones = jnp.ones((3,), jnp.float32)
    zeros = jnp.zeros((3,), jnp.float32)
    noop = sample_batched(logits, key, temp, top_k, top_p,
                          counts=counts, repetition=ones,
                          presence=zeros, frequency=zeros)
    plain = sample_batched(logits, key, temp, top_k, top_p, counts=None)
    np.testing.assert_array_equal(np.asarray(noop), np.asarray(plain))


def test_fused_mixed_batch_token_parity(engine):
    """A mixed batch (3 plain greedy rows + 1 repetition-penalized row)
    decodes every row token-for-token as that row does alone."""
    budgets = [16] * ROWS
    batched = _run_batch(engine, budgets, penalize_last=True)
    assert batched == _solo(engine, budgets, penalize_last=True), (
        "mixed batch diverged from the rows' solo runs"
    )


def test_a_penalised_window_runs_the_one_decode_root(engine):
    """Counts ride the ONE decode root: a penalised window traces `decode`
    under its counts key, is accounted as a counts window, and the
    sentinel knows no other decode root."""
    before = engine.scheduler.stats.counts_windows
    _run_batch(engine, [8] * ROWS, penalize_last=True)
    snap = engine.introspect.sentinel.snapshot()
    assert [root for root in snap if root.startswith("decode")] == ["decode"]
    assert "decode_penalized" not in snap
    assert snap["decode"]["traces"] >= 1
    assert engine.scheduler.stats.counts_windows > before


def test_a_penalised_row_does_not_park_batch_speculation():
    """`_spec_possible` (the batch-level speculation gate): a penalized
    row does not veto speculation for the batch — its counts thread the
    verify call."""
    eng = _engine(spec_tokens=2, max_seq_len=64, prefill_buckets=(16,))
    try:
        sch = eng.scheduler
        saved = sch._rows, sch._offsets
        sch._rows = [
            SimpleNamespace(penalized=True),
            SimpleNamespace(penalized=False),
        ]
        sch._offsets = np.zeros((2,), np.int32)
        try:
            assert sch._spec_possible() is True
        finally:
            sch._rows, sch._offsets = saved
    finally:
        eng.close()


# ------------------------------------------------- overlap / readback


def test_overlap_parity_under_retirement_and_admission(engine):
    """Look-ahead must be invisible in the tokens: 6 requests through 4
    rows (queueing + re-admission) with staggered budgets (retirement
    churn mid-flight) decode each as it does alone."""
    budgets = [8, 12, 16, 20, 24, 28]
    solo = _solo(engine, budgets)
    assert _run_batch(engine, budgets) == solo, (
        "look-ahead changed tokens under retirement/admission"
    )
    # STREAMED rows (no look-ahead: a window's delivery waits for the burst
    # that its retirements admit, ISSUE 40) send the same tokens, event by
    # event
    assert _run_streams(engine, budgets) == solo


def _run_streams(eng, budgets):
    """The budgets as concurrent streams; per row, the tokens of its
    content events in order (checked against its done line's)."""
    sent: list = [None] * len(budgets)

    def run(i):
        got = []
        for ev in eng.generate_stream(
            PROMPTS[i % ROWS], max_new_tokens=budgets[i], temperature=0.0
        ):
            if ev.get("done"):
                assert got == ev["result"].token_ids
                sent[i] = got
            else:
                got.extend(ev["tokens"])

    threads = [threading.Thread(target=run, args=(i,))
               for i in range(len(budgets))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return sent


def _run_burst(eng, budgets):
    """Every row admitted in ONE burst: the requests are queued while the
    scheduler's condition is held (an RLock: submit() re-enters it), so its
    loop cannot pop the first before the last is in. What the ring then
    does is a function of the budgets alone, not of thread arrival."""
    sch = eng.scheduler
    with sch._cond:
        reqs = [
            sch.submit(eng._make_request(
                PROMPTS[i % ROWS], budget, 0.0, 0, 1.0, None
            ))
            for i, budget in enumerate(budgets)
        ]
    for req in reqs:
        while not (ev := req.events.get(timeout=120)).get("done"):
            pass
        assert ev.get("result") is not None, ev


def test_overlap_removes_host_sync_stalls(engine):
    """The ring's steady state (uniform budgets, no queue/stream/spec):
    some readback windows must find another window already in flight
    (stalls < syncs).

    Driven as one admission burst (_run_burst): four rows of 48 tokens at
    decode_chunk 4 are a first window of 8 chunks with a second of 4
    dispatched behind it, so the first readback finds the ring occupied
    and only the last one stalls. Arriving one by one, as threads under a
    loaded machine do, each row can run alone on windows that cover its
    whole budget, and every sync is then rightly a stall."""
    budgets = [48] * ROWS
    s0, t0 = _C_HOST_SYNCS.value(), _C_SYNC_STALLS.value()
    _run_burst(engine, budgets)
    syncs, stalls = _C_HOST_SYNCS.value() - s0, _C_SYNC_STALLS.value() - t0
    assert (syncs, stalls) == (2, 1), (
        f"the ring did not hold its second window: {stalls}/{syncs} stalled"
    )


def test_overlap_chain_compile_space_is_pinned(engine):
    """Sharding-keyed double-compile regression: a ring-empty dispatch
    re-enters the decode chain from the host numpy mirrors, which lower
    with a DIFFERENT arg sharding than chained device outputs — without
    the scheduler's device_put commitment that silently doubles the
    decode root's executable space and lands a recompile mid-serve.
    Post-warm, repeat steady-state batches (each one draining the ring
    and re-entering from the mirrors) must compile NOTHING new."""
    budgets = [32] * ROWS
    _run_batch(engine, budgets)  # warm every (bsz, width) key
    traces0 = _decode_traces(engine)
    for _ in range(2):
        _run_batch(engine, budgets)
    assert _decode_traces(engine) == traces0, (
        "steady-state repeat batches recompiled the decode root"
    )
    snap = engine.introspect.sentinel.snapshot()
    assert snap["decode"]["storms"] == 0


# ------------------------------------------------- sticky-width batches


def test_sticky_width_holds_bucket_and_releases_on_idle():
    """Grow-only while work flows: after a staggered batch fully
    retires, the bucket holds its width through the hysteresis window —
    and only an idle sweep past `_sticky_idle_s` drops it."""
    eng = _engine()
    try:
        _run_batch(eng, [4, 8, 12, 16])
        sch = eng.scheduler
        deadline = time.monotonic() + 5.0
        while sch.active > 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert sch._bsz == ROWS, (
            f"sticky bucket shrank to {sch._bsz} right after retirement"
        )
        # collapse the hysteresis window; the next sweep releases
        sch._sticky_idle_s = 0.0
        sch._compact_and_shrink()
        assert sch._bsz == 1
    finally:
        eng.close()


def test_sticky_width_avoids_retirement_retraces():
    """What the grow-only bucket buys: post-warm, a staggered-budget
    batch retires row by row through ZERO new decode traces."""
    churn = [8, 16, 24, 32]
    eng = _engine()
    try:
        _run_batch(eng, [32] * ROWS)  # warm the full-width traces
        traces0 = _decode_traces(eng)
        _run_batch(eng, churn)
        assert _decode_traces(eng) == traces0, (
            "the engine recompiled decode during retirement churn"
        )
    finally:
        eng.close()


def test_sticky_growth_is_hbm_gated(monkeypatch):
    """Growth into a KNOWN memory ceiling is refused: with a tiny
    BEE2BEE_HBM_BYTES budget the headroom gate denies the bucket grow,
    the denial is counted, and the queued requests still complete by
    retrying into retirement holes at the current width."""
    monkeypatch.setenv("BEE2BEE_HBM_BYTES", "1024")
    eng = _engine()
    try:
        tokens = _run_batch(eng, [4, 4, 4, 4])
        assert all(len(t) == 4 for t in tokens)
        sch = eng.scheduler
        assert sch._bsz == 1, (
            f"bucket grew to {sch._bsz} through a denied headroom gate"
        )
        assert sch.stats.width_grow_denials > 0
    finally:
        eng.close()
