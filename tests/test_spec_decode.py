"""Self-speculative decoding (engine/spec.py + the _spec_verify_fn jit
root + the scheduler's spec step):

- the n-gram drafter proposes real continuations (and nothing on
  non-repetitive tails);
- greedy spec-on decode is TOKEN-FOR-TOKEN identical to spec-off greedy,
  rectangular and paged, including stop tokens landing inside a draft;
- mixed batches gate per row: greedy rows speculate while sampled rows
  in the same batch advance normally and everyone completes;
- paged pool accounting: blocks claimed to cover draft slots (including
  later-rejected ones) are all released at retirement and reused;
- acceptance counters surface in SchedulerStats and engine.info.
"""

from __future__ import annotations

import threading

import pytest

from bee2bee_tpu.engine import EngineConfig, InferenceEngine
from bee2bee_tpu.engine.spec import NgramDrafter, find_ngram_draft, should_disable

KW = dict(
    max_seq_len=128, dtype="float32", cache_dtype="float32",
    decode_chunk=4, prefill_buckets=(16, 32, 64), max_batch=4,
)
# periodic prompt: the drafter finds its tail n-gram earlier in the
# sequence from the very first decode steps
REP_PROMPT = [5, 6, 7, 8, 9] * 3 + [5, 6, 7]


@pytest.fixture(scope="module")
def ref_engine():
    eng = InferenceEngine("tiny-llama", engine_config=EngineConfig(**KW))
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def spec_engine():
    eng = InferenceEngine(
        "tiny-llama", engine_config=EngineConfig(**KW, spec_tokens=6)
    )
    yield eng
    eng.close()


# ------------------------------------------------------------ drafter unit


def test_drafter_periodic_sequence_drafts_full_k():
    ctx = [1, 2, 3, 4] * 6
    d = find_ngram_draft(ctx, 5)
    assert len(d) == 5
    # the draft must continue the period after the tail ...1,2,3,4
    assert d == [1, 2, 3, 4, 1]


def test_drafter_constant_run_is_not_starved_by_overlap():
    """An all-same-token run: the latest suffix occurrence overlaps the
    tail and has ~no continuation — the drafter must fall back to a
    roomier occurrence and still draft k tokens."""
    d = find_ngram_draft([7] * 30, 6)
    assert d == [7] * 6


def test_drafter_no_match_on_fresh_tail():
    # tail [98, 99] never re-occurs
    assert find_ngram_draft([1, 2, 3, 4, 98, 99], 4) == []
    # too short for min_match
    assert find_ngram_draft([1, 2], 4, min_match=2) == []
    assert find_ngram_draft([1, 2, 3], 0) == []


def test_drafter_respects_min_match():
    # only a single-token suffix repeats: min_match=2 rejects it
    ctx = [9, 1, 2, 3, 9, 4, 5, 6, 9]
    assert find_ngram_draft(ctx, 4, min_match=2) == []
    # min_match=1 matches the [9] suffix; the latest occurrence with a
    # full 4 tokens of room is index 4, so the draft continues from there
    assert find_ngram_draft(ctx, 4, min_match=1) == [4, 5, 6, 9]


def test_should_disable_and_drafter_validation():
    assert not should_disable(10, 1, 64, 0.25)  # probe budget not spent
    assert should_disable(64, 2, 64, 0.25)  # collapsed
    assert not should_disable(64, 32, 64, 0.25)  # healthy
    with pytest.raises(ValueError):
        NgramDrafter(0)
    with pytest.raises(ValueError):
        NgramDrafter(4, min_match=3, max_match=2)


# ------------------------------------------------------------ greedy parity


def test_greedy_parity_spec_on_vs_off(ref_engine, spec_engine):
    """THE acceptance bar: token-for-token identical output, and
    speculation must actually have engaged (otherwise the test proves
    nothing)."""
    r0 = ref_engine.generate(REP_PROMPT, max_new_tokens=40, temperature=0.0)
    r1 = spec_engine.generate(REP_PROMPT, max_new_tokens=40, temperature=0.0)
    assert r1.token_ids == r0.token_ids
    st = spec_engine.scheduler.stats
    assert st.spec_steps > 0 and st.spec_drafted > 0
    assert 0 <= st.spec_accepted <= st.spec_drafted


def test_greedy_parity_non_repetitive_prompt(ref_engine, spec_engine):
    """A prompt with no repetition: drafts rarely fire, but whatever the
    spec path does must still match plain greedy exactly."""
    prompt = [(i * 37) % 400 + 3 for i in range(24)]
    r0 = ref_engine.generate(prompt, max_new_tokens=24, temperature=0.0)
    r1 = spec_engine.generate(prompt, max_new_tokens=24, temperature=0.0)
    assert r1.token_ids == r0.token_ids


def test_greedy_parity_paged(ref_engine):
    """Speculation over the paged pool: the verify chunk scatters through
    block tables instead of the rectangular rows — same tokens out."""
    eng = InferenceEngine(
        "tiny-llama",
        engine_config=EngineConfig(**KW, spec_tokens=6),
    )
    try:
        r0 = ref_engine.generate(REP_PROMPT, max_new_tokens=40, temperature=0.0)
        r1 = eng.generate(REP_PROMPT, max_new_tokens=40, temperature=0.0)
        assert r1.token_ids == r0.token_ids
        assert eng.scheduler.stats.spec_steps > 0
    finally:
        eng.close()


def test_stop_token_inside_accepted_draft(ref_engine, spec_engine):
    """A stop token landing mid-draft must cut the output exactly where
    non-speculative decode would."""
    free = ref_engine.generate(REP_PROMPT, max_new_tokens=24, temperature=0.0)
    stop_at = free.token_ids[10]
    cut = free.token_ids.index(stop_at)  # first occurrence wins
    r = spec_engine.generate(
        REP_PROMPT, max_new_tokens=24, temperature=0.0, stop_tokens=[stop_at]
    )
    assert r.token_ids == free.token_ids[:cut]
    assert r.finish_reason == "stop"


def test_greedy_parity_streaming(ref_engine, spec_engine):
    """Streamed spec decode: chunk events concatenate to the same ids."""
    r0 = ref_engine.generate(REP_PROMPT, max_new_tokens=24, temperature=0.0)
    toks: list[int] = []
    for ev in spec_engine.generate_stream(
        REP_PROMPT, max_new_tokens=24, temperature=0.0
    ):
        if ev.get("done"):
            result = ev["result"]
        else:
            toks.extend(ev.get("tokens") or [])
    assert toks == r0.token_ids == result.token_ids


def test_oversized_spec_tokens_does_not_pin_windows(ref_engine):
    """spec_tokens that never fits the cache headroom: rows must not
    count as spec-eligible, so multi-chunk readback windows resume
    (regression: the capacity veto ran only in the draft collection,
    leaving _window_size pinned at 1 chunk for the whole generation
    with zero speculation possible) — and output parity still holds."""
    from bee2bee_tpu.tracing import get_tracer

    eng = InferenceEngine(
        "tiny-llama", engine_config=EngineConfig(**KW, spec_tokens=100)
    )
    try:
        n_before = len(get_tracer().recent(limit=2048, name="engine.decode_window"))
        r0 = ref_engine.generate(REP_PROMPT, max_new_tokens=40, temperature=0.0)
        r1 = eng.generate(REP_PROMPT, max_new_tokens=40, temperature=0.0)
        assert r1.token_ids == r0.token_ids
        assert eng.scheduler.stats.spec_steps == 0
        windows = get_tracer().recent(
            limit=2048, name="engine.decode_window"
        )[n_before:]
        assert any(w["attrs"]["chunks"] > 1 for w in windows), (
            "every readback window stayed pinned to one chunk despite "
            "speculation being impossible"
        )
    finally:
        eng.close()


def test_near_capacity_row_in_batch_does_not_pin_windows():
    """A near-capacity row vetoes every spec step for the whole batch
    (the [B, K+1] write extent must fit every active row) — while it
    lives, the window pin must lift too (regression: an eligible
    roomy row kept W=1 while the veto discarded its drafts), and the
    roomy row's greedy output still matches spec-off decode."""
    from bee2bee_tpu.tracing import get_tracer

    # decode_chunk=2: a near-capacity row's remaining budget is always
    # <= K+1 (admission clamps generation to the cache), so with larger
    # chunks the budget cap alone forces W=1 and the pin lift would be
    # unobservable
    small = dict(KW, max_seq_len=64, decode_chunk=2)
    ref = InferenceEngine("tiny-llama", engine_config=EngineConfig(**small))
    eng = InferenceEngine(
        "tiny-llama", engine_config=EngineConfig(**small, spec_tokens=6)
    )
    try:
        long_prompt = [(i * 13) % 400 + 3 for i in range(50)]  # crosses
        # the veto (offset+6+1 > 64) with several budget tokens left
        truth_a = ref.generate(REP_PROMPT, max_new_tokens=30, temperature=0.0)
        n_before = len(get_tracer().recent(limit=2048, name="engine.decode_window"))
        results: dict = {}

        def run(tag, prompt, n):
            results[tag] = eng.generate(prompt, max_new_tokens=n, temperature=0.0)

        threads = [
            threading.Thread(target=run, args=("a", REP_PROMPT, 30)),
            threading.Thread(target=run, args=("b", long_prompt, 13)),
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert results["a"].token_ids == truth_a.token_ids
        assert results["b"].finish_reason == "length"
        windows = get_tracer().recent(
            limit=2048, name="engine.decode_window"
        )[n_before:]
        assert any(w["attrs"]["chunks"] > 1 for w in windows), (
            "windows stayed pinned to one chunk while the near-capacity "
            "row vetoed every spec step"
        )
    finally:
        ref.close()
        eng.close()


def test_spec_near_capacity_falls_back_cleanly(ref_engine):
    """Rows whose offset is within K+1 of capacity must NOT take the
    verify path (the fixed-width rectangular write would clamp) — parity
    right up to the cache-imposed length cap."""
    small = dict(KW, max_seq_len=64)
    ref = InferenceEngine("tiny-llama", engine_config=EngineConfig(**small))
    eng = InferenceEngine(
        "tiny-llama", engine_config=EngineConfig(**small, spec_tokens=6)
    )
    try:
        prompt = REP_PROMPT  # 18 tokens; budget clamps to the cache
        r0 = ref.generate(prompt, max_new_tokens=60, temperature=0.0)
        r1 = eng.generate(prompt, max_new_tokens=60, temperature=0.0)
        assert r1.token_ids == r0.token_ids
    finally:
        ref.close()
        eng.close()


# ------------------------------------------------------------ mixed batches


def test_mixed_batch_greedy_spec_rows_plus_sampled_rows(ref_engine, spec_engine):
    """Concurrent greedy + sampled requests share the batch: greedy rows
    speculate (parity vs the spec-off engine), sampled rows advance
    their normal one token per forward and run to completion."""
    greedy_truth = [
        ref_engine.generate(REP_PROMPT, max_new_tokens=30, temperature=0.0).token_ids,
        ref_engine.generate(
            REP_PROMPT + [3], max_new_tokens=30, temperature=0.0
        ).token_ids,
    ]
    st = spec_engine.scheduler.stats
    drafted_before = st.spec_drafted
    results: dict = {}

    def run(tag, prompt, temp):
        results[tag] = spec_engine.generate(
            prompt, max_new_tokens=30, temperature=temp, top_k=20,
            stop_tokens=[],
        )

    threads = [
        threading.Thread(target=run, args=("g0", REP_PROMPT, 0.0)),
        threading.Thread(target=run, args=("g1", REP_PROMPT + [3], 0.0)),
        threading.Thread(target=run, args=("s0", REP_PROMPT, 0.9)),
        threading.Thread(target=run, args=("s1", list(range(3, 27)), 1.2)),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert results["g0"].token_ids == greedy_truth[0]
    assert results["g1"].token_ids == greedy_truth[1]
    for tag in ("s0", "s1"):
        r = results[tag]
        assert r.new_tokens > 0
        assert r.finish_reason in ("length", "eos", "stop")
    assert st.spec_drafted > drafted_before  # greedy rows did speculate


# ------------------------------------------------------- paged accounting


def test_paged_pool_releases_draft_blocks_after_rejection_and_retire():
    """Blocks claimed to cover the [offset, offset+K+1) verify extent —
    including slots whose drafts were rejected — must all return to the
    free list at retirement, and a follow-up request must reuse them."""
    eng = InferenceEngine(
        "tiny-llama",
        engine_config=EngineConfig(**KW, spec_tokens=6),
    )
    try:
        sch = eng.scheduler
        free0 = sch.cache.alloc.free_count
        r1 = eng.generate(REP_PROMPT, max_new_tokens=40, temperature=0.0)
        st = sch.stats
        assert st.spec_steps > 0
        assert st.spec_accepted < st.spec_drafted + st.spec_steps * 2, (
            "suspicious: nothing was ever rejected — rejection-path "
            "accounting not exercised"
        )
        # no prefix cache configured: every block the row ever claimed
        # (draft tail included) must be free again
        assert sch.cache.alloc.free_count == free0
        r2 = eng.generate(REP_PROMPT, max_new_tokens=40, temperature=0.0)
        assert sch.cache.alloc.free_count == free0
        assert r2.token_ids == r1.token_ids  # reused blocks, same tokens
        assert sch.cache.alloc.hwm <= sch.cache.alloc.num_blocks - 1
    finally:
        eng.close()


def test_paged_spec_with_prefix_cache_pins_survive():
    """Spec + paged + prefix cache: the pinned prompt blocks stay pinned
    across spec steps; only the pins remain out of the free list after
    retirement."""
    from bee2bee_tpu.engine.paged import ceil_div

    eng = InferenceEngine(
        "tiny-llama",
        engine_config=EngineConfig(
            **KW, spec_tokens=6, prefix_cache_entries=2
        ),
    )
    try:
        sch = eng.scheduler
        free0 = sch.cache.alloc.free_count
        eng.generate(REP_PROMPT, max_new_tokens=32, temperature=0.0)
        pinned = ceil_div(len(REP_PROMPT), eng.engine_cfg.kv_block_size)
        assert sch.cache.alloc.free_count == free0 - pinned
        # the repeat admits from the pinned prefix and still retires clean
        eng.generate(REP_PROMPT, max_new_tokens=32, temperature=0.0)
        assert sch.stats.prefix_hits >= 1
        assert sch.cache.alloc.free_count == free0 - pinned
    finally:
        eng.close()


# ------------------------------------------------------------ observability


def test_spec_counters_in_stats_and_info(spec_engine):
    spec_engine.generate(REP_PROMPT, max_new_tokens=24, temperature=0.0)
    st = spec_engine.scheduler.stats
    assert st.spec_drafted > 0
    assert 0.0 <= st.spec_acceptance <= 1.0
    info = spec_engine.info["spec"]
    assert info["spec_tokens"] == 6
    assert info["drafted"] == st.spec_drafted
    assert info["accepted"] == st.spec_accepted
    assert info["acceptance"] == round(st.spec_acceptance, 4)


def test_info_spec_present_without_scheduler():
    """info must not lazily allocate the batch cache just to report."""
    eng = InferenceEngine("tiny-llama", engine_config=EngineConfig(**KW))
    try:
        assert eng.info["spec"] == {
            "spec_tokens": 0, "drafted": 0, "accepted": 0, "acceptance": 0.0
        }
        assert eng._scheduler is None
    finally:
        eng.close()


def test_adaptive_disable_stops_drafting():
    """An impossible acceptance floor disables per-row speculation after
    the probe budget — generation still completes with greedy parity and
    draft volume stays bounded by the probe."""
    ref = InferenceEngine("tiny-llama", engine_config=EngineConfig(**KW))
    eng = InferenceEngine(
        "tiny-llama",
        engine_config=EngineConfig(
            **KW, spec_tokens=6, spec_min_accept=1.1, spec_probe_tokens=12
        ),
    )
    try:
        r0 = ref.generate(REP_PROMPT, max_new_tokens=40, temperature=0.0)
        r1 = eng.generate(REP_PROMPT, max_new_tokens=40, temperature=0.0)
        assert r1.token_ids == r0.token_ids
        st = eng.scheduler.stats
        # disabled once drafted tokens (plus K-weighted misses) cross the
        # probe budget: nowhere near one draft per generated token
        assert 0 < st.spec_drafted <= 12
    finally:
        ref.close()
        eng.close()
