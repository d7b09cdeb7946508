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

import threading
from dataclasses import replace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bee2bee_tpu.models import core
from bee2bee_tpu.models.config import get_config
from bee2bee_tpu.ops import ragged_paged_attention

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
    kp = jnp.asarray(rng.standard_normal((Hkv, NB, BS, hd)), dtype)
    vp = jnp.asarray(rng.standard_normal((Hkv, NB, BS, hd)), dtype)
    q = jnp.asarray(rng.standard_normal((B, T, H, hd)), dtype)
    S = MB * BS
    # gathered view [B, S, Hkv, hd] — what the dense path attends over
    kg = jnp.transpose(kp[:, tables], (1, 2, 3, 0, 4)).reshape(B, S, Hkv, hd)
    vg = jnp.transpose(vp[:, tables], (1, 2, 3, 0, 4)).reshape(B, S, Hkv, hd)
    s_idx = np.arange(S)[None, None, :]
    q_pos = (offs[:, None] + np.arange(T)[None, :])[:, :, None]
    mask = jnp.asarray(s_idx <= q_pos)  # [B, T, S] — for the dense ref
    return q, kp, vp, jnp.asarray(tables), jnp.asarray(offs), mask, kg, vg


def _dense_ref(q, kg, vg, mask, cfg=CFG):
    return core._attention(q, kg, vg, mask[:, None, :, :], cfg)


def _assert_close(got, want, atol=2e-5):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=atol)


def test_ragged_decode_lengths_across_block_boundaries():
    """T=1 decode rows whose lengths sit just below, at, and past block
    boundaries (BS=8): the per-row page walk must mask the exact ragged
    extent."""
    q, kp, vp, tb, off, mask, kg, vg = _pool_case(
        offs=[0, 7, 8, 21], T=1, H=4, Hkv=2, hd=16
    )
    out = ragged_paged_attention(q, kp, vp, tb, off)
    _assert_close(out, _dense_ref(q, kg, vg, mask))


def test_ragged_null_block_tail_is_masked():
    """Table entries past the live extent map to null block 0 (the
    engine's pow2-bucketed width padding): they must contribute exactly
    nothing, matching the dense reference over the same padded view."""
    q, kp, vp, tb, off, mask, kg, vg = _pool_case(
        offs=[3, 12], T=1, H=4, Hkv=2, hd=16, extra_tables=3, seed=1
    )
    assert int((np.asarray(tb) == 0).sum()) >= 6  # tails really padded
    out = ragged_paged_attention(q, kp, vp, tb, off)
    assert np.isfinite(np.asarray(out)).all()
    _assert_close(out, _dense_ref(q, kg, vg, mask))


def test_ragged_dead_row_all_null_is_finite():
    """A dead batch row (retired mid-batch) has its whole table nulled:
    output is garbage-but-finite, and live rows are untouched."""
    q, kp, vp, tb, off, mask, kg, vg = _pool_case(
        offs=[9, 4], T=1, H=4, Hkv=2, hd=16, seed=2
    )
    tb = tb.at[1].set(0)
    out = ragged_paged_attention(q, kp, vp, tb, off)
    assert np.isfinite(np.asarray(out)).all()
    want = _dense_ref(q[:1], kg[:1], vg[:1], mask[:1])
    _assert_close(out[:1], want)


@pytest.mark.parametrize("H,Hkv", [(4, 4), (8, 2), (4, 1)],
                         ids=["mha", "gqa4", "mqa"])
def test_ragged_gqa_ratios(H, Hkv):
    q, kp, vp, tb, off, mask, kg, vg = _pool_case(
        offs=[5, 18], T=2, H=H, Hkv=Hkv, hd=8, seed=3
    )
    out = ragged_paged_attention(q, kp, vp, tb, off)
    _assert_close(out, _dense_ref(q, kg, vg, mask))


def test_ragged_sliding_window_softcap_and_scale():
    """The gemma-2 stack: the sliding window arrives as the prefetched
    scalar (0 = full causal; a traced value works — the per-layer
    alternation selects it with jnp.where), softcap and the score-scale
    override as scalar params — all must match the dense path, which is
    the ModelConfig-coverage contract."""
    cfg = replace(CFG, attn_logit_softcap=30.0, attn_scale=13)
    q, kp, vp, tb, off, mask, kg, vg = _pool_case(
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
            q, kp, vp, tb, off, window=window,
            sm_scale=1.0 / math.sqrt(13), logit_softcap=30.0,
        )
        _assert_close(out, _dense_ref(q, kg, vg, maskw, cfg))
    # a window wider than any offset never masks: must equal full causal
    out = ragged_paged_attention(q, kp, vp, tb, off, window=10_000)
    _assert_close(out, _dense_ref(q, kg, vg, mask))


def test_ragged_spec_verify_shape():
    """[B, K+1] — the speculative-decode verify chunk: per-row offsets,
    rows at different depths, causality within the chunk."""
    K = 5
    q, kp, vp, tb, off, mask, kg, vg = _pool_case(
        offs=[2, 15, 24], T=K + 1, H=4, Hkv=2, hd=16, seed=5
    )
    out = ragged_paged_attention(q, kp, vp, tb, off)
    _assert_close(out, _dense_ref(q, kg, vg, mask))


def test_ragged_prefill_chunk_rows():
    """A bucket-wide chunk (T=16) at ragged per-row offsets — chunked
    prefill re-anchoring lands rows at arbitrary positions; q-row tiling
    (block_q below the row count) must not change the math."""
    q, kp, vp, tb, off, mask, kg, vg = _pool_case(
        offs=[0, 11], T=16, H=4, Hkv=2, hd=16, seed=6
    )
    out = ragged_paged_attention(q, kp, vp, tb, off, block_q=8)
    _assert_close(out, _dense_ref(q, kg, vg, mask))


def test_ragged_under_jit():
    q, kp, vp, tb, off, mask, kg, vg = _pool_case(
        offs=[4, 9], T=1, H=4, Hkv=2, hd=16, seed=7
    )
    f = jax.jit(lambda *a: ragged_paged_attention(*a))
    _assert_close(f(q, kp, vp, tb, off), _dense_ref(q, kg, vg, mask))


def _quantize_pool(kp, vp):
    """f32 pool → (int8 pool, [Hkv, NB] scales), the per-page-per-head
    symmetric amax recipe core._quantized_page_write applies on write."""
    def one(p):
        s = np.max(np.abs(np.asarray(p, np.float32)), axis=(2, 3)) / 127.0
        safe = np.where(s > 0, s, 1.0)
        q = np.clip(
            np.rint(np.asarray(p, np.float32) / safe[:, :, None, None]),
            -127, 127,
        ).astype(np.int8)
        return jnp.asarray(q), jnp.asarray(s.astype(np.float32))

    kq, ks = one(kp)
    vq, vs = one(vp)
    return kq, ks, vq, vs


def test_ragged_int8_pool_dequant_matches_dense_on_dequantized_view():
    """ISSUE 12 kernel contract: with an int8 pool + [Hkv, NB] scales the
    kernel dequantizes K before QK^T and V before PV per gathered block —
    it must match the dense reference attending over the HOST-dequantized
    gathered view exactly (same values enter both softmaxes, so the only
    tolerance is the usual online-softmax reordering). Covers ragged
    decode lengths, null-block tails, and the [B, K+1] verify shape."""
    for offs, T, extra in ([0, 7, 8, 21], 1, 0), ([3, 12], 1, 3), ([2, 15, 24], 6, 0):
        q, kp, vp, tb, off, mask, _kg, _vg = _pool_case(
            offs=offs, T=T, H=4, Hkv=2, hd=16, extra_tables=extra, seed=11
        )
        kq, ks, vq, vs = _quantize_pool(kp, vp)
        out = ragged_paged_attention(q, kq, vq, tb, off, k_scale=ks, v_scale=vs)
        # dense view over the DEQUANTIZED pool (what the engine's int8
        # dense fallback builds), gathered exactly like _pool_case does
        kdq = jnp.asarray(kq, jnp.float32) * ks[:, :, None, None]
        vdq = jnp.asarray(vq, jnp.float32) * vs[:, :, None, None]
        B, S = tb.shape[0], tb.shape[1] * kp.shape[2]
        kg = jnp.transpose(kdq[:, tb], (1, 2, 3, 0, 4)).reshape(B, S, 2, 16)
        vg = jnp.transpose(vdq[:, tb], (1, 2, 3, 0, 4)).reshape(B, S, 2, 16)
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


def test_ragged_int8_requires_both_scales():
    q, kp, vp, tb, off, *_ = _pool_case(offs=[4], T=1, H=4, Hkv=2, hd=16)
    kq, ks, _vq, _vs = _quantize_pool(kp, vp)
    with pytest.raises(ValueError, match="k_scale and v_scale"):
        ragged_paged_attention(q, kq, vp, tb, off, k_scale=ks)


def test_ragged_bf16_storage_f32_accumulation():
    q, kp, vp, tb, off, mask, kg, vg = _pool_case(
        offs=[10], T=1, H=4, Hkv=2, hd=16, seed=8, dtype=jnp.bfloat16
    )
    out = ragged_paged_attention(q, kp, vp, tb, off)
    assert out.dtype == jnp.bfloat16
    want = _dense_ref(
        q.astype(jnp.float32), kg.astype(jnp.float32),
        vg.astype(jnp.float32), mask,
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want), atol=0.08, rtol=0.08
    )


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
            for blocks in eng.scheduler._prefix_cache._entries.values()
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
            for blocks in eng.scheduler._prefix_cache._entries.values()
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
