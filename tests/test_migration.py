"""Live generation migration (ISSUE 9): KV-block export/import, graceful
drain, disaggregated prefill→decode, and migration-based failover.

The acceptance pins:
- a generation started on node A, drained mid-decode, and finished on a
  peer produces token-for-token greedy parity with an unmigrated rollout,
  with ZERO re-prefill forwards on the happy path (scheduler counters);
- chaos-injected migration failures (corrupt piece, target pool
  exhaustion, link death mid-stream) degrade to the re-prefill fallback
  with typed ``migration:<reason>`` incident bundles — never a hung
  generation;
- drain plumbing: typed 503 ``draining`` + Retry-After at admission, the
  drain flag rides the telemetry digest, RouterPolicy excludes draining
  peers.
"""

from __future__ import annotations

import asyncio
import contextlib
import json

import numpy as np
import pytest

from bee2bee_tpu.engine import EngineConfig, InferenceEngine

# ONE config for every engine in this file: identical programs hit the
# per-run XLA compile cache, and identical rng_seed means every engine
# holds bit-identical random-init weights — the cross-"node" parity
# precondition (real deployments load the same checkpoint).
CFG = dict(
    max_seq_len=128,
    prefill_buckets=(16, 32, 64),
    dtype="float32",
    cache_dtype="float32",
    decode_chunk=4,
    max_batch=4,
)
PROMPT = "the quick brown fox jumps over the lazy dog"


def _engine(**over) -> InferenceEngine:
    return InferenceEngine("tiny-llama", engine_config=EngineConfig(**{**CFG, **over}))


def _drain_events(req, base_out=()):  # -> (tokens, result)
    out = list(base_out)
    while True:
        ev = req.events.get(timeout=60)
        if ev.get("imported"):
            continue
        if ev.get("done"):
            if ev.get("result") is None:
                raise RuntimeError(ev.get("error"))
            return out, ev["result"]
        out.extend(ev.get("tokens") or [])


def _checkpoint_mid_decode(engine, prompt=PROMPT, max_new_tokens=24,
                           min_tokens=5, **kw):
    """Start a streamed generation, stop consuming after `min_tokens`,
    checkpoint it. Returns (snapshot, kv, request)."""
    gen = engine.generate_stream(prompt, max_new_tokens=max_new_tokens, **kw)
    seen = []
    for ev in gen:
        assert not ev.get("done"), "finished before the checkpoint"
        seen.extend(ev.get("tokens") or [])
        if len(seen) >= min_tokens:
            break
    (req,) = engine.scheduler.live_requests()
    snap = engine.scheduler.checkpoint(req)
    assert snap is not None
    kv = snap.pop("_kv", None)
    return snap, kv, req


# --------------------------------------------------- scheduler-level parity


def test_kv_import_roundtrip_greedy_parity():
    """The tentpole primitive: checkpoint mid-decode on A, scatter the
    blocks into B's pool, resume — token-for-token the unmigrated rollout,
    with zero prefill compute on B (import_reprefills stays 0)."""
    a, b = _engine(), _engine()
    try:
        base = a.generate(PROMPT, max_new_tokens=24)
        snap, kv, _req = _checkpoint_mid_decode(a)
        assert kv is not None and kv["k"].shape == kv["v"].shape
        # the snapshot's wire half is pure JSON (KV_EXPORT `gen` field)
        json.dumps(snap)
        # live-row invariant: KV covers prompt + out[:-1]
        assert snap["offset"] == len(snap["ids"]) + len(snap["out"]) - 1
        assert snap["cur"] == snap["out"][-1]
        assert a.scheduler.stats.migrated_out == 1

        req2 = b.import_generation(snap, kv)
        out, result = _drain_events(req2, snap["out"])
        assert out == base.token_ids
        assert result.finish_reason == base.finish_reason
        assert b.scheduler.stats.migrated_in == 1
        assert b.scheduler.stats.import_reprefills == 0
    finally:
        a.close()
        b.close()


def test_lane_aligned_pool_exports_at_the_models_head_size():
    """On a TPU the in-place path stores the pool lane-aligned
    (core.init_paged_pool: tiny-llama's 16 in 128 lanes here, forced onto the
    CPU engine). The ragged kernels serve from it token for token, blocks
    leave it at the model's head size, and a peer laid out either way takes
    them: aligned -> plain and plain -> aligned both resume with parity."""
    import functools

    import jax

    from bee2bee_tpu.models import core

    def aligned_engine():
        eng = _engine(attention="flash")
        sch = eng.scheduler
        sch.cache.pool = jax.jit(functools.partial(
            core.init_paged_pool, eng.model_cfg, eng.pool_blocks,
            eng.engine_cfg.kv_block_size, sch.cache.pool["kv"].dtype,
            lane_aligned=True,
        ))()
        assert sch.cache.pool["kv"].shape[-1] == 128 != eng.model_cfg.head_dim
        return eng

    plain, a, b = _engine(), aligned_engine(), aligned_engine()
    try:
        base = plain.generate(PROMPT, max_new_tokens=24)
        assert a.generate(PROMPT, max_new_tokens=24).token_ids == base.token_ids
        for src, dst in ((a, plain), (plain, b)):
            snap, kv, _req = _checkpoint_mid_decode(src)
            assert kv["k"].shape[-1] == kv["v"].shape[-1] == src.model_cfg.head_dim
            out, result = _drain_events(dst.import_generation(snap, kv), snap["out"])
            assert out == base.token_ids
            assert dst.scheduler.stats.import_reprefills == 0
        assert not np.asarray(b.scheduler.cache.pool["kv"][..., 16:]).any()
    finally:
        for eng in (plain, a, b):
            eng.close()


def test_reprefill_import_rung_parity():
    """The fallback rung: same snapshot, no KV shipped — the target
    re-prefills prompt+accepted and still resumes token-for-token."""
    a, b = _engine(), _engine()
    try:
        base = a.generate(PROMPT, max_new_tokens=24)
        snap, _kv, _req = _checkpoint_mid_decode(a)
        req2 = b.import_generation(dict(snap))  # kv withheld
        out, _result = _drain_events(req2, snap["out"])
        assert out == base.token_ids
        assert b.scheduler.stats.import_reprefills == 1
    finally:
        a.close()
        b.close()


def test_penalized_row_migrates_with_rebuilt_counts():
    """Occurrence counts never ride the wire — they rebuild from ids+out
    at import. Greedy + repetition penalty is deterministic, so parity
    catches a wrong rebuild."""
    a, b = _engine(), _engine()
    try:
        kw = dict(repetition_penalty=1.3)
        base = a.generate(PROMPT, max_new_tokens=20, **kw)
        snap, kv, _req = _checkpoint_mid_decode(
            a, max_new_tokens=20, min_tokens=4, **kw
        )
        req2 = b.import_generation(snap, kv)
        out, _result = _drain_events(req2, snap["out"])
        assert out == base.token_ids
    finally:
        a.close()
        b.close()


def test_queued_request_checkpoints_meta_only():
    """A not-yet-admitted request checkpoints without device state and
    imports as a plain fresh admission (outcome parity still holds)."""
    eng = _engine(max_batch=1)
    b = _engine(max_batch=1)
    try:
        base = eng.generate(PROMPT, max_new_tokens=12)
        # saturate the single row with a long generation, then queue one
        gen = eng.generate_stream("occupy the only row", max_new_tokens=64)
        next(gen)  # admitted
        from bee2bee_tpu.engine.scheduler import Request  # noqa: F401

        queued = eng._make_request(PROMPT, 12, 0.0, 0, 1.0, None, stream=True)
        eng.scheduler.submit(queued)
        snap = eng.scheduler.checkpoint(queued)
        assert snap is not None and snap.get("_kv") is None
        assert snap["out"] == [] and snap["kv_blocks"] == 0
        req2 = b.import_generation(snap)
        out, _result = _drain_events(req2)
        assert out == base.token_ids
        gen.close()
    finally:
        eng.close()
        b.close()


def test_checkpoint_of_finished_request_returns_none():
    eng = _engine()
    try:
        req = eng._make_request(PROMPT, 4, 0.0, 0, 1.0, None)
        eng.scheduler.submit(req)
        while True:
            ev = req.events.get(timeout=60)
            if ev.get("done"):
                break
        assert eng.scheduler.checkpoint(req) is None
    finally:
        eng.close()


def test_cow_shared_prefix_refcounts_across_migration():
    """CoW-shared prefix case: the migrating row shares pinned prefix
    blocks on the SOURCE; after checkpoint the pins survive and the row's
    refs drop. The TARGET pins the imported prompt blocks in its own
    prefix cache; after retirement its pool holds exactly those pins."""
    a = _engine(prefix_cache_entries=4)
    b = _engine(prefix_cache_entries=4)
    try:
        from bee2bee_tpu.engine.paged import ceil_div

        base = a.generate(PROMPT, max_new_tokens=24)  # pins the prompt
        sch_a = a.scheduler
        pinned_a = sch_a.cache.alloc.used_count
        assert len(sch_a.cache.prefix) >= 1

        snap, kv, _req = _checkpoint_mid_decode(a)  # prefix HIT on admit
        assert sch_a.stats.prefix_hits >= 1, "second admission missed CoW"
        # source: the released row dropped every ref it took; only cache
        # pins (and nothing of the migrated row) remain
        assert sch_a.cache.alloc.used_count == pinned_a
        for blocks in sch_a.cache.prefix._entries.values():
            for blk in blocks:
                assert sch_a.cache.alloc.refcount(blk) == 1

        req2 = b.import_generation(snap, kv)
        out, _result = _drain_events(req2, snap["out"])
        assert out == base.token_ids
        sch_b = b.scheduler
        n_prompt_blocks = ceil_div(len(snap["ids"]), b.engine_cfg.kv_block_size)
        # target after retirement: the import pinned the prompt's blocks
        # (so repeat prompts CoW-share there too) and released the rest
        assert len(sch_b.cache.prefix) == 1
        assert sch_b.cache.alloc.used_count == n_prompt_blocks
        for blocks in sch_b.cache.prefix._entries.values():
            for blk in blocks:
                assert sch_b.cache.alloc.refcount(blk) == 1
        # retiring the pins returns the pool to empty on both ends
        sch_a.cache.prefix.clear()
        sch_b.cache.prefix.clear()
        assert sch_a.cache.alloc.used_count == 0
        assert sch_b.cache.alloc.used_count == 0
    finally:
        a.close()
        b.close()


def test_int8_kv_import_roundtrip_greedy_parity():
    """ISSUE 12: quantized pages migrate — an int8-pool checkpoint ships
    pages AND their per-page-per-head scales (at roughly half the bf16
    page bytes), the target scatters both, and decode resumes
    token-for-token with ZERO re-prefill forwards. Pages share scales
    with their bytes, so the imported rollout is bit-identical to the
    unmigrated one."""
    a, b = _engine(cache_dtype="int8"), _engine(cache_dtype="int8")
    try:
        base = a.generate(PROMPT, max_new_tokens=24)
        snap, kv, _req = _checkpoint_mid_decode(a)
        assert sorted(kv) == ["k", "k_scale", "v", "v_scale"]
        assert kv["k"].dtype == np.int8 and kv["k_scale"].dtype == np.float32
        # scales are per (layer, head, page) — tiny next to the pages
        assert kv["k_scale"].shape == kv["k"].shape[:3]
        page_bytes = kv["k"].nbytes + kv["v"].nbytes
        scale_bytes = kv["k_scale"].nbytes + kv["v_scale"].nbytes
        assert scale_bytes < page_bytes / 16
        json.dumps(snap)  # the wire half stays pure JSON

        req2 = b.import_generation(snap, kv)
        out, result = _drain_events(req2, snap["out"])
        assert out == base.token_ids
        assert result.finish_reason == base.finish_reason
        assert b.scheduler.stats.migrated_in == 1
        assert b.scheduler.stats.import_reprefills == 0
    finally:
        a.close()
        b.close()


def test_int8_import_validation_and_signature_gate():
    """Layout discipline for quantized pages: an int8 engine refuses a
    scale-less kv typed; a full-precision engine refuses int8 pages
    (dtype mismatch) typed; and the migration signatures differ — the
    mesh-level KV gate that bounces an int8 exporter off a bf16 importer
    BEFORE any tensor bytes scatter."""
    a = _engine(cache_dtype="int8")
    b = _engine()  # the full-precision pool (float32 on the CPU suite)
    try:
        # 64 tokens, not the helper's 24: nothing here compares a rollout, and a
        # 24-token request can retire before checkpoint() on a loaded machine
        snap, kv, _req = _checkpoint_mid_decode(a, max_new_tokens=64)
        no_scales = {name: kv[name] for name in ("k", "v")}
        with pytest.raises(ValueError, match="kv tensors"):
            a.import_generation(dict(snap), no_scales)
        with pytest.raises(ValueError, match="kv tensors"):
            b.import_generation(dict(snap), kv)  # scale keys ≠ f32 layout
        assert a.migration_signature() != b.migration_signature()
        assert a.migration_signature()["cache_dtype"] == "int8"
        # and the layout-free rung still works across the dtype split: kv
        # withheld → b re-prefills prompt+accepted at ITS precision and
        # decodes on (the continuation may legitimately differ from a's
        # int8-pool rollout — the accepted prefix is what must survive)
        snap2, _kv2, _ = _checkpoint_mid_decode(a, max_new_tokens=64)
        req2 = b.import_generation(dict(snap2))
        out, _result = _drain_events(req2, snap2["out"])
        assert out[:len(snap2["out"])] == snap2["out"]
        assert len(out) >= len(snap2["out"])
        assert b.scheduler.stats.import_reprefills == 1
    finally:
        a.close()
        b.close()


def test_import_pool_exhausted_is_typed_and_immediate():
    """A target whose pool cannot host the blocks fails the import with a
    typed pool_exhausted event — never a requeue-hang."""
    a = _engine()
    tiny = _engine(kv_pool_blocks=3)  # null block + 2: can't host 3 blocks
    try:
        # a budget well past the 16 tokens waited for: the row decodes on
        # while this thread reads, and must still be live at the checkpoint
        snap, kv, _req = _checkpoint_mid_decode(a, min_tokens=16, max_new_tokens=64)
        assert snap["kv_blocks"] >= 3
        req2 = tiny.import_generation(snap, kv)
        ev = req2.events.get(timeout=60)
        assert ev.get("done") and ev.get("result") is None
        assert ev.get("error_kind") == "pool_exhausted"
        assert tiny.scheduler.stats.migrated_in == 0
    finally:
        a.close()
        tiny.close()


def test_import_validation_rejects_bad_snapshots():
    a, b = _engine(), _engine(kv_block_size=8)
    try:
        snap, kv, _req = _checkpoint_mid_decode(a)
        with pytest.raises(ValueError, match="block_size"):
            b.import_generation(snap, kv)
        bad = {**snap, "model": "tiny-gpt2"}
        with pytest.raises(ValueError, match="model"):
            a.import_generation(bad, kv)
        bad = {**snap, "offset": snap["offset"] + 1}
        with pytest.raises(ValueError, match="invariant"):
            a.import_generation(bad, kv)
        assert a.migration_signature() != b.migration_signature()
    finally:
        a.close()
        b.close()


# ----------------------------------------------------------- mesh plumbing


@contextlib.asynccontextmanager
async def _mesh_with_engines(n=3, roles=None, engine_over=None):
    """N loopback nodes, each serving tiny-llama on its own engine; all
    bootstrapped off node 0 with services announced and digests gossiped."""
    from bee2bee_tpu.meshnet.node import P2PNode
    from bee2bee_tpu.services.tpu import TPUService
    from tests.test_meshnet import _settle

    roles = roles or [None] * n
    over = engine_over or [{}] * n
    nodes, svcs = [], []
    try:
        for i in range(n):
            node = P2PNode(host="127.0.0.1", port=0, disagg_role=roles[i])
            node.ping_interval_s = 0.1
            await node.start()
            svc = TPUService("tiny-llama", engine=_engine(**over[i]))
            node.add_service(svc)
            nodes.append(node)
            svcs.append(svc)
        for node in nodes[1:]:
            assert await node.connect_bootstrap(nodes[0].addr)
        assert await _settle(
            lambda: all(len(x.peers) == n - 1 for x in nodes), timeout=10
        )
        for node, svc in zip(nodes, svcs):
            await node.announce_service(svc)
        for node in nodes:
            await node.gossip_telemetry()
        assert await _settle(
            lambda: all(len(x.health.fresh()) == n - 1 for x in nodes),
            timeout=10,
        )
        yield nodes, svcs
    finally:
        for node in nodes:
            with contextlib.suppress(Exception):
                await node.stop()
        for svc in svcs:
            if svc.engine is not None:
                svc.engine.close()


async def _start_streamed(node, svc, prompt=PROMPT, max_new_tokens=96,
                          min_tokens=2):
    """Drive a streamed generation through the node's own serving path
    (the self-request shortcut → _execute_local → TPUService) and wait
    until it has produced `min_tokens`. Returns (task, chunks)."""
    chunks: list[str] = []
    task = asyncio.create_task(node.request_generation(
        node.peer_id, prompt, model="tiny-llama",
        max_new_tokens=max_new_tokens, temperature=0.0,
        stream=True, on_chunk=chunks.append,
    ))
    for _ in range(400):
        await asyncio.sleep(0.05)
        reqs = svc.engine.scheduler.live_requests()
        if reqs and len(reqs[0].out_ids) >= min_tokens:
            return task, chunks
        if task.done():
            task.result()  # surface the error
    raise AssertionError("generation never reached the checkpoint window")


@pytest.mark.async_timeout(240)
async def test_three_node_drain_token_parity_zero_reprefill():
    """THE acceptance walk: start on A, drain A mid-decode, finish on a
    peer — token-for-token greedy parity, zero re-prefill forwards
    anywhere (pinned by every scheduler's import_reprefills), drain state
    in the digest, router exclusion, typed 503 on new work."""
    async with _mesh_with_engines(3) as (nodes, svcs):
        a, b, c = nodes
        base = svcs[1].engine.generate(PROMPT, max_new_tokens=96)
        task, _chunks = await _start_streamed(a, svcs[0])

        summary = await a.begin_drain()
        assert summary["migrated"] == 1 and summary["failed"] == 0, summary

        result = await task
        assert result["text"] == base.text
        assert result["tokens"] == base.new_tokens

        # zero re-prefill forwards on the happy path — scheduler-pinned
        assert svcs[0].engine.scheduler.stats.migrated_out == 1
        assert sum(s.engine.scheduler.stats.migrated_in for s in svcs) == 1
        assert all(
            s.engine.scheduler.stats.import_reprefills == 0 for s in svcs
        )

        # drain state rides the digest; scored routing excludes A
        digest = a.telemetry_digest()
        assert digest.get("draining") is True
        await a.gossip_telemetry()
        await asyncio.sleep(0.1)
        assert b.health.fresh()[a.peer_id].get("draining") is True
        prov = b.pick_provider("tiny-llama", remote_only=True)
        assert prov is not None and prov["provider_id"] == c.peer_id

        # new local work on A: typed 503 draining with a Retry-After hint
        from bee2bee_tpu.router.admission import AdmissionReject

        with pytest.raises(AdmissionReject) as exc:
            await a.admission.acquire("default")
        assert exc.value.kind == "draining"
        assert exc.value.status == 503
        assert exc.value.retry_after_s > 0


@pytest.mark.async_timeout(240)
async def test_drain_stop_exits_with_goodbye():
    """drain(stop=True): the node leaves clean after the bridged stream
    finishes — peers see a GOODBYE (health digest retired immediately),
    not a TTL'd zombie."""
    from tests.test_meshnet import _settle

    async with _mesh_with_engines(2) as (nodes, svcs):
        a, b = nodes
        task, _chunks = await _start_streamed(a, svcs[0])
        summary = await a.begin_drain(stop=True)
        assert summary["migrated"] == 1
        result = await task
        assert result.get("tokens")
        assert await _settle(lambda: a._stopped, timeout=20)
        assert await _settle(lambda: a.peer_id not in b.health.fresh(), timeout=10)


@pytest.mark.async_timeout(240)
async def test_chaos_corrupt_piece_falls_back_to_reprefill():
    """A corrupted KV piece is refused by hash verification (typed
    hash_mismatch) and the ladder re-prefills — parity still holds and a
    migration:hash_mismatch incident bundle exists."""
    from bee2bee_tpu.health import get_recorder
    from bee2bee_tpu.meshnet.chaos import ChaosMigration

    recorder = get_recorder()
    recorder.clear()
    async with _mesh_with_engines(2) as (nodes, svcs):
        a, b = nodes
        base = svcs[1].engine.generate(PROMPT, max_new_tokens=96)
        chaos = ChaosMigration(a, action="corrupt_piece", at_chunk=0)
        task, _chunks = await _start_streamed(a, svcs[0])
        summary = await a.begin_drain()
        chaos.restore()
        assert chaos.triggered.is_set()
        assert summary["reprefilled"] == 1 and summary["failed"] == 0, summary
        result = await task
        assert result["text"] == base.text
        assert svcs[1].engine.scheduler.stats.import_reprefills == 1
        recorder.flush()
        kinds = {e["kind"] for e in recorder.list_incidents()}
        assert "migration:hash_mismatch" in kinds


@pytest.mark.async_timeout(240)
async def test_corrupt_scale_tensor_falls_back_to_reprefill():
    """ISSUE 12: the int8 export's SCALE tensors are verified exactly
    like the pages — a corrupted k_scale fails its sha256 at the target
    (typed hash_mismatch, the bytes never touch the pool) and the ladder
    re-prefills; the generation still completes with the accepted prefix
    intact."""
    import numpy as np  # noqa: F811 — local alias for clarity

    from bee2bee_tpu import protocol
    from bee2bee_tpu.health import get_recorder

    recorder = get_recorder()
    recorder.clear()
    over = [{"cache_dtype": "int8"}, {"cache_dtype": "int8"}]
    async with _mesh_with_engines(2, engine_over=over) as (nodes, svcs):
        a, b = nodes
        orig = a.migration._send_chunk
        tampered = asyncio.Event()

        async def tamper(ws, frame: bytes, seq: int):
            if seq == 0 and not tampered.is_set():
                tampered.set()
                msg, tensors = protocol.decode_binary(frame)
                assert "k_scale" in tensors, sorted(tensors)
                arr = np.array(tensors["k_scale"])  # writable copy
                arr.view(np.uint8).reshape(-1)[0] ^= 0xFF
                # re-encode with the ORIGINAL hashes header: only the
                # scale payload bytes lie
                frame = protocol.encode_binary(msg, dict(tensors, k_scale=arr))
            await orig(ws, frame, seq)

        a.migration._send_chunk = tamper
        task, _chunks = await _start_streamed(a, svcs[0])
        summary = await a.begin_drain()
        a.migration._send_chunk = orig
        assert tampered.is_set()
        assert summary["reprefilled"] == 1 and summary["failed"] == 0, summary
        result = await task
        assert result.get("tokens")
        assert svcs[1].engine.scheduler.stats.import_reprefills == 1
        recorder.flush()
        kinds = {e["kind"] for e in recorder.list_incidents()}
        assert "migration:hash_mismatch" in kinds


@pytest.mark.async_timeout(240)
async def test_int8_exporter_refused_by_fullprec_importer_then_reprefills():
    """ISSUE 12: an int8-pool node draining toward a full-precision-pool
    peer is refused TYPED at the signature gate (cache_dtype mismatch —
    no tensor bytes ever scatter), and because `incompatible` indicts the
    layout pairing rather than the peer, the ladder's layout-free
    re-prefill rung lands on the SAME peer and the generation completes."""
    from bee2bee_tpu.health import get_recorder

    recorder = get_recorder()
    recorder.clear()
    over = [{"cache_dtype": "int8"}, {}]  # a quantized, b full precision
    async with _mesh_with_engines(2, engine_over=over) as (nodes, svcs):
        a, b = nodes
        # drive-by pin: the telemetry digest advertises WHICH pool layout
        # each peer runs (cache_dtype + effective capacity, keyed by
        # service — a node may host mixed-precision pools), so the
        # router/fleet view can tell a doubled int8 pool from a bf16 one
        (ka,) = a.telemetry_digest()["kv"].values()
        (kb,) = b.telemetry_digest()["kv"].values()
        assert ka["cache_dtype"] == "int8"
        assert kb["cache_dtype"] == "float32"
        assert ka["capacity_tokens"] == kb["capacity_tokens"] > 0
        task, _chunks = await _start_streamed(a, svcs[0])
        summary = await a.begin_drain()
        assert summary["reprefilled"] == 1 and summary["failed"] == 0, summary
        result = await task
        assert result.get("tokens")
        assert svcs[1].engine.scheduler.stats.import_reprefills == 1
        recorder.flush()
        kinds = {e["kind"] for e in recorder.list_incidents()}
        assert "migration:incompatible" in kinds


@pytest.mark.async_timeout(240)
async def test_chaos_target_pool_exhausted_falls_back():
    """Target pool exhaustion mid-import rejects typed; the ladder
    re-prefills (here: on the same sole peer once the chaos lifts — the
    rung is what's pinned) and the generation completes."""
    from bee2bee_tpu.health import get_recorder
    from bee2bee_tpu.meshnet.chaos import ChaosMigration

    recorder = get_recorder()
    recorder.clear()
    async with _mesh_with_engines(3) as (nodes, svcs):
        a, b, c = nodes
        base = svcs[1].engine.generate(PROMPT, max_new_tokens=96)
        chaos_b = ChaosMigration(b, action="exhaust_target")
        chaos_c = ChaosMigration(c, action="exhaust_target")
        task, _chunks = await _start_streamed(a, svcs[0])
        # lift the chaos on the SECOND rung only: the KV rung must fail
        # typed first
        orig_fallback = a.migration._migrate_once

        async def unchaos_then(*args, **kw):
            if args[3] is None:  # the re-prefill rung (kv=None)
                chaos_b.restore()
                chaos_c.restore()
            return await orig_fallback(*args, **kw)

        a.migration._migrate_once = unchaos_then
        summary = await a.begin_drain()
        a.migration._migrate_once = orig_fallback
        assert chaos_b.triggered.is_set() or chaos_c.triggered.is_set()
        assert summary["reprefilled"] == 1 and summary["failed"] == 0, summary
        result = await task
        assert result["text"] == base.text
        recorder.flush()
        kinds = {e["kind"] for e in recorder.list_incidents()}
        assert "migration:pool_exhausted" in kinds


@pytest.mark.async_timeout(240)
async def test_chaos_kill_link_mid_stream_falls_back():
    """The source→target link dies mid-KV_BLOCKS: the rung fails typed,
    the target abandons its partial import, and the ladder re-prefills on
    the surviving peer — never a hung generation."""
    from bee2bee_tpu.health import get_recorder
    from bee2bee_tpu.meshnet.chaos import ChaosMigration

    recorder = get_recorder()
    recorder.clear()
    async with _mesh_with_engines(3) as (nodes, svcs):
        a, b, c = nodes
        base = svcs[1].engine.generate(PROMPT, max_new_tokens=96)
        chaos = ChaosMigration(a, action="kill_link", at_chunk=0)
        task, _chunks = await _start_streamed(a, svcs[0])
        summary = await a.begin_drain()
        chaos.restore()
        assert chaos.triggered.is_set()
        assert summary["failed"] == 0, summary
        assert summary["reprefilled"] == 1
        result = await task
        assert result["text"] == base.text
        # no dangling partial import anywhere
        assert not b.migration._imports and not c.migration._imports
        recorder.flush()
        kinds = {e["kind"] for e in recorder.list_incidents()}
        assert "migration:export_failed" in kinds


@pytest.mark.async_timeout(240)
async def test_every_rung_dead_yields_typed_error_not_hang():
    """No target at any rung: the consumer gets a typed error done-event
    (and a migration:unrecoverable bundle) — the no-hung-generation
    contract."""
    from bee2bee_tpu.health import get_recorder

    recorder = get_recorder()
    recorder.clear()
    async with _mesh_with_engines(2) as (nodes, svcs):
        a, b = nodes
        task, _chunks = await _start_streamed(a, svcs[0])
        (req,) = svcs[0].engine.scheduler.live_requests()
        snap = await asyncio.to_thread(svcs[0].engine.scheduler.checkpoint, req)
        kv = snap.pop("_kv", None)
        # every peer refuses: mark B draining so no rung has a target
        b.draining = True
        await b.gossip_telemetry()
        await asyncio.sleep(0.2)
        outcome = await a.migration._migrate_with_fallback(
            req, svcs[0], snap, kv, "drain"
        )
        assert outcome == "failed"
        with pytest.raises(Exception, match="migration_failed"):
            await task
        recorder.flush()
        kinds = {e["kind"] for e in recorder.list_incidents()}
        assert "migration:no_target" in kinds
        assert "migration:unrecoverable" in kinds


@pytest.mark.async_timeout(240)
async def test_disagg_prefill_handoff_to_decode_peer():
    """Disaggregated serving: a prefill-designated node ships every
    freshly prefilled generation to the decode-designated peer (never the
    plain one), with full output parity and TTFT measured at the prefill
    node as usual."""
    async with _mesh_with_engines(
        3, roles=["prefill", "decode", None]
    ) as (nodes, svcs):
        a, b, c = nodes
        assert svcs[0].engine.scheduler.handoff_after_prefill
        base = svcs[1].engine.generate(PROMPT, max_new_tokens=16)
        chunks: list[str] = []
        result = await a.request_generation(
            a.peer_id, PROMPT, model="tiny-llama", max_new_tokens=16,
            temperature=0.0, stream=True, on_chunk=chunks.append,
        )
        assert result["text"] == base.text
        assert "".join(chunks) == base.text
        sch_a = svcs[0].engine.scheduler
        assert sch_a.stats.prefill_handoffs == 1
        assert sch_a.stats.migrated_out == 1
        assert svcs[1].engine.scheduler.stats.migrated_in == 1, (
            "handoff must land on the decode-designated peer"
        )
        assert svcs[2].engine.scheduler.stats.migrated_in == 0


@pytest.mark.async_timeout(240)
async def test_pool_exhaustion_mid_decode_migrates_instead_of_erroring():
    """Migration-based failover: a row the local pool can't grow (the
    old typed-error path) migrates to a peer with headroom and finishes
    with parity."""
    # pool sized to admit but not to finish: the prompt takes 1 block,
    # decode needs more as it crosses block boundaries
    async with _mesh_with_engines(
        2, engine_over=[{"kv_pool_blocks": 3, "max_batch": 1}, {}]
    ) as (nodes, svcs):
        a, b = nodes
        base = svcs[1].engine.generate("hi", max_new_tokens=40)
        chunks: list[str] = []
        result = await a.request_generation(
            a.peer_id, "hi", model="tiny-llama", max_new_tokens=40,
            temperature=0.0, stream=True, on_chunk=chunks.append,
        )
        assert result["text"] == base.text
        assert svcs[0].engine.scheduler.stats.migrated_out == 1
        assert svcs[1].engine.scheduler.stats.migrated_in == 1


# ------------------------------------------------------------ drain surface


async def test_admin_drain_endpoint_and_typed_503():
    """POST /admin/drain flips the node; new /chat answers 503 with
    error_kind=draining and a Retry-After header; GET /admin/drain
    reports status (engine-less FakeService node: plumbing only)."""
    from bee2bee_tpu.meshnet.node import P2PNode
    from bee2bee_tpu.services.fake import FakeService
    from tests.test_health import _health_app

    node = P2PNode(host="127.0.0.1", port=0)
    await node.start()
    node.add_service(FakeService("fake-model", reply="ok"))
    client = await _health_app(node)
    try:
        r = await client.post("/chat", json={"prompt": "hi", "model": "fake-model"})
        assert r.status == 200

        r = await client.post("/admin/drain", json={})
        assert r.status == 200
        summary = await r.json()
        assert summary["draining"] is True

        r = await client.get("/admin/drain")
        assert (await r.json())["draining"] is True

        r = await client.post("/chat", json={"prompt": "hi", "model": "fake-model"})
        assert r.status == 503
        body = await r.json()
        assert body["error_kind"] == "draining"
        assert int(r.headers["Retry-After"]) >= 1

        # the p2p twin: gen_request answers a typed GEN_ERROR frame
        sent = []

        class _WS:
            async def send(self, raw):
                sent.append(raw)

        await node._serve_gen_request(_WS(), {
            "type": "gen_request", "rid": "r1", "prompt": "hi",
            "model": "fake-model",
        })
        import json as _json

        frame = _json.loads(sent[-1])
        assert frame["type"] == "gen_error"
        assert frame["error_kind"] == "draining"
        assert frame["retry_after_s"] > 0
    finally:
        await client.close()
        await node.stop()


async def test_migration_import_skips_slo_shed_but_never_drain():
    """A migration import is evacuated state, not new demand: the SLO
    shed does not apply to it — but a draining target still refuses
    (it is exporting its own rows), and so do the queue bounds."""
    from bee2bee_tpu.router.admission import (
        AdmissionConfig,
        AdmissionController,
        AdmissionReject,
    )

    burn = {"v": 10.0}
    draining = {"v": False}
    ctrl = AdmissionController(
        config=AdmissionConfig(),
        slo_burn=lambda: burn["v"],
        draining=lambda: draining["v"],
    )
    with pytest.raises(AdmissionReject) as exc:
        await ctrl.acquire("t")
    assert exc.value.kind == "slo_shed"
    ticket = await ctrl.acquire("t", migration=True)
    ticket.release()
    draining["v"] = True
    with pytest.raises(AdmissionReject) as exc:
        await ctrl.acquire("t", migration=True)
    assert exc.value.kind == "draining"


def test_router_policy_excludes_draining_peers():
    from bee2bee_tpu.router.policy import RouterPolicy

    cands = [
        {"provider_id": "p1", "local": False, "price_per_token": 0.0},
        {"provider_id": "p2", "local": False, "price_per_token": 0.0},
    ]
    fresh = {
        "p1": {"draining": True},
        "p2": {"gauge": {"engine.batch_fill": 0.9}},  # loaded but staying
    }
    winner, decision = RouterPolicy().pick(cands, fresh)
    assert winner["provider_id"] == "p2"
    # even the all-burning waiver never re-admits a draining peer
    fresh["p2"] = {"slo": {"o": {"status": "burning"}}}
    winner, _ = RouterPolicy().pick(cands, fresh)
    assert winner is not None and winner["provider_id"] == "p2"


def test_migration_incident_kinds_are_per_reason():
    """Satellite: migration:<reason> kinds are registered per CAUSE, so
    the flight recorder's per-kind cooldown can't let one failing path
    mask another — or mask an slo:* trip."""
    import tempfile

    from bee2bee_tpu.health import FlightRecorder
    from bee2bee_tpu.meshnet.migrate import REASON_CODES, MigrationError

    assert {"hash_mismatch", "pool_exhausted", "no_target", "stream_lost",
            "unrecoverable"} <= REASON_CODES
    # unknown codes clamp into the closed set (bounded incident kinds)
    assert MigrationError("not-a-code").code == "import_rejected"
    with tempfile.TemporaryDirectory() as d:
        rec = FlightRecorder(incident_dir=d)
        first = rec.incident("migration:hash_mismatch", detail="x")
        assert first is not None
        # same kind cools down...
        assert rec.incident("migration:hash_mismatch", detail="x") is None
        # ...but a different failure reason, and an SLO trip, still land
        assert rec.incident("migration:pool_exhausted", detail="y") is not None
        assert rec.incident("slo:ttft_p95", detail="z") is not None
        rec.flush()
        kinds = {e["kind"] for e in rec.list_incidents()}
        assert kinds == {
            "migration:hash_mismatch", "migration:pool_exhausted",
            "slo:ttft_p95",
        }
