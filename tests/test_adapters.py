"""Batched multi-LoRA serving (adapters/, ROADMAP item 1): the hot-swap
pool, per-row adapter selection inside one decode step (greedy parity vs
merged-weights reference engines), the sha256 adapter manifest, DHT
paging over the mesh, router affinity, tenant mapping, and the /v1
``<base>:<adapter>`` surface with its typed 404."""

from __future__ import annotations

import asyncio
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bee2bee_tpu.adapters import (
    AdapterPoolBusy,
    UnknownAdapter,
    clamp_adapter_name,
    split_model_adapter,
)
from bee2bee_tpu.adapters.pool import AdapterPool
from bee2bee_tpu.engine import EngineConfig, InferenceEngine
from bee2bee_tpu.models import core, get_config
from bee2bee_tpu.train.lora import (
    AdapterLoadError,
    LoraConfig,
    init_lora,
    load_adapters,
    merge_lora,
    save_adapters,
)

CFG = get_config("tiny-llama")
ECFG = dict(
    max_seq_len=64, prefill_buckets=(16,), dtype="float32",
    cache_dtype="float32", decode_chunk=4,
)


def _base_params():
    return jax.tree.map(
        np.asarray,
        jax.device_get(core.init_params(CFG, jax.random.key(0), dtype=jnp.float32)),
    )


def _adapter(seed: int, lcfg: LoraConfig, shift: float = 0.03):
    # shift breaks the zero-init identity so each adapter's output is
    # observably its own
    return jax.tree.map(
        lambda x: x + shift, init_lora(CFG, lcfg, jax.random.key(seed))
    )


def _pool_engine(n_slots=4, **over):
    return InferenceEngine(
        CFG, params=_base_params(),
        engine_config=EngineConfig(max_adapters=n_slots, **{**ECFG, **over}),
    )


def _merged_engine(adapters, lcfg):
    return InferenceEngine(
        CFG, params=merge_lora(_base_params(), jax.device_get(adapters), lcfg),
        engine_config=EngineConfig(**ECFG),
    )


# ---------------------------------------------------------------- naming


def test_split_model_adapter_and_clamp():
    assert split_model_adapter("tiny-llama:acme") == ("tiny-llama", "acme")
    assert split_model_adapter("tiny-llama") == ("tiny-llama", None)
    assert split_model_adapter(None) == (None, None)
    # only the FIRST colon splits; the adapter half comes back RAW so
    # callers can distinguish "no adapter" from "malformed adapter" —
    # clamping "a:b" to None here would silently serve the plain base
    assert split_model_adapter("base:a:b") == ("base", "a:b")
    assert clamp_adapter_name("a:b") is None
    assert clamp_adapter_name("ok-name_1") == "ok-name_1"
    assert clamp_adapter_name("x" * 65) is None
    assert clamp_adapter_name("sneaky/key") is None
    assert clamp_adapter_name(7) is None
    assert clamp_adapter_name("") is None


# ------------------------------------------------------------------ pool


def test_pool_load_lru_evict_and_refcount():
    pool = AdapterPool(CFG, slots=2)
    lcfg = LoraConfig(rank=4)
    pool.load("a", _adapter(1, lcfg), lcfg)
    pool.load("b", _adapter(2, lcfg), lcfg)
    assert pool.resident() == ["a", "b"]
    # touching "a" makes "b" the LRU victim
    slot_a = pool.acquire("a")
    pool.release(slot_a)
    pool.load("c", _adapter(3, lcfg), lcfg)
    assert pool.resident() == ["a", "c"]
    assert pool.evictions == 1
    # an in-flight ref pins its slot: with both slots referenced nothing
    # can be evicted — typed backpressure
    s_a, s_c = pool.acquire("a"), pool.acquire("c")
    with pytest.raises(AdapterPoolBusy):
        pool.load("d", _adapter(4, lcfg), lcfg)
    with pytest.raises(AdapterPoolBusy):
        pool.evict("a")
    pool.release(s_a)
    pool.release(s_c)
    assert pool.evict("c") is True
    assert pool.resident() == ["a"]
    with pytest.raises(UnknownAdapter):
        pool.acquire("c")


def test_pool_rank_padding_and_target_subset():
    pool = AdapterPool(CFG, slots=2)
    big = LoraConfig(rank=8, targets=("wq", "wv"))
    pool.load("big", _adapter(1, big), big)
    # smaller rank zero-pads; subset of targets leaves the rest zero
    small = LoraConfig(rank=2, targets=("wq",))
    pool.load("small", _adapter(2, small), small)
    assert pool.rank == 8 and set(pool.targets) == {"wq", "wv"}
    # a LARGER rank or a NEW target cannot stack: typed errors
    with pytest.raises(AdapterLoadError):
        too_big = LoraConfig(rank=16, targets=("wq",))
        pool.load("huge", _adapter(3, too_big), too_big)
    with pytest.raises(AdapterLoadError):
        other = LoraConfig(rank=4, targets=("wo",))
        pool.load("other", _adapter(4, other), other)


def test_pool_shape_mismatch_is_typed_not_jit_crash():
    pool = AdapterPool(CFG, slots=1)
    lcfg = LoraConfig(rank=4)
    bad = _adapter(1, lcfg)
    bad["wq"]["a"] = bad["wq"]["a"][:, :-1, :]  # wrong din
    with pytest.raises(AdapterLoadError, match="shape"):
        pool.load("bad", bad, lcfg)


# ------------------------------------------- manifest (save/load, sha256)


def test_adapter_manifest_roundtrip_and_tamper(tmp_path):
    lcfg = LoraConfig(rank=4, alpha=8.0, targets=("wq", "wo"))
    adapters = init_lora(CFG, lcfg, jax.random.key(2))
    p = tmp_path / "a.npz"
    save_adapters(p, adapters, lcfg)
    loaded, lcfg2 = load_adapters(p, model_cfg=CFG)
    assert lcfg2 == lcfg
    for a, b in zip(jax.tree.leaves(adapters), jax.tree.leaves(loaded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # tamper ONE tensor inside the zip: the per-tensor sha256 manifest
    # must catch it as a typed load error, not hand garbage to a pool
    import zipfile

    with np.load(p) as z:
        names = [n for n in z.files if not n.startswith("__meta_")]
        data = {n: z[n] for n in z.files}
    victim = names[0]
    data[victim] = data[victim] + 1e-3
    np.savez(p, **data)
    with pytest.raises(AdapterLoadError, match="hash mismatch"):
        load_adapters(p)

    # unreadable file → typed, not zipfile traceback
    p2 = tmp_path / "junk.npz"
    p2.write_bytes(b"not a zip")
    with pytest.raises(AdapterLoadError):
        load_adapters(p2)
    assert zipfile  # silence lint


def test_rank_mismatch_is_typed_at_load(tmp_path):
    """An adapter whose declared rank disagrees with the engine's model
    is refused at load — never a shape crash inside jit."""
    other = get_config("tiny-gpt2")  # d_ff 256 vs tiny-llama's 128
    lcfg = LoraConfig(rank=4, targets=("w_up",))
    adapters = init_lora(other, lcfg, jax.random.key(0))
    p = tmp_path / "o.npz"
    save_adapters(p, adapters, lcfg)
    with pytest.raises(AdapterLoadError, match="shape"):
        load_adapters(p, model_cfg=CFG)  # tiny-llama engine, tiny-gpt2 factors


def test_model_target_mismatch_is_typed():
    """validate_targets' per-model check (w_gate on a non-gated MLP)
    surfaces as the typed AdapterLoadError through the shared shape
    gate — a mesh fetch of an incompatible adapter must not book an
    infrastructure fetch_failed incident for a model mismatch."""
    from bee2bee_tpu.train.lora import validate_adapter_shapes

    gpt = get_config("tiny-gpt2")  # gelu: no w_gate exists
    lcfg = LoraConfig(rank=4, targets=("wq", "w_gate"))
    with pytest.raises(AdapterLoadError, match="w_gate"):
        validate_adapter_shapes(gpt, {}, lcfg)


# ------------------------------------------------- engine serving parity


def test_per_adapter_greedy_parity_vs_merged_reference():
    """Each adapter served from the pool == a dedicated engine built from
    trainer-style merged params (the ISSUE acceptance pin)."""
    lcfg = LoraConfig(rank=4, alpha=32.0)
    a1, a2 = _adapter(1, lcfg), _adapter(2, lcfg, shift=-0.02)
    eng = _pool_engine()
    eng.load_adapter("a1", a1, lcfg)
    eng.load_adapter("a2", a2, lcfg)
    m1, m2 = _merged_engine(a1, lcfg), _merged_engine(a2, lcfg)
    base = InferenceEngine(
        CFG, params=_base_params(), engine_config=EngineConfig(**ECFG)
    )
    try:
        prompt = "multi tenant decode"
        g0 = eng.generate(prompt, max_new_tokens=8, temperature=0.0)
        g1 = eng.generate(prompt, max_new_tokens=8, temperature=0.0, adapter="a1")
        g2 = eng.generate(prompt, max_new_tokens=8, temperature=0.0, adapter="a2")
        w0 = base.generate(prompt, max_new_tokens=8, temperature=0.0)
        w1 = m1.generate(prompt, max_new_tokens=8, temperature=0.0)
        w2 = m2.generate(prompt, max_new_tokens=8, temperature=0.0)
        assert g0.token_ids == w0.token_ids  # adapter-less rows stay exact
        assert g1.token_ids == w1.token_ids
        assert g2.token_ids == w2.token_ids
        # the adapters actually did something
        assert g1.token_ids != g0.token_ids
        assert g2.token_ids != g1.token_ids
    finally:
        for e in (eng, m1, m2, base):
            e.close()


def test_mixed_batch_three_adapters_plus_base_one_decode_step():
    """3 adapters + an adapter-less row decode in ONE shared batch (per-
    row selection inside the same step), each matching its dedicated
    merged-weights engine token-for-token."""
    lcfg = LoraConfig(rank=4, alpha=32.0)
    ads = {f"a{i}": _adapter(i, lcfg, shift=0.02 * i) for i in (1, 2, 3)}
    eng = _pool_engine()
    for name, ad in ads.items():
        eng.load_adapter(name, ad, lcfg)
    rows = [None, "a1", "a2", "a3"]
    outs: dict = {}
    barrier = threading.Barrier(len(rows))

    def run(i, name):
        barrier.wait()
        outs[i] = eng.generate(
            f"tenant row {i}", max_new_tokens=8, temperature=0.0, adapter=name
        )

    ths = [
        threading.Thread(target=run, args=(i, name))
        for i, name in enumerate(rows)
    ]
    try:
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        # all four shared the batch: one engine, one pool, rows together
        assert eng.scheduler.stats.peak_active == len(rows)
        for i, name in enumerate(rows):
            if name is None:
                ref = InferenceEngine(
                    CFG, params=_base_params(), engine_config=EngineConfig(**ECFG)
                )
            else:
                ref = _merged_engine(ads[name], lcfg)
            want = ref.generate(f"tenant row {i}", max_new_tokens=8, temperature=0.0)
            ref.close()
            assert outs[i].token_ids == want.token_ids, (i, name)
    finally:
        eng.close()


def test_hot_swap_mid_traffic_in_flight_generation_unaffected():
    """Evict+load (the DHT paging moves) while a generation is in flight
    on ANOTHER adapter: the live row keeps its factors and its greedy
    parity; the live adapter itself refuses eviction (refcount)."""
    lcfg = LoraConfig(rank=4, alpha=32.0)
    a1, a2, a3 = (_adapter(i, lcfg, shift=0.02 * i) for i in (1, 2, 3))
    eng = _pool_engine(n_slots=2)
    eng.load_adapter("a1", a1, lcfg)
    eng.load_adapter("a2", a2, lcfg)
    m1 = _merged_engine(a1, lcfg)
    try:
        stream = eng.generate_stream(
            "hot swap victim", max_new_tokens=24, temperature=0.0, adapter="a1"
        )
        first = next(stream)  # generation is now admitted + in flight
        # the in-flight adapter cannot be yanked
        with pytest.raises(AdapterPoolBusy):
            eng.unload_adapter("a1")
        # but a COLD adapter can hot-swap out for a freshly paged-in one
        assert eng.unload_adapter("a2") is True
        eng.load_adapter("a3", a3, lcfg)
        assert eng.resident_adapters() == ["a1", "a3"]
        toks = list(first.get("tokens") or [])
        for ev in stream:
            if ev.get("done"):
                break
            toks.extend(ev.get("tokens") or [])
        want = m1.generate("hot swap victim", max_new_tokens=24, temperature=0.0)
        assert toks == want.token_ids  # swap never touched the live row
        # retired → refcount returned → now evictable
        assert eng.unload_adapter("a1") is True
    finally:
        eng.close()
        m1.close()


def test_unknown_adapter_typed_before_submit_and_info():
    eng = _pool_engine(n_slots=2)
    try:
        with pytest.raises(UnknownAdapter):
            eng.generate("x", max_new_tokens=4, adapter="nope")
        lcfg = LoraConfig(rank=4)
        eng.load_adapter("a1", _adapter(1, lcfg), lcfg)
        info = eng.info["adapters"]
        assert info["resident"] == ["a1"]
        assert info["slots"] == 2 and info["rank"] == 4
    finally:
        eng.close()


def test_no_pool_engine_rejects_adapter_requests():
    eng = InferenceEngine(
        CFG, params=_base_params(), engine_config=EngineConfig(**ECFG)
    )
    try:
        with pytest.raises(UnknownAdapter):
            eng.generate("x", max_new_tokens=4, adapter="a1")
    finally:
        eng.close()


def test_adapter_rows_skip_prefix_cache_sharing():
    """A prompt prefilled under an adapter must NOT seed (or hit) the
    base model's prefix cache — adapted wk/wv writes different K/V."""
    lcfg = LoraConfig(rank=4, alpha=32.0)
    eng = _pool_engine(prefix_cache_entries=4)
    eng.load_adapter("a1", _adapter(1, lcfg), lcfg)
    m1 = _merged_engine(_adapter(1, lcfg), lcfg)
    base = InferenceEngine(
        CFG, params=_base_params(), engine_config=EngineConfig(**ECFG)
    )
    try:
        prompt = "shared prefix prompt with enough tokens to span blocks"
        ga = eng.generate(prompt, max_new_tokens=6, temperature=0.0, adapter="a1")
        assert eng.scheduler.stats.prefix_hits == 0
        g0 = eng.generate(prompt, max_new_tokens=6, temperature=0.0)
        # the adapter row seeded nothing: the base row cannot have hit
        assert eng.scheduler.stats.prefix_hits == 0
        gb = eng.generate(prompt, max_new_tokens=6, temperature=0.0)
        assert eng.scheduler.stats.prefix_hits == 1  # base-base still shares
        ga2 = eng.generate(prompt, max_new_tokens=6, temperature=0.0, adapter="a1")
        assert eng.scheduler.stats.prefix_hits == 1  # adapter row never hits
        want_a = m1.generate(prompt, max_new_tokens=6, temperature=0.0)
        want_0 = base.generate(prompt, max_new_tokens=6, temperature=0.0)
        assert ga.token_ids == ga2.token_ids == want_a.token_ids
        assert g0.token_ids == gb.token_ids == want_0.token_ids
    finally:
        eng.close()
        m1.close()
        base.close()


def test_import_refuses_nonresident_adapter_snapshot():
    """Live migration: a snapshot pinned to an adapter the target does
    not hold is a typed refusal (the KV and all future decode depend on
    the adapted projections)."""
    eng = _pool_engine()
    try:
        snap = {
            "v": 1, "model": CFG.name, "ids": [1, 2, 3], "out": [4],
            "max_new_tokens": 8, "adapter": "ghost",
        }
        with pytest.raises(ValueError, match="not resident"):
            eng.import_generation(snap)
    finally:
        eng.close()


def test_spec_decode_composes_with_adapters():
    """Greedy spec rows keep token parity when decoding under an adapter
    (the [B, K+1] verify forward gathers the same per-row factors)."""
    lcfg = LoraConfig(rank=4, alpha=32.0)
    a1 = _adapter(1, lcfg)
    eng = _pool_engine(spec_tokens=4)
    eng.load_adapter("a1", a1, lcfg)
    m1 = _merged_engine(a1, lcfg)
    try:
        # a repetitive prompt so the n-gram drafter actually drafts
        prompt = "ab ab ab ab ab ab ab ab"
        got = eng.generate(prompt, max_new_tokens=16, temperature=0.0, adapter="a1")
        want = m1.generate(prompt, max_new_tokens=16, temperature=0.0)
        assert got.token_ids == want.token_ids
        assert eng.scheduler.stats.spec_steps > 0
    finally:
        eng.close()
        m1.close()


# ----------------------------------------------------- telemetry surface


def test_pool_metrics_and_digest_residency():
    from bee2bee_tpu.metrics import get_registry

    lcfg = LoraConfig(rank=4)
    eng = _pool_engine(n_slots=2)
    eng.load_adapter("acme", _adapter(1, lcfg), lcfg)
    try:
        reg = get_registry()
        assert reg.get("adapter.pool_resident").value() >= 1
        before = reg.get("adapter.requests").total()
        eng.generate("metrics", max_new_tokens=4, temperature=0.0, adapter="acme")
        assert reg.get("adapter.requests").total() == before + 1
        # the per-adapter label series exists (bounded by residency)
        assert any(
            dict(labels).get("adapter") == "acme"
            for labels, _v in reg.get("adapter.requests").series()
        )
        rendered = reg.render()
        assert "bee2bee_adapter_pool_resident" in rendered
        assert "bee2bee_adapter_requests_total" in rendered
    finally:
        eng.close()


# -------------------------------------------------- mesh paging + router


def _tiny_svc(engine):
    from bee2bee_tpu.services.tpu import TPUService

    return TPUService(CFG.name, engine=engine)


async def test_publish_fetch_roundtrip_and_gen_request_paging():
    """The full hot-swap leg: node A publishes an adapter as pieces on
    the DHT; node B (adapter NOT resident) receives a gen_request for
    '<base>:<name>', pages the factors in, serves with merged-weights
    parity, and re-announces residency. Unknown names answer the typed
    unknown_adapter gen_error."""
    from bee2bee_tpu.adapters.distrib import fetch_adapter, publish_adapter
    from bee2bee_tpu.dht import DHTNode
    from tests.test_meshnet import _settle, mesh

    lcfg = LoraConfig(rank=4, alpha=32.0)
    a1 = _adapter(1, lcfg)
    async with mesh(2) as (a, b):
        dht = DHTNode()
        await dht.start()
        a.dht = dht
        b.dht = dht
        eng_b = _pool_engine()
        m1 = _merged_engine(a1, lcfg)
        try:
            await publish_adapter(a, dht, CFG.name, "acme", a1, lcfg)
            # direct fetch path: hash-verified + shape-validated
            got, got_cfg = await fetch_adapter(b, dht, CFG.name, "acme",
                                               model_cfg=CFG)
            assert got_cfg.rank == 4
            for x, y in zip(jax.tree.leaves(a1), jax.tree.leaves(got)):
                np.testing.assert_array_equal(
                    np.asarray(jax.device_get(x)), np.asarray(y)
                )

            # serving path: b holds the BASE engine only; the request
            # names the adapter via the model id and pages it in
            svc = _tiny_svc(eng_b)
            await b.announce_service(svc)
            await a.connect_bootstrap(b.addr)
            await _settle(lambda: a.peers and b.peers)
            assert not eng_b.has_adapter("acme")
            out = await a.request_generation(
                next(iter(a.peers)), "paged in tenant", model=f"{CFG.name}:acme",
                max_new_tokens=6, temperature=0.0,
            )
            assert eng_b.has_adapter("acme")
            want = m1.generate("paged in tenant", max_new_tokens=6,
                               temperature=0.0)
            assert out["text"] == want.text
            # residency reached A's provider table (ADAPTER_ANNOUNCE)
            await _settle(lambda: any(
                "acme" in (meta.get("adapters") or [])
                for svcs in a.providers.values() for meta in svcs.values()
            ))
            assert any(
                f"{CFG.name}:acme" in (meta.get("models") or [])
                for svcs in a.providers.values() for meta in svcs.values()
            )

            # unknown adapter: typed gen_error, not a generic failure
            with pytest.raises(Exception, match="unknown_adapter"):
                await a.request_generation(
                    next(iter(a.peers)), "x", model=f"{CFG.name}:ghost",
                    max_new_tokens=4, temperature=0.0,
                )
        finally:
            eng_b.close()
            m1.close()
            await dht.stop()


async def test_fetch_corrupt_piece_is_typed_and_incident():
    """A corrupted adapter piece fails sha256 verification: ensure_adapter
    answers False (typed 404 upstream) and writes the adapter:fetch_failed
    incident."""
    from bee2bee_tpu.adapters.distrib import publish_adapter
    from bee2bee_tpu.dht import DHTNode
    from tests.test_meshnet import _settle, mesh

    lcfg = LoraConfig(rank=4)
    a1 = _adapter(1, lcfg)
    async with mesh(2) as (a, b):
        dht = DHTNode()
        await dht.start()
        b.dht = dht
        eng_b = _pool_engine()
        try:
            manifest = await publish_adapter(a, dht, CFG.name, "acme", a1, lcfg)
            victim = manifest.pieces[0]
            a.piece_store[victim.sha256] = b"corrupt" * 8
            await a.connect_bootstrap(b.addr)
            await _settle(lambda: a.peers and b.peers)
            svc = _tiny_svc(eng_b)
            b.add_service(svc)
            events_before = len([
                e for e in b.recorder.events(limit=500)
                if e.get("kind") == "incident"
            ])
            ok = await b.ensure_adapter(svc, "acme")
            assert ok is False
            assert not eng_b.has_adapter("acme")
            # the typed incident landed (adapter:fetch_failed)
            assert any(
                "adapter:fetch_failed" in str(e)
                for e in b.recorder.events(limit=500)
            ), events_before
        finally:
            eng_b.close()
            await dht.stop()


def test_router_credits_adapter_resident_peer():
    """Placement: a peer whose digest advertises the adapter wins over an
    otherwise-equal peer; a burning peer is still excluded regardless."""
    from bee2bee_tpu.router.policy import RouterPolicy

    pol = RouterPolicy()
    cands = [
        {"provider_id": "p1", "service": "tpu", "local": False, "models": ["m"]},
        {"provider_id": "p2", "service": "tpu", "local": False, "models": ["m"]},
    ]
    idle = {"v": 1, "gauge": {"engine.batch_fill": 0.2}}
    with_adapter = dict(idle, adapters={"tpu": ["acme"]})
    winner, decision = pol.pick(
        cands, {"p1": idle, "p2": with_adapter}, adapter="acme"
    )
    assert winner["provider_id"] == "p2"
    assert decision["breakdown"]["adapter_resident"] is True
    # affinity never routes to a burning peer: p2 burning → p1 wins
    burning = dict(with_adapter, slo={"ttft": {"status": "burning"}})
    winner, _ = pol.pick(cands, {"p1": idle, "p2": burning}, adapter="acme")
    assert winner["provider_id"] == "p1"
    # and residency never beats an outright-loaded node
    loaded = dict(
        with_adapter,
        gauge={"engine.batch_fill": 1.0, "engine.paged_blocks_total": 100.0,
               "engine.paged_blocks_free": 1.0},
        hist={"engine.queue_wait_ms": {"p95": 5000.0}},
    )
    winner, _ = pol.pick(cands, {"p1": idle, "p2": loaded}, adapter="acme")
    assert winner["provider_id"] == "p1"


def test_tenant_default_adapter_config():
    from bee2bee_tpu.router.tenants import TenantRegistry, parse_tenant_config

    specs = parse_tenant_config({
        "acme": {"api_key": "k-acme", "weight": 4, "adapter": "acme-v2"},
        "hobby": {"api_key": "k-hobby"},
    })
    reg = TenantRegistry(specs)
    assert reg.default_adapter("acme") == "acme-v2"
    assert reg.default_adapter("hobby") is None
    assert reg.default_adapter("default") is None
    with pytest.raises(ValueError, match="adapter"):
        parse_tenant_config({"bad": {"adapter": "a/b"}})


# ------------------------------------------------------------ API surface


async def test_v1_unknown_adapter_404_and_resident_serving():
    from aiohttp.test_utils import TestClient, TestServer

    from bee2bee_tpu.api import build_app
    from tests.test_meshnet import mesh

    lcfg = LoraConfig(rank=4, alpha=32.0)
    a1 = _adapter(1, lcfg)
    eng = _pool_engine()
    eng.load_adapter("acme", a1, lcfg)
    m1 = _merged_engine(a1, lcfg)
    async with mesh(1) as (node,):
        node.add_service(_tiny_svc(eng))
        client = TestClient(TestServer(build_app(node)))
        await client.start_server()
        try:
            # unknown adapter on a KNOWN base model: typed 404
            r = await client.post("/v1/chat/completions", json={
                "model": f"{CFG.name}:ghost",
                "messages": [{"role": "user", "content": "hi"}],
                "max_tokens": 4,
            })
            assert r.status == 404
            body = await r.json()
            assert body["error"]["error_kind"] == "unknown_adapter"

            # resident adapter serves with parity through /v1
            r = await client.post("/v1/completions", json={
                "model": f"{CFG.name}:acme", "prompt": "v1 tenant",
                "max_tokens": 6, "temperature": 0.0,
            })
            assert r.status == 200
            body = await r.json()
            want = m1.generate(
                "v1 tenant", max_new_tokens=6, temperature=0.0
            )
            assert body["choices"][0]["text"] == want.text
            # /v1/models lists the adapter-extended name
            r = await client.get("/v1/models")
            ids = [m["id"] for m in (await r.json())["data"]]
            assert f"{CFG.name}:acme" in ids
        finally:
            await client.close()
            eng.close()
            m1.close()


async def test_busy_pool_is_503_backpressure_not_404(monkeypatch):
    """A valid adapter hitting a slot-saturated pool must surface as the
    retryable pool_exhausted 503 (+ Retry-After), never as a 404: an SDK
    treats unknown_adapter as permanent and would never retry, and the
    router would never get the chance to place the request elsewhere."""
    from aiohttp.test_utils import TestClient, TestServer

    from bee2bee_tpu.api import build_app
    from tests.test_meshnet import mesh

    eng = _pool_engine()
    async with mesh(1) as (node,):
        node.add_service(_tiny_svc(eng))

        async def busy_ensure(svc, name):
            raise AdapterPoolBusy("all 4 adapter slots have in-flight rows")

        monkeypatch.setattr(node, "ensure_adapter", busy_ensure)
        client = TestClient(TestServer(build_app(node)))
        await client.start_server()
        try:
            r = await client.post("/v1/chat/completions", json={
                "model": f"{CFG.name}:acme",
                "messages": [{"role": "user", "content": "hi"}],
                "max_tokens": 4,
            })
            assert r.status == 503
            body = await r.json()
            assert body["error"]["error_kind"] == "pool_exhausted"
            assert "Retry-After" in r.headers
        finally:
            await client.close()
            eng.close()


async def test_colon_tag_backends_serve_verbatim():
    """The '<base>:<adapter>' grammar must not eat a backend's own
    colon-containing model ids (ollama-style 'llama3:8b'): a non-adapter
    service advertising the full id verbatim serves it whole — while a
    pool-LESS engine still answers the typed 404 for an adapter-
    qualified id (the verbatim fallback must never reopen the
    silently-serve-the-plain-base hole)."""
    from aiohttp.test_utils import TestClient, TestServer

    from bee2bee_tpu.api import build_app
    from bee2bee_tpu.services.fake import FakeService
    from tests.test_meshnet import mesh

    eng = InferenceEngine(
        CFG, params=_base_params(), engine_config=EngineConfig(**ECFG)
    )
    async with mesh(1) as (node,):
        node.add_service(FakeService("llama3:8b"))
        node.add_service(_tiny_svc(eng))
        client = TestClient(TestServer(build_app(node)))
        await client.start_server()
        try:
            r = await client.post("/v1/completions", json={
                "model": "llama3:8b", "prompt": "hi", "max_tokens": 4,
            })
            assert r.status == 200
            body = await r.json()
            assert body["choices"][0]["text"]

            r = await client.post("/v1/completions", json={
                "model": f"{CFG.name}:acme", "prompt": "hi", "max_tokens": 4,
            })
            assert r.status == 404
            body = await r.json()
            assert body["error"]["error_kind"] == "unknown_adapter"
        finally:
            await client.close()
            eng.close()


def _teacher_forced_logits(eng, ids, adapter=None):
    """[T, V] float32 logits of ``ids`` in ONE full forward under
    ``adapter`` (the pool's stacked factors, selected by slot as the served
    rows select them): no sampled token feeds back, so a near-tie between
    two candidates moves a logit by rounding, never the sequence."""
    kw = {}
    if adapter is not None:
        pool = eng.adapter_pool
        slot = pool.slot_of(adapter)
        factors, scales = pool.device_args()
        kw = dict(adapters=factors, adapter_ids=np.asarray([slot], np.int32),
                  adapter_scales=scales)
    logits, _ = core.forward(
        eng.params, CFG, jnp.asarray([ids], jnp.int32), None, jnp.int32(0), **kw
    )
    return np.asarray(logits[0], np.float32)


# float32 weights: pooled x@A@B against merged x@(W + AB) differ by
# summation order only — 4.5e-7 measured on these shapes (logits up to
# 0.64), while this adapter moves them by 0.24 from the base model's
ADAPTER_LOGIT_TOL = 1e-4


async def test_tenant_default_adapter_applies_on_plain_model(monkeypatch):
    """A tenant with a configured default adapter gets it when the model
    id names none. Asserted on what cannot tie: the pool's per-adapter
    admission counter, the text of the SAME engine asked for the adapter
    by name, and teacher-forced logits (pooled == merged within
    ADAPTER_LOGIT_TOL, and further than that from the base model's)."""
    import json as _json

    from aiohttp.test_utils import TestClient, TestServer

    from bee2bee_tpu.adapters.pool import _C_REQUESTS
    from bee2bee_tpu.api import build_app
    from tests.test_meshnet import mesh

    monkeypatch.setenv("BEE2BEE_TENANTS", _json.dumps({
        "acme": {"api_key": "k-acme", "adapter": "acme"},
    }))
    lcfg = LoraConfig(rank=4, alpha=32.0)
    a1 = _adapter(1, lcfg)
    eng = _pool_engine()
    eng.load_adapter("acme", a1, lcfg)
    m1 = _merged_engine(a1, lcfg)
    async with mesh(1) as (node,):
        node.add_service(_tiny_svc(eng))
        client = TestClient(TestServer(build_app(node)))
        await client.start_server()
        try:
            admitted = _C_REQUESTS.value(adapter="acme")
            r = await client.post(
                "/chat",
                json={"prompt": "tenant routed", "model": CFG.name,
                      "max_new_tokens": 6, "temperature": 0.0},
                headers={"X-API-KEY": "k-acme"},
            )
            assert r.status == 200
            got = (await r.json())["text"]
            # the row was admitted under the tenant's adapter ...
            assert _C_REQUESTS.value(adapter="acme") == admitted + 1
            named = eng.generate("tenant routed", max_new_tokens=6,
                                 temperature=0.0, adapter="acme")
            assert got == named.text
            # ... and that adapter is the trainer's merged model, not the base
            ids = eng.tokenizer.encode("tenant routed") + named.token_ids
            pooled = _teacher_forced_logits(eng, ids, adapter="acme")
            merged = _teacher_forced_logits(m1, ids)
            plain = _teacher_forced_logits(eng, ids)
            assert np.max(np.abs(pooled - merged)) <= ADAPTER_LOGIT_TOL
            assert np.max(np.abs(pooled - plain)) > 100 * ADAPTER_LOGIT_TOL
        finally:
            await client.close()
            eng.close()
            m1.close()


def test_hello_metadata_and_digest_carry_adapters():
    lcfg = LoraConfig(rank=4)
    eng = _pool_engine()
    eng.load_adapter("acme", _adapter(1, lcfg), lcfg)
    svc = _tiny_svc(eng)
    try:
        meta = svc.get_metadata()
        assert meta["adapters"] == ["acme"]
        assert f"{CFG.name}:acme" in meta["models"]
        from bee2bee_tpu.meshnet.node import P2PNode

        node = P2PNode(host="127.0.0.1", port=0)
        node.add_service(svc)
        digest = node.telemetry_digest()
        assert digest["adapters"] == {"tpu": ["acme"]}
    finally:
        eng.close()


if __name__ == "__main__":  # pragma: no cover
    asyncio.run(test_publish_fetch_roundtrip_and_gen_request_paging())
