"""Tracing subsystem tests: spans, nesting, stats, serving integration."""

from __future__ import annotations

import threading

import pytest

from bee2bee_tpu.tracing import Span, Tracer, get_tracer


def test_span_records_duration_and_attrs():
    tr = Tracer()
    with tr.span("work", model="tiny") as s:
        pass
    [rec] = tr.recent()
    assert rec["name"] == "work"
    assert rec["attrs"] == {"model": "tiny"}
    assert rec["duration_ms"] >= 0
    assert rec["error"] is None
    assert s.span_id == rec["span_id"]


def test_span_captures_error_and_reraises():
    tr = Tracer()
    with pytest.raises(ValueError):
        with tr.span("boom"):
            raise ValueError("nope")
    [rec] = tr.recent()
    assert rec["error"] == "ValueError: nope"
    assert tr.stats()["boom"]["errors"] == 1


def test_nested_spans_link_parent():
    tr = Tracer()
    with tr.span("outer") as outer:
        with tr.span("inner"):
            pass
    inner_rec = tr.recent(name="inner")[0]
    outer_rec = tr.recent(name="outer")[0]
    assert inner_rec["parent_id"] == outer.span_id
    assert outer_rec["parent_id"] is None


def test_ring_buffer_bounded():
    tr = Tracer(capacity=10)
    for i in range(25):
        with tr.span("s"):
            pass
    assert len(tr.recent(limit=100)) == 10
    assert tr.stats()["s"]["count"] == 10


def test_stats_percentiles():
    tr = Tracer()
    for i in range(20):
        with tr.span("x"):
            pass
    st = tr.stats()["x"]
    assert st["count"] == 20
    assert 0 <= st["p50_ms"] <= st["p95_ms"] <= st["max_ms"]


def test_thread_safety_smoke():
    tr = Tracer(capacity=4096)

    def worker():
        for _ in range(200):
            with tr.span("t"):
                pass

    threads = [threading.Thread(target=worker) for _ in range(8)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    assert tr.stats()["t"]["count"] == 1600


def test_global_tracer_singleton():
    assert get_tracer() is get_tracer()


def test_serving_paths_emit_spans():
    """FakeService request through the node records a gen.local span, and
    the /trace route surfaces it."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from bee2bee_tpu.api import build_app
    from bee2bee_tpu.meshnet.node import P2PNode
    from bee2bee_tpu.services.fake import FakeService

    get_tracer().clear()

    async def run():
        node = P2PNode(host="127.0.0.1", port=0)
        await node.start()
        try:
            node.add_service(FakeService("tiny"))
            app = build_app(node)
            client = TestClient(TestServer(app))
            await client.start_server()
            try:
                resp = await client.post("/chat", json={"prompt": "hi"})
                assert resp.status == 200
                trace = await (await client.get("/trace")).json()
                # non-stream /chat executes the service inline (executor),
                # so at minimum the route exposes stats+recent and engine
                # spans appear once a local gen runs via the node path
                assert "stats" in trace and "recent" in trace
                await node.request_generation(node.peer_id, "hello", model="tiny")
                trace = await (await client.get("/trace")).json()
                assert "gen.local" in trace["stats"]
                rec = [r for r in trace["recent"] if r["name"] == "gen.local"]
                assert rec and rec[-1]["attrs"]["service"] == "fake"
            finally:
                await client.close()
        finally:
            await node.stop()

    asyncio.run(run())


def test_engine_emits_prefill_spans():
    import jax

    from bee2bee_tpu.engine.engine import EngineConfig, InferenceEngine
    from bee2bee_tpu.models import core
    from bee2bee_tpu.models.config import get_config

    get_tracer().clear()
    cfg = get_config("tiny-gpt2")
    params = core.init_params(cfg, jax.random.key(0))
    eng = InferenceEngine(
        cfg, params, engine_config=EngineConfig(max_seq_len=128, decode_chunk=8)
    )
    out = eng.generate("hello", max_new_tokens=8, temperature=0.0)
    assert out.new_tokens > 0
    stats = get_tracer().stats()
    assert "engine.admit" in stats  # prefill + row splice + first token
    assert "engine.decode_window" in stats  # batched decode chunks + readback
    eng.close()
