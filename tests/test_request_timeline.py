"""A streamed request's timeline (ISSUE 25): eight stamps on one clock from
the gateway's accept to its first written byte, the segment histograms
observed together at that write, the scheduler's loop phases as a counter,
and the shape of every counted compile."""

from __future__ import annotations

import asyncio
import json
import logging
import threading
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from bee2bee_tpu.api import build_app
from bee2bee_tpu.engine import EngineConfig, InferenceEngine
from bee2bee_tpu.meshnet.node import P2PNode
from bee2bee_tpu.metrics import get_registry
from bee2bee_tpu.services.base import BaseService
from bee2bee_tpu.services.fake import FakeService
from bee2bee_tpu.services.tpu import TPUService
from bee2bee_tpu.tracing import PhaseClock, RequestTiming, get_tracer, request_timing

STAMPS = ["accept", "admitted", "submit", "row", "first_token", "first_text",
          "first_line", "first_write"]
SEGMENTS = ["gateway.admission_wait_ms", "gateway.dispatch_ms",
            "engine.queue_wait_ms", "engine.prefill_ms", "engine.first_text_ms",
            "service.holdback_ms", "gateway.write_ms"]
AT_FIRST_WRITE = ["gateway.ttft_ms", "gateway.admission_wait_ms", "gateway.dispatch_ms",
                  "engine.first_text_ms", "service.holdback_ms", "gateway.write_ms"]


def totals(names) -> dict[str, tuple[int, float]]:
    """(count, sum) of each histogram's unlabeled series."""
    return {n: get_registry().get(n).totals() for n in names}


def grew(before, after) -> dict[str, tuple[int, float]]:
    return {n: (after[n][0] - before[n][0], after[n][1] - before[n][1]) for n in after}


@pytest.fixture(scope="module")
def engine():
    eng = InferenceEngine(
        "tiny-llama",
        engine_config=EngineConfig(max_seq_len=128, prefill_buckets=(32,), decode_chunk=4),
    )
    # warm the shapes the tests meet, so a phase or a segment is not a compile
    eng.generate("warm the prefill and the decode", max_new_tokens=12, temperature=0.0)
    yield eng
    eng.close()


async def gateway(services):
    node = P2PNode(host="127.0.0.1", port=0)
    await node.start()
    for svc in services:
        node.add_service(svc)
    client = TestClient(TestServer(build_app(node)))
    await client.start_server()
    return node, client


async def stream_lines(client, path, body) -> list[dict]:
    resp = await client.post(path, json=body)
    assert resp.status == 200, await resp.text()
    return [json.loads(ln) for ln in (await resp.text()).splitlines() if ln.strip()]


async def test_streamed_chat_stamps_one_timeline_and_observes_each_segment_once(engine):
    node, client = await gateway([TPUService("tiny-llama", engine=engine)])
    try:
        get_tracer().clear()
        before = totals(["gateway.ttft_ms", *SEGMENTS])
        lines = await stream_lines(client, "/chat", {
            "prompt": "the mesh hums", "model": "tiny-llama", "stream": True,
            "max_new_tokens": 10, "temperature": 0.0,
        })
        delta = grew(before, totals(["gateway.ttft_ms", *SEGMENTS]))
    finally:
        await client.close()
        await node.stop()

    assert all(set(ln) == {"text"} for ln in lines[:-1])  # content lines gain no field
    timing = lines[-1]["timing"]
    timeline = timing["timeline_ms"]
    assert list(timeline) == STAMPS
    stamps = [timeline[k] for k in STAMPS]
    assert stamps[0] == 0.0 and stamps == sorted(stamps)
    [span] = get_tracer().recent(name="gen.local")
    assert span["attrs"]["timing"]["timeline_ms"] == timeline

    # one request: every histogram took exactly one observation, and the
    # seven segments are the whole of the gateway's own time to first byte
    assert {n: c for n, (c, _) in delta.items()} == dict.fromkeys(delta, 1)
    assert delta["gateway.ttft_ms"][1] == pytest.approx(timeline["first_write"], abs=0.01)
    assert sum(delta[n][1] for n in SEGMENTS) == pytest.approx(
        delta["gateway.ttft_ms"][1], abs=1.0)


async def test_a_streams_pump_turns_and_frame_writes_are_annotated(engine, monkeypatch):
    """What the capture's Python tracer used to be the only witness of
    (ISSUE 41): a turn of the stream's pump thread is `svc.pump`, opened once
    the event is off the request's queue (never over the wait for it), and
    the gateway's write of a frame is `gateway.write` on the loop's thread."""
    from contextlib import contextmanager

    import bee2bee_tpu.api as api_mod
    import bee2bee_tpu.services.base as base_mod

    spans: list[tuple[str, str, float]] = []

    @contextmanager
    def recording(name):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            spans.append((name, threading.current_thread().name, time.perf_counter() - t0))

    monkeypatch.setattr(api_mod, "annotate", recording)
    monkeypatch.setattr(base_mod, "annotate", recording)
    stream = engine.generate_stream

    def slow_events(**kwargs):
        for ev in stream(**kwargs):
            time.sleep(0.05)  # the pump waits for its event: no turn yet
            yield ev

    monkeypatch.setattr(engine, "generate_stream", slow_events)
    node, client = await gateway([TPUService("tiny-llama", engine=engine)])
    try:
        lines = await stream_lines(client, "/chat", {
            "prompt": "the mesh hums", "model": "tiny-llama", "stream": True,
            "max_new_tokens": 10, "temperature": 0.0,
        })
    finally:
        await client.close()
        await node.stop()
    pumps = [s for s in spans if s[0] == "svc.pump"]
    writes = [s for s in spans if s[0] == "gateway.write"]
    assert {s[0] for s in spans} == {"svc.pump", "gateway.write"}
    assert len(writes) == len(lines) and len(pumps) >= len(lines) - 1
    assert all(seconds < 0.04 for _, _, seconds in pumps), pumps
    assert {t for _, t, _ in writes} == {threading.current_thread().name}
    assert not {t for _, t, _ in pumps} & {t for _, t, _ in writes}


def test_annotate_is_a_trace_annotation_where_jax_is_loaded_and_nothing_elsewhere(monkeypatch):
    import sys
    from contextlib import nullcontext

    from jax.profiler import TraceAnnotation

    from bee2bee_tpu.tracing import annotate

    assert isinstance(annotate("test.annotate"), TraceAnnotation)
    with annotate("test.annotate"):
        pass
    monkeypatch.delitem(sys.modules, "jax")  # a process that never loaded it
    assert isinstance(annotate("test.annotate"), nullcontext)


async def test_streamed_v1_sse_observes_the_same_histograms(engine):
    node, client = await gateway([TPUService("tiny-llama", engine=engine)])
    try:
        before = totals(AT_FIRST_WRITE)
        resp = await client.post("/v1/completions", json={
            "prompt": "the mesh hums", "model": "tiny-llama", "stream": True,
            "max_tokens": 6, "temperature": 0.0,
        })
        assert resp.status == 200
        assert "data: [DONE]" in await resp.text()
        delta = grew(before, totals(AT_FIRST_WRITE))
    finally:
        await client.close()
        await node.stop()
    assert {n: c for n, (c, _) in delta.items()} == dict.fromkeys(AT_FIRST_WRITE, 1)


async def test_unary_chat_observes_admission_wait_and_dispatch_only(engine):
    node, client = await gateway([TPUService("tiny-llama", engine=engine)])
    try:
        before = totals(AT_FIRST_WRITE)
        resp = await client.post("/chat", json={
            "prompt": "the mesh hums", "model": "tiny-llama",
            "max_new_tokens": 4, "temperature": 0.0,
        })
        body = await resp.json()
        delta = grew(before, totals(AT_FIRST_WRITE))
    finally:
        await client.close()
        await node.stop()
    assert resp.status == 200
    counts = {n: c for n, (c, _) in delta.items()}
    assert counts == {**dict.fromkeys(AT_FIRST_WRITE, 0),
                      "gateway.admission_wait_ms": 1, "gateway.dispatch_ms": 1}
    # no stream event, so no first text: the timeline ends at the first token
    assert list(body["timing"]["timeline_ms"]) == STAMPS[:5]


async def test_service_without_an_engine_leaves_the_middle_of_the_timeline_empty():
    node, client = await gateway([FakeService("fake-model", reply="a b c d e f")])
    try:
        before = totals(AT_FIRST_WRITE)
        lines = await stream_lines(client, "/chat", {
            "prompt": "hi", "model": "fake-model", "stream": True})
        delta = grew(before, totals(AT_FIRST_WRITE))
    finally:
        await client.close()
        await node.stop()
    assert list(lines[-1]["timing"]["timeline_ms"]) == ["accept", "admitted", "first_write"]
    counts = {n: c for n, (c, _) in delta.items()}
    assert counts == {**dict.fromkeys(AT_FIRST_WRITE, 0),
                      "gateway.ttft_ms": 1, "gateway.admission_wait_ms": 1}


class GatedService(BaseService):
    """Streams one line, but not before the test opens the gate."""

    def __init__(self):
        super().__init__("gated")
        self.gate = threading.Event()

    def get_metadata(self):
        return {"models": ["gated"], "price_per_token": 0.0, "max_new_tokens": 8}

    def execute(self, params):
        raise NotImplementedError

    def execute_stream(self, params):
        self.gate.wait(10.0)
        yield self.stream_line({"text": "too late"})
        yield self.stream_line({"done": True})


async def test_client_gone_before_the_first_byte_observes_nothing():
    svc = GatedService()
    node, client = await gateway([svc])
    try:
        get_tracer().clear()
        before = totals(AT_FIRST_WRITE)
        resp = await client.post("/chat", json={
            "prompt": "hi", "model": "gated", "stream": True})
        assert resp.status == 200  # headers are out, no content yet
        resp.close()
        await asyncio.sleep(0.1)
        svc.gate.set()
        for _ in range(100):  # the handler ends (its span closes) once the pump has
            if get_tracer().recent(name="gen.local"):
                break
            await asyncio.sleep(0.05)
        assert get_tracer().recent(name="gen.local")
        delta = grew(before, totals(AT_FIRST_WRITE))
    finally:
        svc.gate.set()
        await client.close()
        await node.stop()
    assert {n: c for n, (c, _) in delta.items()} == dict.fromkeys(AT_FIRST_WRITE, 0)


class OneByteTokenizer:
    """Token n decodes to the n-th byte of a fixed UTF-8 string."""

    def __init__(self, text: str):
        self.raw = text.encode("utf-8")

    def decode(self, ids):
        return bytes(self.raw[i] for i in ids).decode("utf-8", errors="replace")


def test_first_text_stamp_waits_for_the_first_non_empty_event():
    from bee2bee_tpu.engine.scheduler import Request

    # "é" is two bytes: the first alone decodes to U+FFFD, which is held back
    req = Request([0], 8, 0.0, 0, 1.0, set(), None, OneByteTokenizer("éa"), stream=True)
    assert req.accept(0)
    req.emit([0])
    assert req.events.get_nowait()["text"] == ""
    assert req.timing.t_first_text == 0.0
    assert req.accept(1)
    before = time.perf_counter()
    req.emit([1])
    assert req.events.get_nowait()["text"] == "é"
    assert before <= req.timing.t_first_text <= time.perf_counter()
    stamped = req.timing.t_first_text
    assert req.accept(2)
    req.emit([2])
    assert req.events.get_nowait()["text"] == "a"
    assert req.timing.t_first_text == stamped  # the FIRST one only


def test_engine_takes_the_gateways_record_once(engine):
    with request_timing() as record:
        first = engine._make_request("one", 4, 0.0, 0, 1.0, None)
        second = engine._make_request("two", 4, 0.0, 0, 1.0, None)
    assert first.timing is record and record.t_submit >= record.t_accept > 0
    assert second.timing is not record  # one record, one engine request
    assert engine._make_request("three", 4, 0.0, 0, 1.0, None).timing.t_accept == 0.0


def test_timeline_ms_counts_from_the_earliest_stamp_reached():
    assert RequestTiming().timeline_ms() == {}
    rec = RequestTiming(t_submit=10.0, t_admit=10.5, t_first=11.25)
    assert rec.timeline_ms() == {"submit": 0.0, "row": 500.0, "first_token": 1250.0}


def test_phase_clock_charges_a_nested_phase_to_itself_only():
    counter = get_registry().counter("test.phase_clock_seconds")
    clock = PhaseClock("test", counter)
    t0 = time.perf_counter()
    with clock.phase("outer"):
        time.sleep(0.02)
        with clock.phase("inner"):
            time.sleep(0.03)
        time.sleep(0.01)
    wall = time.perf_counter() - t0
    outer, inner = counter.value(phase="outer"), counter.value(phase="inner")
    assert inner >= 0.03 and 0.03 <= outer < 0.03 + 0.03
    assert outer + inner == pytest.approx(wall, abs=0.005)


def test_phase_clock_flush_credits_the_open_phase_from_another_thread():
    counter = get_registry().counter("test.phase_flush_seconds")
    clock = PhaseClock("test", counter)
    entered, leave = threading.Event(), threading.Event()

    def blocked():
        with clock.phase("fetch"):
            entered.set()
            leave.wait(5.0)

    worker = threading.Thread(target=blocked)
    t0 = time.perf_counter()
    worker.start()
    entered.wait(5.0)
    time.sleep(0.03)
    clock.flush()  # what a scrape does: the phase has not ended yet
    mid = counter.value(phase="fetch")
    assert 0.03 <= mid <= time.perf_counter() - t0
    leave.set()
    worker.join()
    assert mid <= counter.value(phase="fetch") <= time.perf_counter() - t0
    clock.flush()  # nothing open: nothing credited
    assert counter.value(phase="fetch") <= time.perf_counter() - t0


def test_scheduler_phases_account_for_the_loops_wall_time(engine):
    counter = get_registry().get("engine.phase_seconds")
    phases = ("admit", "dispatch", "fetch", "settle", "process", "compact")
    before = {p: counter.value(phase=p) for p in phases}
    t0 = time.perf_counter()
    out = engine.generate("phases of one generation", max_new_tokens=48, temperature=0.0)
    wall = time.perf_counter() - t0
    assert out.new_tokens == 48
    spent = {p: counter.value(phase=p) - before[p] for p in phases}
    assert all(v > 0 for v in spent.values()), spent
    assert sum(spent.values()) == pytest.approx(wall, rel=0.10), (spent, wall)


def test_counted_compile_names_its_shape_once(caplog):
    import jax
    import numpy as np

    from bee2bee_tpu.engine.introspect import RetraceSentinel

    get_tracer().clear()
    fn = RetraceSentinel().watch(
        "timeline_test_root", jax.jit(lambda x: x + 1), key_fn=lambda x: x.shape)
    with caplog.at_level(logging.INFO, logger="bee2bee_tpu.introspect"):
        fn(np.zeros((2, 3), np.float32))
        fn(np.ones((2, 3), np.float32))  # same shape: the jit's cache hit, no compile
    [span] = get_tracer().recent(name="engine.compile")
    assert span["attrs"] == {"root": "timeline_test_root", "key": "(2, 3)"}
    logged = [r.getMessage() for r in caplog.records if "compile:" in r.getMessage()]
    assert logged == ["compile: root=timeline_test_root key=(2, 3)"]
