"""Service-layer tests: contract shape, transcript parsing, stop-word
scrubbing, and TPUService over a real (tiny) engine."""

import json

import pytest

from bee2bee_tpu.services import BaseService, FakeService, ServiceError
from bee2bee_tpu.services.base import parse_transcript, scrub_stop_words
from bee2bee_tpu.services.tpu import TPUService
from bee2bee_tpu.engine import EngineConfig


def test_result_dict_schema():
    out = BaseService.result_dict("hi", 10, 0, price_per_token=0.5)
    assert out["text"] == "hi"
    assert out["tokens"] == 10
    assert out["cost"] == 5.0
    assert out["latency_ms"] >= 0
    assert out["price_per_token"] == 0.5


def test_fake_service_execute_and_stream():
    svc = FakeService("m", reply="hello world")
    out = svc.execute({"prompt": "x"})
    assert out["text"] == "hello world"
    lines = [json.loads(ln) for ln in svc.execute_stream({"prompt": "x"})]
    assert "".join(ln.get("text", "") for ln in lines) == "hello world"
    # the done line carries the node's real accounting (tokens + cost)
    assert lines[-1]["done"] is True
    assert lines[-1]["tokens"] == 2  # "hello world" = 2 fake tokens
    assert lines[-1]["cost"] == 0.0


def test_fake_service_missing_prompt():
    with pytest.raises(ServiceError, match="Missing prompt"):
        FakeService("m").execute({})


def test_parse_transcript_plain_prompt():
    msgs, was = parse_transcript("just a question")
    assert not was
    assert msgs == [{"role": "user", "content": "just a question"}]


def test_parse_transcript_chat():
    msgs, was = parse_transcript(
        "user: hi there\nassistant: hello!\nuser: second question\nwith a second line"
    )
    assert was
    assert [m["role"] for m in msgs] == ["user", "assistant", "user"]
    assert msgs[2]["content"] == "second question\nwith a second line"


def test_scrub_stop_words():
    assert scrub_stop_words("a fine answer\nuser: next?") == "a fine answer"
    assert scrub_stop_words("clean text stays") == "clean text stays"
    # marker at position 0 is NOT scrubbed (reference keeps leading role text)
    assert scrub_stop_words("assistant: x")


@pytest.fixture(scope="module")
def tpu_service():
    svc = TPUService(
        "tiny-llama",
        price_per_token=0.001,
        max_new_tokens=16,
        engine_config=EngineConfig(
            max_seq_len=128, prefill_buckets=(16, 32), dtype="float32",
            cache_dtype="float32", decode_chunk=8,
        ),
    )
    return svc.load_sync()


def test_tpu_service_execute(tpu_service):
    out = tpu_service.execute({"prompt": "hello", "max_new_tokens": 8, "temperature": 0})
    assert set(out) >= {"text", "tokens", "latency_ms", "price_per_token", "cost"}
    assert out["tokens"] > 0
    assert out["cost"] == pytest.approx(out["tokens"] * 0.001)
    assert out["tokens_per_sec"] >= 0


def test_tpu_service_stream_matches_contract(tpu_service):
    lines = [json.loads(ln) for ln in tpu_service.execute_stream({"prompt": "hi", "temperature": 0})]
    assert lines[-1]["done"] is True
    assert lines[-1]["tokens"] > 0  # real engine count on the done line
    assert lines[-1]["cost"] == pytest.approx(lines[-1]["tokens"] * 0.001)
    assert all("text" in ln or "done" in ln for ln in lines)


def test_tpu_service_caps_max_new_tokens(tpu_service):
    # service max is 16; a request for 10k must be capped, not crash
    out = tpu_service.execute({"prompt": "x", "max_new_tokens": 10_000, "temperature": 0})
    assert out["tokens"] <= 16


def test_tpu_service_metadata(tpu_service):
    meta = tpu_service.get_metadata()
    assert meta["models"] == ["tiny-llama"]
    assert meta["backend"] == "tpu"
    assert meta["engine"]["model"] == "tiny-llama"


def test_tpu_service_unloaded_raises():
    svc = TPUService("tiny-llama")
    with pytest.raises(ServiceError, match="not loaded"):
        svc.execute({"prompt": "x"})


def test_ollama_service_unreachable_is_clean_error():
    from bee2bee_tpu.services.ollama import OllamaService

    svc = OllamaService("some-model", host="http://127.0.0.1:1")  # nothing there
    with pytest.raises(ServiceError, match="unreachable"):
        svc.execute({"prompt": "x"})
    meta = svc.get_metadata()
    assert meta["backend"] == "ollama"


def test_tpu_service_stream_not_truncated(tpu_service):
    """Streamed text must equal non-streamed text (the stream once broke
    after the first chunk)."""
    out = tpu_service.execute({"prompt": "count with me", "max_new_tokens": 16, "temperature": 0})
    lines = [
        json.loads(ln)
        for ln in tpu_service.execute_stream(
            {"prompt": "count with me", "max_new_tokens": 16, "temperature": 0}
        )
    ]
    streamed = "".join(ln.get("text", "") for ln in lines)
    assert streamed == out["text"]


def test_default_2048_request_does_not_crash(tpu_service):
    # the reference default (max_new_tokens=2048) against a 128-token cache
    out = tpu_service.execute({"prompt": "defaults", "max_new_tokens": 2048, "temperature": 0})
    assert out["tokens"] > 0


# ---- loop-native offload wrappers (meshlint ML-A001 remediation):
# services whose execute/execute_stream block (ollama's requests round
# trips) expose async twins that run the sync path in a worker thread —
# the node's gateway picks them up via getattr, sync callers unchanged.


async def test_execute_via_thread_offloads_and_returns_result():
    import asyncio
    import threading

    class Blocking(FakeService):
        def execute(self, params):
            params = dict(params, thread=threading.current_thread().name)
            return super().execute(params)

    svc = Blocking("m", reply="offloaded")
    svc_async = svc._execute_via_thread
    out = await svc_async({"prompt": "x"})
    assert out["text"] == "offloaded"
    # the blocking body ran OFF the loop thread
    assert svc.calls[-1]["thread"] != threading.current_thread().name
    # the loop stayed responsive while execute ran (trivially true here,
    # but pins the contract: the wrapper must be awaitable concurrently)
    await asyncio.gather(svc_async({"prompt": "y"}), asyncio.sleep(0))


async def test_stream_via_thread_yields_lines_and_raises():
    import json as _json

    svc = FakeService("m", reply="0123456789", chunk_size=4)
    lines = [ln async for ln in svc._stream_via_thread({"prompt": "x"})]
    parsed = [_json.loads(ln) for ln in lines]
    assert "".join(p.get("text", "") for p in parsed) == "0123456789"
    assert parsed[-1]["done"] is True

    class Exploding(FakeService):
        def execute_stream(self, params):
            yield self.stream_line({"text": "a"})
            raise RuntimeError("backend died")

    got = []
    with pytest.raises(RuntimeError, match="backend died"):
        async for ln in Exploding("m")._stream_via_thread({"prompt": "x"}):
            got.append(ln)
    assert got  # the pre-crash line still arrived


def test_ollama_exposes_async_wrappers():
    from bee2bee_tpu.services.ollama import OllamaService

    svc = OllamaService("m")
    assert callable(getattr(svc, "execute_async"))
    assert callable(getattr(svc, "execute_stream_async"))


@pytest.mark.parametrize("rows,streams,width", [
    (0, 0, None),      # a service that batches nothing: the loop's default executor
    (2, 4, None),      # both fit the default executor
    (64, 0, 65),       # every batch row plus one queued stream
    (64, 96, 96),      # every stream admission lets in: the scheduler's queue sees them
    (16, 1000, 1000),
])
def test_pump_pool_is_as_wide_as_the_streams_admission_lets_in(monkeypatch, rows, streams, width):
    """A stream that was admitted but waits for a pump thread is invisible to
    the scheduler's queue: a freed row would stand empty until a done event
    has travelled to its caller and back (PERF.md, PR 40)."""
    import os

    monkeypatch.setattr(os, "cpu_count", lambda: 13)  # default executor: 17 threads
    svc = BaseService("m")
    svc.stream_rows = rows
    pool = svc.pump_executor(streams)
    try:
        assert (pool._max_workers if pool is not None else None) == width
        assert svc.pump_executor(streams) is pool  # made once
    finally:
        if pool is not None:
            pool.shutdown()


async def test_stream_via_thread_stops_pump_when_consumer_abandons():
    """A consumer that stops iterating (client hung up, error raised at
    the node layer) must stop the backend pull at the next line — the
    thread must not keep generating the full response."""
    import asyncio
    import threading

    started = threading.Event()
    release = threading.Event()
    pulled = []

    class Slow(FakeService):
        def execute_stream(self, params):
            for i in range(1000):
                pulled.append(i)
                if i == 0:
                    started.set()
                else:
                    # wait until the consumer has bailed before each next
                    # line, so the cancel flag is observable deterministically
                    release.wait(timeout=5)
                yield self.stream_line({"text": str(i)})

    gen = Slow("m")._stream_via_thread({"prompt": "x"})
    first = await gen.__anext__()
    assert '"0"' in first
    await gen.aclose()  # consumer abandons mid-stream
    release.set()
    # give the worker thread a moment to observe the cancel flag
    for _ in range(100):
        await asyncio.sleep(0.01)
        if len(pulled) <= 3:
            break
    assert len(pulled) <= 3, f"pump kept pulling after abandon: {len(pulled)}"
