"""Telemetry layer tests (ISSUE 5).

- metrics.py units: counter/gauge/histogram semantics, log-spaced buckets,
  percentile estimates, never-throw record paths, thread safety, and a
  Prometheus text-exposition golden check (line-level syntax validation).
- tracing.py trace-context propagation units: inject/extract/use_trace_ctx,
  malformed-context tolerance, cross-node stitch_trace.
- Route tests: /metrics (Prometheus + JSON content negotiation) and
  /trace?trace_id= fragments on a live loopback node.
- Cross-node propagation: a RELAYED generation (api → node → relay →
  service) and a PIPELINE-STAGE generation each produce spans on every hop
  sharing ONE trace_id with correct parent links — the stitched timeline
  the acceptance criteria name.
- The streamed gen.local span satellite: span covers the full stream
  lifetime and records tokens/errors, not just setup.
- Per-request timing breakdown end-to-end: node /chat (plain + streamed),
  the web gateway's opt-in [Meta] trailer, and GatewayClient.last_meta.
"""

from __future__ import annotations

import json
import math
import re
import threading
import time

import pytest

from bee2bee_tpu.metrics import (
    DEFAULT_BUCKETS_MS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    log_buckets,
)
from bee2bee_tpu.tracing import (
    TraceContext,
    Tracer,
    current_trace_ctx,
    extract_trace,
    get_tracer,
    inject_trace,
    stitch_trace,
    use_trace_ctx,
)

# ----------------------------------------------------------- metrics units


def test_counter_inc_labels_and_value():
    c = Counter("test.reqs")
    c.inc()
    c.inc(2, op="gen")
    c.inc(3, op="gen")
    assert c.value() == 1
    assert c.value(op="gen") == 5
    assert c.value(op="other") == 0


def test_gauge_set_and_add():
    g = Gauge("test.rows")
    g.set(7)
    assert g.value() == 7
    g.add(2)
    assert g.value() == 9
    g.set(1.5, stage="0")
    assert g.value(stage="0") == 1.5


def test_gauge_clear_drops_series_from_exposition():
    """A gauge with no current reading must DISAPPEAR from the exposition
    (api.py clears p50 when the rolling window empties) — serving the last
    stale value, or a synthetic 0, would both read as live measurements."""
    reg = MetricsRegistry()
    g = reg.gauge("win.p50")
    assert "bee2bee_win_p50" not in _parse_prom(reg.render())
    g.set(2.5)
    assert _parse_prom(reg.render())["bee2bee_win_p50"] == [("", 2.5)]
    g.clear()
    assert "bee2bee_win_p50" not in _parse_prom(reg.render())
    g.clear()  # clearing an absent series is a no-op, not an error


def test_histogram_buckets_and_percentiles():
    h = Histogram("test.lat_ms", buckets=(1.0, 2.0, 4.0, 8.0))
    for v in (0.5, 1.5, 3.0, 6.0, 100.0):
        h.observe(v)
    s = h._series[()]
    # per-bucket (non-cumulative) placement: one value each + one overflow
    assert s.counts == [1, 1, 1, 1, 1]
    assert s.count == 5
    assert s.sum == pytest.approx(111.0)
    # percentile estimates resolve to bucket upper bounds
    assert h.percentile(0.5) == 4.0
    # the +Inf bucket reports the top finite bound
    assert h.percentile(0.99) == 8.0
    assert h.percentile(0.5, missing="label") == 0.0


def test_log_buckets_cover_range():
    bs = log_buckets(1.0, 1000.0)
    assert bs[0] == 1.0 and bs[-1] >= 1000.0
    assert all(b2 / b1 == 2.0 for b1, b2 in zip(bs, bs[1:]))
    assert len(DEFAULT_BUCKETS_MS) == 17


def test_record_paths_never_throw():
    c, g, h = Counter("t.c"), Gauge("t.g"), Histogram("t.h")
    c.inc("garbage")
    c.inc(float("nan"))
    g.set(object())
    g.set(float("inf"))
    h.observe("nope")
    h.observe(float("-inf"))
    assert c.value() == 0
    assert g.value() == 0
    assert h.series_count() == 0


def test_registry_idempotent_and_kind_collision():
    reg = MetricsRegistry()
    assert reg.counter("a.b") is reg.counter("a.b")
    with pytest.raises(ValueError):
        reg.gauge("a.b")


# one Prometheus sample line: name{labels} value
_SAMPLE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [^ ]+$")


def _parse_prom(text: str) -> dict[str, list[tuple[str, float]]]:
    """{metric_name: [(labels_str, value)]}; raises on bad sample lines."""
    out: dict[str, list[tuple[str, float]]] = {}
    for ln in text.splitlines():
        if not ln:
            raise ValueError("blank line inside exposition")
        if ln.startswith("#"):
            continue
        assert _SAMPLE.match(ln), f"invalid sample line: {ln!r}"
        head, raw = ln.rsplit(" ", 1)
        name, _, labels = head.partition("{")
        value = math.inf if raw == "+Inf" else float(raw)
        out.setdefault(name, []).append((labels.rstrip("}"), value))
    return out


def test_prometheus_exposition_golden():
    reg = MetricsRegistry()
    reg.counter("gen.requests", "requests").inc(3, op="chat")
    reg.gauge("pool.free").set(11)
    h = reg.histogram("lat.ms", buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(5.0)
    h.observe(50.0, kind="slow")
    series = _parse_prom(reg.render())
    # counter: _total suffix, labels escaped/rendered
    assert series["bee2bee_gen_requests_total"] == [('op="chat"', 3.0)]
    assert series["bee2bee_pool_free"] == [("", 11.0)]
    # histogram: cumulative buckets + +Inf == count, sum present
    unlabeled = [v for l, v in series["bee2bee_lat_ms_bucket"] if "kind" not in l]
    assert unlabeled == [1.0, 2.0, 2.0]  # le=1, le=10, le=+Inf (cumulative)
    assert ("", 2.0) in series["bee2bee_lat_ms_count"]
    labeled = [v for l, v in series["bee2bee_lat_ms_bucket"] if "kind" in l]
    assert labeled == [0.0, 0.0, 1.0]
    # dotted names are flattened, never emitted raw
    assert not any("." in name for name in series)


def test_registry_thread_safety():
    reg = MetricsRegistry()
    c = reg.counter("t.par")
    h = reg.histogram("t.par_ms")

    def worker():
        for i in range(500):
            c.inc()
            h.observe(float(i % 50))

    threads = [threading.Thread(target=worker) for _ in range(8)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    assert c.value() == 4000
    assert h.series_count() == 4000


def test_snapshot_shape():
    reg = MetricsRegistry()
    reg.counter("c.x").inc(2)
    reg.histogram("h.y", buckets=(1.0, 2.0)).observe(1.5)
    snap = reg.snapshot()
    assert snap["c.x"]["type"] == "counter"
    assert snap["c.x"]["series"] == [{"labels": {}, "value": 2.0}]
    hy = snap["h.y"]
    assert hy["type"] == "histogram" and hy["buckets"] == [1.0, 2.0]
    assert hy["series"][0]["count"] == 1
    assert "p50" in hy["series"][0]


# ------------------------------------------------------ trace context units


def test_inject_extract_roundtrip_inside_span():
    tr = Tracer()
    assert current_trace_ctx() is None
    frame = inject_trace({"type": "gen_request"})
    assert "trace_ctx" not in frame  # no-op outside any span
    with tr.span("outer") as s:
        ctx = current_trace_ctx()
        assert ctx is not None and ctx.span_id == s.span_id
        frame = inject_trace({"type": "gen_request"})
        got = extract_trace(frame)
        assert got == TraceContext(s.trace_id, s.span_id)


def test_extract_tolerates_missing_and_malformed():
    assert extract_trace({}) is None
    assert extract_trace({"trace_ctx": "not-a-dict"}) is None
    assert extract_trace({"trace_ctx": {"trace_id": 7, "span_id": "s"}}) is None
    assert extract_trace(
        {"trace_ctx": {"trace_id": "t", "span_id": "s"}}
    ) == TraceContext("t", "s")


def test_use_trace_ctx_parents_remote_spans():
    tr = Tracer()
    ctx = TraceContext("trace_remote", "span_remote")
    with use_trace_ctx(ctx):
        with tr.span("worker.op") as s:
            assert s.trace_id == "trace_remote"
            assert s.parent_id == "span_remote"
    # context is restored on exit, and None ctx is a no-op
    assert current_trace_ctx() is None
    with use_trace_ctx(None):
        assert current_trace_ctx() is None


def _mk_frag(sid, parent, start, node):
    return {
        "node": node,
        "spans": [{"span_id": sid, "parent_id": parent, "trace_id": "T",
                   "start_ms": start, "name": f"s.{sid}"}],
    }


def test_stitch_trace_merges_fragments():
    stitched = stitch_trace([
        _mk_frag("b", "a", 2.0, "node2"),
        _mk_frag("a", None, 1.0, "node1"),
        _mk_frag("b", "a", 2.0, "node3"),  # duplicate span_id: dropped
    ])
    assert stitched["trace_id"] == "T"
    assert stitched["nodes"] == ["node1", "node2"]
    assert [s["span_id"] for s in stitched["spans"]] == ["a", "b"]
    assert stitched["spans"][0]["node"] == "node1"
    # every fragment answered: the stitch is complete
    assert stitched["incomplete"] is False
    assert stitched["missing_peers"] == []


def test_stitch_trace_degrades_on_unreachable_and_partial_fragments():
    """ISSUE 6 satellite: an unreachable peer or a partial fragment no
    longer fails the stitch — the merged PARTIAL timeline returns with
    incomplete=true and the offenders in missing_peers."""
    stitched = stitch_trace([
        _mk_frag("a", None, 1.0, "node1"),
        {"node": "node2", "unreachable": True},
        {"node": "node3", "partial": True},
    ])
    assert [s["span_id"] for s in stitched["spans"]] == ["a"]
    assert stitched["incomplete"] is True
    assert stitched["missing_peers"] == ["node2", "node3"]
    # expected_nodes that contributed nothing also count as missing
    stitched = stitch_trace(
        [_mk_frag("a", None, 1.0, "node1")],
        expected_nodes=["node1", "node4"],
    )
    assert stitched["missing_peers"] == ["node4"]
    assert stitched["incomplete"] is True
    # a peer that both failed once and answered once (duplicate fragment
    # pair) counts as answered
    stitched = stitch_trace([
        {"node": "node1", "unreachable": True},
        _mk_frag("a", None, 1.0, "node1"),
    ])
    assert stitched["incomplete"] is False
    assert stitched["missing_peers"] == []


async def test_stitch_route_reports_unreachable_peer_as_missing():
    """/trace?stitch=1 marks a peer whose api endpoint cannot be reached
    as a missing peer instead of silently shrinking the timeline."""
    from aiohttp.test_utils import TestClient, TestServer

    from bee2bee_tpu.api import build_app
    from bee2bee_tpu.meshnet.node import P2PNode
    from bee2bee_tpu.services.fake import FakeService
    from tests.test_meshnet import _settle

    get_tracer().clear()
    a = P2PNode(host="127.0.0.1", port=0)
    # b advertises an api port nothing listens on (9: discard/closed)
    b = P2PNode(host="127.0.0.1", port=0, api_port=9, announce_host="127.0.0.1")
    await a.start()
    await b.start()
    client = None
    try:
        a.add_service(FakeService("tiny", reply="stitch me"))
        assert await b.connect_bootstrap(a.addr)
        assert await _settle(lambda: a.peers and b.peers)
        await a.request_generation(a.peer_id, "x", model="tiny")
        tid = get_tracer().recent(name="gen.local")[-1]["trace_id"]
        client = TestClient(TestServer(build_app(a)))
        await client.start_server()
        r = await client.get(
            "/trace", params={"trace_id": tid, "stitch": "1"}
        )
        stitched = await r.json()
        assert any(s["name"] == "gen.local" for s in stitched["spans"])
        assert stitched["incomplete"] is True
        assert b.peer_id in stitched["missing_peers"]
    finally:
        if client is not None:
            await client.close()
        await b.stop()
        await a.stop()


async def test_stitch_route_reports_endpointless_peer_as_missing():
    """A peer that advertises NO api endpoint can't be asked for its
    fragment at all — it must land in missing_peers, not be silently
    skipped with the stitch still claiming complete."""
    from aiohttp.test_utils import TestClient, TestServer

    from bee2bee_tpu.api import build_app
    from bee2bee_tpu.meshnet.node import P2PNode
    from bee2bee_tpu.services.fake import FakeService
    from tests.test_meshnet import _settle

    get_tracer().clear()
    a = P2PNode(host="127.0.0.1", port=0)
    b = P2PNode(host="127.0.0.1", port=0)  # api_port defaults to None
    await a.start()
    await b.start()
    client = None
    try:
        a.add_service(FakeService("tiny", reply="stitch me"))
        assert await b.connect_bootstrap(a.addr)
        assert await _settle(lambda: a.peers and b.peers)
        assert all(
            not info.get("api_port") for info in a.peers.values()
        ), "test premise: b advertises no api endpoint"
        await a.request_generation(a.peer_id, "x", model="tiny")
        tid = get_tracer().recent(name="gen.local")[-1]["trace_id"]
        client = TestClient(TestServer(build_app(a)))
        await client.start_server()
        stitched = await (await client.get(
            "/trace", params={"trace_id": tid, "stitch": "1"}
        )).json()
        assert stitched["incomplete"] is True
        assert b.peer_id in stitched["missing_peers"]
    finally:
        if client is not None:
            await client.close()
        await b.stop()
        await a.stop()


# ------------------------------------------------------------- route tests


async def _node_app():
    from aiohttp.test_utils import TestClient, TestServer

    from bee2bee_tpu.api import build_app
    from bee2bee_tpu.meshnet.node import P2PNode
    from bee2bee_tpu.services.fake import FakeService

    node = P2PNode(host="127.0.0.1", port=0)
    await node.start()
    node.add_service(FakeService("tiny", reply="four token reply here"))
    client = TestClient(TestServer(build_app(node)))
    await client.start_server()
    return node, client


async def test_metrics_route_prometheus_and_json():
    node, client = await _node_app()
    try:
        r = await client.post("/chat", json={"prompt": "hi", "model": "tiny"})
        assert r.status == 200
        body = await r.json()
        # per-request timing breakdown in the generation response metadata
        t = body["timing"]
        assert t["ttft_ms"] >= 0 and t["decode_tokens"] == 4

        # a serving node imports the engine; its histograms/gauges must
        # appear in the same exposition (the acceptance criterion names
        # TTFT/inter-token histograms and block-pool occupancy)
        import bee2bee_tpu.engine.engine  # noqa: F401 — registers TTFT/TPOT
        import bee2bee_tpu.engine.paged  # noqa: F401 — registers pool gauges
        import bee2bee_tpu.engine.scheduler  # noqa: F401 — queue-wait/step

        r = await client.get("/metrics")
        assert r.status == 200
        assert r.headers["Content-Type"].startswith("text/plain")
        text = await r.text()
        series = _parse_prom(text)
        for must in ("bee2bee_service_execute_ms_count", "bee2bee_peers",
                     "bee2bee_total_requests",
                     "bee2bee_mesh_frames_sent_total"):
            assert must in series, f"{must} missing from /metrics"
        assert series["bee2bee_service_execute_ms_count"][0][1] >= 1
        for must in ("bee2bee_engine_ttft_ms", "bee2bee_engine_inter_token_ms",
                     "bee2bee_engine_queue_wait_ms",
                     "bee2bee_engine_paged_blocks_in_use"):
            assert must in text, f"{must} missing from /metrics"

        # JSON twin via ?format= and via Accept:
        r = await client.get("/metrics", params={"format": "json"})
        snap = (await r.json())["metrics"]
        assert snap["service.execute_ms"]["type"] == "histogram"
        r = await client.get(
            "/metrics", headers={"Accept": "application/json"}
        )
        assert (await r.json())["node"] == node.peer_id
    finally:
        await client.close()
        await node.stop()


async def test_trace_route_returns_fragment_by_id():
    get_tracer().clear()
    node, client = await _node_app()
    try:
        await node.request_generation(node.peer_id, "hello", model="tiny")
        recent = get_tracer().recent(name="gen.local")
        assert recent, "gen.local span missing"
        tid = recent[-1]["trace_id"]
        r = await client.get("/trace", params={"trace_id": tid})
        frag = await r.json()
        assert frag["node"] == node.peer_id and frag["trace_id"] == tid
        assert all(s["trace_id"] == tid for s in frag["spans"])
        assert any(s["name"] == "gen.local" for s in frag["spans"])
    finally:
        await client.close()
        await node.stop()


# ------------------------------------- cross-node propagation: relay path


async def test_trace_ctx_survives_api_node_relay_service():
    """The acceptance walk: api → node A → relay B → service C. Every
    hop's spans share the originating trace_id, and parent links chain
    api.chat → gen.p2p(A) → gen.p2p(B) → gen.local(C)."""
    from aiohttp.test_utils import TestClient, TestServer

    from bee2bee_tpu.api import build_app
    from tests.test_hop_coverage import MODEL, _wire_a_b_c
    from tests.test_meshnet import mesh

    get_tracer().clear()
    async with mesh(3) as (a, b, c):
        await _wire_a_b_c(a, b, c)
        client = TestClient(TestServer(build_app(a)))
        await client.start_server()
        try:
            r = await client.post("/chat", json={"prompt": "hop", "model": MODEL})
            assert r.status == 200
            body = await r.json()
            # the relay forwards the timing breakdown end-to-end too
            assert body["timing"]["ttft_ms"] >= 0
        finally:
            await client.close()

        spans = {s["span_id"]: s for s in get_tracer().recent(limit=1000)}
        root = next(s for s in spans.values() if s["name"] == "api.chat")
        tid = root["trace_id"]
        chain = [s for s in spans.values() if s["trace_id"] == tid]
        by_name = {}
        for s in chain:
            by_name.setdefault(s["name"], []).append(s)
        # two p2p hops (A→B and B's relay leg B→C) + the far gen.local
        assert len(by_name["gen.p2p"]) == 2
        assert len(by_name["gen.local"]) == 1
        # parent links chain hop-under-hop back to the api span
        hop1 = next(s for s in by_name["gen.p2p"] if s["parent_id"] == root["span_id"])
        hop2 = next(s for s in by_name["gen.p2p"] if s is not hop1)
        assert hop2["parent_id"] == hop1["span_id"], (
            "relay hop does not parent under the first p2p hop"
        )
        assert by_name["gen.local"][0]["parent_id"] == hop2["span_id"], (
            "service-side span does not parent under the relay hop"
        )
        # a /trace?trace_id= fragment from the serving node contains the
        # chain (nodes share this process, hence one tracer), and
        # stitch_trace assembles fragments into one timeline
        frag = {"node": c.peer_id, "spans": get_tracer().for_trace(tid)}
        stitched = stitch_trace([frag])
        assert stitched["trace_id"] == tid
        assert len(stitched["spans"]) >= 4


# --------------------------------- cross-node propagation: pipeline stages


async def test_trace_ctx_survives_pipeline_stage_tasks():
    """Stage tasks carry trace_ctx: worker-side stage.task spans parent
    under the coordinator's pipeline.generate span, sharing its trace."""
    from bee2bee_tpu.meshnet.pipeline import PipelineCoordinator
    from tests.test_meshnet import _settle, mesh

    get_tracer().clear()
    async with mesh(3) as (coord, w0, w1):
        assert await coord.connect_bootstrap(w0.addr)
        assert await coord.connect_bootstrap(w1.addr)
        assert await _settle(lambda: len(coord.peers) == 2)
        pc = PipelineCoordinator(
            coord, "tiny-llama", [w0.peer_id, w1.peer_id],
            max_seq_len=64, dtype="float32", rng_seed=0,
        )
        await pc.load()
        out = await pc.generate([5, 9, 42], max_new_tokens=2, temperature=0.0)
        assert len(out) == 2

    spans = get_tracer().recent(limit=2000)
    root = next(s for s in spans if s["name"] == "pipeline.generate")
    assert root["attrs"]["tokens"] == 2
    stage_spans = [
        s for s in spans
        if s["name"] == "stage.task" and s["trace_id"] == root["trace_id"]
    ]
    # prefill + decode steps across two workers — every one under the trace
    assert len(stage_spans) >= 2
    span_ids = {s["span_id"] for s in spans if s["trace_id"] == root["trace_id"]}
    assert all(s["parent_id"] in span_ids for s in stage_spans), (
        "stage.task spans must parent inside the originating trace"
    )


# ------------------------------------------------- streamed gen.local span


async def test_stream_span_covers_stream_lifetime_and_records_tokens():
    """ISSUE 5 satellite: the gen.local span of a STREAMED generation must
    span the whole stream (duration >= stream duration), and carry the
    token count + timing off the done line."""
    from aiohttp.test_utils import TestClient, TestServer

    from bee2bee_tpu.api import build_app
    from bee2bee_tpu.meshnet.node import P2PNode
    from bee2bee_tpu.services.fake import FakeService

    get_tracer().clear()
    node = P2PNode(host="127.0.0.1", port=0)
    await node.start()
    # 6 chunks x 30 ms: stream wall time far exceeds setup time
    node.add_service(FakeService(
        "tiny", reply="stream span must cover me", chunk_size=4, delay_s=0.03,
    ))
    client = TestClient(TestServer(build_app(node)))
    await client.start_server()
    try:
        t0 = time.monotonic()
        r = await client.post(
            "/chat", json={"prompt": "x", "model": "tiny", "stream": True}
        )
        lines = [json.loads(l) for l in (await r.text()).splitlines() if l]
        stream_s = time.monotonic() - t0
        done = next(l for l in lines if l.get("done"))
        assert done["timing"]["ttft_ms"] >= 0
    finally:
        await client.close()
        await node.stop()

    [span] = get_tracer().recent(name="gen.local")
    assert span["duration_ms"] >= 6 * 30 * 0.9, (
        f"gen.local span ({span['duration_ms']}ms) does not cover the "
        f"stream ({stream_s * 1000:.0f}ms) — it timed only the setup"
    )
    assert span["attrs"]["tokens"] == done["tokens"]
    assert span["attrs"]["chunks"] >= 6
    assert span["attrs"]["timing"]["decode_tokens"] == done["tokens"]


async def test_stream_span_records_service_error():
    from aiohttp.test_utils import TestClient, TestServer

    from bee2bee_tpu.api import build_app
    from bee2bee_tpu.meshnet.node import P2PNode
    from bee2bee_tpu.services.fake import FakeService

    get_tracer().clear()
    node = P2PNode(host="127.0.0.1", port=0)
    await node.start()
    node.add_service(FakeService("tiny", fail_with="backend on fire"))
    client = TestClient(TestServer(build_app(node)))
    await client.start_server()
    try:
        r = await client.post(
            "/chat", json={"prompt": "x", "model": "tiny", "stream": True}
        )
        assert r.status == 200  # error rides INSIDE the stream
        assert "backend on fire" in await r.text()
    finally:
        await client.close()
        await node.stop()
    [span] = get_tracer().recent(name="gen.local")
    assert span["error"] == "backend on fire"


# ------------------------------------------- gateway + client timing e2e


async def test_gateway_meta_trailer_and_client_last_meta():
    """The web tier: opt-in [Meta] trailer carries tokens/cost/timing;
    GatewayClient strips it from the text and exposes it as last_meta."""
    from aiohttp.test_utils import TestServer

    from bee2bee_tpu.client import GatewayClient
    from bee2bee_tpu.web.bridge import MeshBridge
    from bee2bee_tpu.web.gateway import create_web_app
    from tests.test_meshnet import _settle, mesh

    async with mesh(1) as (node,):
        node.add_service(FakeServiceForGateway())
        bridge = MeshBridge(seeds=[node.addr])
        await bridge.start()
        server = TestServer(create_web_app(bridge))
        await server.start_server()
        try:
            assert await _settle(lambda: bridge.active_ws is not None)
            g = GatewayClient(f"http://127.0.0.1:{server.port}")
            seen: list[str] = []
            text = await g.generate(
                "hello", model="gw-model", with_meta=True, on_chunk=seen.append
            )
            assert text == "gateway meta reply"
            assert g.last_meta is not None
            assert g.last_meta["tokens"] == 3
            assert g.last_meta["timing"]["decode_tokens"] == 3
            # the trailer is metadata, not output: a live-streaming UI fed
            # by on_chunk must never render it
            assert "".join(seen) == "gateway meta reply"
            # without the flag the stream is byte-identical to before
            text = await g.generate("hello", model="gw-model")
            assert text == "gateway meta reply"
            assert g.last_meta is None
        finally:
            await server.close()
            await bridge.stop()


def FakeServiceForGateway():
    from bee2bee_tpu.services.fake import FakeService

    return FakeService("gw-model", reply="gateway meta reply")


async def test_client_meta_flushes_heldback_tail_without_trailer():
    """Version skew: a gateway that ignores "meta" never sends the [Meta]
    trailer. Text ending in a marker-prefix lookalike ("\\n\\n") is held
    back mid-stream as a possible trailer start — it must still reach
    on_chunk once the stream ends, so streamed == returned text."""
    from aiohttp import web
    from aiohttp.test_utils import TestServer

    from bee2bee_tpu.client import GatewayClient

    async def generate(request):
        resp = web.StreamResponse()
        await resp.prepare(request)
        await resp.write(b"old gateway reply\n\n")
        await resp.write_eof()
        return resp

    app = web.Application()
    app.router.add_post("/api/p2p/generate", generate)
    server = TestServer(app)
    await server.start_server()
    try:
        g = GatewayClient(f"http://127.0.0.1:{server.port}")
        seen: list[str] = []
        text = await g.generate(
            "x", model="m", with_meta=True, on_chunk=seen.append
        )
        assert text == "old gateway reply\n\n"
        assert "".join(seen) == text
        assert g.last_meta is None
    finally:
        await server.close()


# -------------------------------------------------- engine instrumentation


def test_block_allocator_tracks_pool_gauges():
    from bee2bee_tpu.engine.paged import BlockAllocator
    from bee2bee_tpu.metrics import get_registry

    reg = get_registry()
    alloc = BlockAllocator(num_blocks=8)
    g_used = reg.gauge("engine.paged_blocks_in_use")
    g_free = reg.gauge("engine.paged_blocks_free")
    assert reg.gauge("engine.paged_blocks_total").value() == 8
    blocks = alloc.alloc(3)
    assert g_used.value() == 3 and g_free.value() == 4  # null block excluded
    alloc.deref(blocks)
    assert g_used.value() == 0 and g_free.value() == 7


def test_engine_emits_timing_breakdown_and_histograms():
    """The serving distributions the ROADMAP is judged by: one generation
    observes TTFT/e2e histograms and returns the full breakdown."""
    import jax

    from bee2bee_tpu.engine.engine import EngineConfig, InferenceEngine
    from bee2bee_tpu.metrics import get_registry
    from bee2bee_tpu.models import core
    from bee2bee_tpu.models.config import get_config

    reg = get_registry()
    h_ttft = reg.histogram("engine.ttft_ms")
    h_queue = reg.histogram("engine.queue_wait_ms")
    h_step = reg.histogram("engine.step_ms")
    before = (h_ttft.series_count(), h_queue.series_count(),
              h_step.series_count())

    cfg = get_config("tiny-gpt2")
    params = core.init_params(cfg, jax.random.key(0))
    eng = InferenceEngine(
        cfg, params, engine_config=EngineConfig(max_seq_len=128, decode_chunk=8)
    )
    try:
        out = eng.generate("hello there", max_new_tokens=8, temperature=0.0)
    finally:
        eng.close()
    t = out.timings
    assert t["decode_tokens"] == out.new_tokens
    assert t["ttft_ms"] >= 0
    assert t["queue_wait_ms"] is not None and t["prefill_ms"] is not None
    # queue_wait + prefill compose to ttft (same clock, split at admission)
    assert t["queue_wait_ms"] + t["prefill_ms"] == pytest.approx(
        t["ttft_ms"], abs=0.01
    )
    assert t["tokens_per_s"] >= 0
    assert t["spec_acceptance"] is None  # spec off in this config
    assert h_ttft.series_count() == before[0] + 1
    assert h_queue.series_count() == before[1] + 1
    assert h_step.series_count() > before[2]


def test_queue_cancelled_request_skips_latency_histograms():
    """A request cancelled while still QUEUED never produced a token: its
    t_first is the cancel instant, so observing it would record the
    client's abandon wait as a TTFT — a cancel burst under load would
    inflate p95/p99 although serving never got slower."""
    from types import SimpleNamespace

    import bee2bee_tpu.engine.engine as eng_mod
    from bee2bee_tpu.tracing import RequestTiming

    before = (eng_mod._H_TTFT.series_count(), eng_mod._H_E2E.series_count())
    fake_engine = SimpleNamespace(
        metrics=SimpleNamespace(record=lambda n, lat: None),
        tokenizer=SimpleNamespace(decode=lambda ids: ""),
    )
    req = SimpleNamespace(
        # the scheduler's queue-cancel path: t_admit never set (0 marks
        # "never entered admission"), t_first = t_done = cancel time
        timing=RequestTiming(t_submit=1.0, t_admit=0.0, t_first=9.0, t_done=9.0),
        out_ids=[], bucket=None, chunks_decoded=0,
        spec_drafted=0, spec_accepted=0, finish="cancelled", prompt_tokens=3,
    )
    res = eng_mod.InferenceEngine._build_result(fake_engine, req)
    assert res.finish_reason == "cancelled"
    assert res.timings["queue_wait_ms"] is None  # no admission split exists
    assert res.timings["prefill_ms"] is None
    assert eng_mod._H_TTFT.series_count() == before[0]
    assert eng_mod._H_E2E.series_count() == before[1]
