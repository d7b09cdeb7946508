"""Multi-node mesh tests: N real P2PNodes on localhost port 0 in one asyncio
loop (SURVEY §4's prescription — the reference only had manual scripts).
Uses FakeService so no model loads."""

import asyncio
from contextlib import asynccontextmanager

import pytest

from bee2bee_tpu.meshnet.node import P2PNode
from bee2bee_tpu.services.fake import FakeService


@asynccontextmanager
async def mesh(n: int):
    """N live nodes on localhost port 0 (stopped on exit)."""
    nodes = [P2PNode(host="127.0.0.1", port=0) for _ in range(n)]
    for node in nodes:
        await node.start()
    try:
        yield nodes
    finally:
        for node in nodes:
            await node.stop()


async def _settle(cond, timeout=5.0, interval=0.05):
    """Poll until cond() is truthy."""
    for _ in range(int(timeout / interval)):
        if cond():
            return True
        await asyncio.sleep(interval)
    return False


async def test_hello_handshake_populates_peer_tables():
    async with mesh(2) as (a, b):
        assert await b.connect_bootstrap(a.addr)
        assert await _settle(lambda: a.peers and b.peers)
        assert list(a.peers) == [b.peer_id]
        assert list(b.peers) == [a.peer_id]
        assert a.peers[b.peer_id]["addr"] == b.addr


async def test_join_link_bootstrap():
    async with mesh(2) as (a, b):
        assert await b.connect_bootstrap(a.join_link())
        assert await _settle(lambda: b.peers)


async def test_service_announce_and_provider_discovery():
    async with mesh(2) as (a, b):
        await b.connect_bootstrap(a.addr)
        await _settle(lambda: a.peers and b.peers)
        await a.announce_service(FakeService("test-model", price_per_token=0.5))
        assert await _settle(lambda: b.providers)
        provs = b.list_providers("test-model")
        assert len(provs) == 1
        assert provs[0]["provider_id"] == a.peer_id
        assert provs[0]["price_per_token"] == 0.5


async def test_request_generation_roundtrip():
    async with mesh(2) as (a, b):
        a.add_service(FakeService("test-model", reply="mesh says hi"))
        await b.connect_bootstrap(a.addr)
        await _settle(lambda: b.providers)
        result = await b.request_generation(a.peer_id, "ping", model="test-model")
        assert result["text"] == "mesh says hi"
        assert "latency_ms" in result


async def test_request_generation_streaming():
    async with mesh(2) as (a, b):
        a.add_service(FakeService("test-model", reply="0123456789", chunk_size=3))
        await b.connect_bootstrap(a.addr)
        await _settle(lambda: b.providers)
        chunks = []
        result = await b.request_generation(
            a.peer_id, "ping", model="test-model", on_chunk=chunks.append
        )
        assert "".join(chunks) == "0123456789"
        assert result.get("streamed") or result.get("text") == "0123456789"


async def test_gen_error_propagates():
    async with mesh(2) as (a, b):
        a.add_service(FakeService("test-model", fail_with="boom"))
        await b.connect_bootstrap(a.addr)
        await _settle(lambda: b.providers)
        with pytest.raises(RuntimeError, match="boom"):
            await b.request_generation(a.peer_id, "ping", model="test-model")


async def test_self_request_shortcut():
    async with mesh(1) as (n,):
        n.add_service(FakeService("m", reply="self"))
        result = await n.request_generation(n.peer_id, "x", model="m")
        assert result["text"] == "self"


async def test_swarm_relay_one_hop():
    """C asks B (no service); B relays to A (has service). Reference §3.3."""
    async with mesh(3) as (a, b, c):
        a.add_service(FakeService("relay-model", reply="via relay"))
        await b.connect_bootstrap(a.addr)
        await _settle(lambda: b.providers)
        await c.connect_bootstrap(b.addr)
        await _settle(lambda: c.peers)
        result = await c.request_generation(b.peer_id, "q", model="relay-model")
        assert result["text"] == "via relay"


async def test_relay_no_provider_errors():
    async with mesh(2) as (a, b):
        await b.connect_bootstrap(a.addr)
        await _settle(lambda: b.peers)
        with pytest.raises(RuntimeError, match="consensus_deadlock"):
            await b.request_generation(a.peer_id, "q", model="nope")


async def test_peer_gossip_three_nodes():
    """C bootstraps to A and learns about B from A's peer_list."""
    async with mesh(3) as (a, b, c):
        await b.connect_bootstrap(a.addr)
        await _settle(lambda: a.peers)
        await c.connect_bootstrap(a.addr)
        assert await _settle(lambda: len(c.peers) >= 2), f"gossip failed: {list(c.peers)}"


async def test_piece_transfer_hash_verified():
    async with mesh(2) as (a, b):
        blob = b"\x01\x02" * 5000
        digest = a.store_piece(blob)
        await b.connect_bootstrap(a.addr)
        await _settle(lambda: b.peers)
        got = await b.request_piece(a.peer_id, digest)
        assert got == blob


async def test_piece_not_found():
    async with mesh(2) as (a, b):
        await b.connect_bootstrap(a.addr)
        await _settle(lambda: b.peers)
        with pytest.raises(RuntimeError, match="piece_not_found"):
            await b.request_piece(a.peer_id, "0" * 64)


async def test_auto_reconnect_after_unclean_drop():
    """Dialer redials a peer lost without GOODBYE (reference node.py:286-289
    reconnect loop / bridge.js:83-95)."""
    async with mesh(2) as (a, b):
        b.reconnect_initial_s = 0.1
        await b.connect_bootstrap(a.addr)
        await _settle(lambda: a.peers and b.peers)
        # unclean drop: the listener side closes without saying goodbye
        await a.peers[b.peer_id]["ws"].close()
        await _settle(lambda: not b.peers, timeout=2.0)
        assert await _settle(lambda: b.peers and a.peers, timeout=5.0), (
            "dialer should redial after an unclean drop"
        )


async def test_no_reconnect_after_goodbye():
    """An ordinary (non-bootstrap) peer's clean GOODBYE must not trigger
    redial — the peer chose to leave. (Bootstrap goodbyes DO redial: see
    test_bootstrap_redialed_after_clean_restart.)"""
    a = P2PNode(host="127.0.0.1", port=0)
    b = P2PNode(host="127.0.0.1", port=0)
    await a.start()
    await b.start()
    try:
        b.reconnect_initial_s = 0.05
        assert await b._connect_peer(a.addr)  # dialed, NOT bootstrap
        await _settle(lambda: a.peers and b.peers)
        addr = a.addr
        await a.stop()  # sends GOODBYE to b
        await _settle(lambda: not b.peers)
        await asyncio.sleep(0.3)
        assert addr in b._departed
        assert not b._reconnecting, "goodbye peer must not be redialed"
    finally:
        await b.stop()


async def test_reconnect_gives_up_for_ordinary_peers():
    """Non-bootstrap peers stop being redialed after reconnect_window_s."""
    async with mesh(2) as (a, b):
        b.reconnect_initial_s = 0.05
        b.reconnect_max_s = 0.05
        b.reconnect_window_s = 0.2
        # make the dialed addr a non-bootstrap peer connection
        assert await b._connect_peer(a.addr)
        await _settle(lambda: a.peers and b.peers)
        listener = a._server
        # closes the listener AND its established connections: b sees an
        # unclean drop and every redial hits a dead port
        listener.close()
        await listener.wait_closed()
        await _settle(lambda: not b.peers, timeout=2.0)
        assert await _settle(lambda: not b._reconnecting, timeout=5.0), (
            "redial loop should give up after the window"
        )
        assert not b.peers


async def test_bootstrap_redialed_after_clean_restart():
    """A bootstrap peer's graceful restart (GOODBYE) must still be redialed
    — only ordinary peers' goodbyes suppress reconnection."""
    a = P2PNode(host="127.0.0.1", port=0)
    await a.start()
    port = a.port
    b = P2PNode(host="127.0.0.1", port=0)
    await b.start()
    b.reconnect_initial_s = 0.1
    b.reconnect_max_s = 0.2
    a2 = None
    try:
        await b.connect_bootstrap(a.addr)
        await _settle(lambda: a.peers and b.peers)
        await a.stop()  # graceful: sends GOODBYE
        await _settle(lambda: not b.peers)
        a2 = P2PNode(host="127.0.0.1", port=port)  # restart on the same addr
        await a2.start()
        assert await _settle(lambda: b.peers and a2.peers, timeout=5.0), (
            "bootstrap not redialed after clean restart"
        )
    finally:
        if a2 is not None:
            await a2.stop()
        await b.stop()


async def test_disconnect_cleans_peer_table():
    a = P2PNode(host="127.0.0.1", port=0)
    b = P2PNode(host="127.0.0.1", port=0)
    await a.start()
    await b.start()
    try:
        await b.connect_bootstrap(a.addr)
        await _settle(lambda: a.peers and b.peers)
        await b.stop()
        assert await _settle(lambda: not a.peers), "a should drop b after disconnect"
    finally:
        await a.stop()


async def test_pick_provider_prefers_cheap_then_fast():
    async with mesh(2) as (a, b):
        await b.connect_bootstrap(a.addr)
        await _settle(lambda: a.peers and b.peers)
        await a.announce_service(FakeService("m1", price_per_token=0.9))
        b.add_service(FakeService("m1", price_per_token=0.1))
        await _settle(lambda: b.providers)
        pick = b.pick_provider("m1")
        assert pick["provider_id"] == b.peer_id  # cheaper local wins
        pick2 = b.pick_provider()  # no model filter: still cheapest
        assert pick2["price_per_token"] == 0.1


async def test_status_schema():
    async with mesh(1) as (a,):
        st = a.status()
        for key in ("peer_id", "addr", "uptime_s", "peers", "local_services", "metrics"):
            assert key in st


def test_parse_dht_bootstrap():
    from bee2bee_tpu.meshnet.runtime import _parse_dht_bootstrap

    assert _parse_dht_bootstrap("") == []
    assert _parse_dht_bootstrap("10.0.0.5:9000, dht.example.com") == [
        ("10.0.0.5", 9000), ("dht.example.com", 8468),
    ]
    assert _parse_dht_bootstrap("2001:db8::5") == [("2001:db8::5", 8468)]
    assert _parse_dht_bootstrap("[2001:db8::5]:9000") == [("2001:db8::5", 9000)]
    import pytest as _pytest
    with _pytest.raises(ValueError, match="invalid port"):
        _parse_dht_bootstrap("10.0.0.5:84O8")


# ------------------------------------------------------ the Transport seam


def test_default_transport_is_real_websockets_in_a_fresh_interpreter():
    """websockets >= 14 loads `websockets.exceptions` only on explicit
    import: in a process where nothing else imported it first, building
    the default transport used to die with AttributeError (and every CLI
    entry point with it). A fresh interpreter is the only honest check —
    in this process some earlier test has long imported the submodule."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from bee2bee_tpu.transport import default_transport\n"
        "assert 'websockets.exceptions' not in sys.modules\n"
        "t = default_transport()\n"
        "assert t.name == 'websockets', t.name\n"
        "assert issubclass(t.exceptions.ConnectionClosed, Exception)\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=120
    )
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_default_transport_does_not_swallow_non_import_errors(monkeypatch):
    """Only a MISSING package selects the loopback shim; a package that is
    there and breaks must surface, not quietly change the wire."""
    from bee2bee_tpu import transport

    def broken(self):
        raise AttributeError("module 'websockets' has no attribute 'exceptions'")

    monkeypatch.setattr(transport, "_DEFAULT", None)
    monkeypatch.setattr(transport.WebsocketsTransport, "__init__", broken)
    with pytest.raises(AttributeError):
        transport.default_transport()

    def missing(self):
        raise ImportError("No module named 'websockets'")

    monkeypatch.setattr(transport.WebsocketsTransport, "__init__", missing)
    assert transport.default_transport().name == "loopback"
