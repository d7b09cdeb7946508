"""FleetSim: N P2PNode control planes on one loop, virtual everything.

The harness owns the two seams end to end: it installs a `VirtualClock`
process-wide (`set_clock`) so call-time resolvers (flight recorder,
digest builders, dataclass defaults) follow the simulation, hands every
node a per-host `SimTransport` into one seeded `SimNet`, zeroes the
metrics registry so telemetry digests start from the same bytes every
run, and restores the previous clock on `stop()`.

Scenario vocabulary:

- `run_for(seconds)` — advance virtual time (wall cost: only the work).
- `drive(coro)` — await a mesh future (a generation, a drain) by
  advancing time deadline-by-deadline until it resolves.
- `kill(i)` / `add_node()` — churn, process-death semantics via
  `meshnet.chaos.hard_kill`.
- `net.partition(a, b)` / `net.heal()` — region split-brain.
- `trace_fingerprint()` / `journal_fingerprint()` — the replay
  comparison surface: same seed ⇒ bit-identical strings.

Determinism checklist baked in (docs/SIMULATION.md): metrics sampling
off (`ping_metrics_enabled=False` — psutil digits would differ between
replays), services answer on the loop (`SimService.execute_async` — an
executor thread would race the schedule), registry reset between runs
(digest counter values are part of frame bytes), uuid-derived ids are
fixed-width so frame *sizes* stay replay-stable even though id bytes
differ.
"""

from __future__ import annotations

import asyncio
import contextlib
import json
from typing import Any

from ..clock import set_clock
from ..health import swap_digest_providers
from ..meshnet.chaos import hard_kill
from ..meshnet.node import P2PNode
from ..metrics import get_registry
from ..services.base import ServiceError
from ..services.fake import FakeService
from .clock import VirtualClock
from .transport import LinkProfile, SimNet


class SimService(FakeService):
    """FakeService that answers on the event loop in virtual time.

    The base class's `execute()` runs in the node's executor (a real
    thread — its interleaving would poison the deterministic schedule)
    and stamps wall-clock latencies into the result (frame bytes that
    differ between replays). `execute_async` keeps the whole request on
    the loop with clock-derived, replay-stable timings."""

    def __init__(self, clock=None, **kw):
        super().__init__(**kw)
        self._clock = clock

    async def execute_async(self, params: dict[str, Any]) -> dict[str, Any]:
        self.calls.append(dict(params))
        if self.fail_with:
            raise ServiceError(self.fail_with)
        if self.exec_delay_s and self._clock is not None:
            await self._clock.sleep(self.exec_delay_s)
        text = self._reply_for(params)
        n = len(text.split())
        lat_ms = int(self.exec_delay_s * 1000.0)
        return {
            "text": text,
            "tokens": n,
            "latency_ms": lat_ms,
            "price_per_token": self.price_per_token,
            "cost": self.price_per_token * n,
            "timing": {
                "queue_wait_ms": 0.0,
                "prefill_ms": float(lat_ms),
                "ttft_ms": float(lat_ms),
                "decode_tokens": n,
                "tokens_per_s": 0.0,
                "spec_acceptance": None,
            },
        }


class FleetSim:
    def __init__(
        self,
        n: int,
        seed: int = 0,
        controllers: int = 1,
        ping_interval_s: float = 1.0,
        regions: dict[int, str] | None = None,
        profile: LinkProfile | None = None,
        quantum_s: float = 0.005,
        with_service: bool = True,
        trace_enabled: bool = True,
        perturb=None,
    ):
        self.clock = VirtualClock()
        self.net = SimNet(
            self.clock, seed=seed, default_profile=profile,
            quantum_s=quantum_s, trace_enabled=trace_enabled,
        )
        # interleaving fuzzer hook (simnet.fuzz.SchedulePerturbation):
        # biases same-deadline sleeper order, stretches delivery times by
        # whole quanta, and forces yields at send points. None = canonical
        # deterministic schedule.
        self.clock.perturb = perturb
        self.net.perturb = perturb
        self.n = n
        self.seed = seed
        self.controllers = controllers
        self.ping_interval_s = ping_interval_s
        self.regions = dict(regions or {})
        self.with_service = with_service
        self.nodes: list[P2PNode] = []
        self.dead: set[str] = set()
        self._prev_clock = None
        self._prev_providers: dict = {}
        self._started = False

    # ------------------------------------------------------------ build

    @staticmethod
    def host_for(i: int) -> str:
        return f"10.0.{i // 250}.{i % 250 + 1}"

    def build_node(self, i: int) -> P2PNode:
        host = self.host_for(i)
        region = self.regions.get(i, "default")
        self.net.set_region(host, region)
        node = P2PNode(
            host=host,
            port=9000,
            region=region,
            node_id=f"sim-{i:04d}",
            fleet_controller=(i < self.controllers),
            clock=self.clock,
            transport=self.net.transport(host),
        )
        node.ping_metrics_enabled = False
        if self.ping_interval_s is not None:
            # re-derive the cadence-coupled TTLs the ctor computed from
            # the production default (health TTL and lease TTL are both
            # "3 ticks" — the ratio is the contract, not the seconds)
            node.ping_interval_s = self.ping_interval_s
            node.health.ttl_s = 3.0 * self.ping_interval_s
            node.fleet.lease.ttl_s = 3.0 * self.ping_interval_s
        if self.with_service:
            node.add_service(SimService(clock=self.clock, model_name="sim-model"))
        return node

    # ------------------------------------------------------------ lifecycle

    async def start(self, bootstrap: bool = True) -> "FleetSim":
        self._prev_clock = set_clock(self.clock)
        # engine-less control planes: no live-engine digest blocks (they
        # read the wall clock — see health.swap_digest_providers)
        self._prev_providers = swap_digest_providers({})
        self._started = True
        # zero shared-registry counters: telemetry digests carry their
        # values, and a replay must produce the same frame bytes
        get_registry().reset_all()
        for i in range(self.n):
            self.nodes.append(self.build_node(i))
        for node in list(self.nodes):  # snapshot: add_node() appends mid-start
            await node.start()
        if bootstrap:
            await self.bootstrap()
        return self

    async def stop(self) -> None:
        if not self._started:
            return
        for node in reversed(self.nodes):
            if node.peer_id in self.dead:
                continue
            with contextlib.suppress(Exception):
                await node.stop()
        await self.clock.settle()
        self._started = False
        set_clock(self._prev_clock)
        swap_digest_providers(self._prev_providers)

    async def __aenter__(self) -> "FleetSim":
        return await self.start()

    async def __aexit__(self, *exc) -> None:
        await self.stop()

    # ------------------------------------------------------------ bootstrap

    async def bootstrap(self, max_virtual_s: float = 60.0) -> float:
        """Join everyone through node 0 (hello → peer_list → fan-out
        dials) and advance time until the mesh is fully connected.
        Returns the virtual seconds it took."""
        t0 = self.clock.time()
        seed_addr = self.nodes[0].addr
        for node in self.nodes[1:]:
            await node._connect_peer(seed_addr)  # noqa: SLF001 — harness
        deadline = t0 + max_virtual_s
        while not self.mesh_connected():
            if self.clock.time() >= deadline:
                raise RuntimeError(
                    f"bootstrap stalled at peer counts {self.peer_counts()}"
                )
            await self._advance_one_deadline()
        return self.clock.time() - t0

    async def _advance_one_deadline(self) -> None:
        nxt = self.clock.next_deadline()
        if nxt is None:
            await self.clock.settle()
            if self.clock.next_deadline() is None:
                raise RuntimeError("simulation deadlock: no pending timers")
            nxt = self.clock.next_deadline()
        await self.clock.run_for(max(nxt - self.clock.time(), 0.0))

    async def run_for(self, seconds: float) -> None:
        await self.clock.run_for(seconds)

    # ------------------------------------------------------------ inspection

    def alive(self) -> list[P2PNode]:
        return [n for n in self.nodes if n.peer_id not in self.dead]

    def peer_counts(self) -> list[int]:
        return [len(n.peers) for n in self.alive()]

    def mesh_connected(self) -> bool:
        want = len(self.alive()) - 1
        return all(len(n.peers) >= want for n in self.alive())

    def gossip_coverage(self) -> float:
        """Fraction of (observer, subject) pairs where the observer holds
        a FRESH telemetry digest for the subject. 1.0 = converged."""
        alive = self.alive()
        if len(alive) < 2:
            return 1.0
        want = {n.peer_id for n in alive}
        got = 0
        for n in alive:
            fresh = set(n.health.fresh().keys())
            got += len(fresh & (want - {n.peer_id}))
        return got / (len(alive) * (len(alive) - 1))

    def journals(self) -> dict[str, list[dict]]:
        """Every controller-enabled node's fleet decision journal."""
        return {
            n.peer_id: [dict(e) for e in n.fleet.decisions]
            for n in self.nodes
            if n.fleet.enabled
        }

    def journal_fingerprint(self) -> str:
        return json.dumps(self.journals(), sort_keys=True, default=str)

    def trace_fingerprint(self) -> str:
        return json.dumps(self.net.trace)

    # ------------------------------------------------------------ scenario verbs

    async def drive(self, coro, max_virtual_s: float = 300.0):
        """Await a mesh future (a generation, a drain, a migration) by
        advancing virtual time deadline-by-deadline until it resolves."""
        task = asyncio.ensure_future(coro)
        await self.clock.settle()
        deadline = self.clock.time() + max_virtual_s
        while not task.done() and self.clock.time() < deadline:
            await self._advance_one_deadline()
        if not task.done():
            task.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await task
            raise TimeoutError(
                f"drive(): not resolved after {max_virtual_s} virtual s"
            )
        return task.result()

    async def kill(self, i: int) -> None:
        """Process-death: sockets die, no GOODBYE, node stops responding."""
        node = self.nodes[i]
        self.dead.add(node.peer_id)
        await hard_kill(node)
        await self.clock.settle()

    async def add_node(self) -> P2PNode:
        """Grow the fleet by one (churn scenarios). Joins through node 0's
        address; caller advances time until it melds in."""
        i = len(self.nodes)
        node = self.build_node(i)
        self.nodes.append(node)
        self.n += 1
        await node.start()
        await node._connect_peer(self.nodes[0].addr)  # noqa: SLF001
        return node
