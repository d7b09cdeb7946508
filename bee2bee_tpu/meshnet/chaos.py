"""Deterministic fault injection for the meshnet pipeline.

The chaos tests' original `_hard_kill` lived in tests/test_chaos.py;
failover needs the same process-death semantics PLUS per-stage, per-step
precision ("kill stage 1 on its 3rd forward"), so both live here as
product code — operators can drive game-day drills with the same
primitives the test suite uses (docs/ROBUSTNESS.md).

- `hard_kill(node)`: every socket dies, no GOODBYE, nothing keeps
  responding — what a power loss or OOM kill looks like to the mesh.
- `ChaosStage(node, action=..., at_step=N)`: intercepts the node's stage
  task handling and, at the Nth matching task, kills the node, delays
  the task, or black-holes it (and everything after — a wedged process
  that still holds its sockets open).
"""

from __future__ import annotations

import asyncio
import contextlib

from .. import protocol

# the stage-serving task kinds a ChaosStage counts as "steps"
FORWARD_KINDS = (
    protocol.TASK_PART_FORWARD,
    protocol.TASK_PART_FORWARD_RELAY,
    protocol.TASK_DECODE_RUN,
)


async def hard_kill(node) -> None:
    """Process-death semantics for an in-process node: every socket dies,
    no GOODBYE is sent, nothing of the node keeps responding."""
    node._stopped = True  # noqa: SLF001 — simulating death, not clean stop
    for info in list(node.peers.values()):
        with contextlib.suppress(Exception):
            await info["ws"].close()
    if node._server is not None:
        node._server.close()
        await node._server.wait_closed()
    for t in list(node._tasks):
        t.cancel()


class ChaosMigration:
    """Deterministic fault injection for live generation migration
    (meshnet/migrate.py). The satellite contract: every faulted path
    degrades down the fallback ladder (KV → re-prefill → typed error)
    with a ``migration:<reason>`` incident bundle, never a hung
    generation.

    action:
      - "kill_link":      close the source→target connection after
                          ``at_chunk`` KV_BLOCKS frames left (mid-stream
                          transport death: the source's ladder re-prefills
                          on another peer; the target abandons its partial
                          import on the drop).
      - "kill_source":    hard_kill the whole SOURCE node at that point
                          (process death: nothing falls back — the target
                          must still clean up, nothing may hang).
      - "corrupt_piece":  flip a payload byte of chunk ``at_chunk`` so its
                          sha256 fails at the target (typed hash_mismatch
                          reject → re-prefill fallback).
      - "exhaust_target": wrap the TARGET node's engine schedulers so the
                          next KV import raises pool-exhausted (typed
                          reject → re-prefill fallback elsewhere).

    ``triggered`` is an asyncio.Event for deterministic sequencing;
    ``restore()`` unwraps everything (no-op after "kill_source").
    """

    def __init__(self, node, action: str = "kill_link", at_chunk: int = 0):
        if action not in (
            "kill_link", "kill_source", "corrupt_piece", "exhaust_target"
        ):
            raise ValueError(f"unknown chaos action {action!r}")
        self.node = node
        self.action = action
        self.at_chunk = int(at_chunk)
        self.triggered = asyncio.Event()
        self._restores: list = []
        if action in ("kill_link", "kill_source", "corrupt_piece"):
            mgr = node.migration
            orig = mgr._send_chunk

            async def wrapped(ws, frame: bytes, seq: int):
                if seq >= self.at_chunk and action == "kill_source":
                    if not self.triggered.is_set():
                        self.triggered.set()
                        await hard_kill(node)
                    raise ConnectionError("chaos: source killed mid-stream")
                if seq >= self.at_chunk and action == "kill_link":
                    self.triggered.set()
                    with contextlib.suppress(Exception):
                        await ws.close()
                    raise ConnectionError("chaos: link dropped mid-stream")
                if seq == self.at_chunk and action == "corrupt_piece":
                    self.triggered.set()
                    frame = frame[:-1] + bytes([frame[-1] ^ 0xFF])
                await orig(ws, frame, seq)

            mgr._send_chunk = wrapped
            self._restores.append(lambda: setattr(mgr, "_send_chunk", orig))
        else:  # exhaust_target
            from ..engine.paged import PoolExhausted

            # the wrapper below runs on the ENGINE SCHEDULER THREAD;
            # asyncio.Event.set is not thread-safe, so the trigger hops
            # back onto the loop that owns the event
            try:
                loop = asyncio.get_running_loop()
            except RuntimeError:  # constructed outside a loop (sync test)
                loop = None

            for svc in node.local_services.values():
                eng = getattr(svc, "engine", None)
                sch = getattr(eng, "scheduler", None) if eng else None
                if sch is None:
                    continue
                orig_import = sch._paged_import

                def failing(req, b, st, _sch=sch, _orig=orig_import):
                    if loop is not None:
                        loop.call_soon_threadsafe(self.triggered.set)
                    else:
                        self.triggered.set()
                    raise PoolExhausted("chaos: import pool exhausted")

                sch._paged_import = failing
                self._restores.append(
                    lambda _sch=sch, _orig=orig_import: setattr(
                        _sch, "_paged_import", _orig
                    )
                )

    def restore(self) -> None:
        for undo in self._restores:
            undo()
        self._restores.clear()


class ChaosController:
    """Deterministic fault injection for the elastic fleet control loop
    (fleet/controller.py). The tentpole contract (tests/test_fleet.py):
    a controller death or network split never strands a draining node,
    a half-provisioned replica never receives traffic, and no in-flight
    generation is dropped.

    Faults:
      - ``await kill_leader()``: hard_kill the node currently holding
        the lease (mid-drain if the test timed it so) — the successor's
        orphan scan must adopt or roll back whatever it left behind.
      - ``partition(a, b, ops=None)``: drop frames between nodes a and b
        in BOTH directions (default: only the fleet ops — lease gossip
        and actions — the nastier case where telemetry still flows but
        leadership is invisible). ``heal()`` restores delivery.
      - ``await usurp(node, epoch=None)``: force ``node`` to claim the
        lease NOW (default: at the current highest epoch — a true
        split-brain tie). Both leaders broadcast; the deterministic
        ordering (higher epoch, then smaller peer id) must leave exactly
        one standing.
      - ``fail_probe(node, fails=1)``: the next ``fails`` warm-up probes
        on that controller report failure — the provision-probe chaos
        rung (replica must be rolled back to standby, never eligible).

    ``restore()`` undoes partitions and probe wraps (kills stay dead).
    """

    def __init__(self, nodes=()):
        self.nodes = list(nodes)
        self._restores: list = []

    # ------------------------------------------------------------- leaders

    def leader(self):
        """The node currently believing it holds the lease (None if no
        node does; tests settle on exactly one)."""
        leaders = [n for n in self.nodes if n.fleet.is_leader and not n._stopped]
        return leaders[0] if leaders else None

    def leaders(self):
        return [n for n in self.nodes if n.fleet.is_leader and not n._stopped]

    async def kill_leader(self):
        """Process-death semantics for the current leader; returns the
        killed node (its in-flight action dies with it)."""
        node = self.leader()
        if node is None:
            raise AssertionError("no leader to kill")
        await hard_kill(node)
        return node

    # ---------------------------------------------------------- partitions

    def partition(self, a, b, ops: tuple[str, ...] | None = None) -> None:
        """Drop `ops` frames (default: the fleet control plane) between
        nodes a and b, both directions, at the RECEIVER — the sender
        still believes it spoke, exactly like a one-way-lossy network."""
        drop_ops = ops or (
            protocol.FLEET_LEASE, protocol.FLEET_ACTION, protocol.FLEET_ACK
        )
        for me, other in ((a, b), (b, a)):
            orig = me._on_message
            other_id = other.peer_id

            async def filtered(ws, data, _me=me, _orig=orig,
                               _other=other_id):
                if data.get("type") in drop_ops:
                    pid = await _me._peer_for(ws)
                    if pid == _other:
                        return  # dropped on the virtual wire
                await _orig(ws, data)

            me._on_message = filtered
            self._restores.append(
                lambda _me=me, _orig=orig: setattr(_me, "_on_message", _orig)
            )

    def heal(self) -> None:
        """Restore every partition/probe wrap installed so far."""
        self.restore()

    # ----------------------------------------------------------- usurpation

    async def usurp(self, node, epoch: int | None = None):
        """Force `node`'s controller to claim leadership immediately —
        bypassing the lapse wait — and broadcast the claim. With the
        default epoch (the highest seen) this manufactures a genuine
        double-leader split-brain whose resolution must be deterministic."""
        ctrl = node.fleet
        ctrl.epoch = int(epoch) if epoch is not None else max(
            1, ctrl.lease.highest_epoch
        )
        ctrl.is_leader = True
        await ctrl._broadcast_lease()
        return ctrl

    # --------------------------------------------------------------- probes

    def fail_probe(self, node, fails: int = 1) -> None:
        """Make the next `fails` warm-up probes on this controller fail
        (the replica must end back in standby, never eligible)."""
        prov = node.fleet.provisioner
        orig = prov.probe
        state = {"left": int(fails)}

        async def failing(target, _orig=orig, _state=state):
            if _state["left"] > 0:
                _state["left"] -= 1
                return False, "chaos: probe failure injected"
            return await _orig(target)

        prov.probe = failing
        self._restores.append(
            lambda _prov=prov, _orig=orig: setattr(_prov, "probe", _orig)
        )

    def restore(self) -> None:
        # reversed: stacked wraps on one node (two partitions, repeated
        # fail_probe) must unwind inner-first, or an outer restore would
        # re-install the inner wrapper it captured as "original"
        for undo in reversed(self._restores):
            undo()
        self._restores.clear()


class ChaosStage:
    """Wrap one stage worker node's task handler with a scheduled fault.

    action:
      - "kill":      hard_kill the node at step `at_step`; the triggering
                     task (and everything after) is dropped.
      - "blackhole": silently drop every matching task from `at_step` on
                     — the node stays connected but never answers, which
                     is the StageTimeout path.
      - "delay":     sleep `delay_s` before handling each matching task
                     from `at_step` on (latency injection).
      - "error":     answer every matching task from `at_step` on with a
                     typed TASK_ERROR instead of running it — the
                     StageError path (the node stays alive and serves
                     everything the fault does NOT match).

    Steps count tasks whose kind is in `kinds` (default: the forward /
    relay / ring-decode serving kinds) AND that pass `match` (an optional
    ``match(data) -> bool`` predicate — e.g. scope the fault to ONE
    microbatch group's request_id, which is how the group-scoped failover
    tests fail one group's chain while the others keep decoding).
    `triggered` is an asyncio.Event tests can await for deterministic
    sequencing; `steps_seen` exposes the count. `restore()` un-wraps the
    handler (no-op after "kill").
    """

    def __init__(
        self,
        node,
        action: str = "kill",
        at_step: int = 1,
        delay_s: float = 1.0,
        kinds: tuple[str, ...] = FORWARD_KINDS,
        match=None,  # optional predicate over the task frame dict
    ):
        if action not in ("kill", "blackhole", "delay", "error"):
            raise ValueError(f"unknown chaos action {action!r}")
        self.node = node
        self.action = action
        self.at_step = int(at_step)
        self.delay_s = float(delay_s)
        self.kinds = tuple(kinds)
        self.match = match
        self.steps_seen = 0
        self.triggered = asyncio.Event()
        self._orig = node._handle_task
        node._handle_task = self._handle_task

    async def _answer_error(self, ws, data) -> None:
        """Route a typed TASK_ERROR the way a real failed task would: a
        relayed task reports to the ORIGIN coordinator (which is the peer
        awaiting the reply), a first-hop task answers the sender."""
        origin = data.get("origin_peer")
        task_id = data.get("origin_task_id") if origin else data.get("task_id")
        reply_ws = ws
        if origin:
            info = self.node.peers.get(origin)
            if info is None:
                return  # origin gone: nothing awaits the reply
            reply_ws = info["ws"]
        await self.node._send(reply_ws, protocol.msg(
            protocol.TASK_ERROR, task_id=task_id,
            error="chaos: injected stage error",
            error_kind=protocol.ERR_KIND_ERROR,
        ))

    async def _handle_task(self, ws, data):
        if data.get("kind") in self.kinds and (
            self.match is None or self.match(data)
        ):
            self.steps_seen += 1
            if self.steps_seen >= self.at_step:
                if self.action == "kill":
                    if not self.triggered.is_set():
                        self.triggered.set()
                        await hard_kill(self.node)
                    return  # the dead never answer
                if self.action == "blackhole":
                    self.triggered.set()
                    return  # connected but mute: the timeout path
                if self.action == "error":
                    self.triggered.set()
                    await self._answer_error(ws, data)
                    return
                self.triggered.set()
                await self.node.clock.sleep(self.delay_s)
        await self._orig(ws, data)

    def restore(self) -> None:
        self.node._handle_task = self._orig
