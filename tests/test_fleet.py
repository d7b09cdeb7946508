"""Elastic fleet control loop (ISSUE 13): burn-rate-driven scale-out/in
with chaos-proof controller leasing.

The acceptance pins (via meshnet.chaos.ChaosController):

- deterministic lease arithmetic: claims order by (epoch, holder), a
  lapsed lease is taken over, a split-brain tie resolves to exactly one
  leader on both sides, and replica actions are epoch-gated;
- scale OUT is probe-gated: a standby walks standby → warming →
  (probe) → eligible, the router and migration plane never touch it
  before the flip, and a failed probe rolls it back to standby;
- scale IN drains the telemetry-worst node down the existing
  drain+migrate path and converts it to a warm standby;
- chaos: a leader killed mid-drain (or partitioned away) never strands
  the draining node — the successor adopts the orphan to completion or
  rolls it back when the fleet needs the capacity — and no in-flight
  generation is dropped anywhere in the matrix.

Model-free: FakeService fleets (the token-level drain/migrate story is
pinned by tests/test_migration.py; this file pins the CONTROL loop).
"""

from __future__ import annotations

import asyncio
import contextlib
import json
import time

import pytest

from bee2bee_tpu.fleet import FleetConfig, parse_fleet_config
from bee2bee_tpu.fleet.lease import LeaseKeeper, LeaseView, lease_beats
from bee2bee_tpu.health import (
    SloTracker,
    controller_aggregates,
    get_recorder,
    parse_slo_config,
)
from bee2bee_tpu.meshnet.chaos import ChaosController, hard_kill
from bee2bee_tpu.meshnet.node import P2PNode
from bee2bee_tpu.metrics import get_registry
from bee2bee_tpu.services.fake import FakeService
from tests.test_meshnet import _settle

MODEL = "fleet-model"
REPLY = "fleet reply " * 16  # long enough to stream across chunks

# a latency objective every FakeService call violates (exec_delay_s
# above threshold_ms), over the histogram FakeService actually observes
SLOW_SLO = [{
    "name": "exec_p95", "kind": "latency", "metric": "service.execute_ms",
    "threshold_ms": 16.0, "target": 0.95,
}]


def _cfg(**over) -> FleetConfig:
    """Test-cadence controller config (ticks ride a 0.1 s ping)."""
    base = dict(
        model=MODEL, min_replicas=1, max_replicas=8,
        # scale-in DISABLED by default (an idle loopback fleet would
        # otherwise start draining mid-test); the scale-in tests opt in
        out_sustain_ticks=2, in_sustain_ticks=10_000,
        scale_out_cooldown_s=0.5, scale_in_cooldown_s=0.5,
        ack_timeout_s=2.0, settle_timeout_s=2.0, probe_timeout_s=5.0,
        action_timeout_s=8.0, lease_ttl_s=0.4, claim_stagger_s=0.15,
        # the queue-wait HISTOGRAM is cumulative and process-global:
        # engine tests earlier in the suite leave a real p95 there that
        # no fleet in this file drives — it must never veto headroom
        headroom_queue_p95_ms=1e12,
    )
    base.update(over)
    return FleetConfig(**base)


@contextlib.asynccontextmanager
async def _fleet(controllers=1, actives=1, standbys=0, cfg=None,
                 slow_slo=False, exec_delay=0.0, stream_delay=0.0):
    """Loopback fleet: `controllers` lease-competing serving nodes,
    `actives` plain serving nodes, `standbys` warm standbys (service
    loaded + announced, digest-excluded). All on a 0.1 s ping cadence
    with digests gossiped and settled."""
    cfg = cfg or _cfg()
    # loopback fleets share the ONE process registry with every engine
    # test that ran before this file: stale batch-fill/row/pool gauges
    # would read as fake load (vetoing headroom) or fake live rows
    # (wedging drain quiescence). FakeService fleets drive none of these
    # — clear them so the digests say what THIS fleet is doing.
    for name in ("engine.batch_fill", "engine.active_rows",
                 "engine.paged_blocks_in_use", "engine.paged_blocks_free",
                 "engine.paged_blocks_total"):
        m = get_registry().get(name)
        if m is not None and hasattr(m, "clear"):
            m.clear()
    nodes, ctrls, acts, stands = [], [], [], []
    try:
        for i in range(controllers + actives + standbys):
            is_ctrl = i < controllers
            is_standby = i >= controllers + actives
            node = P2PNode(
                host="127.0.0.1", port=0,
                fleet_controller=is_ctrl,
                fleet_state="standby" if is_standby else None,
            )
            node.ping_interval_s = 0.1
            node.health.ttl_s = 1.5
            node.fleet.config = cfg
            node.fleet.lease.ttl_s = cfg.lease_ttl_s
            if slow_slo:
                node.slo = SloTracker(
                    objectives=parse_slo_config(SLOW_SLO),
                    fast_window_s=1.0, slow_window_s=5.0,
                )
            await node.start()
            svc = FakeService(
                MODEL, reply=REPLY, chunk_size=8,
                exec_delay_s=exec_delay, delay_s=stream_delay,
            )
            node.add_service(svc)
            nodes.append(node)
            (ctrls if is_ctrl else stands if is_standby else acts).append(node)
        for node in nodes[1:]:
            assert await node.connect_bootstrap(nodes[0].addr)
        n = len(nodes)
        assert await _settle(
            lambda: all(len(x.peers) == n - 1 for x in nodes), timeout=10
        )
        for node in nodes:
            await node.announce_service(node.local_services["fake"])
        for node in nodes:
            await node.gossip_telemetry()
        assert await _settle(
            lambda: all(len(x.health.fresh()) == n - 1 for x in nodes),
            timeout=10,
        )
        yield nodes, ctrls, acts, stands
    finally:
        for node in nodes:
            with contextlib.suppress(Exception):
                await node.stop()


async def _settle_leader(ctrls, timeout=10.0):
    """Exactly one leader AND every other controller has observed its
    lease — later epoch arithmetic is deterministic only once the reign
    is actually known fleet-wide."""
    chaos = ChaosController(ctrls)

    def converged():
        leaders = chaos.leaders()
        if len(leaders) != 1:
            return False
        holder = leaders[0].peer_id
        for c in ctrls:
            if c is leaders[0] or c._stopped:
                continue
            cur = c.fleet.lease.current()
            if cur is None or cur.holder != holder:
                return False
        return True

    assert await _settle(converged, timeout=timeout), (
        f"leaders: {[c.peer_id for c in chaos.leaders()]}"
    )
    return chaos.leader()


def _drive_load(node, stop: asyncio.Event, interval=0.05) -> asyncio.Task:
    """Background open-loop load through the node's own serving path —
    keeps the (shared-registry) SLO histograms burning until `stop`."""
    async def loop():
        while not stop.is_set():
            with contextlib.suppress(Exception):
                await node.request_generation(
                    node.peer_id, "burn", model=MODEL, max_new_tokens=8
                )
            await asyncio.sleep(interval)

    return asyncio.create_task(loop())


class _CaptureWs:
    """Fake ws: collects frames node._send writes at it."""

    def __init__(self):
        self.sent: list[dict] = []

    async def send(self, raw):
        self.sent.append(json.loads(raw))


def _fake_peer(node, pid: str, controller: bool = False) -> _CaptureWs:
    """Register a capture ws as a live peer connection — fleet frame
    handlers resolve identity via node._peer_for(ws), so action/ack
    tests must speak from a REGISTERED connection. With ``controller``
    the peer also advertises fleet_controller in a fresh digest (the
    eligibility gate lease/action frames are vetted against)."""
    ws = _CaptureWs()
    node.peers[pid] = {"ws": ws, "addr": None, "last_seen": time.time()}
    if controller:
        node.health.update(pid, {"fleet_controller": True})
    return ws


def _acks(ws: _CaptureWs) -> list[dict]:
    """The fleet_ack frames the node wrote at ws (the monitor loop also
    pings registered peers — filter those out)."""
    return [f for f in ws.sent if f.get("type") == "fleet_ack"]


# ------------------------------------------------------------- lease units


def test_lease_ordering_is_total_and_deterministic():
    assert lease_beats(2, "node-b", 1, "node-a")  # higher epoch wins
    assert not lease_beats(1, "node-a", 2, "node-b")
    assert lease_beats(1, "node-a", 1, "node-b")  # tie → smaller id
    assert not lease_beats(1, "node-b", 1, "node-a")


def test_lease_keeper_observe_and_lapse():
    k = LeaseKeeper(ttl_s=10.0)
    v = k.observe({"holder": "node-a", "epoch": 1, "ttl_s": 10.0}, now=100.0)
    assert v.holder == "node-a" and k.highest_epoch == 1
    # a same-epoch larger id loses; a higher epoch wins
    v = k.observe({"holder": "node-b", "epoch": 1, "ttl_s": 10.0}, now=101.0)
    assert v.holder == "node-a"
    v = k.observe({"holder": "node-b", "epoch": 2, "ttl_s": 10.0}, now=102.0)
    assert v.holder == "node-b" and k.highest_epoch == 2
    # fresh within ttl, lapsed past it — lapse timed from expiry, not
    # from the poll
    assert k.current(now=111.9) is not None
    assert k.current(now=112.1) is None
    assert k.lapsed_for(now=114.0) == pytest.approx(2.0)
    # any live claim beats a dead reign, even a lower epoch from a
    # smaller... no: epoch floor still applies via authorizes; observe
    # replaces the lapsed view
    v = k.observe({"holder": "node-z", "epoch": 3, "ttl_s": 10.0}, now=115.0)
    assert v.holder == "node-z"
    # released zeroes the TTL
    k.observe({"holder": "node-z", "epoch": 3, "ttl_s": 10.0,
               "released": True}, now=116.0)
    assert k.current(now=116.1) is None


def test_lease_keeper_authorizes_epoch_gated():
    k = LeaseKeeper(ttl_s=10.0)
    # bootstrap: nothing observed → first claimant is trusted
    assert k.authorizes("node-a", 1, now=100.0)
    k.observe({"holder": "node-a", "epoch": 5, "ttl_s": 10.0}, now=100.0)
    assert not k.authorizes("node-b", 4, now=101.0)   # stale epoch
    assert k.authorizes("node-a", 5, now=101.0)       # the holder itself
    assert not k.authorizes("node-z", 5, now=101.0)   # tie lost to holder
    assert k.authorizes("node-0", 5, now=101.0)       # tie won (smaller id)
    assert k.authorizes("node-z", 6, now=101.0)       # higher epoch
    # junk never authorizes
    assert not k.authorizes("", 7, now=101.0)
    assert not k.authorizes("node-a", "junk", now=101.0)


def test_authorizes_follows_the_reinstalled_lower_epoch_reign():
    """A higher epoch observed once from a now-dead claimant must not
    permanently refuse the leader whose renewals we actively accept:
    once the higher reign lapses and the live lower-epoch holder is
    re-installed as current, its actions authorize again (the all-time
    epoch floor gates only lease-less claimants)."""
    k = LeaseKeeper(ttl_s=10.0)
    k.observe({"holder": "node-a", "epoch": 5, "ttl_s": 10.0}, now=100.0)
    # a partitioned rival claims epoch 6, then dies
    k.observe({"holder": "node-b", "epoch": 6, "ttl_s": 10.0}, now=101.0)
    assert not k.authorizes("node-a", 5, now=102.0)  # b's reign is fresh
    # b's lease lapses; a's ongoing renewal re-installs a as current
    k.observe({"holder": "node-a", "epoch": 5, "ttl_s": 10.0}, now=112.0)
    assert k.current(now=112.5).holder == "node-a"
    assert k.authorizes("node-a", 5, now=112.5), (
        "the recognized current holder must be authorized despite the "
        "lapsed higher epoch in history"
    )
    # but with NO fresh lease, the floor still gates claimants
    assert not k.authorizes("node-x", 5, now=130.0)
    assert k.authorizes("node-x", 6, now=130.0)


def test_lease_view_describe_roundtrip():
    v = LeaseView(holder="n", epoch=3, ttl_s=5.0, received_at=50.0)
    d = v.describe(now=51.0)
    assert d["holder"] == "n" and d["epoch"] == 3 and d["fresh"] is True
    assert d["age_s"] == pytest.approx(1.0)


# ------------------------------------------------------------ config units


def test_parse_fleet_config_validates_loudly():
    assert parse_fleet_config({"min_replicas": 2}).min_replicas == 2
    with pytest.raises(ValueError, match="unknown keys"):
        parse_fleet_config({"min_replica": 2})
    with pytest.raises(ValueError, match="must be a JSON object"):
        parse_fleet_config([1])
    with pytest.raises(ValueError, match="min_replicas > max_replicas"):
        parse_fleet_config({"min_replicas": 9, "max_replicas": 2})
    with pytest.raises(ValueError, match="burn_quorum"):
        parse_fleet_config({"burn_quorum": 0.0})
    with pytest.raises(ValueError, match=">= 0"):
        parse_fleet_config({"ack_timeout_s": -1})


def test_load_fleet_config_env(monkeypatch):
    from bee2bee_tpu.fleet import load_fleet_config

    monkeypatch.setenv("BEE2BEE_FLEET_CONFIG", '{"max_replicas": 3}')
    assert load_fleet_config().max_replicas == 3
    monkeypatch.setenv("BEE2BEE_FLEET_CONFIG", '{"bogus": 1}')
    with pytest.raises(ValueError):
        load_fleet_config()


# --------------------------------------------------------- decision units


def _controller_for_units(**over):
    node = P2PNode(host="127.0.0.1", port=0, fleet_controller=True)
    node.fleet.config = _cfg(in_sustain_ticks=3, **over)
    node.fleet.is_leader = True
    return node.fleet


def test_decide_hysteresis_sustain_and_cooldown():
    ctrl = _controller_for_units()
    burning = {
        "eligible": 2, "eligible_ids": ["a", "b"], "burning": 2,
        "burning_frac": 1.0, "fill_mean": 0.9, "queue_p95_max": 900.0,
    }
    standby_digests = {"s": {"fleet_state": "standby"}}
    # one burning tick is a blip, not a trend
    d, _, _ = ctrl._decide(100.0, burning, standby_digests)
    assert d == "noop"
    d, _, t = ctrl._decide(100.1, burning, standby_digests)
    assert d == "scale_out" and t == "s"
    # cooldown: a just-completed action blocks the next
    ctrl._action = {"kind": "scale_out", "target": "s"}
    ctrl._finish_action(True, "fleet:scale_out", "unit")
    ctrl._burn_streak = 5
    d, reason, _ = ctrl._decide(100.2, burning, standby_digests)
    assert d == "noop" and "cooldown" in reason
    # bounds: at max_replicas burning never scales out
    ctrl2 = _controller_for_units()
    ctrl2._burn_streak = 5
    maxed = {**burning, "eligible": ctrl2.config.max_replicas}
    d, reason, _ = ctrl2._decide(200.0, maxed, standby_digests)
    assert d == "noop" and "max_replicas" in reason
    # no standby → burning stays a noop, loudly
    ctrl3 = _controller_for_units()
    ctrl3._burn_streak = 5
    d, reason, _ = ctrl3._decide(300.0, burning, {})
    assert d == "noop" and "no standby" in reason


def test_decide_repairs_below_min_replicas_without_burn():
    """A dead replica reports no burn — the floor itself must trigger
    the scale-out, with no sustain window (capacity is already gone)."""
    ctrl = _controller_for_units(min_replicas=2)
    dead_fleet = {
        "eligible": 1, "eligible_ids": ["a"], "burning": 0,
        "burning_frac": 0.0, "fill_mean": 0.0, "queue_p95_max": 0.0,
    }
    standby_digests = {"s": {"fleet_state": "standby"}}
    d, reason, target = ctrl._decide(100.0, dead_fleet, standby_digests)
    assert d == "scale_out" and target == "s" and "repair" in reason
    # without a standby it is a loud noop, not silence
    d, reason, _ = ctrl._decide(100.1, dead_fleet, {})
    assert d == "noop" and "below min_replicas" in reason


def test_decide_scale_in_needs_sustained_headroom_and_remote_target():
    ctrl = _controller_for_units()
    me = ctrl.node.peer_id
    idle = {
        "eligible": 3, "eligible_ids": sorted([me, "node-x", "node-y"]),
        "burning": 0, "burning_frac": 0.0, "fill_mean": 0.0,
        "queue_p95_max": 0.0,
    }
    digests = {"node-x": {}, "node-y": {}}
    for i in range(ctrl.config.in_sustain_ticks - 1):
        d, _, _ = ctrl._decide(100.0 + i, idle, digests)
        assert d == "noop"
    d, _, target = ctrl._decide(110.0, idle, digests)
    assert d == "scale_in" and target in ("node-x", "node-y")
    # min_replicas floor
    ctrl2 = _controller_for_units()
    ctrl2._headroom_streak = 99
    floor = {**idle, "eligible": ctrl2.config.min_replicas}
    d, reason, _ = ctrl2._decide(100.0, floor, digests)
    assert d == "noop" and "min_replicas" in reason
    # never drains itself: only the local node eligible → no candidate
    ctrl3 = _controller_for_units()
    ctrl3._headroom_streak = 99
    me3 = ctrl3.node.peer_id
    solo = {**idle, "eligible": 2, "eligible_ids": [me3, "zz-remote"]}
    d, _, target = ctrl3._decide(100.0, solo, {"zz-remote": {}})
    assert d == "scale_in" and target == "zz-remote"


def test_pick_worst_is_highest_router_penalty():
    ctrl = _controller_for_units()
    agg = {"eligible_ids": ["node-hot", "node-cool"]}
    digests = {
        "node-hot": {"hist": {"engine.queue_wait_ms": {"p95": 5000.0}},
                     "gauge": {"engine.batch_fill": 1.0}},
        "node-cool": {"gauge": {"engine.batch_fill": 0.0}},
    }
    assert ctrl._pick_worst(agg, digests) == "node-hot"


# ------------------------------------------------- routing exclusion units


def test_router_never_routes_to_standby_or_warming():
    from bee2bee_tpu.router.policy import RouterPolicy

    policy = RouterPolicy()
    cands = [
        {"provider_id": "warm", "local": False},
        {"provider_id": "live", "local": False},
    ]
    fresh = {"warm": {"fleet_state": "warming"}, "live": {}}
    winner, decision = policy.pick(cands, fresh)
    assert winner["provider_id"] == "live"
    # an unprobed replica is excluded even when it is the ONLY candidate
    # (no all-burning-style waiver — better no pick than an unprobed one)
    winner, _ = policy.pick(cands[:1], fresh)
    assert winner is None
    fresh["warm"]["fleet_state"] = "standby"
    winner, _ = policy.pick(cands[:1], fresh)
    assert winner is None


async def test_migration_targets_exclude_unprobed_replicas():
    async with _fleet(controllers=0, actives=2) as (nodes, _, acts, _s):
        a, b = acts
        assert b.peer_id in a.migration.migration_targets(MODEL)
        # b flips to warming: it must stop being a migration target on
        # the next gossip — live state is traffic too
        b.fleet_state = "warming"
        await b.gossip_telemetry()
        assert await _settle(
            lambda: b.peer_id not in a.migration.migration_targets(MODEL),
            timeout=5,
        )


# -------------------------------------------------------- live fleet tests


@pytest.mark.async_timeout(120)
async def test_single_controller_claims_and_journals_noops():
    async with _fleet(controllers=1, actives=1) as (nodes, ctrls, acts, _s):
        leader = await _settle_leader(ctrls)
        assert leader is ctrls[0]
        assert await _settle(
            lambda: any(
                d["decision"] == "noop" for d in leader.fleet.decisions
            ),
            timeout=10,
        )
        # the follower holds the leader's lease view
        assert await _settle(
            lambda: (
                acts[0].fleet.lease.current() is not None
                and acts[0].fleet.lease.current().holder == leader.peer_id
            ),
            timeout=10,
        )
        st = leader.fleet.status()
        assert st["is_leader"] and st["lease"]["holder"] == leader.peer_id
        assert st["aggregates"].get("eligible") == 2


@pytest.mark.async_timeout(120)
async def test_leader_death_deterministic_takeover():
    recorder = get_recorder()
    recorder.clear()
    async with _fleet(controllers=2, actives=1) as (nodes, ctrls, acts, _s):
        leader = await _settle_leader(ctrls)
        epoch0 = leader.fleet.epoch
        other = next(c for c in ctrls if c is not leader)
        await hard_kill(leader)
        assert await _settle(lambda: other.fleet.is_leader, timeout=15), (
            "the surviving controller never took over the lapsed lease"
        )
        assert other.fleet.epoch > epoch0  # a takeover is a NEW reign
        recorder.flush()
        kinds = {e["kind"] for e in recorder.list_incidents()}
        assert "fleet:takeover" in kinds


@pytest.mark.async_timeout(120)
async def test_split_brain_tie_resolves_to_smaller_peer_id():
    async with _fleet(controllers=2, actives=0) as (nodes, ctrls, _a, _s):
        leader = await _settle_leader(ctrls)
        other = next(c for c in ctrls if c is not leader)
        chaos = ChaosController(ctrls)
        # force a genuine double-leader at the SAME epoch
        await chaos.usurp(other, epoch=leader.fleet.epoch)
        assert await _settle(lambda: len(chaos.leaders()) == 1, timeout=15)
        winner = chaos.leader()
        assert winner.peer_id == min(c.peer_id for c in ctrls), (
            "equal-epoch split-brain must resolve to the smaller peer id"
        )
        # the loser stepped down explicitly, not by timeout
        loser = next(c for c in ctrls if c is not winner)
        assert loser.fleet.stats["stepdowns"] >= 1


@pytest.mark.async_timeout(120)
async def test_lease_partition_heals_to_single_leader():
    async with _fleet(controllers=2, actives=0) as (nodes, ctrls, _a, _s):
        leader = await _settle_leader(ctrls)
        other = next(c for c in ctrls if c is not leader)
        chaos = ChaosController(ctrls)
        # the nasty split: telemetry still flows, leadership is invisible
        chaos.partition(leader, other)
        assert await _settle(lambda: other.fleet.is_leader, timeout=15), (
            "the partitioned follower never claimed the invisible lease"
        )
        assert len(chaos.leaders()) == 2  # AP by design during the split
        assert other.fleet.epoch > leader.fleet.epoch
        chaos.heal()
        # on heal the higher epoch wins on BOTH sides
        assert await _settle(
            lambda: len(chaos.leaders()) == 1
            and chaos.leader() is other,
            timeout=15,
        )
        assert leader.fleet.stats["stepdowns"] >= 1


@pytest.mark.async_timeout(120)
async def test_stale_epoch_action_is_refused():
    async with _fleet(controllers=0, actives=1) as (nodes, _c, acts, _s):
        b = acts[0]
        b.fleet.lease.observe(
            {"holder": "node-000leader", "epoch": 5, "ttl_s": 30.0}
        )
        stale_ws = _fake_peer(b, "node-zzz-stale", controller=True)
        leader_ws = _fake_peer(b, "node-000leader", controller=True)
        await b.fleet.on_action(stale_ws, {
            "rid": "r1", "action": "drain", "epoch": 4,
            "holder": "node-zzz-stale",
        })
        acks = _acks(stale_ws)
        assert acks and acks[0]["ok"] is False
        assert acks[0]["error"] == "stale_epoch"
        assert b.draining is False  # the stale command changed nothing
        # the rightful holder's command lands
        await b.fleet.on_action(leader_ws, {
            "rid": "r2", "action": "drain", "epoch": 5,
            "holder": "node-000leader",
        })
        assert _acks(leader_ws)[-1]["ok"] is True and b.draining is True


@pytest.mark.async_timeout(120)
async def test_forged_holder_action_is_dropped():
    """A connected peer that copies the gossiped leader identity (with
    an arbitrarily high epoch) must neither command the node nor poison
    its lease view / epoch floor: on_action binds the claimed holder to
    the sending connection, exactly like on_lease."""
    async with _fleet(controllers=0, actives=1) as (nodes, _c, acts, _s):
        b = acts[0]
        b.fleet.lease.observe(
            {"holder": "node-000leader", "epoch": 5, "ttl_s": 30.0}
        )
        # evil IS controller-eligible here, so this pins the holder
        # binding specifically (eligibility alone would not save us)
        evil_ws = _fake_peer(b, "node-evil", controller=True)
        await b.fleet.on_action(evil_ws, {
            "rid": "rf", "action": "drain", "epoch": 10_000,
            "holder": "node-000leader",
        })
        # dropped silently: no ack, no drain, no epoch-floor bump —
        # the rightful leader's reign stays intact
        assert not _acks(evil_ws)
        assert b.draining is False
        assert b.fleet.lease.highest_epoch == 5
        cur = b.fleet.lease.current()
        assert cur is not None and cur.holder == "node-000leader"
        # a connection that is not a known peer at all is dropped too
        await b.fleet.on_action(_CaptureWs(), {
            "rid": "rg", "action": "drain", "epoch": 5,
            "holder": "node-000leader",
        })
        assert b.draining is False


@pytest.mark.async_timeout(120)
async def test_non_controller_self_claim_is_refused():
    """Connection binding alone is not enough: a plain serving peer
    self-claiming an invented high epoch under its OWN identity must
    not command the node either — lease and action frames only count
    from peers whose fresh digest advertises fleet_controller."""
    async with _fleet(controllers=0, actives=1) as (nodes, _c, acts, _s):
        b = acts[0]
        b.fleet.lease.observe(
            {"holder": "node-000leader", "epoch": 5, "ttl_s": 30.0}
        )
        rogue_ws = _fake_peer(b, "node-rogue")  # NOT controller-eligible
        await b.fleet.on_action(rogue_ws, {
            "rid": "rr", "action": "drain", "epoch": 10_000,
            "holder": "node-rogue",
        })
        acks = _acks(rogue_ws)
        assert acks and acks[0]["ok"] is False
        assert acks[0]["error"] == "not_controller"
        assert b.draining is False
        assert b.fleet.lease.highest_epoch == 5  # floor unpoisoned
        # its lease claims are dropped too — the recognized reign and
        # the epoch floor both stay with the rightful leader
        await b.fleet.on_lease(rogue_ws, {
            "holder": "node-rogue", "epoch": 10_000, "ttl_s": 30.0,
        })
        cur = b.fleet.lease.current()
        assert cur is not None and cur.holder == "node-000leader"
        assert b.fleet.lease.highest_epoch == 5


@pytest.mark.async_timeout(120)
async def test_forged_ack_is_ignored():
    """A FLEET_ACK only completes an action when it arrives over the
    connection the action went out on — another peer replaying the rid
    cannot fake a drain/activate completion."""
    async with _fleet(controllers=0, actives=1) as (nodes, _c, acts, _s):
        b = acts[0]
        target_ws = _fake_peer(b, "node-target")
        evil_ws = _fake_peer(b, "node-evil")
        task = asyncio.create_task(
            b.fleet.send_action("node-target", "undrain", timeout=5.0)
        )
        assert await _settle(lambda: bool(b.fleet._acks), timeout=2)
        rid = next(iter(b.fleet._acks))
        await b.fleet.on_ack(evil_ws, {"rid": rid, "ok": True})
        _, _, fut = b.fleet._acks[rid]
        assert not fut.done()  # the forged ack changed nothing
        await b.fleet.on_ack(target_ws, {"rid": rid, "ok": True})
        ack = await task
        assert ack["ok"] is True


def test_lease_keeper_boot_grace_before_first_claim():
    k = LeaseKeeper(ttl_s=10.0)
    k._lapse_started = 100.0  # the boot instant, on the fake clock
    # nothing ever observed: one full TTL of silence must pass before
    # the void counts as a lapse, so a freshly booted node cannot claim
    # (and usurp a live incumbent) before the incumbent's gossip arrives
    assert k.lapsed_for(now=100.0) is None
    assert k.lapsed_for(now=109.9) is None
    assert k.lapsed_for(now=112.0) == pytest.approx(2.0)
    # once a lease HAS been observed the grace never applies again:
    # lapse counts straight from the TTL expiry
    k.observe({"holder": "node-a", "epoch": 1, "ttl_s": 10.0}, now=112.0)
    assert k.lapsed_for(now=121.0) is None
    assert k.lapsed_for(now=124.0) == pytest.approx(2.0)


def test_lease_boot_grace_re_anchors_at_mesh_join():
    # construction→start can take longer than a TTL (first jit compile,
    # retried bootstrap): node.start() re-anchors the grace so it is
    # not silently consumed before the node has even joined the mesh
    k = LeaseKeeper(ttl_s=10.0)
    k._lapse_started = 50.0  # constructed long ago on the fake clock
    assert k.lapsed_for(now=100.0) == pytest.approx(40.0)  # grace eaten
    k.reset_boot_grace(now=100.0)  # the node actually starts here
    assert k.lapsed_for(now=105.0) is None
    assert k.lapsed_for(now=112.0) == pytest.approx(2.0)
    # once a lease is held, re-anchoring is a no-op (restarting the
    # monitor loop must not erase a known reign's lapse bookkeeping)
    k.observe({"holder": "node-a", "epoch": 1, "ttl_s": 10.0}, now=112.0)
    k.reset_boot_grace(now=500.0)
    assert k.current(now=113.0) is not None


def test_lease_boot_grace_deferral_is_capped():
    # a rolling bootstrap — or a crash-looping peer minting a fresh
    # random id per restart — keeps re-anchoring the grace on every
    # first contact; the first claim must still be bounded (three TTLs
    # past the first anchor), or the fleet stays leaderless forever
    k = LeaseKeeper(ttl_s=10.0)
    k.reset_boot_grace(now=100.0)  # node start: anchor cap = 120
    for t in (109.0, 118.0, 127.0, 136.0):  # endless fresh peer ids
        k.reset_boot_grace(now=t)
    # the anchor clamps at 120 → the grace ends at 130, not at 146
    assert k.lapsed_for(now=129.0) is None
    assert k.lapsed_for(now=132.0) == pytest.approx(2.0)


@pytest.mark.async_timeout(180)
async def test_burn_scale_out_probes_then_flips_standby_eligible():
    recorder = get_recorder()
    recorder.clear()
    async with _fleet(
        controllers=1, actives=1, standbys=1,
        slow_slo=True, exec_delay=0.05,
    ) as (nodes, ctrls, acts, stands):
        leader = await _settle_leader(ctrls)
        standby = stands[0]
        # while standby: never routable, in the standby bucket
        prov = acts[0].pick_provider(MODEL, remote_only=True)
        assert prov is not None and prov["provider_id"] != standby.peer_id
        stop = asyncio.Event()
        load = _drive_load(leader, stop)
        try:
            assert await _settle(
                lambda: standby.fleet_state is None, timeout=60
            ), (
                f"standby never became eligible; journal: "
                f"{list(leader.fleet.decisions)[-5:]}"
            )
        finally:
            stop.set()
            with contextlib.suppress(Exception):
                await load
        assert leader.fleet.stats["scale_out"] == 1
        recorder.flush()
        kinds = {e["kind"] for e in recorder.list_incidents()}
        assert "fleet:scale_out" in kinds
        # the probe generation actually served on the replica
        assert any(
            c.get("prompt") == leader.fleet.config.probe_prompt
            for c in standby.local_services["fake"].calls
        ), "replica flipped eligible without serving the warm-up probe"


@pytest.mark.async_timeout(180)
async def test_provision_probe_failure_rolls_back_to_standby():
    recorder = get_recorder()
    recorder.clear()
    async with _fleet(controllers=1, actives=0, standbys=1) as (
        nodes, ctrls, _a, stands,
    ):
        leader = await _settle_leader(ctrls)
        standby = stands[0]
        chaos = ChaosController([leader])
        chaos.fail_probe(leader, fails=1)
        try:
            out = await leader.fleet.override("scale_out")
            assert out["ok"], out
            assert await _settle(
                lambda: leader.fleet._action is None, timeout=30
            )
        finally:
            chaos.restore()
        assert standby.fleet_state == "standby", (
            "a replica that failed its probe must return to standby"
        )
        assert leader.fleet.stats["scale_out"] == 0
        assert leader.fleet.stats["provision_failed"] == 1
        recorder.flush()
        kinds = {e["kind"] for e in recorder.list_incidents()}
        assert "fleet:provision_failed" in kinds
        # stats pin that NO scale-out completed this test (disk bundles
        # persist across tests, so the negative is asserted off stats)


@pytest.mark.async_timeout(180)
async def test_headroom_scale_in_drains_worst_to_standby():
    recorder = get_recorder()
    recorder.clear()
    async with _fleet(
        controllers=1, actives=2,
        cfg=_cfg(min_replicas=2, in_sustain_ticks=3),
        stream_delay=0.02,
    ) as (nodes, ctrls, acts, _s):
        leader = await _settle_leader(ctrls)
        # the worst-node pick weighs per-peer RTT, so either active may
        # be chosen — pin the INVARIANTS, not the victim: in-flight
        # generations on BOTH candidates must complete untouched (zero
        # dropped generations, whichever one drains)
        streams = [
            asyncio.create_task(a.request_generation(
                a.peer_id, "inflight", model=MODEL,
                max_new_tokens=64, stream=True, on_chunk=lambda _t: None,
            ))
            for a in acts
        ]
        assert await _settle(
            lambda: any(a.fleet_state == "standby" for a in acts),
            timeout=60,
        ), f"journal: {list(leader.fleet.decisions)[-5:]}"
        drained = next(a for a in acts if a.fleet_state == "standby")
        survivor = next(a for a in acts if a is not drained)
        assert survivor.fleet_state is None  # exactly one scaled in
        assert drained.draining is False, (
            "scale-in left the node draining instead of standby"
        )
        for result in [await s for s in streams]:
            assert result["text"] == REPLY
        assert leader.fleet.stats["scale_in"] == 1
        # at min_replicas now: the loop must hold, not flap
        agg = leader.fleet.status()["aggregates"]
        assert agg.get("eligible") == 2
        recorder.flush()
        kinds = {e["kind"] for e in recorder.list_incidents()}
        assert "fleet:scale_in" in kinds


@pytest.mark.async_timeout(180)
async def test_leader_killed_mid_drain_successor_adopts_orphan():
    """THE chaos acceptance walk: the leader dies while its scale-in
    drain is in flight (the target still has live rows). The successor
    takes over the lapsed lease, finds the orphaned draining peer in the
    digests, adopts the drain to completion — and the in-flight
    generation on the target completes. Nothing is stranded, nothing is
    dropped."""
    recorder = get_recorder()
    recorder.clear()
    rows = get_registry().gauge(
        "engine.active_rows", "live engine batch rows"
    )
    async with _fleet(
        controllers=2, actives=2, cfg=_cfg(min_replicas=1, action_timeout_s=30.0),
        stream_delay=0.05,
    ) as (nodes, ctrls, acts, _s):
        leader = await _settle_leader(ctrls)
        successor = next(c for c in ctrls if c is not leader)
        target = acts[0]
        try:
            # live rows pin the drain in its awaiting-quiesce phase
            # (loopback nodes share one registry, so every digest shows
            # them — which is exactly what holds _await_drained open)
            rows.set(2.0)
            chunks: list[str] = []
            stream = asyncio.create_task(target.request_generation(
                target.peer_id, "inflight", model=MODEL,
                max_new_tokens=64, stream=True, on_chunk=chunks.append,
            ))
            out = await leader.fleet.override(
                "scale_in", target=target.peer_id
            )
            assert out["ok"], out
            assert await _settle(lambda: target.draining, timeout=10)
            assert leader.fleet._action is not None
            await hard_kill(leader)  # mid-drain, action in flight
            assert await _settle(
                lambda: successor.fleet.is_leader, timeout=15
            )
            # the successor adopts the orphaned drain (fleet is idle —
            # no capacity pressure, so adoption, not rollback)
            assert await _settle(
                lambda: successor.fleet._action is not None
                or target.fleet_state == "standby",
                timeout=15,
            )
            result = await stream  # zero dropped generations
            assert result["text"] == REPLY
            rows.clear()  # the live work finished; drain can quiesce
            assert await _settle(
                lambda: target.fleet_state == "standby"
                and not target.draining,
                timeout=30,
            ), "orphaned drain was neither completed nor rolled back"
        finally:
            rows.clear()
        assert successor.fleet.stats["adopted"] >= 1
        recorder.flush()
        kinds = {e["kind"] for e in recorder.list_incidents()}
        assert "fleet:takeover" in kinds
        assert "fleet:drain_adopted" in kinds
        assert "fleet:scale_in" in kinds


@pytest.mark.async_timeout(180)
async def test_orphaned_drain_rolled_back_when_fleet_burning():
    """The other adoption branch: the fleet is burning, so the orphaned
    drain's capacity is NEEDED — the new leader rolls it back (undrain)
    instead of completing the scale-in."""
    recorder = get_recorder()
    recorder.clear()
    async with _fleet(
        controllers=1, actives=1, slow_slo=True, exec_delay=0.05,
    ) as (nodes, ctrls, acts, _s):
        leader = await _settle_leader(ctrls)
        target = acts[0]
        stop = asyncio.Event()
        load = _drive_load(leader, stop)
        try:
            # wait until the leader's own view says the fleet burns
            assert await _settle(
                lambda: (leader.fleet._last_agg or {}).get("burning", 0) > 0,
                timeout=30,
            )
            # a dead predecessor's FLEET drain left this node draining
            # (an operator drain would be left alone — separate test)
            target.draining = True
            target.drain_source = "fleet"
            await target.gossip_telemetry()
            assert await _settle(lambda: not target.draining, timeout=30), (
                "burning fleet never rolled the orphaned drain back"
            )
        finally:
            stop.set()
            with contextlib.suppress(Exception):
                await load
        assert target.fleet_state is None  # eligible again, not standby
        assert leader.fleet.stats["rolled_back"] >= 1
        recorder.flush()
        kinds = {e["kind"] for e in recorder.list_incidents()}
        assert "fleet:drain_rollback" in kinds


@pytest.mark.async_timeout(120)
async def test_operator_drain_is_never_reconciled_by_the_fleet():
    """A deliberate POST /admin/drain (drain_source="operator") is not
    the controller's state to fix: even a burning fleet must not undrain
    a node the operator is about to kill, and an idle one must not
    convert it to standby."""
    async with _fleet(
        controllers=1, actives=1, slow_slo=True, exec_delay=0.05,
    ) as (nodes, ctrls, acts, _s):
        leader = await _settle_leader(ctrls)
        target = acts[0]
        stop = asyncio.Event()
        load = _drive_load(leader, stop)
        try:
            assert await _settle(
                lambda: (leader.fleet._last_agg or {}).get("burning", 0) > 0,
                timeout=30,
            )
            await target.begin_drain(wait=False)  # the operator's drain
            assert target.drain_source == "operator"
            await target.gossip_telemetry()
            # give the (burning) leader several ticks to take the bait
            await asyncio.sleep(1.0)
            assert target.draining is True, (
                "the controller undrained an operator's deliberate drain"
            )
            assert target.fleet_state is None
            assert leader.fleet.stats["rolled_back"] == 0
            assert leader.fleet.stats["adopted"] == 0
        finally:
            stop.set()
            with contextlib.suppress(Exception):
                await load


@pytest.mark.async_timeout(120)
async def test_dead_replica_below_min_is_repaired_from_standby():
    """min_replicas is a floor to RESTORE, not just a scale-in bound: a
    crashed replica's digest goes stale and vanishes — it reports no
    burn, so only the repair path can activate the warm standby."""
    async with _fleet(
        controllers=1, actives=1, standbys=1,
    ) as (nodes, ctrls, acts, stands):
        leader = await _settle_leader(ctrls)
        standby = stands[0]
        assert await _settle(
            lambda: (leader.fleet._last_agg or {}).get("eligible") == 2,
            timeout=10,
        )
        # the floor goes up only once the fleet it guards stands whole:
        # _fleet assembles the nodes around a controller that already
        # ticks, and a leader that reigns before the active's first digest
        # arrives (a starved worker) would "repair" a replica that is only
        # late to report — the standby gone before the kill, eligible 3
        assert standby.fleet_state == "standby"
        leader.fleet.config = _cfg(min_replicas=2)  # = controller + active
        await hard_kill(acts[0])  # no drain flag, no burn — just gone
        assert await _settle(
            lambda: standby.fleet_state is None, timeout=30
        ), (
            f"standby never activated after the replica died; journal: "
            f"{list(leader.fleet.decisions)[-5:]}"
        )
        # the standby flips (and gossips) BEFORE it acks, and the leader
        # books the scale-out only on that ack
        assert await _settle(
            lambda: leader.fleet.stats["scale_out"] == 1, timeout=10
        ), leader.fleet.stats


@pytest.mark.async_timeout(120)
async def test_orphaned_warming_replica_is_reprobed_or_returned():
    """A provision that died between activate and the probe leaves a
    warming node: the leader's orphan scan re-probes it to eligibility
    (never leaves it invisible capacity)."""
    async with _fleet(controllers=1, actives=1) as (nodes, ctrls, acts, _s):
        leader = await _settle_leader(ctrls)
        orphan = acts[0]
        orphan.fleet_state = "warming"  # a dead controller's half-provision
        await orphan.gossip_telemetry()
        assert await _settle(
            lambda: orphan.fleet_state is None, timeout=30
        ), "orphaned warming replica was never re-probed to a terminal state"
        assert leader.fleet.stats["adopted"] >= 1
        # the re-probe really served
        assert any(
            c.get("prompt") == leader.fleet.config.probe_prompt
            for c in orphan.local_services["fake"].calls
        )


@pytest.mark.async_timeout(120)
async def test_fleet_endpoint_and_override():
    from aiohttp.test_utils import TestClient, TestServer

    from bee2bee_tpu.api import build_app

    async with _fleet(controllers=1, actives=1) as (nodes, ctrls, acts, _s):
        leader = await _settle_leader(ctrls)
        follower = acts[0]
        client = TestClient(TestServer(build_app(leader)))
        fclient = TestClient(TestServer(build_app(follower)))
        await client.start_server()
        await fclient.start_server()
        try:
            r = await client.get("/fleet")
            assert r.status == 200
            st = await r.json()
            assert st["is_leader"] is True
            assert st["lease"]["holder"] == leader.peer_id
            assert isinstance(st["decisions"], list)
            assert st["config"]["model"] == MODEL

            r = await client.post("/fleet/override", json={})
            assert r.status == 400
            r = await client.post(
                "/fleet/override", json={"action": "pause"}
            )
            assert r.status == 200 and leader.fleet.paused
            assert await _settle(
                lambda: any(
                    d["decision"] == "paused"
                    for d in leader.fleet.decisions
                ),
                timeout=10,
            )
            r = await client.post(
                "/fleet/override", json={"action": "resume"}
            )
            assert r.status == 200 and not leader.fleet.paused
            # scale overrides only run on the leader — 409 points at it
            r = await fclient.post(
                "/fleet/override", json={"action": "scale_in"}
            )
            assert r.status == 409
            body = await r.json()
            assert body["error"] == "not_leader"
            assert body["leader"] == leader.peer_id
            # no standby in this fleet: a forced scale_out is a typed 400
            r = await client.post(
                "/fleet/override", json={"action": "scale_out"}
            )
            assert r.status == 400
            assert "standby" in (await r.json())["error"]
        finally:
            await client.close()
            await fclient.close()


@pytest.mark.async_timeout(120)
async def test_mesh_health_serves_controller_aggregates():
    from aiohttp.test_utils import TestClient, TestServer

    from bee2bee_tpu.api import build_app

    async with _fleet(controllers=1, actives=1, standbys=1) as (
        nodes, ctrls, acts, stands,
    ):
        await _settle_leader(ctrls)
        client = TestClient(TestServer(build_app(acts[0])))
        await client.start_server()
        try:
            view = await (await client.get("/mesh/health")).json()
            fleet = view["aggregate"]["fleet"]
            assert stands[0].peer_id in fleet["standby"]
            assert fleet["nodes"] == 3
        finally:
            await client.close()


def test_controller_aggregates_pure_units():
    # bucketing: draining/standby/warming never count toward headroom,
    # a non-serving digest is "other" when a serving set is given
    digests = {
        "a": {"slo": {"o": {"status": "burning", "burn_fast": 12.0}},
              "gauge": {"engine.batch_fill": 0.8, "engine.active_rows": 3,
                        "engine.paged_blocks_total": 100,
                        "engine.paged_blocks_free": 10}},
        "b": {"gauge": {"engine.batch_fill": 0.2}},
        "c": {"draining": True, "gauge": {"engine.batch_fill": 0.0}},
        "d": {"fleet_state": "standby"},
        "e": {"fleet_state": "warming"},
        "f": {},  # gossiping client, not a replica
    }
    agg = controller_aggregates(
        digests, serving={"a", "b", "c", "d", "e"}
    )
    assert agg["nodes"] == 6
    assert agg["eligible"] == 2 and agg["eligible_ids"] == ["a", "b"]
    assert agg["draining"] == ["c"] and agg["standby"] == ["d"]
    assert agg["warming"] == ["e"] and agg["other"] == ["f"]
    assert agg["burning"] == 1 and agg["burning_frac"] == 0.5
    assert agg["burn_fast_max"] == 12.0
    assert agg["fill_mean"] == pytest.approx(0.5)
    assert agg["pool_free_min"] == pytest.approx(0.1)
    assert agg["active_rows_total"] == 3.0
    # empty eligible set: every rate degrades to zero, not a crash
    empty = controller_aggregates({"c": {"draining": True}})
    assert empty["eligible"] == 0 and empty["burning_frac"] == 0.0
    assert empty["pool_free_min"] is None
