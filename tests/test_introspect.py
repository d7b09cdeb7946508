"""Engine economics plane tests (ISSUE 15): the retrace sentinel and its
warm-up contract, the FLOPs model, the goodput/MFU meter, the HBM ledger
+ pool forecast (and the admission shed it feeds), the digest /
/mesh/health ride, the /debug/profile round trip, and the benchdiff
regression gate — the acceptance walk plus the unit contracts under it.
"""

from __future__ import annotations

import importlib.util
import io
import json
import threading
import time
import zipfile
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from bee2bee_tpu.api import build_app
from bee2bee_tpu.engine import EngineConfig, InferenceEngine
from bee2bee_tpu.engine import introspect as intro_mod
from bee2bee_tpu.engine.introspect import (
    DeviceProfiler,
    FlopsModel,
    GoodputMeter,
    HbmLedger,
    PoolForecast,
    ProfileInProgress,
    RetraceSentinel,
    peak_flops_per_device,
)
from bee2bee_tpu.health import FlightRecorder, build_digest, fleet_view, render_fleet_prom
from bee2bee_tpu.meshnet.node import P2PNode
from bee2bee_tpu.metrics import get_registry
from bee2bee_tpu.models import get_config
from bee2bee_tpu.models.core import init_params, matmul_params_per_token
from bee2bee_tpu.services.tpu import TPUService

ECFG = dict(
    max_seq_len=64, prefill_buckets=(16,), dtype="float32",
    cache_dtype="float32", decode_chunk=4,
)


def _engine(**over):
    return InferenceEngine(
        "tiny-llama", engine_config=EngineConfig(**{**ECFG, **over})
    )


# ------------------------------------------------------- retrace sentinel


def test_sentinel_warmup_and_declared_growth_fire_nothing(tmp_path):
    rec = FlightRecorder(incident_dir=tmp_path)
    s = RetraceSentinel(recorder=rec)
    fn = s.watch(
        "unit_root",
        jax.jit(lambda x: x * 2),
        key_fn=lambda x: (int(x.shape[0]),),
        allowed=lambda key: key[0] in (4, 8),
    )
    fn(jnp.ones((4,)))          # boot warm-up
    fn(jnp.ones((4,)))          # cache hit: no trace at all
    fn(jnp.ones((8,)))          # LATE declared bucket growth
    snap = s.snapshot()["unit_root"]
    assert snap["traces"] == 2 and snap["storms"] == 0
    assert not s.storming()
    rec.flush()
    assert rec.list_incidents() == []


def test_sentinel_undeclared_key_storms_immediately(tmp_path):
    rec = FlightRecorder(incident_dir=tmp_path)
    s = RetraceSentinel(recorder=rec)
    fn = s.watch(
        "unit_root",
        jax.jit(lambda x: x + 1),
        key_fn=lambda x: (int(x.shape[0]),),
        allowed=lambda key: key[0] == 4,
    )
    fn(jnp.ones((4,)))
    fn(jnp.ones((7,)))          # UNDECLARED shape in steady state
    snap = s.snapshot()["unit_root"]
    assert snap["storms"] == 1 and s.storming()
    rec.flush()
    incs = rec.list_incidents()
    assert [i["kind"] for i in incs] == ["engine:retrace_storm"]
    bundle = rec.load_incident(incs[0]["id"])
    assert bundle["extra"]["root"] == "unit_root"
    assert "(7,)" in bundle["extra"]["key"]


def test_sentinel_counts_and_logs_a_key_function_that_raises(tmp_path, caplog):
    """A key function that raises leaves its root UN-KEYED (counted, never
    classified): each such compile is counted under
    ``engine.compile_key_errors{root}`` and the first of a root is logged;
    the call itself never sees the error."""
    from bee2bee_tpu.engine.introspect import _C_KEY_ERRORS

    def errors():
        return sum(s["value"] for s in _C_KEY_ERRORS.snapshot()["series"]
                   if s["labels"] == {"root": "unit_keyless"})

    def key_fn(x):
        raise TypeError("no such argument")

    s = RetraceSentinel(recorder=FlightRecorder(incident_dir=tmp_path))
    fn = s.watch("unit_keyless", jax.jit(lambda x: x * 3), key_fn=key_fn)
    before = errors()
    with caplog.at_level("WARNING", logger="bee2bee_tpu.introspect"):
        assert float(fn(jnp.ones((2,)))[0]) == 3.0
        fn(jnp.ones((3,)))
    assert errors() - before == 2
    said = [r for r in caplog.records if "key function of root=unit_keyless" in r.getMessage()]
    assert len(said) == 1 and said[0].exc_info
    snap = s.snapshot()["unit_keyless"]
    assert snap["traces"] == 2 and snap["storms"] == 0


def test_sentinel_repeat_key_storms_only_past_threshold(tmp_path):
    """A single recompile of a seen key (weak-type flip, clear_caches) is
    noise; a per-step retrace is the storm. Constant key + changing
    shapes = every call a fresh trace of the SAME key."""
    rec = FlightRecorder(incident_dir=tmp_path)
    s = RetraceSentinel(recorder=rec, storm_window_s=60.0, storm_repeats=3)
    fn = s.watch("unit_root", jax.jit(lambda x: x - 1), key_fn=lambda x: ())
    fn(jnp.ones((1,)))                      # first-seen (): warm-up
    fn(jnp.ones((2,)))                      # repeat 1
    fn(jnp.ones((3,)))                      # repeat 2: still quiet
    assert s.snapshot()["unit_root"]["storms"] == 0
    fn(jnp.ones((4,)))                      # repeat 3: storm
    assert s.snapshot()["unit_root"]["storms"] == 1
    rec.flush()
    assert [i["kind"] for i in rec.list_incidents()] == ["engine:retrace_storm"]


def test_sentinel_distinct_key_repeats_do_not_storm(tmp_path):
    """A cache-flush re-warm recompiles many SEEN keys once each — that
    must not pool into one storm; only the same key storming is the
    per-step-retrace signal. Driven by a fake jit whose cache size we
    control directly (every call books as a fresh trace)."""

    class FakeJit:
        def __init__(self):
            self.n = 0

        def __call__(self, key):
            self.n += 1
            return key

        def _cache_size(self):
            return self.n

    rec = FlightRecorder(incident_dir=tmp_path)
    s = RetraceSentinel(recorder=rec, storm_window_s=60.0, storm_repeats=3)
    fn = s.watch("unit_root", FakeJit(), key_fn=lambda key: key)
    for key in ("a", "b", "c"):            # first-seen: warm-up
        fn(key)
    for key in ("a", "b", "c"):            # one repeat each: a re-warm
        fn(key)
    assert s.snapshot()["unit_root"]["storms"] == 0
    fn("a")                                 # "a" repeats 2nd...
    fn("a")                                 # ...3rd: NOW it storms
    assert s.snapshot()["unit_root"]["storms"] == 1


def test_sentinel_counts_overlapping_compiles(tmp_path):
    """Two concurrent first compiles through ONE root (StageRunner
    allows max_concurrent_forwards > 1) must BOTH count and classify —
    each call compares against its own pre-dispatch baseline, not a
    shared last-size."""

    class SlowJit:
        def __init__(self):
            self.n = 0
            self.lock = threading.Lock()

        def __call__(self, key):
            time.sleep(0.05)  # overlap the two "compiles"
            with self.lock:
                self.n += 1

        def _cache_size(self):
            with self.lock:
                return self.n

    s = RetraceSentinel(recorder=FlightRecorder(incident_dir=tmp_path))
    fn = s.watch("unit_root", SlowJit(), key_fn=lambda key: key)
    threads = [threading.Thread(target=fn, args=(k,)) for k in ("x", "y")]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10.0)
    assert s.snapshot()["unit_root"]["traces"] == 2


def test_declared_batch_ladder_covers_non_pow2_shrink():
    """max_batch=6: the scheduler's shrink ladder reaches 3 (6 -> 3 ->
    1) — every rung must be declared warm-up, or a routine batch shrink
    fires a false retrace-storm incident."""
    eng = _engine(max_batch=6)
    try:
        assert {1, 2, 3, 4, 6} <= set(eng._declared_batch_sizes)
    finally:
        eng.close()


def test_engine_warmup_is_quiet_and_counts_roots(tmp_path):
    """A full generation's boot compiles — prefill bucket, decode ladder,
    CoW — are all declared warm-up: counted, never stormed."""
    eng = _engine()
    eng.introspect.sentinel._recorder = FlightRecorder(incident_dir=tmp_path)
    try:
        r = eng.generate("economics warm-up", max_new_tokens=4)
        assert r.new_tokens > 0
        snap = eng.introspect.sentinel.snapshot()
        assert snap["prefill"]["traces"] >= 1
        assert snap["decode"]["traces"] >= 1
        assert all(s["storms"] == 0 for s in snap.values()), snap
        assert not eng.introspect.sentinel.storming()
        rec = eng.introspect.sentinel._recorder
        rec.flush()
        assert rec.list_incidents() == []
    finally:
        eng.close()


def test_engine_seeded_steady_state_retrace_fires_typed_incident(tmp_path):
    """THE acceptance walk: force an undeclared prefill width through the
    engine's registered prefill root (the scheduler only ever emits the
    declared bucket widths — this simulates the bug class where a code
    change slips an unbucketed shape into the hot path)."""
    eng = _engine()
    rec = FlightRecorder(incident_dir=tmp_path)
    eng.introspect.sentinel._recorder = rec
    try:
        eng.generate("seed the caches", max_new_tokens=4)  # warm-up
        sch = eng.scheduler
        # width 32 is NOT in the declared prefill space ({16, 64} for
        # this config) but is block-aligned, so the trace compiles fine
        tokens = np.zeros((1, 32), np.int32)
        tokens[0, :4] = [1, 2, 3, 4]
        tbl = np.ascontiguousarray(sch.cache.tables[0:1, : eng.blocks_per_row])
        # write_ceil=0 nulls every KV write: the call is a pure compile
        # probe, no pool block is touched
        sch.cache.pool, _ = eng._prefill(
            eng.params, tokens, sch.cache.pool,
            np.asarray([4], np.int32), np.int32(0), tbl,
            np.int32(0), np.int32(0),
        )
        snap = eng.introspect.sentinel.snapshot()
        assert snap["prefill"]["storms"] == 1
        assert eng.introspect.sentinel.storming()
        rec.flush()
        incs = rec.list_incidents()
        assert [i["kind"] for i in incs] == ["engine:retrace_storm"]
        bundle = rec.load_incident(incs[0]["id"])
        assert bundle["extra"]["root"] == "prefill"
        assert "UNDECLARED" in bundle["detail"]
        # the storm also rides the counter the digest folds in
        storms = get_registry().get("engine.retrace_storms")
        assert storms.value(root="prefill") >= 1
    finally:
        eng.close()


# ------------------------------------------------------------ FLOPs model


def test_matmul_params_per_token_matches_real_param_tree():
    """The FLOPs model's 2·N term counts exactly the matmul weights the
    forward streams: pinned against the REAL init_params pytree (attn +
    mlp matrices + the tied lm-head logits matmul)."""
    cfg = get_config("tiny-llama")
    params = init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    attn, mlp = params["layers"]["attn"], params["layers"]["mlp"]
    counted = sum(attn[k].size for k in ("wq", "wk", "wv", "wo"))
    counted += sum(v.size for v in mlp.values())
    counted += cfg.vocab_size * cfg.d_model  # tied head: logits matmul
    assert matmul_params_per_token(cfg) == counted


def test_flops_model_scales_with_context():
    cfg = get_config("tiny-llama")
    fm = FlopsModel(cfg)
    base = fm.flops(1.0, 0.0)
    assert base == 2.0 * matmul_params_per_token(cfg)
    attn_per_ctx = 4.0 * cfg.n_layers * cfg.n_heads * (
        cfg.d_model // cfg.n_heads
    )
    assert fm.flops(1.0, 100.0) == pytest.approx(base + 100 * attn_per_ctx)
    assert fm.flops(3.0, 10.0) == pytest.approx(3 * fm.flops(1.0, 10.0))


def test_peak_flops_env_override_and_tpu_table(monkeypatch):
    assert peak_flops_per_device("tpu", "TPU v4") == pytest.approx(275e12)
    assert peak_flops_per_device("tpu", "TPU v5e") == pytest.approx(197e12)
    assert peak_flops_per_device("cpu") > 0
    monkeypatch.setenv("BEE2BEE_PEAK_FLOPS", "123e9")
    assert peak_flops_per_device("cpu") == pytest.approx(123e9)
    monkeypatch.setenv("BEE2BEE_PEAK_FLOPS", "not-a-number")
    assert peak_flops_per_device("tpu", "TPU v3") == pytest.approx(123e12)


def test_peak_flops_table_is_keyed_by_jax_device_kind_and_unknown_is_an_error(
    monkeypatch,
):
    """The table matches `device_kind` AS JAX REPORTS IT (a v5e chip says
    "TPU v5 lite"), and an accelerator it lacks raises — a utilization
    over a guessed peak is a wrong number with a real device's name on
    it. The CPU keeps its nominal placeholder (proxy MFU only)."""
    monkeypatch.delenv("BEE2BEE_PEAK_FLOPS", raising=False)
    assert peak_flops_per_device("tpu", "TPU v5 lite") == pytest.approx(197e12)
    assert peak_flops_per_device("tpu", "TPU v6 lite") == pytest.approx(918e12)
    assert peak_flops_per_device("cpu", "cpu") == pytest.approx(1e11)
    for platform, kind in (("tpu", "TPU v9"), ("tpu", ""), ("gpu", "H100")):
        with pytest.raises(ValueError, match="no peak FLOP/s known"):
            peak_flops_per_device(platform, kind)
    # the env override is the escape hatch for a part the table lacks
    monkeypatch.setenv("BEE2BEE_PEAK_FLOPS", "1e15")
    assert peak_flops_per_device("gpu", "H100") == pytest.approx(1e15)


# ---------------------------------------------------------- goodput meter


def test_goodput_meter_fraction_and_mfu():
    cfg = get_config("tiny-llama")
    meter = GoodputMeter(FlopsModel(cfg), peak_flops=1e9, window_s=60.0)
    meter.record_dispatch(100.0, 10.0, scheduled=100)
    meter.note_useful(40)
    time.sleep(0.01)
    snap = meter.refresh()
    assert snap["scheduled_tokens_total"] == 100
    assert snap["useful_tokens_total"] == 40
    # rates share one dt, so the fraction is exact
    assert snap["goodput_fraction"] == pytest.approx(0.4, rel=1e-3)
    assert snap["mfu"] > 0
    assert snap["goodput_tokens_per_s"] > 0


def test_goodput_meter_clears_when_idle():
    meter = GoodputMeter(None, peak_flops=1.0, window_s=0.05)
    meter.record_dispatch(10.0, 0.0, scheduled=10)
    meter.refresh()
    reg = get_registry()
    assert reg.get("engine.mfu").series()
    time.sleep(0.15)  # the busy burst ages out of the window
    snap = meter.refresh()
    assert "mfu" not in snap  # totals only — no rates reported
    assert not reg.get("engine.mfu").series()
    assert not reg.get("engine.goodput_tokens_per_s").series()


# ------------------------------------------------- HBM ledger + forecast


def test_hbm_ledger_components_sum_and_unregister_clears(monkeypatch):
    monkeypatch.delenv("BEE2BEE_HBM_BYTES", raising=False)

    class _Dev:  # a stats-less device (CPU contract)
        id = 0

        def memory_stats(self):
            return None

    ledger = HbmLedger(devices=[_Dev()])
    w = np.zeros((128,), np.float32)          # 512 B
    kv = {"k": np.zeros((64,), np.int8)}      # 64 B
    ledger.register("weights", lambda: w)
    ledger.register("kv_pool", lambda: kv)
    snap = ledger.snapshot()
    assert snap["components"] == {"weights": 512, "kv_pool": 64}
    assert snap["accounted_bytes"] == 576
    assert "headroom_frac" not in snap        # no stats, no budget
    g = get_registry().get("engine.hbm_bytes")
    assert g.value(component="weights") == 512

    monkeypatch.setenv("BEE2BEE_HBM_BYTES", "1024")
    snap = ledger.snapshot()
    assert snap["bytes_limit"] == 1024
    assert snap["headroom_frac"] == pytest.approx(1 - 576 / 1024, abs=1e-3)

    ledger.unregister("kv_pool")
    snap = ledger.snapshot()
    assert "kv_pool" not in snap["components"]
    assert g.value(component="kv_pool") == 0  # cleared series reads 0


def test_hbm_ledger_device_stats_add_workspace_residual():
    class _Dev:
        id = 0

        def memory_stats(self):
            return {"bytes_in_use": 1000, "bytes_limit": 4000}

    ledger = HbmLedger(devices=[_Dev()])
    ledger.register("weights", lambda: np.zeros((100,), np.int8))  # 100 B
    snap = ledger.snapshot()
    assert snap["bytes_in_use"] == 1000
    assert snap["components"]["workspace_other"] == 900
    assert snap["headroom_frac"] == pytest.approx(0.75)


def test_hbm_ledger_reports_each_device_separately():
    """Tensor-parallel serving must spread, not pile onto device 0: the
    snapshot books every component's shard bytes to the device holding
    them, beside that device's own memory stats."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from bee2bee_tpu.parallel import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(model=4))
    devices = list(mesh.devices.flat)
    w = jax.device_put(
        np.zeros((8, 64), np.float32), NamedSharding(mesh, P(None, "model"))
    )  # 2048 B, a quarter per device
    rep = jax.device_put(np.zeros((16,), np.float32), NamedSharding(mesh, P()))
    ledger = HbmLedger(devices=devices)
    ledger.register("weights", lambda: {"w": w, "rep": rep})
    snap = ledger.snapshot()
    assert snap["components"]["weights"] == 2048 + 4 * 64
    assert [d["id"] for d in snap["devices"]] == sorted(d.id for d in devices)
    assert all(d["components"] == {"weights": 512 + 64} for d in snap["devices"])


def test_pool_forecast_eta_projection():
    f = PoolForecast(window_s=30.0)
    t = 1000.0
    f.feed(0, 100, now=t)
    f.feed(50, 50, now=t + 5.0)       # 10 blocks/s growth
    assert f.eta_s(now=t + 5.0) == pytest.approx(5.0)
    # shrinking pool: no exhaustion trend
    f2 = PoolForecast()
    f2.feed(50, 50, now=t)
    f2.feed(10, 90, now=t + 5.0)
    assert f2.eta_s(now=t + 5.0) is None
    # a burst inside 2 s cannot fabricate a trend
    f3 = PoolForecast()
    f3.feed(0, 100, now=t)
    f3.feed(90, 10, now=t + 0.5)
    assert f3.eta_s(now=t + 0.5) is None


async def test_admission_sheds_on_pool_exhaust_forecast():
    from bee2bee_tpu.router import AdmissionReject
    from bee2bee_tpu.router.admission import (
        KIND_POOL,
        AdmissionConfig,
        AdmissionController,
    )

    eta = {"v": None}
    ctrl = AdmissionController(
        AdmissionConfig(max_concurrent=1, pool_eta_shed_s=5.0),
        pool_eta=lambda: eta["v"],
    )
    (await ctrl.acquire("default")).release()   # no forecast: admits
    eta["v"] = 2.0
    (await ctrl.acquire("default")).release()   # slots free: admits
    held = await ctrl.acquire("default")
    with pytest.raises(AdmissionReject) as ei:
        await ctrl.acquire("default")           # all busy + dry-in-2s
    assert ei.value.kind == KIND_POOL and ei.value.status == 503
    held.release()
    eta["v"] = 60.0                             # far horizon: admits
    (await ctrl.acquire("default")).release()


# ------------------------------------------- digest + fleet aggregation


def test_engine_generation_rides_digest_and_info():
    eng = _engine()
    try:
        eng.generate("ride the digest", max_new_tokens=4)
        d = build_digest()  # the live path runs the digest providers
        intro = d.get("introspect")
        assert intro, f"digest missing introspect block: {d.keys()}"
        assert intro["compiles"]["prefill"]["traces"] >= 1
        assert intro.get("goodput_tokens_per_s", 0) > 0
        assert intro.get("mfu") is not None
        assert intro["storming"] is False
        intro_info = eng.info["introspect"]
        assert intro_info["compiles"]["decode"]["traces"] >= 1
        # scheduled >= useful by construction: the fraction honors 0..1
        assert 0.0 <= intro_info["goodput"]["goodput_fraction"] <= 1.0
    finally:
        eng.close()


def test_engine_close_clears_economics_gauges():
    """A closed engine must not serve its last busy MFU/HBM readings
    forever — node.py's incident gauge snapshot and the admission
    forecast shed read these gauges directly."""
    eng = _engine()
    eng.generate("then close", max_new_tokens=4)
    eng.introspect.refresh()
    reg = get_registry()
    assert reg.get("engine.hbm_bytes").series()
    eng.close()
    assert not reg.get("engine.mfu").series()
    assert not reg.get("engine.goodput_tokens_per_s").series()
    assert not reg.get("engine.hbm_bytes").series()
    assert not reg.get("engine.pool_exhaust_eta_s").series()
    # the ledger's source closures pin the KV pool + params — released
    assert not eng.introspect.ledger._sources


def test_fleet_view_aggregates_economics():
    from bee2bee_tpu.health import HealthStore

    store = HealthStore(ttl_s=60.0)
    store.update("peer-fast", {"introspect": {
        "mfu": 0.4, "goodput_tokens_per_s": 100.0,
        "hbm": {"headroom_frac": 0.5}, "storming": False,
    }})
    store.update("peer-squeezed", {"introspect": {
        "mfu": 0.2, "goodput_tokens_per_s": 50.0,
        "hbm": {"headroom_frac": 0.03}, "storming": True,
    }})
    view = fleet_view("me", {}, store)
    agg = view["aggregate"]
    assert agg["goodput_tokens_per_s_total"] == pytest.approx(150.0)
    assert agg["mfu_mean"] == pytest.approx(0.3)
    assert agg["hbm_headroom_frac_min"] == pytest.approx(0.03)
    assert agg["hbm_headroom_min_peer"] == "peer-squeezed"
    assert agg["retrace_storming_peers"] == ["peer-squeezed"]

    prom = render_fleet_prom(view)
    assert 'bee2bee_mesh_peer_mfu{peer="peer-fast"} 0.4' in prom
    assert 'bee2bee_mesh_peer_hbm_headroom_frac{peer="peer-squeezed"} 0.03' in prom
    assert 'bee2bee_mesh_peer_retrace_storming{peer="peer-squeezed"} 1' in prom
    assert 'bee2bee_mesh_peer_retrace_storming{peer="peer-fast"}' not in prom


def test_router_penalizes_squeezed_and_storming_peers():
    from bee2bee_tpu.router.policy import RouterPolicy, RouterWeights

    pol = RouterPolicy(RouterWeights())
    healthy = {"introspect": {"hbm": {"headroom_frac": 0.5},
                              "storming": False}}
    squeezed = {"introspect": {"hbm": {"headroom_frac": 0.0},
                               "storming": True}}

    def _score(digest):
        return pol.score({"local": True}, digest, rtt_ms=None,
                         max_price=0.0, prompt_hashes=[])

    s_healthy, b_healthy = _score(healthy)
    s_bad, b_bad = _score(squeezed)
    assert b_bad["hbm"] == pytest.approx(1.0)
    assert b_bad["storming"] is True
    assert s_bad > s_healthy  # penalty score: lower wins
    # no ledger reading = absent subsystem, not unknown pressure
    _, b_none = _score({"introspect": {}})
    assert b_none["hbm"] == 0.0 and b_none["storming"] is False


async def test_mesh_health_route_carries_fleet_goodput():
    node = P2PNode(host="127.0.0.1", port=0)
    await node.start()
    eng = _engine()
    client = None
    try:
        node.add_service(TPUService("tiny-llama", engine=eng))
        client = TestClient(TestServer(build_app(node)))
        await client.start_server()
        r = await client.post("/chat", json={
            "prompt": "fleet economics", "model": "tiny-llama",
            "max_new_tokens": 4, "temperature": 0.0,
        })
        assert r.status == 200
        body = await (await client.get("/mesh/health")).json()
        agg = body["aggregate"]
        assert agg["goodput_tokens_per_s_total"] > 0
        assert "mfu_mean" in agg
        me = body["peers"][node.peer_id]
        assert me["introspect"]["compiles"]["prefill"]["traces"] >= 1
    finally:
        if client is not None:
            await client.close()
        eng.close()
        await node.stop()


# -------------------------------------------------------- device profiler


def test_device_profiler_capture_and_listing(tmp_path):
    prof = DeviceProfiler(profile_dir=tmp_path)
    header = prof.capture(duration_s=0.05)
    assert header["id"].startswith("prof-")
    assert header["bytes"] > 0
    listing = prof.list_profiles()
    assert [p["id"] for p in listing] == [header["id"]]
    data = prof.load_profile(header["id"])
    zf = zipfile.ZipFile(io.BytesIO(data))
    assert zf.namelist(), "profile zip is empty"
    assert prof.load_profile("prof-nope") is None
    assert prof.active is None


def test_device_profiler_refuses_concurrent_capture(tmp_path):
    prof = DeviceProfiler(profile_dir=tmp_path)
    started = threading.Event()

    def workload():
        started.set()
        time.sleep(0.01)

    t = threading.Thread(
        target=prof.capture, kwargs={"duration_s": 0.5, "workload": workload}
    )
    t.start()
    try:
        assert started.wait(5.0)
        with pytest.raises(ProfileInProgress):
            prof.capture(duration_s=0.05)
    finally:
        t.join(10.0)
    prof.capture(duration_s=0.05)  # serial capture fine again


async def test_debug_profile_route_round_trip(tmp_path, monkeypatch):
    from bee2bee_tpu.router.tenants import TenantRegistry, parse_tenant_config

    monkeypatch.setattr(intro_mod, "_PROFILER", DeviceProfiler(tmp_path))
    node = P2PNode(host="127.0.0.1", port=0)
    node.tenants = TenantRegistry(
        parse_tenant_config({"acme": {"api_key": "tenant-key"}})
    )
    await node.start()
    client = TestClient(TestServer(build_app(node, api_key="sekrit")))
    await client.start_server()
    try:
        # ADMIN surface: no key, no capture (401 at the app middleware);
        # a TENANT key opens the door but not the profiler (typed 403 —
        # a device profile leaks whole-node execution detail)
        r = await client.post("/debug/profile", json={"duration_s": 0.05})
        assert r.status == 401
        r = await client.post(
            "/debug/profile", json={"duration_s": 0.05},
            headers={"X-API-KEY": "tenant-key"},
        )
        assert r.status == 403
        r = await client.post(
            "/debug/profile", json={"duration_s": 0.05},
            headers={"X-API-KEY": "sekrit"},
        )
        assert r.status == 200
        header = await r.json()
        assert header["id"].startswith("prof-")

        # the GET surface (listing + zip download) is admin-gated too:
        # a tenant key must not download whole-node device profiles
        r = await client.get("/debug/profile",
                             headers={"X-API-KEY": "tenant-key"})
        assert r.status == 403
        r = await client.get(f"/debug/profile?id={header['id']}",
                             headers={"X-API-KEY": "tenant-key"})
        assert r.status == 403

        key = {"X-API-KEY": "sekrit"}
        r = await client.get("/debug/profile", headers=key)
        body = await r.json()
        assert [p["id"] for p in body["profiles"]] == [header["id"]]
        assert body["active"] is None

        r = await client.get(f"/debug/profile?id={header['id']}",
                             headers=key)
        assert r.status == 200
        assert r.headers["Content-Type"] == "application/zip"
        zf = zipfile.ZipFile(io.BytesIO(await r.read()))
        assert zf.namelist()

        r = await client.get("/debug/profile?id=prof-unknown", headers=key)
        assert r.status == 404

        r = await client.post(
            "/debug/profile", json={"duration_s": "soon"},
            headers={"X-API-KEY": "sekrit"},
        )
        assert r.status == 400
        r = await client.post(
            "/debug/profile", json=[1, 2],  # valid JSON, not an object
            headers={"X-API-KEY": "sekrit"},
        )
        assert r.status == 400
    finally:
        await client.close()
        await node.stop()


def _traced_levels(monkeypatch) -> list[tuple[int, int]]:
    """(python_tracer_level, host_tracer_level) of every capture started."""
    seen: list[tuple[int, int]] = []
    start = jax.profiler.start_trace

    def starting(log_dir, *args, profiler_options=None, **kwargs):
        seen.append((profiler_options.python_tracer_level,
                     profiler_options.host_tracer_level))
        return start(log_dir, *args, profiler_options=profiler_options, **kwargs)

    monkeypatch.setattr(jax.profiler, "start_trace", starting)
    return seen


def test_a_capture_leaves_the_python_tracer_off_unless_asked(tmp_path, monkeypatch):
    """jax's default (python_tracer_level=1) makes every Python call an event
    and stretches the host phases it then times; the host tracer, which
    records the program's TraceAnnotations, keeps jax's default."""
    seen = _traced_levels(monkeypatch)
    default_host = jax.profiler.ProfileOptions().host_tracer_level
    prof = DeviceProfiler(profile_dir=tmp_path)
    prof.capture(duration_s=0.05)
    prof.capture(duration_s=0.05, python_tracer=True)
    assert seen == [(0, default_host), (1, default_host)] and default_host > 0


@pytest.mark.parametrize("body,level", [
    ({"duration_s": 0.05}, 0),  # what the benchmark posts
    ({"duration_s": 0.05, "python_tracer": True}, 1),
    ({"duration_s": 0.05, "python_tracer": "yes"}, 0),  # only the JSON true asks
    ({"duration_s": 0.05, "python_tracer": False}, 0),
], ids=["default", "asked", "not_a_bool", "refused"])
async def test_debug_profile_route_python_tracer_field(tmp_path, monkeypatch, body, level):
    monkeypatch.setattr(intro_mod, "_PROFILER", DeviceProfiler(tmp_path))
    seen = _traced_levels(monkeypatch)
    node = P2PNode(host="127.0.0.1", port=0)
    await node.start()
    client = TestClient(TestServer(build_app(node)))
    await client.start_server()
    try:
        r = await client.post("/debug/profile", json=body)
        assert r.status == 200 and (await r.json())["id"].startswith("prof-")
        assert [lv for lv, _ in seen] == [level]
    finally:
        await client.close()
        await node.stop()


async def test_debug_profile_route_concurrent_capture_409(tmp_path, monkeypatch):
    prof = DeviceProfiler(tmp_path)
    monkeypatch.setattr(intro_mod, "_PROFILER", prof)
    node = P2PNode(host="127.0.0.1", port=0)
    await node.start()
    client = TestClient(TestServer(build_app(node)))
    await client.start_server()
    try:
        with prof._lock:  # simulate an in-flight capture
            prof._active = {"id": "prof-busy", "started": time.time(),
                            "duration_s": 30.0}
        r = await client.post("/debug/profile", json={"duration_s": 0.05})
        assert r.status == 409
        body = await r.json()
        assert body["error_kind"] == "profile_in_progress"
    finally:
        await client.close()
        await node.stop()


# ------------------------------------------------------------- benchdiff


def _benchdiff():
    path = Path(__file__).resolve().parent.parent / "scripts" / "benchdiff.py"
    spec = importlib.util.spec_from_file_location("benchdiff", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _bench_art(tmp_path, name, value, tok, platform):
    obj = {
        "metric": "serve_tokens_per_sec_x", "value": value, "unit": "tok/s",
        "platform": platform, "schema_version": 2,
        "extras": {"rung": {"platform": platform, "tok_per_s": tok}},
    }
    p = tmp_path / name
    p.write_text(json.dumps(obj))
    return str(p)


def test_benchdiff_gates_regressions_and_platforms(tmp_path):
    bd = _benchdiff()
    base = _bench_art(tmp_path, "BENCH_a.json", 100.0, 50.0, "cpu")
    regressed = _bench_art(tmp_path, "BENCH_b.json", 100.0, 30.0, "cpu")
    ok = _bench_art(tmp_path, "BENCH_c.json", 101.0, 51.0, "cpu")
    tpu = _bench_art(tmp_path, "BENCH_d.json", 900.0, 700.0, "tpu")

    lines: list[str] = []
    assert bd.diff([base, regressed], out=lines.append) == 1
    assert any("REGRESSION" in l for l in lines)
    assert bd.diff([base, ok], out=lines.append) == 0
    # cross-platform comparison REFUSES (exit 2), loud about why
    lines.clear()
    assert bd.diff([base, tpu], out=lines.append) == 2
    assert any("REFUSING" in l for l in lines)
    assert bd.diff([base, tpu], allow_cross_platform=True,
                   out=lines.append) == 0
    # threshold is configurable: a 40% drop passes a 50% gate
    assert bd.diff([base, regressed], threshold=0.5, out=lines.append) == 0
    assert bd._self_check() == 0


def test_benchdiff_refuses_unknown_schema(tmp_path):
    bd = _benchdiff()
    base = _bench_art(tmp_path, "BENCH_a.json", 100.0, 50.0, "cpu")
    newer = json.loads(Path(base).read_text())
    newer["schema_version"] = 99
    p = tmp_path / "BENCH_z.json"
    p.write_text(json.dumps(newer))
    assert bd.diff([base, str(p)], out=lambda *_: None) == 2
