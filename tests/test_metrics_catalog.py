"""Golden full-surface /metrics scrape (ISSUE 15): the metric catalog in
docs/OBSERVABILITY.md IS a test fixture.

One node boots with every metric-bearing subsystem live — paged int8 KV,
speculative decode, the adapter pool, the fleet controller — serves one
generation, and scrapes its own /metrics. Then, in both directions:

- every scraped ``bee2bee_*`` family under a documented subsystem prefix
  must have a catalog row (an undocumented metric is drift), and
- every catalog row must be present in the scrape OR carry an entry in
  ``ALLOWED_ABSENT`` naming why this boot legitimately doesn't serve it
  (a documented-but-vanished metric is drift too).

The ALLOWED_ABSENT ledger is deliberate absence, not tolerance: each
entry states the condition under which the family appears, and the list
itself is checked against the catalog so it can't rot either.
"""

from __future__ import annotations

import re
from pathlib import Path

from aiohttp.test_utils import TestClient, TestServer

from bee2bee_tpu.api import build_app
from bee2bee_tpu.engine import EngineConfig, InferenceEngine
from bee2bee_tpu.meshnet.node import P2PNode
from bee2bee_tpu.services.tpu import TPUService

DOC = Path(__file__).resolve().parent.parent / "docs" / "OBSERVABILITY.md"

# catalog rows this boot legitimately does NOT serve, and why. Every key
# must exist in the catalog (pinned below) — retiring the metric means
# retiring this entry too.
ALLOWED_ABSENT = {
    # recurrent models only (falcon-h1, granite-4.0-h): this boot serves tiny-llama, whose
    # rows own K/V pages and nothing else (tests/test_falcon_h1.py reads them)
    "engine.state_rows": "no recurrent state: the boot's model has no mixer",
    "engine.state_bytes": "no recurrent state: the boot's model has no mixer",
    "engine.ssm_step_rows": "no recurrent state: the boot's model has no mixer",
    "engine.ssm_step_kernel_calls": "no recurrent state: the boot's model has no mixer",
    "engine.ssm_scan_tokens": "no recurrent state: the boot's model has no mixer",
    # sigmoid-routed expert models only (JoyAI-LLM-Flash: tests/test_joyai.py
    # reads them); tiny-llama has no expert layer
    "engine.moe_assignments": "no dropless expert layer in the boot's model",
    "engine.moe_layer_calls": "no dropless expert layer in the boot's model",
    "engine.moe_experts_hit": "no dropless expert layer in the boot's model",
    "engine.moe_expert_load_max": "no dropless expert layer in the boot's model",
    "engine.latent_tokens_read": "no latent attention in the boot's model",
    "engine.prefill_chunks": "no prompt walks more than one prefill window in this boot",
    # models with a sliding-window layer only (tests/test_smallthinker.py reads them)
    "engine.kv_tokens_held": "no layer of the boot's model reads behind a window",
    "engine.kv_tokens_behind_window": "no layer of the boot's model reads behind a window",
    # CPU test backend: device.memory_stats() is None and no
    # BEE2BEE_HBM_BYTES budget is set, so headroom cannot compute
    "engine.hbm_headroom_frac": "no device memory stats on CPU",
    # the forecast gauge exists only while the paged pool is GROWING
    # over its trailing window; one short generation settles flat
    "engine.pool_exhaust_eta_s": "pool not growing in this boot",
    # event-driven histograms with no driving event in this boot
    "mesh.migration_export_ms": "no live migration performed",
    "pipeline.stage_task_ms": "no pipeline stage traffic",
    # derived stage gauges clear when no stage traffic exists (the
    # empty-gauge contract docs/OBSERVABILITY.md pins)
    "pipeline.bubble_fraction": "no stage traffic: gauge clears",
    "pipeline.stage_busy_fraction": "no stage traffic: gauge clears",
    # fleet lease gauges are set by the controller tick loop — the
    # first election may not land inside this test's single scrape
    "fleet.leader": "controller tick cadence may not elect in time",
    "fleet.eligible_replicas": "leader-only gauge (see fleet.leader)",
    # set only while waiters actually queue at the front door
    "admission.queued": "no queued waiter at scrape time",
    # SLO gauges are written by the monitor-loop evaluation cadence,
    # which this short boot does not await
    "slo.burn_rate": "monitor loop not awaited",
    "slo.status": "monitor loop not awaited",
    "slo.bad_fraction": "monitor loop not awaited",
    # the per-tier acceptance gauge is published by the goodput meter's
    # refresh cadence, which this single scrape does not await
    "engine.spec_acceptance": "meter refresh not awaited",
    # draft-role counters live on a BEE2BEE_DISAGG=draft node; this boot
    # hosts the target engine, not the drafter program (meshnet/draft.py
    # is never imported, so the families don't even register)
    "mesh.draft_served": "not a draft-role node in this boot",
    "mesh.draft_errors": "not a draft-role node in this boot",
    # the observatory's ring gauge is set by its sampling loop, whose
    # 5 s cadence may not elapse inside this boot's single scrape (the
    # obs.samples/obs.anomalies counters render their 0 default)
    "obs.ring_points": "sampling cadence may not elapse in this boot",
}

# families the economics plane MUST light up after one generation —
# absence here is a wiring regression, not acceptable drift
REQUIRED_PRESENT = {
    "engine.compiles",
    "engine.compile_seconds",
    "engine.mfu",
    "engine.goodput_tokens_per_s",
    "engine.goodput_fraction",
    "engine.scheduled_tokens_per_s",
    "engine.hbm_bytes",
    "engine.tokens_generated",
    # ISSUE 41: one generation places a burst, dispatches a prefill program
    # and settles decode windows
    "engine.admit_seconds",
    "engine.admit_burst_requests",
    "engine.prefill_calls",
    "engine.prefill_rows",  # ISSUE 42: the rows of those programs
    "engine.prefill_tokens",
    "engine.loop_passes",  # ISSUE 46: passes of the stack those calls ran
    "engine.decode_slots",
    "engine.paged_blocks_in_use",
    "adapter.pool_resident",
    "gen.requests",
}

_ROW_RE = re.compile(r"^\|\s*(`[^|]+`)\s*\|\s*(counter|gauge|histogram)\s*\|")
_NAME_RE = re.compile(r"`([^`]+)`")
_BRACE_RE = re.compile(r"\{([^{}]+)\}")

# prometheus exposition line shapes (metrics.py render contract)
_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [^ ]+$"
)


def _expand_braces(name: str) -> list[str]:
    """`a.{b,c}_{d,e}` -> the 4-way product, recursively."""
    m = _BRACE_RE.search(name)
    if not m:
        return [name]
    out = []
    for alt in m.group(1).split(","):
        out.extend(
            _expand_braces(name[: m.start()] + alt.strip() + name[m.end():])
        )
    return out


def parse_catalog(text: str) -> dict[str, str]:
    """{metric_name: kind} from the '### Metric catalog' table."""
    section = text.split("### Metric catalog", 1)[1]
    section = section.split("###", 1)[0]
    out: dict[str, str] = {}
    for line in section.splitlines():
        m = _ROW_RE.match(line.strip())
        if not m:
            continue
        cell, kind = m.group(1), m.group(2)
        for quoted in _NAME_RE.findall(cell):
            for name in _expand_braces(quoted):
                out[name] = kind
    return out


def test_catalog_parses_and_covers_the_economics_plane():
    catalog = parse_catalog(DOC.read_text())
    assert len(catalog) > 50, f"catalog parse collapsed: {len(catalog)} rows"
    for name in REQUIRED_PRESENT | set(ALLOWED_ABSENT):
        assert name in catalog, (
            f"{name!r} is referenced by this test but missing from the "
            "docs/OBSERVABILITY.md catalog — add the row (or retire the "
            "reference)"
        )


_RENDER_SUFFIXES = ("_total", "_bucket", "_sum", "_count")


def _scraped_families(text: str) -> set[str]:
    """Raw metric families from an exposition, `bee2bee_` stripped.
    Render suffixes stay attached — a gauge legitimately named
    ``*_total`` (engine.paged_blocks_total) is indistinguishable from a
    rendered counter here, so matching strips lazily (`_folds`)."""
    fams = set()
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        assert _SAMPLE_RE.match(line), f"unparseable exposition line: {line!r}"
        name = line.split("{", 1)[0].split(" ", 1)[0]
        assert name.startswith("bee2bee_"), f"unprefixed family: {name!r}"
        fams.add(name[len("bee2bee_"):])
    return fams


def _folds(raw: str) -> set[str]:
    """The catalog names a raw scraped family could render from."""
    out = {raw}
    for suffix in _RENDER_SUFFIXES:
        if raw.endswith(suffix):
            out.add(raw[: -len(suffix)])
    return out


async def test_full_surface_scrape_matches_catalog():
    catalog = parse_catalog(DOC.read_text())
    documented = {n.replace(".", "_"): n for n in catalog}
    # subsystem prefixes the catalog owns: a scraped family under one of
    # these MUST be documented; anything else is foreign registry residue
    # from sibling tests sharing the process registry, not drift
    prefixes = {n.split(".")[0] for n in catalog if "." in n}

    node = P2PNode(host="127.0.0.1", port=0, fleet_controller=True)
    await node.start()
    eng = InferenceEngine(
        "tiny-llama",
        engine_config=EngineConfig(
            max_seq_len=64, prefill_buckets=(16,), dtype="float32",
            cache_dtype="int8", spec_tokens=2, max_adapters=2,
            decode_chunk=4,
        ),
    )
    client = None
    try:
        # light the adapter-pool gauges: one random adapter resident
        import jax

        from bee2bee_tpu.train.lora import LoraConfig, init_lora

        lcfg = LoraConfig()
        eng.adapter_pool.load(
            "catalog-adapter",
            init_lora(eng.model_cfg, lcfg, jax.random.key(7)),
            lcfg,
        )
        node.add_service(TPUService("tiny-llama", engine=eng))
        client = TestClient(TestServer(build_app(node)))
        await client.start_server()
        r = await client.post(
            "/chat",
            json={"prompt": "the mesh hums and the mesh hums again",
                  "model": "tiny-llama", "max_new_tokens": 8,
                  "temperature": 0.0},
        )
        assert r.status == 200, f"/chat returned {r.status}"
        # and one streamed: the time-to-first-byte histograms observe at
        # the first content frame's write
        r = await client.post(
            "/chat",
            json={"prompt": "the mesh hums", "model": "tiny-llama",
                  "max_new_tokens": 8, "temperature": 0.0, "stream": True},
        )
        assert r.status == 200 and '"done": true' in await r.text()
        scraped = _scraped_families(await (await client.get("/metrics")).text())
    finally:
        if client is not None:
            await client.close()
        eng.close()
        await node.stop()

    scraped_flat = {fold for raw in scraped for fold in _folds(raw)}

    undocumented = sorted(
        raw for raw in scraped
        if not (_folds(raw) & documented.keys())
        and raw.split("_")[0] in prefixes
    )
    assert not undocumented, (
        "scraped families missing a docs/OBSERVABILITY.md catalog row: "
        f"{undocumented}"
    )

    allowed_flat = {n.replace(".", "_") for n in ALLOWED_ABSENT}
    vanished = sorted(
        name for flat, name in documented.items()
        if flat not in scraped_flat and flat not in allowed_flat
    )
    assert not vanished, (
        "catalog rows neither scraped nor in ALLOWED_ABSENT "
        f"(documented-but-vanished drift): {vanished}"
    )

    missing = sorted(
        n for n in REQUIRED_PRESENT if n.replace(".", "_") not in scraped_flat
    )
    assert not missing, (
        f"economics-plane families absent after a generation: {missing}"
    )


def test_every_device_trace_scope_the_model_opens_is_documented():
    """``tracing.DEVICE_PARTS`` (+ ``DEVICE_WRAPPERS`` + ``DEVICE_NESTED``) IS the set of
    ``jax.named_scope``s that models/core.py, engine/engine.py and
    engine/sampling.py open, which the benchmark's scope readers book device
    time by: the sources open no other (the ``prog.*`` roots are
    ``tracing.prog_scope``'s) and none behind a condition on the model, and
    the document's table names each one."""
    import re

    from bee2bee_tpu.tracing import DEVICE_NESTED, DEVICE_PARTS, DEVICE_WRAPPERS

    pkg = DOC.parent.parent / "bee2bee_tpu"
    src = "".join((pkg / f).read_text() for f in (
        "models/core.py", "engine/engine.py", "engine/sampling.py"))
    opened = set(re.findall(r'named_scope\(\s*"([a-z_]+\.[a-z_]+)"', src))
    table = DEVICE_PARTS + DEVICE_WRAPPERS + DEVICE_NESTED
    assert len(table) == len(set(table))
    # ``ffn_part`` / ``join``: the residual add's scope is a NAME of the table
    # picked by the branch's kind, never a scope or none
    assert opened == set(table), sorted(opened ^ set(table))
    assert "nullcontext" not in src  # no scope that may open as nothing
    doc = DOC.read_text()
    assert not sorted(s for s in table if f"| `{s}` |" not in doc)
