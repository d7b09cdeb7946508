"""meshlint (bee2bee_tpu/analysis): the tier-1 ratchet gate + pass self-tests.

The gate test runs the analyzer over the installed package: any finding not
grandfathered by analysis/baseline.json fails tier-1 — that is the ratchet.
The self-tests prove each pass family actually catches its bug class on
small known-bad fixtures (so a silently-broken pass can't hide behind a
clean repo), and that seeding a typo'd sampling key into a real frame
literal is caught.
"""

from __future__ import annotations

from pathlib import Path

from bee2bee_tpu import protocol
from bee2bee_tpu.analysis import (
    analyze_paths,
    analyze_source,
    declared_key_universe,
    filter_baselined,
    load_baseline,
    rule_catalog,
)
from bee2bee_tpu.analysis.core import PACKAGE_ROOT
from bee2bee_tpu.analysis.schema import FRAME_SCHEMAS, TASK_SCHEMAS


def _rules(findings):
    return [f.rule for f in findings]


# ------------------------------------------------------------------ the gate


def test_package_is_clean_under_baseline():
    """THE tier-1 gate: no non-baselined finding anywhere in the package."""
    findings = analyze_paths([PACKAGE_ROOT])
    new, _old = filter_baselined(findings, load_baseline())
    assert not new, "new meshlint findings (fix them or, for deliberate " \
        "violations, add `# meshlint: ignore[rule] -- reason`):\n" + \
        "\n".join(f.render() for f in new)


def test_seeded_sampling_key_typo_is_caught():
    """The acceptance scenario: typo a sampling key in a REAL frame literal
    (node.py's gen_request) and the frames pass must flag it."""
    src = (PACKAGE_ROOT / "meshnet" / "node.py").read_text()
    seeded = src.replace("temperature=temperature,", "temperture=temperature,", 1)
    assert seeded != src, "node.py gen_request literal moved; update the seed"
    findings = analyze_source(seeded, "meshnet/node.py")
    assert any(
        f.rule == "ML-F001" and "temperture" in f.message for f in findings
    ), findings


def test_seeded_task_field_typo_is_caught():
    src = (PACKAGE_ROOT / "meshnet" / "pipeline.py").read_text()
    seeded = src.replace('"rng_seed": self.rng_seed,', '"rngseed": self.rng_seed,', 1)
    assert seeded != src
    assert any(
        f.rule == "ML-F001" and "rngseed" in f.message
        for f in analyze_source(seeded, "meshnet/pipeline.py")
    )


def test_seeded_message_read_typo_is_caught():
    src = (PACKAGE_ROOT / "meshnet" / "node.py").read_text()
    seeded = src.replace('data.get("peer_id")', 'data.get("peerid")', 1)
    assert seeded != src
    assert any(
        f.rule == "ML-F003" and "peerid" in f.message
        for f in analyze_source(seeded, "meshnet/node.py")
    )


# ------------------------------------------------------- frames pass fixtures


def test_frames_pass_known_bad_fixture():
    src = '''
from .. import protocol

async def send(ws, rid):
    await ws.send(protocol.encode(
        protocol.msg(protocol.GEN_REQUEST, rid=rid, prompt="x", top_kk=5)))
    await ws.send(protocol.encode({"type": protocol.GEN_CHUNK, "rid": rid}))

async def _handle_gen_request(ws, data):
    return data.get("promt")
'''
    rules = _rules(analyze_source(src, "meshnet/fixture.py"))
    assert "ML-F001" in rules  # top_kk undeclared
    assert "ML-F002" in rules  # gen_chunk without text
    assert "ML-F003" in rules  # read of "promt"
    assert "ML-F004" in rules  # no sampling forwarding on that gen_request


def test_frames_pass_run_stage_task_fields():
    src = '''
from .. import protocol

async def load(self, peer):
    await self.node.run_stage_task(
        peer, protocol.TASK_PART_LOAD,
        {"model": "m", "n_stages": 2, "staeg": 0},
    )
'''
    rules = _rules(analyze_source(src, "meshnet/fixture.py"))
    assert "ML-F001" in rules  # staeg
    assert "ML-F002" in rules  # stage missing


def test_frames_pass_accepts_clean_constructions():
    src = '''
from .. import protocol

async def send(ws, rid, extra):
    await ws.send(protocol.encode(protocol.msg(
        protocol.GEN_REQUEST, rid=rid, prompt="x", top_k=4, stop=["a"])))
    await ws.send(protocol.encode(protocol.msg(
        protocol.GEN_SUCCESS, rid=rid, **extra)))

async def _handle_gen_request(ws, data):
    return data.get("prompt"), data.get("top_p"), data["_tensors"]
'''
    assert analyze_source(src, "meshnet/fixture.py") == []


def test_frames_pass_out_of_scope_paths_unchecked():
    src = 'x = {"type": "gen_chunk"}\n'  # missing text+id: finding in scope
    assert _rules(analyze_source(src, "web/fixture.py")).count("ML-F002") == 2
    assert analyze_source(src, "engine/fixture.py") == []


def test_frames_pass_fleet_frames_declared_and_checked():
    """ISSUE 13 CI satellite: the fleet control frames are registry-
    declared, the fleet/ package is in the frames-pass scope, and the
    known-bad fixture proves each bug class is caught there."""
    for op in (protocol.FLEET_LEASE, protocol.FLEET_ACTION, protocol.FLEET_ACK):
        assert op in FRAME_SCHEMAS, f"{op} missing from the schema registry"
    assert "holder" in FRAME_SCHEMAS[protocol.FLEET_LEASE].required
    assert "epoch" in FRAME_SCHEMAS[protocol.FLEET_ACTION].required
    src = '''
from .. import protocol

async def announce(node, ws, rid):
    await ws.send(protocol.encode(protocol.msg(
        protocol.FLEET_LEASE, holder=node.peer_id, epoch=1, ttl=30.0)))
    await ws.send(protocol.encode(protocol.msg(
        protocol.FLEET_ACTION, rid=rid, action="drain", epoch=2)))

async def _handle_fleet_ack(ws, data):
    return data.get("okk")
'''
    rules = _rules(analyze_source(src, "fleet/fixture.py"))
    assert "ML-F001" in rules  # `ttl` is not a declared lease key (ttl_s is)
    assert "ML-F002" in rules  # lease missing ttl_s / action missing holder
    assert "ML-F003" in rules  # read of undeclared "okk"
    # the same constructions built right are clean
    good = '''
from .. import protocol

async def announce(node, ws, rid):
    await ws.send(protocol.encode(protocol.msg(
        protocol.FLEET_LEASE, holder=node.peer_id, epoch=1, ttl_s=30.0)))
    await ws.send(protocol.encode(protocol.msg(
        protocol.FLEET_ACTION, rid=rid, action="drain", epoch=2,
        holder=node.peer_id)))

async def _handle_fleet_ack(ws, data):
    return data.get("ok")
'''
    assert analyze_source(good, "fleet/fixture.py") == []


def test_frames_pass_adapter_frames_declared_and_checked():
    """ISSUE 14 CI satellite: the multi-adapter serving keys are registry-
    declared — `adapter` on GEN_REQUEST and the ADAPTER_ANNOUNCE frame —
    and the known-bad fixtures prove each bug class is caught (a typo'd
    adapter key is a silently-ignored tenant selection on old peers)."""
    assert protocol.ADAPTER_ANNOUNCE in FRAME_SCHEMAS
    assert protocol.ADAPTER in FRAME_SCHEMAS[protocol.GEN_REQUEST].optional
    assert "adapters" in FRAME_SCHEMAS[protocol.ADAPTER_ANNOUNCE].required
    src = '''
from .. import protocol

async def announce(node, ws, rid):
    await ws.send(protocol.encode(protocol.msg(
        protocol.ADAPTER_ANNOUNCE, peer_id=node.peer_id, service="tpu")))
    await ws.send(protocol.encode(protocol.msg(
        protocol.GEN_REQUEST, rid=rid, prompt="x", top_k=2, stop=["a"],
        adaptr="acme")))

async def _handle_adapter_announce(ws, data):
    return data.get("adaptrs")
'''
    rules = _rules(analyze_source(src, "meshnet/fixture.py"))
    assert "ML-F001" in rules  # `adaptr` undeclared on gen_request
    assert "ML-F002" in rules  # announce missing its `adapters` list
    assert "ML-F003" in rules  # read of undeclared "adaptrs"
    good = '''
from .. import protocol

async def announce(node, ws, rid):
    await ws.send(protocol.encode(protocol.msg(
        protocol.ADAPTER_ANNOUNCE, peer_id=node.peer_id, service="tpu",
        adapters=["acme"], models=["m", "m:acme"])))
    await ws.send(protocol.encode(protocol.msg(
        protocol.GEN_REQUEST, rid=rid, prompt="x", top_k=2, stop=["a"],
        adapter="acme")))

async def _handle_adapter_announce(ws, data):
    return data.get("adapters"), data.get("models")
'''
    assert analyze_source(good, "meshnet/fixture.py") == []


def test_frames_pass_draft_frames_declared_and_checked():
    """ISSUE 19 CI satellite: the mesh-drafting wire protocol is registry-
    declared — draft_request/draft_result (meshnet/draft.py) — and the
    known-bad fixture proves each bug class is caught (a typo'd draft key
    is a silently-empty draft stream: the target decodes plain forever
    while the draft peer burns compute into dropped frames)."""
    assert protocol.DRAFT_REQUEST in FRAME_SCHEMAS
    assert protocol.DRAFT_RESULT in FRAME_SCHEMAS
    assert "rid" in FRAME_SCHEMAS[protocol.DRAFT_REQUEST].required
    assert "tokens" in FRAME_SCHEMAS[protocol.DRAFT_REQUEST].optional
    assert "rid" in FRAME_SCHEMAS[protocol.DRAFT_RESULT].required
    assert "pos" in FRAME_SCHEMAS[protocol.DRAFT_RESULT].optional
    src = '''
from .. import protocol

async def request_draft(node, ws, rid, ctx):
    await ws.send(protocol.encode(protocol.msg(
        protocol.DRAFT_REQUEST, rid=rid, base=0, tokns=ctx, k=6)))

async def answer_draft(node, ws, draft):
    await ws.send(protocol.encode(protocol.msg(
        protocol.DRAFT_RESULT, pos=3, draft=draft)))

async def _handle_draft_result(ws, data):
    return data.get("drft")
'''
    rules = _rules(analyze_source(src, "meshnet/fixture.py"))
    assert "ML-F001" in rules  # `tokns` undeclared on draft_request
    assert "ML-F002" in rules  # draft_result missing its required `rid`
    assert "ML-F003" in rules  # read of undeclared "drft"
    good = '''
from .. import protocol

async def request_draft(node, ws, rid, ctx):
    await ws.send(protocol.encode(protocol.msg(
        protocol.DRAFT_REQUEST, rid=rid, base=0, tokens=ctx, k=6,
        model="tiny-llama")))

async def answer_draft(node, ws, rid, draft):
    await ws.send(protocol.encode(protocol.msg(
        protocol.DRAFT_RESULT, rid=rid, pos=3, draft=draft)))

async def _handle_draft_result(ws, data):
    return data.get("pos"), data.get("draft"), data.get("reprime")
'''
    assert analyze_source(good, "meshnet/fixture.py") == []


def test_seeded_draft_frame_typos_are_caught():
    """Typo the draft protocol in the REAL sources and meshlint must
    object: a misspelled construct key on the server's draft_result
    (meshnet/draft.py) and a misspelled read in the node's draft_request
    handler (meshnet/node.py)."""
    src = (PACKAGE_ROOT / "meshnet" / "draft.py").read_text()
    seeded = src.replace(
        "protocol.DRAFT_RESULT, rid=rid, pos=pos,",
        "protocol.DRAFT_RESULT, rid=rid, poss=pos,", 1,
    )
    assert seeded != src, "draft.py result literal moved; update the seed"
    assert "ML-F001" in _rules(analyze_source(seeded, "meshnet/draft.py"))

    src = (PACKAGE_ROOT / "meshnet" / "node.py").read_text()
    seeded = src.replace(
        'rid=str(data.get("rid") or ""), error="no_drafter",',
        'rid=str(data.get("ird") or ""), error="no_drafter",', 1,
    )
    assert seeded != src, "node.py draft handler moved; update the seed"
    assert any(
        f.rule == "ML-F003" and "ird" in f.message
        for f in analyze_source(seeded, "meshnet/node.py")
    )


# -------------------------------------------------------- async pass fixtures


def test_async_pass_known_bad_fixture():
    src = '''
import time, requests

async def bad(self, ws):
    time.sleep(1)
    requests.post("http://x", json={})
    async with self._lock:
        await ws.send("hi")
    await ws.recv()
'''
    rules = _rules(analyze_source(src, "meshnet/fixture.py"))
    assert rules.count("ML-A001") == 2
    assert "ML-A003" in rules
    assert "ML-A002" in rules


def test_async_pass_clean_patterns_pass():
    src = '''
import asyncio
import websockets

async def good(self, addr):
    async with self._lock:
        targets = list(self.peers)
    ws = await websockets.connect(addr, open_timeout=10)
    await self.clock.sleep(0.1)  # the clock seam — also ML-C001-clean

    def offloaded():
        import time
        time.sleep(1)  # meshlint: ignore[ML-C001] -- real wall wait in an executor thread

    await asyncio.get_running_loop().run_in_executor(None, offloaded)
'''
    assert analyze_source(src, "meshnet/fixture.py") == []


def test_async_pass_ws_connect_without_timeout():
    src = '''
import websockets

async def dial(addr):
    return await websockets.connect(addr)
'''
    assert "ML-A002" in _rules(analyze_source(src, "meshnet/fixture.py"))
    # outside the meshnet/web hot-path scope the timeout rule stays quiet
    assert analyze_source(src, "services/fixture.py") == []


# ---------------------------------------------------------- jax pass fixtures


def test_jax_pass_known_bad_fixture():
    src = '''
import jax
import jax.numpy as jnp
import numpy as np

def _decode_fn(cache, x, k):
    v = x.item()
    h = np.asarray(x)
    n = int(k)
    if jnp.any(x > 0):
        x = x + 1
    return x

decode = jax.jit(_decode_fn)
'''
    rules = _rules(analyze_source(src, "engine/fixture.py"))
    assert rules.count("ML-J001") == 3
    assert "ML-J002" in rules


def test_jax_pass_only_flags_jit_reachable():
    src = '''
import numpy as np

def host_side(x):
    return np.asarray(x).item()  # never jit-compiled: fine
'''
    assert analyze_source(src, "engine/fixture.py") == []


def test_jax_pass_sees_spec_verify_wiring():
    """The engine's speculative-decode verify root is wired as
    ``self._spec_verify = jax.jit(self._spec_verify_fn, ...)`` — the
    method-attribute form of jit wrapping. Pin that the root collector
    resolves it: a host sync or traced branch seeded into a fixture
    with exactly that wiring must be flagged (a collector regression
    would silently stop scanning the hottest new jit root)."""
    src = '''
import jax
import jax.numpy as jnp

class Engine:
    def __init__(self):
        self._spec_verify = jax.jit(self._spec_verify_fn, donate_argnums=(4,))

    def _spec_verify_fn(self, params, cur, drafts, draft_lens, cache,
                        offsets, key):
        n = int(draft_lens)
        if jnp.any(cur > 0):
            cur = cur + 1
        return cur, cache
'''
    rules = _rules(analyze_source(src, "engine/engine.py"))
    assert "ML-J001" in rules and "ML-J002" in rules


def test_jax_pass_covers_spec_module_and_real_verify_is_clean():
    """engine/spec.py is inside the jax-pass scope (a path move out of
    engine/ would silently drop it from scanning), and the REAL spec
    module + engine (with the verify fn) lint clean — the ratchet
    baseline stays empty."""
    from bee2bee_tpu.analysis.jaxhygiene import JaxHygienePass

    assert JaxHygienePass().applies("engine/spec.py")
    spec_py = PACKAGE_ROOT / "engine" / "spec.py"
    engine_py = PACKAGE_ROOT / "engine" / "engine.py"
    assert "_spec_verify_fn" in engine_py.read_text()  # the root exists
    assert analyze_paths([spec_py, engine_py]) == []


def test_jax_pass_sees_pallas_call_kernel_roots():
    """ops/ragged.py wires its kernel as
    ``pl.pallas_call(functools.partial(_kernel, ...), ...)`` — pin that
    the root collector resolves the pallas_call body through the partial:
    a host sync or traced-value branch seeded into a fixture with exactly
    that wiring must be flagged (a collector regression would silently
    stop scanning the engine's hottest kernel), and the REAL ragged +
    flash kernel modules lint clean so the ratchet baseline stays empty."""
    src = '''
import functools
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(tables_ref, q_ref, o_ref, *, scale):
    n = int(scale)
    v = q_ref[0].item()
    if jnp.any(q_ref[0] > 0):
        v = v + 1
    o_ref[0] = v


def wrapper(q, tables):
    kernel = functools.partial(_kernel, scale=2.0)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
    )(tables, q)
'''
    rules = _rules(analyze_source(src, "ops/fixture.py"))
    assert "ML-J001" in rules and "ML-J002" in rules
    from bee2bee_tpu.analysis.jaxhygiene import JaxHygienePass

    assert JaxHygienePass().applies("ops/ragged.py")
    ragged_py = PACKAGE_ROOT / "ops" / "ragged.py"
    flash_py = PACKAGE_ROOT / "ops" / "flash.py"
    assert "pallas_call" in ragged_py.read_text()  # the root exists
    assert analyze_paths([ragged_py, flash_py]) == []


def test_jax_pass_catches_host_sync_in_quantize_on_write_root():
    """ISSUE 12: the int8 KV pool's quantize-on-write runs inside the
    engine's jit roots (prefill/decode/spec-verify) and its scan-carried
    layer body — a host-side ``.item()`` / numpy cast there would put a
    device→host sync on EVERY cache write. Pin that the pass catches
    exactly that wiring on a known-bad fixture (jit-root method + scan
    body, mirroring engine._prefill_fn → core.forward's layer scan), and
    that the REAL modules owning the quantized pool lint clean so the
    ratchet baseline stays EMPTY."""
    src = '''
import jax
import jax.numpy as jnp
import numpy as np


class Engine:
    def __init__(self):
        self._prefill = jax.jit(self._prefill_fn, donate_argnums=(2,))

    def _prefill_fn(self, params, tokens, cache, blk, slot):
        # quantize-on-write gone wrong: host amax + scalar cast per write
        amax = np.asarray(tokens).max()
        n = int(slot)
        scale = cache["k_scale"].item()
        return cache, tokens


def forward(pool, scale, xT):
    def layer(carry, xs):
        pool, scale = carry
        if jnp.any(scale > 0):
            pool = pool
        q = np.asarray(xT)
        return (pool, scale), None
    return jax.lax.scan(layer, (pool, scale), xT)
'''
    rules = _rules(analyze_source(src, "engine/engine.py"))
    assert "ML-J001" in rules and "ML-J002" in rules
    from bee2bee_tpu.analysis.jaxhygiene import JaxHygienePass

    assert JaxHygienePass().applies("models/core.py")
    core_py = PACKAGE_ROOT / "models" / "core.py"
    ragged_py = PACKAGE_ROOT / "ops" / "ragged.py"
    scheduler_py = PACKAGE_ROOT / "engine" / "scheduler.py"
    assert "_quantized_page_write" in core_py.read_text()  # the root exists
    assert analyze_paths([core_py, ragged_py, scheduler_py]) == []


def test_jax_pass_sees_decorators_and_scan_bodies():
    src = '''
import jax
import jax.numpy as jnp

@jax.jit
def f(x):
    return x.item()

def outer(xs):
    def step(carry, x):
        if jnp.sum(x):
            carry = carry + 1
        return carry, x
    return jax.lax.scan(step, 0, xs)
'''
    rules = _rules(analyze_source(src, "models/fixture.py"))
    assert "ML-J001" in rules and "ML-J002" in rules


# ------------------------------------------------- suppressions and baseline


def test_suppression_requires_reason():
    src = '''
async def f(ws):
    await ws.recv()  # meshlint: ignore[ML-A002]
'''
    rules = _rules(analyze_source(src, "meshnet/fixture.py"))
    assert "ML-S001" in rules and "ML-A002" in rules  # unexplained ≠ suppressed


def test_suppression_with_reason_suppresses_only_that_rule():
    src = '''
async def f(ws):
    await ws.recv()  # meshlint: ignore[ML-A002] -- loopback shim, in-process peer
'''
    assert analyze_source(src, "meshnet/fixture.py") == []
    wildcard = src.replace("[ML-A002]", "[*]")
    assert analyze_source(wildcard, "meshnet/fixture.py") == []
    wrong_rule = src.replace("[ML-A002]", "[ML-A001]")
    assert _rules(analyze_source(wrong_rule, "meshnet/fixture.py")) == ["ML-A002"]


def test_baseline_is_a_consuming_multiset():
    src = '''
async def f(ws):
    await ws.recv()

async def g(ws):
    await ws.recv()
'''
    findings = analyze_source(src, "meshnet/fixture.py")
    assert _rules(findings) == ["ML-A002", "ML-A002"]
    # identical snippets: one baseline entry absorbs exactly one finding
    from collections import Counter
    baseline = Counter([findings[0].key()])
    new, old = filter_baselined(findings, baseline)
    assert len(new) == 1 and len(old) == 1


def test_cli_exit_codes(tmp_path):
    from bee2bee_tpu.analysis.__main__ import main

    bad = tmp_path / "meshnet"
    bad.mkdir()
    (bad / "x.py").write_text(
        "import time\n\nasync def f(ws):\n    time.sleep(1)\n"
    )
    # a file outside the package scopes by basename; the blocking-call
    # rule applies to every path, so the CLI must exit 1 on it
    assert main([str(bad), "--no-baseline"]) != 0
    assert main([str(PACKAGE_ROOT / "protocol.py")]) == 0
    assert main(["--list-rules"]) == 0


# -------------------------------------------------------- registry invariants


def test_every_message_type_has_a_schema():
    assert set(FRAME_SCHEMAS) >= set(protocol.MESSAGE_TYPES)


def test_every_task_kind_constant_has_a_schema():
    kinds = {
        v
        for k, v in vars(protocol).items()
        if k.startswith("TASK_") and isinstance(v, str) and v != protocol.TASK_ERROR
    }
    assert kinds <= set(TASK_SCHEMAS)


def test_sampling_keys_are_in_the_declared_universe():
    assert set(protocol.SAMPLING_KEYS) <= declared_key_universe()


def test_tenant_and_admission_keys_are_declared():
    """ISSUE 7: the tenant identity field rides gen_request, and every
    admission rejection (the typed 429/503 contract over p2p) carries
    error_kind + retry_after_s on GEN_ERROR — pinned here so a protocol
    change can't drop them from the registry silently."""
    assert protocol.TENANT in FRAME_SCHEMAS[protocol.GEN_REQUEST].allowed_keys()
    gen_error = FRAME_SCHEMAS[protocol.GEN_ERROR]
    assert {"error_kind", "retry_after_s"} <= gen_error.allowed_keys()
    assert {protocol.TENANT, "error_kind", "retry_after_s"} <= declared_key_universe()


def test_admission_rejection_fixture_pins_typed_fields():
    """A GEN_ERROR admission rejection with a typo'd retry field (the
    header-style `retry_after` instead of the wire's `retry_after_s`) is
    exactly the silently-dropped-key class meshlint exists for; the
    correctly-typed construction passes clean."""
    bad = '''
from .. import protocol

async def reject(ws, rid, rej):
    await ws.send(protocol.encode(protocol.msg(
        protocol.GEN_ERROR, rid=rid, error="admission_rejected: rate",
        error_kind="rate_limited", retry_after=1.0)))
'''
    rules = _rules(analyze_source(bad, "meshnet/fixture.py"))
    assert "ML-F001" in rules, rules
    good = bad.replace("retry_after=1.0", "retry_after_s=1.0")
    assert analyze_source(good, "meshnet/fixture.py") == []


def test_seeded_admission_rejection_typo_is_caught_in_real_node():
    """Seed the retry_after_s typo into node.py's REAL admission-reject
    frame literal: the frames pass must flag it (proves the real
    construction is statically checked, not spread-exempted)."""
    src = (PACKAGE_ROOT / "meshnet" / "node.py").read_text()
    seeded = src.replace(
        "retry_after_s=rej.retry_after_s,", "retry_after=rej.retry_after_s,", 1
    )
    assert seeded != src, "node.py admission-reject literal moved; update the seed"
    assert any(
        f.rule == "ML-F001" and "retry_after" in f.message
        for f in analyze_source(seeded, "meshnet/node.py")
    )


def test_rule_catalog_covers_all_emitted_rules():
    cat = rule_catalog()
    for rule in ("ML-F001", "ML-F002", "ML-F003", "ML-F004",
                 "ML-A001", "ML-A002", "ML-A003",
                 "ML-J001", "ML-J002", "ML-S001"):
        assert rule in cat


def test_out_of_tree_paths_scope_by_package_structure(tmp_path):
    """Analyzing a checkout/copy OUTSIDE the installed package must still
    scope files by their meshnet/engine/... structure — a basename
    fallback would silently skip the frames/jax passes there."""
    from bee2bee_tpu.analysis.core import virtual_path

    d = tmp_path / "clone" / "bee2bee_tpu" / "meshnet"
    d.mkdir(parents=True)
    f = d / "node.py"
    f.write_text("")
    assert virtual_path(f) == "meshnet/node.py"
    d2 = tmp_path / "copy" / "engine"
    d2.mkdir(parents=True)
    assert virtual_path(d2 / "scheduler.py") == "engine/scheduler.py"


def test_f004_attributed_per_frame_not_per_function():
    """One copy_sampling call must exempt ONLY the frame it targets —
    a second knob-less gen_request in the same function still fails."""
    src = '''
from .. import protocol

async def two_frames(ws, payload, rid):
    covered = {"type": protocol.GEN_REQUEST, "rid": rid, "prompt": "x"}
    protocol.copy_sampling(payload, covered)
    await ws.send(protocol.encode(covered))
    naked = {"type": protocol.GEN_REQUEST, "rid": rid, "prompt": "y"}
    await ws.send(protocol.encode(naked))
'''
    findings = analyze_source(src, "web/fixture.py")
    f004 = [f for f in findings if f.rule == "ML-F004"]
    assert len(f004) == 1 and "naked" in f004[0].snippet, findings


def test_f004_covers_msg_assigned_frames():
    src = '''
from .. import protocol

async def send(ws, body, rid):
    m = protocol.msg(protocol.GEN_REQUEST, rid=rid, prompt="x")
    protocol.copy_sampling(body, m)
    await ws.send(protocol.encode(m))
'''
    assert analyze_source(src, "meshnet/fixture.py") == []


def test_a003_lock_naming_does_not_match_block_vocabulary():
    """'block' contains the substring 'lock': the paged-cache vocabulary
    (block pools, blocked peers) must not trip the lock-held rule."""
    src = '''
async def fine(self, ws):
    async with self.block_pool_guard:
        await ws.send("hi")
    async with self.unblock_gate:
        await ws.send("hi")

async def held(self, ws):
    async with self.rw_lock:
        await ws.send("hi")
'''
    findings = analyze_source(src, "meshnet/fixture.py")
    assert _rules(findings) == ["ML-A003"]
    # the one finding anchors to the await inside the real lock block
    assert findings[0].line == 10


# --------------------------------------------------- telemetry pass fixtures


def test_telemetry_pass_known_bad_fixture():
    """ML-T001: every dynamic-name construction a span/metric call can
    smuggle a request-varying string through — f-string, + concat,
    %-format, .format()."""
    src = '''
from ..tracing import get_tracer
from ..metrics import get_registry

def f(rid, op, clock):
    with get_tracer().span(f"gen.{rid}"):
        pass
    with clock.phase("stage." + op):
        pass
    get_registry().counter("frames_%s" % op).inc()
    get_registry().histogram(name="lat.{}".format(op)).observe(1.0)
'''
    rules = _rules(analyze_source(src, "engine/fixture.py"))
    assert rules == ["ML-T001"] * 4, rules


def test_telemetry_pass_accepts_literal_and_variable_names():
    """Literal dotted constants pass; so does forwarding a plain variable
    (the literal is checked at ITS call site), and request-varying data in
    attrs/labels — the pattern the rule exists to steer people toward."""
    src = '''
from ..tracing import get_tracer
from ..metrics import get_registry

SPAN_NAME = "gen.local"

def f(rid, op):
    with get_tracer().span("gen.p2p", rid=rid):
        pass
    with get_tracer().span(SPAN_NAME):
        pass
    get_registry().counter("mesh.frames_sent").inc(op=op)
    "a,b".split(",")[0].count("a")  # str.count names no metric
'''
    assert analyze_source(src, "meshnet/fixture.py") == []


def test_telemetry_pass_covers_loop_phase_names():
    """A loop phase is an annotation name and a counter label value: the
    scheduler's `_phase` decorator and PhaseClock.phase take literals."""
    src = '''
def _phase(name):
    return lambda fn: fn

class S:
    @_phase("admit")
    def _admit(self):
        with self._phases.phase("fetch"):
            pass

    @_phase(f"turn.{1}")
    def _step(self, i):
        with self._phases.phase("window_%d" % i):
            pass
'''
    findings = analyze_source(src, "engine/fixture.py")
    assert _rules(findings) == ["ML-T001"] * 2
    assert [f.line for f in findings] == [11, 13]


def test_telemetry_pass_covers_parts_annotations_and_program_scopes():
    """ISSUE 41's names: a phase's part (PhaseClock.part: an annotation name
    and a label value), a plain host annotation (tracing.annotate) and a jit
    root's scope (tracing.prog_scope) are literals too."""
    src = '''
from ..tracing import annotate, prog_scope

class S:
    @prog_scope("prog.decode")
    def _decode_fn(self, x):
        return x

    @prog_scope("prog.%s" % "decode")
    def _other_fn(self, x):
        return x

    def _admit(self, bucket, part):
        self._phases.part("dispatch")
        self._phases.part(None)
        self._phases.part(part)
        self._phases.part(f"bucket_{bucket}")
        with annotate("svc.pump"):
            pass
        with annotate("svc." + part):
            pass
'''
    findings = analyze_source(src, "engine/fixture.py")
    assert _rules(findings) == ["ML-T001"] * 3
    assert [f.line for f in findings] == [9, 17, 20]


def test_the_loops_turn_is_in_the_hot_loop_region():
    """ML-J003 covers `_turn` (ISSUE 41: the loop's body, phase `turn`): a
    host sync written there is one the readback ring did not schedule."""
    src = '''
import jax
import numpy as np

class S:
    def _turn(self):
        self._admit()
        np.asarray(self._inflight[0]["toks"])
        jax.device_get(self._cur)

    def _admit(self):  # not the region: the burst's one gather lives here
        return jax.device_get(self._firsts)
'''
    findings = analyze_source(src, "engine/fixture.py")
    assert _rules(findings) == ["ML-J003"] * 2 and [f.line for f in findings] == [8, 9]


def test_telemetry_pass_scans_whole_package():
    """Telemetry calls live in engine/, meshnet/, services/, web/ and
    api.py alike — the pass must not scope itself out of any of them."""
    from bee2bee_tpu.analysis.telemetry import TelemetryPass

    p = TelemetryPass()
    for path in ("engine/scheduler.py", "meshnet/node.py", "api.py",
                 "web/gateway.py", "services/base.py", "tracing.py"):
        assert p.applies(path), path


def test_telemetry_rule_in_catalog():
    assert "ML-T001" in rule_catalog()


# --------------------------------------------------- clock-seam pass fixtures


def test_clock_pass_known_bad_fixture():
    """ML-C001: every direct wall-clock read and bare asyncio timer in a
    clock-seamed package is a finding — each one silently re-couples a
    code path to the host clock and breaks deterministic simulation."""
    src = '''
import asyncio
import time

async def tick(self):
    start = time.time()
    mono = time.monotonic()
    perf = time.perf_counter()
    await asyncio.sleep(1.0)
    await asyncio.wait_for(self.q.get(), timeout=2.0)
    time.sleep(0.1)
'''
    rules = _rules(analyze_source(src, "meshnet/fixture.py"))
    assert rules.count("ML-C001") == 6, rules


def test_clock_pass_seam_calls_are_clean():
    """The seam itself — clock.time()/sleep()/wait_for(), however the
    clock is reached — never matches the bare-module names."""
    src = '''
from ..clock import get_clock

async def tick(self):
    now = self.clock.time()
    await self.clock.sleep(1.0)
    await self.clock.wait_for(self.q.get(), timeout=2.0)
    mono = get_clock().monotonic()
'''
    assert analyze_source(src, "meshnet/fixture.py") == []


def test_clock_pass_scope_covers_all_seamed_packages():
    from bee2bee_tpu.analysis.clockseam import ClockSeamPass

    p = ClockSeamPass()
    for path in ("meshnet/node.py", "fleet/controller.py",
                 "router/policy.py", "health.py"):
        assert p.applies(path), path
    # unseamed packages keep their wall clocks without findings
    for path in ("engine/scheduler.py", "services/base.py", "bench.py",
                 "simnet/clock.py"):
        assert not p.applies(path), path
    src = "import time\n\ndef f():\n    return time.time()\n"
    assert analyze_source(src, "engine/fixture.py") == []


def test_clock_pass_suppression_and_real_exemptions():
    """A justified same-line ignore suppresses the finding; the shipped
    exemptions (NAT round trips in runtime.py, thread joins in
    health.py) carry one, so the ratchet baseline stays EMPTY."""
    src = '''
import time

def deadline(timeout_s):
    return time.time() + timeout_s  # meshlint: ignore[ML-C001] -- real thread-join deadline
'''
    assert analyze_source(src, "health.py") == []
    runtime_py = PACKAGE_ROOT / "meshnet" / "runtime.py"
    health_py = PACKAGE_ROOT / "health.py"
    assert "ignore[ML-C001]" in runtime_py.read_text()
    assert "ignore[ML-C001]" in health_py.read_text()
    assert analyze_paths([runtime_py, health_py]) == []


def test_clock_rule_in_catalog():
    assert "ML-C001" in rule_catalog()


# ---------------------------------------------------- raceguard pass fixtures


def test_raceguard_r001_known_bad_fixture():
    """ML-R001: check `self.X`, await, then write `self.X` without
    re-checking — the await is a suspension point where another
    coroutine can invalidate the check."""
    src = '''
class Booth:
    async def grant(self, who):
        if self.holder is None:
            await self.bookkeeping(who)
            self.holder = who
'''
    rules = _rules(analyze_source(src, "meshnet/fixture.py"))
    assert "ML-R001" in rules, rules


def test_raceguard_r001_clean_twins():
    """Re-checking after the await, or holding a lock around the whole
    check+act, clears the finding."""
    rechecked = '''
class Booth:
    async def grant(self, who):
        if self.holder is None:
            await self.bookkeeping(who)
            if self.holder is None:
                self.holder = who
'''
    locked = '''
class Booth:
    async def grant(self, who):
        async with self._lock:
            if self.holder is None:
                await self.bookkeeping(who)
                self.holder = who
'''
    for src in (rechecked, locked):
        rules = _rules(analyze_source(src, "meshnet/fixture.py"))
        assert "ML-R001" not in rules, rules


def test_raceguard_r002_known_bad_fixture():
    """ML-R002: a create_task handle that is dropped (bare statement) or
    bound to a name never read again — exceptions vanish and asyncio's
    weak reference lets GC cancel the task mid-flight."""
    src = '''
import asyncio

class Svc:
    async def start(self):
        asyncio.create_task(self.loop())
        t = asyncio.create_task(self.other())
        self.ready = True
'''
    rules = _rules(analyze_source(src, "meshnet/fixture.py"))
    assert rules.count("ML-R002") == 2, rules


def test_raceguard_r002_clean_twins():
    """Awaiting the handle, reading the bound attribute (cancellation,
    done-callback), or a tracked spawn helper all clear the finding."""
    src = '''
import asyncio

class Svc:
    async def start(self):
        t = asyncio.create_task(self.loop())
        await t
        self._task = asyncio.create_task(self.other())
        self._task.add_done_callback(print)
        self._tasks.spawn(self.third())
'''
    rules = _rules(analyze_source(src, "meshnet/fixture.py"))
    assert "ML-R002" not in rules, rules


def test_raceguard_r003_known_bad_fixture():
    """ML-R003: a shared container mutated after awaits from two
    distinct coroutine entry points with no lock on any mutation path."""
    src = '''
class Hub:
    async def _handle_join(self, ws, data):
        await self.notify(ws)
        self.subs[data["id"]] = ws

    async def _handle_leave(self, ws, data):
        await self.notify(ws)
        self.subs.pop(data["id"], None)
'''
    rules = _rules(analyze_source(src, "meshnet/fixture.py"))
    assert "ML-R003" in rules, rules


def test_raceguard_r003_clean_twins():
    """A lock on the mutation paths — or a single entry point — clears
    the finding."""
    locked = '''
class Hub:
    async def _handle_join(self, ws, data):
        async with self._lock:
            await self.notify(ws)
            self.subs[data["id"]] = ws

    async def _handle_leave(self, ws, data):
        async with self._lock:
            await self.notify(ws)
            self.subs.pop(data["id"], None)
'''
    single = '''
class Hub:
    async def _handle_join(self, ws, data):
        await self.notify(ws)
        self.subs[data["id"]] = ws
'''
    for src in (locked, single):
        rules = _rules(analyze_source(src, "meshnet/fixture.py"))
        assert "ML-R003" not in rules, rules


def test_raceguard_r004_known_bad_fixture():
    """ML-R004: awaiting inside iteration over a shared container —
    a mutation during the suspension invalidates the iterator."""
    src = '''
class Hub:
    async def broadcast(self, msg):
        for ws in self.conns:
            await ws.send(msg)
'''
    rules = _rules(analyze_source(src, "meshnet/fixture.py"))
    assert "ML-R004" in rules, rules


def test_raceguard_r004_clean_twins():
    """Materializing a snapshot (list()/tuple()/sorted()) or holding a
    lock across the loop clears the finding."""
    src = '''
class Hub:
    async def broadcast(self, msg):
        for ws in list(self.conns):
            await ws.send(msg)
        for ws in sorted(self.conns):
            await ws.send(msg)
        async with self._lock:
            for ws in self.conns:
                await ws.send(msg)
'''
    rules = _rules(analyze_source(src, "meshnet/fixture.py"))
    assert "ML-R004" not in rules, rules


def test_seeded_toctou_in_real_node_is_caught():
    """The acceptance seed: rewrite node.py's begin_drain into a
    check-then-act split across the drain await — ML-R001 must fire on
    the real source."""
    src = (PACKAGE_ROOT / "meshnet" / "node.py").read_text()
    seeded = src.replace(
        "        self.drain_source = source\n"
        "        return await self.migration.drain(stop=stop, wait=wait)",
        "        if self.drain_source is None:\n"
        "            await self.migration.drain(stop=stop, wait=wait)\n"
        "            self.drain_source = source\n"
        "        return {}",
        1,
    )
    assert seeded != src, "begin_drain body moved; update the seed"
    assert any(
        f.rule == "ML-R001" and "drain_source" in f.message
        for f in analyze_source(seeded, "meshnet/node.py")
    )


def test_seeded_dropped_handle_in_real_migrate_is_caught():
    """Drop the stop-task binding in migrate.py — the bare create_task
    statement must trip ML-R002 on the real source."""
    src = (PACKAGE_ROOT / "meshnet" / "migrate.py").read_text()
    seeded = src.replace(
        "self._stop_task = asyncio.create_task", "asyncio.create_task", 1
    )
    assert seeded != src, "migrate.py stop-task spawn moved; update the seed"
    assert any(
        f.rule == "ML-R002" for f in analyze_source(seeded, "meshnet/migrate.py")
    )


def test_toctou_demo_suppression_and_static_detection():
    """The fuzzer's deliberately raceable demo (simnet/fuzz.py) ships
    with a reasoned suppression — stripping it must expose ML-R001, so
    the SAME bug the fuzzer provokes dynamically is also caught
    statically."""
    fuzz_py = PACKAGE_ROOT / "simnet" / "fuzz.py"
    src = fuzz_py.read_text()
    assert "ignore[ML-R001]" in src
    assert analyze_paths([fuzz_py]) == []
    stripped = src.replace("# meshlint: ignore[ML-R001]", "# stripped", 1)
    assert any(
        f.rule == "ML-R001" and "holder" in f.message
        for f in analyze_source(stripped, "simnet/fuzz.py")
    )


def test_raceguard_scope_and_catalog():
    from bee2bee_tpu.analysis.raceguard import RaceGuardPass

    p = RaceGuardPass()
    for path in ("meshnet/node.py", "router/policy.py", "fleet/controller.py",
                 "web/bridge.py", "api.py", "simnet/fuzz.py"):
        assert p.applies(path), path
    for path in ("engine/scheduler.py", "models/llama.py", "bench.py"):
        assert not p.applies(path), path
    for rule in ("ML-R001", "ML-R002", "ML-R003", "ML-R004"):
        assert rule in rule_catalog(), rule
