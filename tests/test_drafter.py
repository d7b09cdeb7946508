"""The tiered drafter stack (engine/spec.py DrafterStack + engine/
drafter.py DraftModel + the MeshDrafter client):

- typed boot gate: unknown drafter spec / vocab mismatch / tokenizer
  fingerprint mismatch is DrafterLoadError at construction, never a
  silent garbage-draft loop at serve time;
- tier policy: rows start on the cheapest alive tier, demote below
  before escalating above, never retry a failed tier, land on "off"
  only when the ladder is exhausted;
- MeshDrafter wire semantics: pending != miss, catch-up salvage of
  stale-but-correct drafts, timeout -> full resend -> typed death,
  reprime/stale-result handling, done frames on forget;
- model-tier greedy parity: a real resident drafter feeding the
  [B, K+1] verify path is token-for-token identical to spec-off decode
  (rectangular, paged, mixed batches, stop-in-draft, near-capacity);
- mesh tier end to end against an in-process fake draft peer, including
  a peer killed mid-generation: typed degradation, zero dropped rows.
"""

from __future__ import annotations

import queue
import threading
import time

import pytest

from bee2bee_tpu.engine import EngineConfig, InferenceEngine
from bee2bee_tpu.engine.drafter import (
    DrafterLoadError,
    DraftModel,
    tokenizer_fingerprint,
    validate_drafter_compat,
)
from bee2bee_tpu.engine.spec import (
    TIER_OFF,
    DrafterStack,
    MeshDrafter,
    NgramDrafter,
)
from bee2bee_tpu.metrics import get_registry

KW = dict(
    max_seq_len=128, dtype="float32", cache_dtype="float32",
    decode_chunk=4, prefill_buckets=(16, 32, 64), max_batch=4,
)
# probe small enough that the n-gram tier fails its audition (and
# escalates to the model tier) within ~2 missed spec attempts
SPEC_KW = dict(KW, spec_tokens=6, spec_probe_tokens=12)
# period-499 token walk: no recurring n-gram, so the n-gram tier drafts
# nothing and the ladder's escalation path is what gets exercised
NONREP = [1 + (j * 97) % 499 for j in range(24)]
REP_PROMPT = [5, 6, 7, 8, 9] * 3 + [5, 6, 7]


@pytest.fixture(scope="module")
def ref_engine():
    eng = InferenceEngine("tiny-llama", engine_config=EngineConfig(**KW))
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def model_engine():
    """tiny-llama drafting for tiny-llama at the same seed: weight-
    identical, so greedy drafts are exactly the target's own greedy
    continuation (acceptance 1.0) — the CPU stand-in for a distilled
    drafter. Paged: the model-tier verify chunk scatters through block
    tables (the rectangular path is covered by the bad-seed engine)."""
    eng = InferenceEngine(
        "tiny-llama",
        engine_config=EngineConfig(**SPEC_KW, drafter="tiny-llama"),
    )
    yield eng
    eng.close()


# ------------------------------------------------------------- boot gate


class _Cfg:
    def __init__(self, vocab):
        self.vocab_size = vocab


class _TokA:
    vocab_size = 512


class _TokB:
    vocab_size = 512


def test_tokenizer_fingerprint_identity():
    # byte-fallback tokenizers: fingerprint is fully determined by type
    # and vocab size
    assert tokenizer_fingerprint(_TokA()) == tokenizer_fingerprint(_TokA())
    assert tokenizer_fingerprint(_TokA()) != tokenizer_fingerprint(_TokB())


def test_validate_drafter_compat_typed_errors():
    validate_drafter_compat(_Cfg(512), _TokA(), _Cfg(512), _TokA())
    with pytest.raises(DrafterLoadError, match="vocab_size"):
        validate_drafter_compat(_Cfg(512), _TokA(), _Cfg(50257), _TokA())
    with pytest.raises(DrafterLoadError, match="tokenizer"):
        validate_drafter_compat(_Cfg(512), _TokA(), _Cfg(512), _TokB())


def test_unknown_drafter_is_typed_boot_error():
    with pytest.raises(DrafterLoadError, match="no-such-model"):
        DraftModel(
            "no-such-model", spec_tokens=4, batch=2, target_max_seq_len=128
        )
    # the engine surfaces the same type at boot, not at the first draft
    with pytest.raises(DrafterLoadError):
        InferenceEngine(
            "tiny-llama",
            engine_config=EngineConfig(
                max_seq_len=32, dtype="float32", cache_dtype="float32",
                decode_chunk=4, prefill_buckets=(16,), max_batch=1,
                spec_tokens=4, drafter="no-such-model",
            ),
        )


def test_drafter_without_spec_tokens_is_config_error():
    with pytest.raises(ValueError, match="spec_tokens"):
        EngineConfig(**KW, drafter="tiny-llama")


# ------------------------------------------------------------ tier policy


class _StubDrafter:
    def __init__(self):
        self.dead = False
        self.forgotten = []

    def forget(self, req):
        self.forgotten.append(req)

    def close(self):
        pass


def test_drafter_stack_tier_policy():
    ng, md, ms = _StubDrafter(), _StubDrafter(), _StubDrafter()
    stack = DrafterStack({"ngram": ng, "model": md, "mesh": ms}, 6)
    # rows start on the cheapest alive tier
    assert stack.start_tier() == "ngram"
    # ngram is the ladder floor: its only exit is UP (escalation)
    assert stack.next_tier("ngram", {"ngram"}) == "model"
    assert stack.next_tier("model", {"ngram", "model"}) == "mesh"
    assert stack.next_tier("mesh", {"ngram", "model", "mesh"}) == TIER_OFF
    # demotion is preferred over escalation: a dying mesh row lands on
    # the local model tier, not off
    assert stack.next_tier("mesh", {"mesh"}) == "model"
    # a dead drafter is skipped even when not in the row's failed set
    ms.dead = True
    assert stack.next_tier("model", {"ngram", "model"}) == TIER_OFF
    # dead cheapest tier: new rows start one rung up
    ng.dead = True
    assert stack.start_tier() == "model"
    with pytest.raises(ValueError):
        DrafterStack({"warp": _StubDrafter()}, 6)
    with pytest.raises(ValueError):
        DrafterStack({}, 6)


def test_drafter_stack_mesh_only_demotes_to_off():
    ms = _StubDrafter()
    stack = DrafterStack({"mesh": ms}, 6)
    assert stack.start_tier() == "mesh"
    assert stack.next_tier("mesh", {"mesh"}) == TIER_OFF


# ------------------------------------------------- mesh client protocol


class _Req:
    def __init__(self, ids):
        self.ids = list(ids)
        self.out_ids = []


class _Wire:
    """Capture-only transport: records payloads, configurable verdict."""

    def __init__(self):
        self.sent = []
        self.ok = True

    def __call__(self, payload):
        self.sent.append(payload)
        return self.ok


def test_mesh_pending_is_free_then_consumes():
    wire = _Wire()
    md = MeshDrafter(4)
    md.attach_transport(wire)
    req = _Req([1, 2, 3])
    # first contact primes the pipeline: full context, no draft yet, and
    # a pending result is None (the row skips the step, zero accounting)
    assert md.propose_batch([(0, req)]) == {0: None}
    assert wire.sent[-1]["base"] == 0 and wire.sent[-1]["tokens"] == [1, 2, 3]
    assert wire.sent[-1]["k"] == 4
    # still in flight, deadline far away: still free
    assert md.propose_batch([(0, req)]) == {0: None}
    assert len(wire.sent) == 1
    md.deliver({"rid": wire.sent[0]["rid"], "pos": 3, "draft": [7, 8, 9, 10]})
    assert md.propose_batch([(0, req)]) == {0: [7, 8, 9, 10]}
    # verify verdict grew the context: observe ships ONLY the delta
    req.out_ids = [7, 8]
    md.observe(req, 2)
    assert wire.sent[-1]["base"] == 3 and wire.sent[-1]["tokens"] == [7, 8]


def test_mesh_catchup_salvages_stale_draft_tail():
    """The row took plain decode windows while the draft was in flight
    (pending rows never stall): a result whose predicted prefix matches
    what the row actually produced is still a valid draft — its tail —
    at the current position."""
    wire = _Wire()
    md = MeshDrafter(4)
    md.attach_transport(wire)
    req = _Req([1, 2, 3])
    md.propose_batch([(0, req)])
    md.deliver({"rid": wire.sent[0]["rid"], "pos": 3, "draft": [7, 8, 9, 10]})
    req.out_ids = [7, 8]          # the target decoded 2 of them itself
    out = md.propose_batch([(0, req)])
    assert out == {0: [9, 10]}    # the salvaged tail, not a miss


def test_mesh_outpaced_correct_draft_is_not_a_miss():
    """A draft fully outrun by plain decode whose every token matched is
    right-but-slow: penalizing it would fail the probe on latency, not
    accuracy."""
    wire = _Wire()
    md = MeshDrafter(2)
    md.attach_transport(wire)
    req = _Req([1, 2, 3])
    md.propose_batch([(0, req)])
    md.deliver({"rid": wire.sent[0]["rid"], "pos": 3, "draft": [7, 8]})
    req.out_ids = [7, 8, 9]       # outpaced: delta 3 >= len(draft) 2
    out = md.propose_batch([(0, req)])
    # not consumable, but None (free), and a fresh request went out
    assert out == {0: None}
    assert wire.sent[-1]["tokens"][-1] == 9


def test_mesh_mispredicted_stale_draft_is_a_miss():
    """A stale draft whose prefix does NOT match the produced tokens is
    a real misprediction — it must count against the probe budget, or a
    bad peer could ride pending/stale cycles through its audition."""
    wire = _Wire()
    md = MeshDrafter(4)
    md.attach_transport(wire)
    req = _Req([1, 2, 3])
    md.propose_batch([(0, req)])
    md.deliver({"rid": wire.sent[0]["rid"], "pos": 3, "draft": [7, 8, 9, 10]})
    req.out_ids = [7, 99]         # prefix mismatch at the second token
    assert md.propose_batch([(0, req)]) == {0: []}   # [] = counted miss


def test_mesh_timeout_resends_full_then_dies_typed():
    wire = _Wire()
    md = MeshDrafter(4, timeout_s=0.0, max_failures=2)
    md.attach_transport(wire)
    req = _Req([1, 2, 3])
    md.propose_batch([(0, req)])              # submit; deadline = now
    time.sleep(0.005)
    out = md.propose_batch([(0, req)])        # first timeout
    assert out == {0: []}                     # a timeout is a real miss
    assert wire.sent[-1]["base"] == 0         # recovery is a full resend
    time.sleep(0.005)
    assert md.propose_batch([(0, req)]) == {0: []}
    assert md.dead and md.dead_reason == "timeout"
    # dead drafter: propose never blocks, always returns the empty miss
    assert md.propose_batch([(0, req)]) == {0: []}


def test_mesh_send_failure_is_no_peer():
    wire = _Wire()
    wire.ok = False
    md = MeshDrafter(4)
    md.attach_transport(wire)
    req = _Req([1, 2])
    # the failing submit itself is free (the row just skips the step);
    # the dead flag is what the scheduler reads to degrade the row
    assert md.propose_batch([(0, req)]) == {0: None}
    assert md.dead and md.dead_reason == "no_peer"
    assert md.propose_batch([(0, req)]) == {0: []}
    md2 = MeshDrafter(4)                      # no transport attached at all
    md2.propose_batch([(0, req)])
    assert md2.dead and md2.dead_reason == "no_peer"


def test_mesh_error_frames_kill_after_max_failures():
    wire = _Wire()
    md = MeshDrafter(4, max_failures=2)
    md.attach_transport(wire)
    req = _Req([1, 2, 3])
    md.propose_batch([(0, req)])
    rid = wire.sent[0]["rid"]
    md.deliver({"rid": rid, "error": "draft_failed"})
    assert not md.dead
    md.propose_batch([(0, req)])              # resubmits (inflight cleared)
    md.deliver({"rid": rid, "error": "draft_failed"})
    assert md.dead and md.dead_reason == "peer_lost"


def test_mesh_reprime_and_stale_results():
    wire = _Wire()
    md = MeshDrafter(4)
    md.attach_transport(wire)
    req = _Req([1, 2, 3])
    md.propose_batch([(0, req)])
    rid = wire.sent[0]["rid"]
    # a result for a position we are not waiting on is dropped
    md.deliver({"rid": rid, "pos": 99, "draft": [5, 5, 5]})
    assert md.propose_batch([(0, req)]) == {0: None}
    # peer lost our baseline (restart/eviction): reprime forces the next
    # submit to ship the full context again
    md.deliver({"rid": rid, "reprime": True})
    md.propose_batch([(0, req)])
    assert wire.sent[-1]["base"] == 0
    # unknown rid: ignored entirely
    md.deliver({"rid": "bogus", "pos": 3, "draft": [1]})


def test_mesh_forget_frees_the_server_row():
    wire = _Wire()
    md = MeshDrafter(4)
    md.attach_transport(wire)
    req = _Req([1, 2, 3])
    md.propose_batch([(0, req)])
    md.forget(req)
    assert wire.sent[-1] == {"rid": wire.sent[0]["rid"], "done": True}
    # forgotten row: a late result is a no-op, a new propose re-keys
    md.deliver({"rid": wire.sent[0]["rid"], "pos": 3, "draft": [1]})
    assert md.propose_batch([(0, req)]) == {0: None}


# --------------------------------------------- model tier: greedy parity


def _tier_stats(eng):
    return dict(eng.scheduler.stats.spec_tiers)


def test_model_tier_parity_and_escalation(ref_engine, model_engine):
    """THE acceptance bar for the model tier: on a prompt where the
    n-gram tier drafts nothing, rows escalate to the resident model
    drafter and output stays token-for-token identical — with the
    same-seed drafter accepting everything it proposes."""
    r0 = ref_engine.generate(NONREP, max_new_tokens=32, temperature=0.0)
    r1 = model_engine.generate(NONREP, max_new_tokens=32, temperature=0.0)
    assert r1.token_ids == r0.token_ids
    tiers = _tier_stats(model_engine)
    assert tiers.get("model", {}).get("drafted", 0) > 0, (
        "the n-gram tier never escalated to the model drafter"
    )
    mt = tiers["model"]
    assert mt["accepted"] == mt["drafted"]    # weight-identical drafter


def test_model_tier_stop_token_inside_draft(ref_engine, model_engine):
    free = ref_engine.generate(NONREP, max_new_tokens=24, temperature=0.0)
    stop_at = free.token_ids[10]
    cut = free.token_ids.index(stop_at)       # first occurrence wins
    r = model_engine.generate(
        NONREP, max_new_tokens=24, temperature=0.0, stop_tokens=[stop_at]
    )
    assert r.token_ids == free.token_ids[:cut]
    assert r.finish_reason == "stop"


@pytest.mark.slow  # batch-of-2 root compiles dominate; single-row parity
# and per-row tier gating already ride tier-1 above
def test_model_tier_mixed_batch(ref_engine, model_engine):
    """Greedy rows escalate to the model drafter while a sampled row in
    the same batch advances normally; everyone completes and the greedy
    rows keep parity."""
    truth = ref_engine.generate(
        NONREP, max_new_tokens=24, temperature=0.0
    ).token_ids
    results: dict = {}

    def run(tag, prompt, temp):
        results[tag] = model_engine.generate(
            prompt, max_new_tokens=24, temperature=temp
        )

    threads = [
        threading.Thread(target=run, args=("g0", NONREP, 0.0)),
        threading.Thread(target=run, args=("s", REP_PROMPT, 0.9)),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert results["g0"].token_ids == truth
    assert len(results["s"].token_ids) == 24


@pytest.mark.slow  # the 96-token prompt compiles a fresh prefill bucket on
# both engines; the veto itself is shape-independent host logic
def test_model_tier_near_capacity_fallback(ref_engine, model_engine):
    """Rows within K+1 of cache capacity must not take the verify path —
    parity right up to the cache-imposed length cap, model tier active.
    A near-capacity prompt (cap − 32) generating past the cap forces
    every row through the veto and the capacity re-anchor mid-stream."""
    long_prompt = [1 + (j * 97) % 499 for j in range(96)]
    r0 = ref_engine.generate(long_prompt, max_new_tokens=44, temperature=0.0)
    r1 = model_engine.generate(long_prompt, max_new_tokens=44, temperature=0.0)
    assert r1.token_ids == r0.token_ids
    assert _tier_stats(model_engine).get("model", {}).get("drafted", 0) > 0


def test_bad_drafter_demotes_to_off_with_parity(ref_engine):
    """A drafter at a DIFFERENT seed proposes garbage: verify rejects it,
    the probe fails the model tier, and with the ladder exhausted the row
    lands on "off" — output parity untouched (the verify path guarantees
    it regardless of draft quality)."""
    eng = InferenceEngine(
        "tiny-llama",
        engine_config=EngineConfig(
            **SPEC_KW, drafter="tiny-llama", drafter_seed=1234
        ),
    )
    try:
        r0 = ref_engine.generate(NONREP, max_new_tokens=24, temperature=0.0)
        r1 = eng.generate(NONREP, max_new_tokens=24, temperature=0.0)
        assert r1.token_ids == r0.token_ids
        tiers = _tier_stats(eng)
        mt = tiers.get("model", {"drafted": 0, "accepted": 0})
        if mt["drafted"]:                     # probe engaged the bad tier
            assert mt["accepted"] < mt["drafted"]
    finally:
        eng.close()


def test_per_tier_counters_on_metrics(model_engine):
    """The per-tier accounting surfaces on /metrics: labeled counters and
    the acceptance gauge the meter refresh publishes."""
    model_engine.generate(NONREP, max_new_tokens=24, temperature=0.0)
    reg = get_registry()
    assert reg.counter("engine.spec_drafted").value(tier="model") > 0
    assert reg.counter("engine.spec_accepted").value(tier="model") > 0
    spec_tiers = (model_engine.introspect.meter.refresh() or {}).get(
        "spec_tiers", {}
    )
    assert spec_tiers.get("model", {}).get("drafted", 0) > 0
    text = reg.render()
    assert 'bee2bee_engine_spec_drafted_total{tier="model"}' in text
    assert "bee2bee_engine_spec_acceptance" in text


# ------------------------------------------------- mesh tier, end to end


class _FakePeer:
    """In-process draft peer: serves draft_request payloads off a known
    greedy continuation on its own thread (the real transport delivers
    off the scheduler thread too, so this exercises the same locking).
    ``stop_after`` kills the peer after N served drafts — the typed
    peer_lost path, mid-generation."""

    def __init__(self, truth, k, stop_after=None):
        self.truth = list(truth)
        self.k = k
        self.stop_after = stop_after
        self.served = 0
        self.md = None                        # bound MeshDrafter
        self._ctx: dict[str, list] = {}
        self._q: queue.Queue = queue.Queue()
        self._t = threading.Thread(target=self._run, daemon=True)
        self._t.start()

    def send(self, payload):
        self._q.put(dict(payload))
        return True

    def close(self):
        self._q.put(None)
        self._t.join(timeout=5)

    def _run(self):
        while True:
            p = self._q.get()
            if p is None:
                return
            rid = p["rid"]
            if p.get("done"):
                self._ctx.pop(rid, None)
                continue
            base = int(p.get("base") or 0)
            ctx = self._ctx.setdefault(rid, [])
            if base == 0:
                ctx[:] = list(p["tokens"])
            elif base == len(ctx):
                ctx.extend(p["tokens"])
            else:
                self.md.deliver({"rid": rid, "reprime": True})
                continue
            if self.stop_after is not None and self.served >= self.stop_after:
                self.md.peer_lost()           # the connection died
                continue
            pos = len(ctx)
            self.served += 1
            self.md.deliver(
                {"rid": rid, "pos": pos,
                 "draft": self.truth[pos:pos + self.k]}
            )


def _mesh_engine_with_peer(truth, stop_after=None):
    eng = InferenceEngine(
        "tiny-llama",
        engine_config=EngineConfig(**SPEC_KW, drafter="mesh"),
    )
    md = eng.scheduler.mesh_drafter
    assert md is not None
    md.timeout_s = 30.0                       # CI boxes compile slowly
    peer = _FakePeer(truth, eng.engine_cfg.spec_tokens, stop_after=stop_after)
    peer.md = md
    md.attach_transport(peer.send)
    return eng, peer


def test_mesh_tier_parity_then_peer_death_degrades_typed(ref_engine):
    """One peer lifecycle, both halves of the contract: with the peer
    alive the mesh tier engages and every truth-fed draft is accepted
    (full parity); then the peer dies and the NEXT generation demotes to
    the local tier (typed, counted) and still completes with parity —
    zero dropped rows, decode never stalls."""
    reg = get_registry()
    degraded0 = reg.counter("engine.spec_mesh_degraded").value(
        reason="peer_lost"
    )
    r0 = ref_engine.generate(NONREP, max_new_tokens=40, temperature=0.0)
    eng, peer = _mesh_engine_with_peer(list(NONREP) + list(r0.token_ids))
    try:
        # warm on a repetitive prompt: the verify root compiles under the
        # n-gram tier, so mesh drafts never race a multi-second jit
        eng.generate(REP_PROMPT, max_new_tokens=12, temperature=0.0)
        r1 = eng.generate(NONREP, max_new_tokens=40, temperature=0.0)
        assert r1.token_ids == r0.token_ids
        tiers = _tier_stats(eng)
        assert tiers.get("mesh", {}).get("drafted", 0) > 0, (
            "the mesh tier never engaged against the fake peer"
        )
        mt = tiers["mesh"]
        assert mt["accepted"] == mt["drafted"]  # truth-fed peer: all accepted

        # kill the peer on its next frame: mid-generation typed degrade
        peer.stop_after = peer.served
        r2 = eng.generate(NONREP, max_new_tokens=40, temperature=0.0)
        assert r2.token_ids == r0.token_ids
        assert len(r2.token_ids) == 40        # nothing dropped or truncated
        md = eng.scheduler.mesh_drafter
        assert md.dead and md.dead_reason == "peer_lost"
        assert reg.counter("engine.spec_mesh_degraded").value(
            reason="peer_lost"
        ) > degraded0
    finally:
        eng.close()
        peer.close()


def test_ngram_tier_still_first_on_repetitive_prompts(ref_engine, model_engine):
    """The ladder starts at the zero-cost floor: on a repetitive prompt
    the n-gram tier drafts successfully and the model tier is never
    consulted for those rows."""
    before = _tier_stats(model_engine).get("ngram", {}).get("drafted", 0)
    r0 = ref_engine.generate(REP_PROMPT, max_new_tokens=30, temperature=0.0)
    r1 = model_engine.generate(REP_PROMPT, max_new_tokens=30, temperature=0.0)
    assert r1.token_ids == r0.token_ids
    assert _tier_stats(model_engine).get("ngram", {}).get("drafted", 0) > before


def test_meshdrafter_validates_spec_tokens():
    with pytest.raises(ValueError):
        MeshDrafter(0)
    assert isinstance(NgramDrafter(4, 1, 4), object)  # ctor smoke
