"""K-EXAONE through the ENGINE (tests/test_exaone.py has the model): the ``mtp``
tier, the model's own multi-token-prediction layer as its drafter. Speculation
changes nothing (greedy text with it equals the text without, token for token,
over rows whose drafts are accepted, rejected and mixed), the drafts the engine
verifies are the plain reference's MTP layer's greedy tokens, a chunked prefill
hands the layer the prompt's next token, the other tiers stay refused, and the
counters count. All at ``tiny-exaone`` size on the CPU."""

from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bee2bee_tpu.engine import EngineConfig, InferenceEngine
from bee2bee_tpu.models import core
from bee2bee_tpu.models.config import get_config

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "benchmark"))
import reference_exaone as plain  # noqa: E402  (the benchmark's plain reference)

CFG = get_config("tiny-exaone")
ENGINE_KW = dict(max_seq_len=128, dtype="float32", cache_dtype="float32",
                 decode_chunk=4, max_batch=4, prefill_buckets=(16, 32, 64),
                 kv_block_size=8, spec_min_accept=0.0)


def _dims(cfg) -> dict:
    n = cfg.n_layers
    return dict(plain.dims_of_preset(cfg), sliding_windows=list(cfg.layer_windows[:n]),
                rope_parameters={"rope_theta": cfg.rope_theta})




def _engine(model="tiny-exaone", params=None, **over) -> InferenceEngine:
    return InferenceEngine(model, params=params,
                           engine_config=EngineConfig(**{**ENGINE_KW, **over}))


def _prompt(seed: int, n: int) -> list[int]:
    return [1] + [int(t) for t in np.random.RandomState(seed).randint(3, 259, n - 1)]


def _echo_weights(kind: str):
    """tiny-exaone's seeded weights with an MTP layer that is RIGHT on every
    position (``accepted``), on some (``mixed``) or on none to speak of
    (``rejected``: the seeded layer as it is). The echo: a trunk whose branches
    add ``alpha`` times their normed output, so that at alpha 0 the next token is
    a function of the last one alone, and an MTP layer that passes the next
    token's normed embedding through and adds nothing: it then says what the
    trunk will say of that token, exactly at alpha 0, mostly at a small alpha."""
    p = jax.device_get(core.init_params(CFG, jax.random.key(0), dtype=jnp.float32))
    if kind == "rejected":
        return p
    alpha = {"accepted": 0.0, "mixed": 0.22}[kind]

    def quiet(group, a):
        return dict(group, ln1_post={"scale": np.full_like(group["ln1_post"]["scale"], a)},
                    ln2_post={"scale": np.full_like(group["ln2_post"]["scale"], a)})

    D = CFG.d_model
    mtp = dict(p["mtp"], block=quiet(p["mtp"]["block"], 0.0),
               eh_proj=np.concatenate([np.eye(D, dtype=np.float32),
                                       np.zeros((D, D), np.float32)]))
    return dict(p, layers=quiet(p["layers"], alpha),
                dense_layers=quiet(p["dense_layers"], alpha), mtp=mtp)


def _spy_on_the_verify_steps(eng, seen: list):
    """Every verify step the engine runs from here on, read out of its verify
    windows' own buffers: (cur, drafts, draft lengths, offsets) of the rows at
    each step, as the serialized step's arguments were."""
    window = eng._spec_window

    def spy(params, cur, draft, drafting, budget, pool, offsets, *rest, steps, **kw):
        cur, start = np.asarray(cur).copy(), np.asarray(offsets).copy()
        drafting, budget = np.asarray(drafting).copy(), np.asarray(budget).copy()
        out = window(params, cur, draft, drafting, budget, pool, offsets, *rest,
                     steps=steps, **kw)
        toks, accs, off = np.asarray(out[4]), np.asarray(out[5]), start
        for i in range(int(steps)):
            lens = np.where(drafting > 0, np.clip(budget - (off - start) - 1, 0, 1), 0)
            seen.append((cur, toks[i, :, :-1], lens, off))
            cur, off = toks[i, :, -1], off + accs[i] + 1
        return out

    eng._spec_window = spy


@pytest.mark.parametrize("kind", ["accepted", "rejected", "mixed"])
def test_speculation_changes_nothing(kind):
    """Greedy text with the ``mtp`` tier on equals the text with it off, token
    for token, over rows whose drafts are all accepted, all rejected and mixed,
    alone and four rows at once."""
    import threading

    weights = _echo_weights(kind)
    spec = {0: (21, 24), 1: (9, 17), 2: (30, 20), 3: (13, 26)}

    def texts(spec_tokens: int):
        eng = _engine(params=jax.tree.map(jnp.asarray, weights), spec_tokens=spec_tokens)
        got: dict[int, list[int]] = {}

        def run(seed):
            n, new = spec[seed]
            got[seed] = eng.generate(_prompt(seed, n), max_new_tokens=new).token_ids

        try:
            run(0)
            alone = got.pop(0)
            threads = [threading.Thread(target=run, args=(s,)) for s in spec]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert got[0] == alone
            st = eng.scheduler.stats
            return got, (st.spec_steps, st.spec_drafted, st.spec_accepted,
                         dict(st.spec_tiers))
        finally:
            eng.close()

    plain_text, (steps0, *_rest) = texts(0)
    spec_text, (steps, drafted, accepted, tiers) = texts(1)
    assert steps0 == 0 and steps > 0
    assert spec_text == plain_text
    assert set(tiers) == {"mtp"} and tiers["mtp"]["drafted"] == drafted > 40
    if kind == "accepted":
        assert accepted >= 0.9 * drafted
        assert steps < 0.7 * sum(new for _, new in spec.values())  # two tokens a step
    elif kind == "rejected":
        assert accepted <= 0.1 * drafted
    else:
        assert 0.15 * drafted < accepted < 0.9 * drafted


def test_the_engines_drafts_are_the_references_mtp_argmax_and_its_text_the_trunks():
    """The draft the engine verifies at every step is the plain reference's MTP
    layer's greedy token for that position (the engine's draft logits, through
    prefill, pool and verify steps, order as the reference's do), and the text is
    the reference trunk's greedy text."""
    eng = _engine(spec_tokens=1)
    seen = []
    _spy_on_the_verify_steps(eng, seen)
    try:
        full = jax.tree.map(jnp.asarray, core.restack_layers(eng.params))
        dims = _dims(eng.model_cfg)
        ids = _prompt(0, 21)
        got = eng.generate(list(ids), max_new_tokens=14).token_ids
        whole = np.asarray([ids + got], np.int32)
        ref, ref_mtp = plain.full_forward(dims, full, whole)
        assert got == [int(t) for t in ref[0, len(ids) - 1:-1].argmax(-1)]
        # (a window's steps past the row's last token made nothing that was kept)
        seen = [step for step in seen if step[3][0] < whole.shape[1]]
        assert len(seen) >= 10
        for cur, drafts, lens, offsets in seen:
            p = int(offsets[0])  # ``cur`` sits at p: the draft is of token p + 1
            assert lens[0] == 1 and cur[0] == whole[0, p]
            assert drafts[0, 0] == int(ref_mtp[0, p - 1].argmax())
        info = eng.info
        assert info["kv"]["cache_layers"] == 6
        assert eng.scheduler.cache.pool["kv"].shape[0] == 6
    finally:
        eng.close()


def test_chunked_prefill_hands_the_mtp_layer_the_prompts_next_token():
    """A prompt that walks three chunks of 16: the MTP rows at the chunk ends are
    made with the PROMPT's next token, so the drafts equal the unchunked run's."""
    def drafts_of(**over):
        eng = _engine(spec_tokens=1, **over)
        seen = []
        _spy_on_the_verify_steps(eng, seen)
        try:
            text = eng.generate(_prompt(4, 41), max_new_tokens=8).token_ids
            return text, [int(drafts[0, 0]) for _, drafts, _, _ in seen]
        finally:
            eng.close()

    assert drafts_of(prefill_chunk=16) == drafts_of()


def test_the_tier_is_the_models_own_and_other_speculation_is_refused():
    from bee2bee_tpu.engine import FeatureUnsupported

    eng = _engine(spec_tokens=1)
    try:
        assert eng.mtp_on and set(eng.scheduler._spec.tiers) == {"mtp"}
    finally:
        eng.close()
    off = _engine(spec_tokens=0)
    try:
        assert not off.mtp_on and off.scheduler._spec is None
    finally:
        off.close()
    with pytest.raises(ValueError, match="spec_tokens=2"):
        _engine(spec_tokens=2)
    with pytest.raises(FeatureUnsupported, match="spec_model_drafter"):
        _engine(spec_tokens=1, drafter="tiny-llama")
    with pytest.raises(FeatureUnsupported, match="prefix_cache"):
        _engine(prefix_cache_entries=4)


def test_a_row_a_decode_window_carried_past_its_draft_leaves_the_tier():
    """A sampled row rides the decode windows; a greedy row whose context moved
    on without a verify step has no draft and goes to ``off``."""
    from types import SimpleNamespace

    from bee2bee_tpu.engine.spec import MtpDrafter

    req = SimpleNamespace(ids=[1, 5, 6], out_ids=[9], mtp_draft=(4, 77))
    assert MtpDrafter().propose_batch([(0, req)]) == {0: [77]}
    req.out_ids.append(10)  # a decode window's token: the draft is stale
    assert MtpDrafter().propose_batch([(0, req)]) == {0: []}
    req.mtp_draft = None
    assert MtpDrafter().propose_batch([(2, req)]) == {2: []}
    eng = _engine(spec_tokens=1)
    try:
        sampled = eng.generate(_prompt(2, 12), max_new_tokens=9, temperature=0.8, top_k=20)
        assert len(sampled.token_ids) == 9
        assert eng.scheduler.stats.spec_drafted == 0
    finally:
        eng.close()


def test_counters_count_the_verify_steps_the_tier_and_the_share():
    import bee2bee_tpu.engine.scheduler  # noqa: F401  (registers the metrics)
    from bee2bee_tpu.metrics import get_registry

    reg = get_registry()
    assign, hit = reg.get("engine.moe_assignments"), reg.get("engine.moe_experts_hit")
    layer_calls = reg.get("engine.moe_layer_calls")
    drafted, accepted = reg.get("engine.spec_drafted"), reg.get("engine.spec_accepted")
    eng = _engine(spec_tokens=1)
    try:
        was = (layer_calls.value(), hit.value(), drafted.value(tier="mtp"),
               accepted.value(tier="mtp"),
               {k: assign.value(kind=k) for k in ("live", "elsewhere", "dead")})
        out = eng.generate(_prompt(0, 21), max_new_tokens=8)
        st = eng.scheduler.stats
        steps = st.spec_steps
        assert steps >= 4 and drafted.value(tier="mtp") - was[2] == st.spec_drafted >= steps - 1
        assert accepted.value(tier="mtp") - was[3] == st.spec_accepted
        assert len(out.token_ids) == 8
        # every decode step was a verify (the last token's too): no decode window
        assert st.chunks == 0
        # five expert-layer calls a forward: four trunk layers and the MTP block's
        forwards = 1 + steps
        assert layer_calls.value() - was[0] == forwards * 5
        now = {k: assign.value(kind=k) - was[4][k] for k in was[4]}
        # the prefill's 21 positions and every verify step's two, four choices each
        # in five expert layers, here or elsewhere
        assert now["live"] + now["elsewhere"] == (21 + 2 * steps) * 4 * 5
        assert now["live"] > 0 and now["elsewhere"] > now["live"]  # 4 of 16 held
        assert 0 < hit.value() - was[1] <= forwards * 5 * 4
    finally:
        eng.close()


# ------------------------------------------------------------ the verify window


def _programs_start(eng, pen: bool):
    """Four rows prefilled into a fresh pool by the engine's own program -> the
    operands of a verify step: row 0 greedy with its first draft FORCED accepted
    (the program's own next token), row 1 greedy with a budget that ends inside
    the window, row 2 sampled, row 3 sampled and, with ``pen``, penalised (its
    counts ride)."""
    R, BS, V = 4, eng.engine_cfg.kv_block_size, eng.model_cfg.vocab_size
    lengths = np.asarray([21, 9, 30, 13], np.int32)
    pages = -(-(int(lengths.max()) + 2 * eng.engine_cfg.decode_chunk + 2) // BS)
    tables = np.zeros((R, 1 << (pages - 1).bit_length()), np.int32)
    tables[:, :pages] = 1 + np.random.default_rng(0).permutation(R * pages).reshape(R, pages)
    tok = np.zeros((R, 32), np.int32)
    for r, n in enumerate(lengths):
        tok[r, :n] = _prompt(r, int(n))
    zero = np.zeros((R,), np.int32)
    pool, logits, extras = eng._prefill(
        eng.params, tok, eng.new_pool(), lengths, zero, tables, zero, lengths,
        mtp_next=np.full((R,), -1, np.int32))
    cur = np.asarray(logits).argmax(-1).astype(np.int32)
    draft = np.asarray(extras["mtp_draft"]).astype(np.int32)
    sampling = (np.asarray([0, 0, 0.8, 0.7], np.float32), np.asarray([0, 0, 20, 0], np.int32),
                np.asarray([1, 1, 1, 0.9], np.float32))
    more = {}
    if pen:
        counts = np.zeros((R, 2, V), np.int32)
        for r, n in enumerate(lengths):
            counts[r, 0] = np.bincount(tok[r, :n], minlength=V)
        more = dict(counts=jnp.asarray(counts), reps=np.asarray([1, 1, 1, 1.3], np.float32),
                    press=np.asarray([0, 0, 0, 0.2], np.float32),
                    freqs=np.asarray([0, 0, 0, 0.1], np.float32))
    return dict(pool=pool, tables=tables, cur=cur, draft=draft, offsets=lengths.copy(),
                sampling=sampling, more=more,
                drafting=np.asarray([1, 1, 0, 0], np.int32),
                budget=np.asarray([40, 3, 40, 40], np.int32))


def _pool_rows(pool, tables, r: int, upto: int):
    """Row r's cached positions below ``upto``, every cache layer (the MTP
    block's is the last): [L, upto, 2, Hkv, hd]."""
    kv = np.asarray(pool["kv"])
    BS = kv.shape[4]
    at = np.arange(upto)
    return kv[:, tables[r, at // BS], :, :, at % BS, :]


@pytest.fixture(scope="module")
def window_engine():
    eng = _engine(params=jax.tree.map(jnp.asarray, _echo_weights("mixed")), spec_tokens=1)
    yield eng
    eng.close()


@pytest.mark.parametrize("pen", [False, True], ids=["plain", "counts"])
@pytest.mark.parametrize("n", [1, 3, 4])
def test_a_verify_window_is_its_steps_chained_by_hand(window_engine, n, pen):
    """A window of n steps (1, 3, decode_chunk) computes what n _spec_verify
    calls chained on the host do with the same keys: the tokens, the verdicts,
    the final cur / draft / offsets / counts, the pool's rows below every row's
    offset in every cache layer and the MTP layer's."""
    eng = window_engine
    N, key = eng.engine_cfg.decode_chunk, jax.random.key(7)
    keys = jax.random.split(key, N)
    # the program's own next token of row 0: its first draft is then accepted
    s = _programs_start(eng, pen)
    own, *_ = eng._spec_verify(
        eng.params, s["cur"], s["draft"][:, None], np.zeros((4,), np.int32), s["pool"],
        s["offsets"], *s["sampling"], None, keys[0], s["tables"], **s["more"])
    forced = int(np.asarray(own)[0])

    # by hand: today's serialized steps, the draft carried by the host
    s = _programs_start(eng, pen)
    s["draft"][0] = forced
    pool, cur, draft, off, more = s["pool"], s["cur"], s["draft"].copy(), s["offsets"], dict(s["more"])
    toks, accs = [], []
    for i in range(n):
        left = s["budget"] - (off - s["offsets"])
        lens = (s["drafting"] * np.clip(left - 1, 0, 1)).astype(np.int32)
        nxt, pool, acc, extras, *cnt = eng._spec_verify(
            eng.params, cur, draft[:, None], lens, pool, off, *s["sampling"], None,
            keys[i], s["tables"], **more)
        if pen:
            more["counts"] = cnt[0]
        nxt, acc = np.asarray(nxt), np.asarray(acc)
        toks.append(np.stack([draft, nxt], axis=1))
        accs.append(acc)
        cur, draft, off = nxt, np.asarray(extras["mtp_draft"]), off + acc + 1
    toks, accs = np.stack(toks), np.stack(accs)
    assert accs[0, 0] == 1  # the forced draft
    assert (accs[:, 2:] == 0).all()  # sampled rows never draft
    if n >= 3:
        assert accs[:, :2].sum() < accs[:, :2].size  # ... and some draft is rejected
        # row 1's budget is 3 tokens: it stops drafting once 2 are given
        given = np.cumsum(accs[:, 1] + 1) - (accs[:, 1] + 1)
        assert (accs[given >= 2, 1] == 0).all()

    # the window: the same steps, the draft fed back on the device
    w = _programs_start(eng, pen)
    w["draft"][0] = forced
    wcur, wpool, woff, wcnt, wtoks, waccs, wdraft, extras = eng._spec_window(
        eng.params, w["cur"], w["draft"][:, None], w["drafting"], w["budget"], w["pool"],
        w["offsets"], *w["sampling"], None, key, w["tables"], **w["more"],
        steps=np.int32(n))
    assert np.asarray(wtoks).shape == (N, 4, 2) and np.asarray(waccs).shape == (N, 4)
    np.testing.assert_array_equal(np.asarray(wtoks)[:n], toks)
    np.testing.assert_array_equal(np.asarray(waccs)[:n], accs)
    np.testing.assert_array_equal(np.asarray(wcur), cur)
    np.testing.assert_array_equal(np.asarray(wdraft)[:, 0], draft)
    np.testing.assert_array_equal(np.asarray(woff), off)
    assert (wcnt is None) == (not pen)
    if pen:
        np.testing.assert_array_equal(np.asarray(wcnt), np.asarray(more["counts"]))
        assert int(np.asarray(wcnt)[3, 1].sum()) == n  # a token a step, generated
    assert extras["moe_stats"].shape == (len(core.moe_stats_names(eng.model_cfg)),)
    for r in range(4):
        np.testing.assert_allclose(
            _pool_rows(wpool, w["tables"], r, int(off[r])),
            _pool_rows(pool, s["tables"], r, int(off[r])), rtol=0, atol=0)
