"""K-EXAONE (exaone_moe: L(dense) L L G L behind a window with RoPE, the full
layer without positions, sigmoid-routed experts top-k beside a shared expert with
the chip holding a SHARE, a sliced untied vocabulary, and a multi-token-
prediction layer as the model's OWN drafter): the model against the plain
reference in LOGITS (trunk and MTP layer) through prefill, verify steps and the
paged pool, the shares adding up and the checkpoint round trip (the engine's
side: tests/test_exaone_engine.py). All at ``tiny-exaone`` size on the CPU."""

from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bee2bee_tpu.engine import EngineConfig
from bee2bee_tpu.models import core
from bee2bee_tpu.models.config import CONFIGS, ModelConfig, config_from_hf, get_config
from bee2bee_tpu.ops.ragged import make_ragged_attn_fn

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "benchmark"))
import reference_exaone as plain  # noqa: E402  (the benchmark's plain reference)

CFG = get_config("tiny-exaone")
WHOLE = dataclasses.replace(CFG, n_experts_held=0, expert_first=0)  # every expert held
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


def _published() -> dict:
    """The catalog row's ``config`` (the published config.json's numbers)."""
    if not CATALOG.is_file():
        pytest.skip("the model catalog is not on this machine")
    for line in CATALOG.read_text().splitlines():
        row = json.loads(line)
        if row["name"] == "K-EXAONE-236B-A23B":
            return row["config"]
    pytest.skip("the catalog has no K-EXAONE row")


def _whole_params(key=3):
    """Seeded weights with EVERY expert held and nothing hiding behind an init
    value (every norm scale random)."""
    p = core.init_params(WHOLE, jax.random.key(key), dtype=jnp.float32)
    k = iter(jax.random.split(jax.random.key(4), 16))

    def scales(group):
        group = dict(group, attn=dict(group["attn"]))
        for ln in ("ln1_post", "ln2_post"):
            group[ln] = {"scale": 0.5 + jax.random.uniform(next(k), group[ln]["scale"].shape)}
        for n in ("q_norm", "k_norm"):
            group["attn"][n] = 0.5 + jax.random.uniform(next(k), group["attn"][n].shape)
        return group

    mtp = dict(p["mtp"], block=scales(p["mtp"]["block"]))
    for n in ("enorm", "hnorm"):
        mtp[n] = {"scale": 0.5 + jax.random.uniform(next(k), mtp[n]["scale"].shape)}
    return dict(p, layers=scales(p["layers"]), dense_layers=scales(p["dense_layers"]), mtp=mtp)


def _share(params, first: int, held: int):
    """The chip's share of ``params``' experts: the stacks cut to [first, first + held)."""
    def cut(group):
        moe = dict(group["moe"])
        for n in ("w_gate", "w_up", "w_down"):
            moe[n] = moe[n][:, first:first + held]
        return dict(group, moe=moe)

    return dict(params, layers=cut(params["layers"]),
                mtp=dict(params["mtp"], block=cut(params["mtp"]["block"])))


@pytest.fixture(scope="module")
def whole():
    return _whole_params()


@pytest.fixture(scope="module")
def params(whole):  # tiny-exaone's own share: experts 4..7
    return _share(whole, CFG.expert_first, CFG.n_experts_held)


def _ids(rows: int, n: int, seed: int = 0):
    return np.random.RandomState(seed).randint(3, CFG.vocab_size, (rows, n)).astype(np.int32)


def _dims(cfg: ModelConfig) -> dict:
    n = cfg.n_layers
    return dict(plain.dims_of_preset(cfg), sliding_windows=list(cfg.layer_windows[:n]),
                rope_parameters={"rope_theta": cfg.rope_theta})


def _program_logits(params, cfg, ids):
    """(trunk logits, MTP logits [R, T - 1, V]) of the program's cache-less forward."""
    ids = jnp.asarray(ids)
    logits, _, hidden = core.forward(params, cfg, ids, None, 0, return_hidden=True)
    mtp, _ = core.mtp_forward(params, cfg, hidden[:, :-1], ids[:, 1:], None, 0)
    return np.asarray(logits), np.asarray(mtp)


# ------------------------------------------------------------------ the model


def test_the_preset_is_the_shape_the_published_model_has():
    assert CFG.layer_windows == (8, 8, 8, 0, 8, 0)  # L L L G L, then the MTP block: full
    assert CFG.cache_layers == 6 and CFG.n_expert_layers == 4 and CFG.n_expert_calls == 5
    assert CFG.experts_held == 4 and CFG.expert_share and CFG.vocab_published == 512
    assert core.pool_bytes_per_token(CFG, 4) == 6 * 2 * 2 * 16 * 4
    big = get_config("k-exaone-236b-a23b-5l-e16")
    assert big.layer_windows == (128, 128, 128, 0, 128, 0) and big.head_dim == 128
    assert core.pool_bytes_per_token(big) == 24576  # 6 layers x 2 x 8 x 128 x 2 B
    p = jax.eval_shape(lambda: core.init_params(big, jax.random.key(0), jnp.bfloat16))
    assert p["layers"]["moe"]["w_up"].shape == (4, 16, 6144, 2048)
    assert p["layers"]["moe"]["router"].shape == (4, 6144, 128)
    assert "router_bias" not in p["layers"]["moe"]  # no selection bias is added
    assert p["mtp"]["eh_proj"].shape == (12288, 6144)
    assert p["mtp"]["block"]["moe"]["w_down"].shape == (1, 16, 2048, 6144)
    assert p["tok_embed"].shape == (19200, 6144) and p["lm_head"].shape == (6144, 19200)
    assert p["dense_layers"]["mlp"]["w_up"].shape == (1, 6144, 18432)
    weights = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in jax.tree.leaves(p))
    assert 9.05e9 < weights < 9.15e9  # ISSUE 54's arithmetic: 9.09 GB


@pytest.mark.parametrize("cfg_name", ["share", "whole"])
def test_forward_and_the_mtp_layer_match_the_plain_reference(whole, params, cfg_name):
    cfg, p = (CFG, params) if cfg_name == "share" else (WHOLE, whole)
    ids = _ids(2, 29)  # past the window of 8 three times over
    ref, ref_mtp = plain.full_forward(_dims(cfg), p, ids)
    with jax.default_matmul_precision("highest"):
        got, got_mtp = _program_logits(p, cfg, ids)
    assert ref.std() > 0.5 and ref_mtp.std() > 0.5
    np.testing.assert_allclose(got, ref, atol=3e-5)
    np.testing.assert_allclose(got_mtp, ref_mtp, atol=3e-5)
    # one position a call (the comparison's own entry) is the same forward
    at, _ = plain.forward_logits(_dims(cfg), p, ids, 17)
    np.testing.assert_allclose(at, ref[:, 17], atol=1e-6)


@pytest.mark.parametrize("perturb", [
    {"window_off": 1}, {"rope_global": True}, {"drop": "qk_norm"},
    {"drop": "routed_scaling_factor"}, {"drop": "norm_topk_prob"}, {"drop": "shared_expert"},
    {"expert_first": 8}, {"dense_as_sparse": True}, {"activation_dtype": "float8_e4m3fn"},
], ids=lambda p: "-".join(f"{k}={v}" for k, v in p.items()))
def test_perturbed_reference_differs(params, perturb):
    """Each ONE thing wrong moves the reference's logits far past rounding."""
    ids = _ids(2, 24, seed=3)
    ref, _ = plain.full_forward(_dims(CFG), params, ids)
    bad, _ = plain.full_forward(_dims(CFG), params, ids, perturb=perturb)
    assert np.abs(bad - ref)[:, 12:].max() > 0.05, perturb


@pytest.mark.parametrize("perturb", [{"mtp_window": 4}, {"mtp_token": "current"}],
                         ids=lambda p: "-".join(f"{k}={v}" for k, v in p.items()))
def test_perturbed_mtp_layer_differs_and_the_trunk_does_not(params, perturb):
    """The MTP layer's own two faults move ITS logits and leave the trunk's."""
    ids = _ids(2, 24, seed=3)
    ref, ref_mtp = plain.full_forward(_dims(CFG), params, ids)
    bad, bad_mtp = plain.full_forward(_dims(CFG), params, ids, perturb=perturb)
    np.testing.assert_array_equal(bad, ref)
    assert np.abs(bad_mtp - ref_mtp)[:, 12:].max() > 0.05, perturb
    # the heads at chosen positions alone are the whole forward's rows there
    at = np.array([[3, 20], [11, 22]])
    some, some_mtp = plain.full_forward(_dims(CFG), params, ids, at=at)
    for r in range(2):
        np.testing.assert_allclose(some[r], ref[r, at[r]], atol=1e-5)
        np.testing.assert_allclose(some_mtp[r], ref_mtp[r, at[r]], atol=1e-5)


@pytest.mark.parametrize("T", [1, 2, core.SELECT_POSITIONS, 64])
def test_take_position_is_the_gather_at_every_width(T):
    """A select over a chunk of a few positions, the gather over a bucket's:
    the same row either way, bit for bit."""
    x = jax.random.normal(jax.random.key(T), (3, T, 40), jnp.float32).astype(jnp.bfloat16)
    index = np.array([0, T - 1, T // 2], np.int32)
    got = jax.jit(core.take_position)(x, index)
    assert got.shape == (3, 1, 40)
    np.testing.assert_array_equal(np.asarray(got[:, 0]), np.asarray(x)[np.arange(3), index])
    text = jax.jit(core.take_position).lower(x, index).as_text()
    assert ("gather" in text) == (T > core.SELECT_POSITIONS)


def test_the_layer_behind_the_trunk_attends_fully_and_unrotated():
    """The MTP block runs as layer ``n_layers`` of the stack: is_sliding_layer's
    rule says what cfg.layer_windows says, for the trunk and behind it."""
    n = CFG.n_layers
    assert CFG.layer_windows == (8, 8, 8, 0, 8, 0)
    flags = [bool(core.is_sliding_layer(CFG, i)) for i in range(n + 1)]
    assert flags == [bool(w) for w in CFG.layer_windows]
    window = core.make_layer_window(CFG)
    assert [int(window(i)[0]) for i in range(n + 1)] == list(CFG.layer_windows)
    assert bool(core.layer_rope_flag(CFG, n)) is False
    # a model without an MTP layer keeps the periodic rule past its depth
    plain_cfg = dataclasses.replace(CFG, mtp_layers=0)
    assert bool(core.is_sliding_layer(plain_cfg, n)) is True


def test_the_reference_swaps_the_last_choice_at_one_position(whole):
    ids = _ids(2, 12, seed=5)
    dims, params = _dims(WHOLE), whole  # (every expert held: a swap always shows)
    base, gaps = plain.forward_logits(dims, params, ids, 9)
    assert gaps.shape == (5, 2) and np.isinf(gaps[0]).all() and (gaps[1:] >= 0).all()
    swaps = np.zeros((5, 2), bool)
    swaps[2, 1] = True
    got, _ = plain.forward_logits(dims, params, ids, 9, swaps)
    np.testing.assert_allclose(got[0], base[0], atol=1e-6)  # row 0 is untouched
    assert np.abs(got[1] - base[1]).max() > 1e-3


def test_the_reference_reads_one_position_a_row_and_the_mtp_head_with_its_gaps(whole):
    """forward_logits at a position of its OWN a row, and with ``mtp`` the MTP
    layer's logits there (six gaps: the MTP block's last), are the whole
    forward's rows; a swap in the MTP block moves the MTP head alone."""
    ids = _ids(2, 14, seed=6)
    dims, params = _dims(WHOLE), whole
    ref, ref_mtp, gaps_at = plain.full_forward(dims, params, ids, at=np.array([[5], [11]]),
                                               with_gaps=True)
    at = np.array([5, 11])
    got, gaps = plain.forward_logits(dims, params, ids, at)
    np.testing.assert_allclose(got, ref[:, 0], atol=1e-5)
    got_mtp, gaps_mtp = plain.forward_logits(dims, params, ids, at, mtp=True)
    np.testing.assert_allclose(got_mtp, ref_mtp[:, 0], atol=1e-5)
    assert gaps.shape == (5, 2) and gaps_mtp.shape == gaps_at[:, :, 0].shape == (6, 2)
    np.testing.assert_allclose(gaps_mtp[:5], gaps, atol=1e-6)
    np.testing.assert_allclose(gaps_mtp, gaps_at[:, :, 0], atol=1e-6)
    swaps = np.zeros((6, 2), bool)
    swaps[5, 0] = True
    swapped, _ = plain.forward_logits(dims, params, ids, at, swaps, mtp=True)
    assert np.abs(swapped[0] - got_mtp[0]).max() > 1e-3
    np.testing.assert_allclose(swapped[1], got_mtp[1], atol=1e-6)


def test_the_routing_rule_keeps_a_rows_best_margin_among_its_near_tied_swaps(whole):
    """routed_margins: a row that disagrees is recomputed with its near-tied
    layers swapped at the position and keeps its best margin; a row that agrees,
    or has no layer inside near_tie, costs no pass."""
    ids = _ids(3, 14, seed=7)
    dims, params = _dims(WHOLE), whole
    at = np.array([6, 9, 12])
    ref, gaps = plain.forward_logits(dims, params, ids, at)
    picks = ref.argmax(-1)
    picks[1] = np.argsort(ref[1])[-2]  # row 1 disagrees, by its top-2 gap
    margins = ref.max(-1) - ref[np.arange(3), picks]
    assert margins[0] == margins[2] == 0 and margins[1] > 0
    none = plain.routed_margins(dims, params, ids, at, picks, margins, gaps, 0.0, None, None, False)
    assert none[1:] == (0, 0) and np.array_equal(none[0], margins)
    best, rescued, passes = plain.routed_margins(dims, params, ids, at, picks, margins, gaps,
                                                 np.inf, None, None, False)
    assert 1 <= passes <= plain.MAX_PASSES and best[0] == best[2] == 0
    assert best[1] <= margins[1] and rescued == int(best[1] <= 0)
    # the best is the least over the single swaps too
    for layer in range(1, 5):
        sw = np.zeros((5, 1), bool)
        sw[layer, 0] = True
        one, _ = plain.forward_logits(dims, params, ids[1:2], at[1:2], sw)
        assert best[1] <= float(one[0].max() - one[0, picks[1]]) + 1e-6


def test_the_shares_add_up(whole):
    """Over every expert_first in 0, 4, 8, 12 the held experts' parts plus the
    shared expert ONCE equal the uncut layer; an assignment to an absent expert
    touches no product and is counted ``elsewhere``."""
    lp = jax.tree.map(lambda a: a[1], whole["layers"]["moe"])
    h = jax.random.normal(jax.random.key(7), (3, 6, CFG.d_model), jnp.float32)
    live = jnp.ones((3, 6), bool).at[2, 4:].set(False)
    full, st_full = core._moe_dropless(h, lp, WHOLE, live=live)
    shared = core._mlp(h, lp["shared"], WHOLE)
    parts, stats = [], []
    for first in (0, 4, 8, 12):
        cfg = dataclasses.replace(CFG, n_experts_held=4, expert_first=first)
        cut = dict(lp, **{n: lp[n][first:first + 4] for n in ("w_gate", "w_up", "w_down")})
        out, st = core._moe_dropless(h, cut, cfg, live=live)
        parts.append(out - shared)
        stats.append(dict(zip(core.moe_stats_names(cfg), np.asarray(st).tolist())))
    np.testing.assert_allclose(np.asarray(sum(parts) + shared), np.asarray(full), atol=3e-6)
    n_live = 16 * CFG.n_experts_per_tok  # 16 live positions x 4 choices
    assert [s["live"] + s["elsewhere"] for s in stats] == [n_live] * 4
    assert sum(s["live"] for s in stats) == n_live
    assert sum(s["hit"] for s in stats) == int(st_full[0])
    assert np.abs(np.asarray(sum(parts))[2, 4:]).max() == 0.0  # dead positions give nothing


def test_the_router_takes_the_top_scores_and_weighs_them_normalised_times_the_scale(whole):
    lp = jax.tree.map(lambda a: a[0], whole["layers"]["moe"])
    x = jax.random.normal(jax.random.key(2), (9, CFG.d_model), jnp.float32)
    topi, w = core._moe_router(x, lp, CFG)
    s = np.asarray(jax.nn.sigmoid(x @ lp["router"]))
    want = np.argsort(-s, axis=1)[:, :4]
    assert np.array_equal(np.sort(np.asarray(topi), 1), np.sort(want, 1))
    np.testing.assert_allclose(np.asarray(w).sum(1), 2.5, rtol=1e-5)
    picked = np.take_along_axis(s, np.asarray(topi), 1)
    np.testing.assert_allclose(np.asarray(w), 2.5 * picked / picked.sum(1, keepdims=True),
                               rtol=1e-5)


def test_center_router_centres_the_trunks_routers_and_the_mtp_blocks():
    """No response to the balancing batch's mean input is left in any of the
    five routers (four trunk layers behind the dense one, the MTP block's)."""
    raw = jax.jit(core._init_params, static_argnums=(0, 2))(
        CFG, jax.random.key(0), jnp.dtype(jnp.float32))
    trunk, mtp = jax.jit(core.center_router, static_argnums=1)(raw, CFG)
    assert trunk.shape == (4, 64, 16) and mtp.shape == (1, 64, 16)
    p = core.init_params(CFG, jax.random.key(0), dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(p["layers"]["moe"]["router"]), np.asarray(trunk))
    np.testing.assert_array_equal(
        np.asarray(p["mtp"]["block"]["moe"]["router"]), np.asarray(mtp))
    assert np.abs(np.asarray(trunk) - np.asarray(raw["layers"]["moe"]["router"])).max() > 1e-3
    assert np.abs(np.asarray(mtp) - np.asarray(raw["mtp"]["block"]["moe"]["router"])).max() > 1e-3
    # idempotent: a centred router has no response left to remove
    again, again_mtp = jax.jit(core.center_router, static_argnums=1)(p, CFG)
    np.testing.assert_allclose(np.asarray(again), np.asarray(trunk), atol=2e-5)
    np.testing.assert_allclose(np.asarray(again_mtp), np.asarray(mtp), atol=2e-5)


# ------------------------------------------- the pool, the window, the verify


@pytest.mark.parametrize("reader,drafts", [
    ("dense", "wrong"), ("dense", "right"), ("dense", "mixed"), ("ragged", "mixed")])
def test_prefill_then_verify_steps_match_the_reference_in_logits(params, reader, drafts):
    """A prefill of 13 (in a bucket of 16) with the MTP layer behind it, then
    verify steps of [cur | draft] through the paged pool, six cache layers deep,
    three times past the window of 8: the trunk's logits at every accepted
    position and the MTP layer's equal the plain reference's full forward. A
    REJECTED draft's K/V (trunk and MTP) is rewritten before any read sees it:
    wrong drafts change nothing."""
    attn = make_ragged_attn_fn() if reader == "ragged" else None
    ids = _ids(2, 40, seed=1)
    ref, ref_mtp = plain.full_forward(_dims(CFG), params, ids)
    cache = core.init_paged_pool(CFG, 16, 8, jnp.float32)
    assert cache["kv"].shape[0] == 6
    tables = np.arange(1, 13, dtype=np.int32).reshape(2, 6)
    n = 13
    tok = np.zeros((2, 16), np.int32)
    tok[:, :n] = ids[:, :n]
    kw = dict(attn_fn=attn, block_tables=tables)
    last = np.asarray([n - 1, n - 1])
    lg, cache, hidden = core.forward(
        params, CFG, tok, cache, np.int32(0), paged_write_ceil=np.int32(n),
        last_index=last, return_hidden=True, **kw)
    np.testing.assert_allclose(np.asarray(lg[:, 0]), ref[:, n - 1], atol=3e-5)
    follow = np.zeros((2, 16), np.int32)
    follow[:, :n] = ids[:, 1:n + 1]
    mlg, cache = core.mtp_forward(
        params, CFG, hidden, follow, cache, np.int32(0), paged_write_ceil=np.int32(n),
        last_index=last, **kw)
    np.testing.assert_allclose(np.asarray(mlg[:, 0]), ref_mtp[:, n - 1], atol=3e-5)
    rng = np.random.RandomState(9)
    pos, accepted_some, rejected_some = np.asarray([n, n]), False, False
    while pos.max() + 3 < ids.shape[1]:
        right = ids[np.arange(2), pos + 1]
        take = {"wrong": np.zeros(2, bool), "right": np.ones(2, bool),
                "mixed": rng.rand(2) < 0.5}[drafts]
        draft = np.where(take, right, (right + 1 + rng.randint(0, 50, 2)) % CFG.vocab_size)
        chunk = np.stack([ids[np.arange(2), pos], draft], axis=1).astype(np.int32)
        lg, cache, hidden = core.forward(
            params, CFG, chunk, cache, pos.astype(np.int32), return_hidden=True, **kw)
        lg = np.asarray(lg)
        # (teacher forcing: a draft is "accepted" where it is the context's
        # own token, not where it is the greedy one)
        acc = take.astype(np.int64)
        follow = np.stack([right, ids[np.arange(2), pos + 2]], axis=1).astype(np.int32)
        mlg, cache = core.mtp_forward(
            params, CFG, hidden, follow, cache, pos.astype(np.int32), **kw)
        mlg = np.asarray(mlg)
        for r in range(2):
            np.testing.assert_allclose(lg[r, 0], ref[r, pos[r]], atol=5e-5)
            np.testing.assert_allclose(mlg[r, 0], ref_mtp[r, pos[r]], atol=5e-5)
            if acc[r]:
                np.testing.assert_allclose(lg[r, 1], ref[r, pos[r] + 1], atol=5e-5)
                np.testing.assert_allclose(mlg[r, 1], ref_mtp[r, pos[r] + 1], atol=5e-5)
        accepted_some |= bool(acc.any())
        rejected_some |= bool((1 - acc).any())
        pos = pos + acc + 1
    assert accepted_some == (drafts != "wrong") and rejected_some == (drafts != "right")


def test_unstacked_layers_round_trip_and_give_the_same_logits(params):
    ids = _ids(2, 11)
    host = jax.device_get(params)
    listed = core.unstack_layers(host, CFG)
    assert len(listed["layers"]) == 5 and "mlp" in listed["layers"][0]
    assert "moe" in listed["layers"][1] and "mtp" in listed
    a, am = _program_logits(params, CFG, ids)
    b, bm = _program_logits(jax.tree.map(jnp.asarray, listed), CFG, ids)
    np.testing.assert_allclose(a, b, atol=2e-5)
    np.testing.assert_allclose(am, bm, atol=2e-5)
    back = core.restack_layers(listed)
    for x, y in zip(jax.tree.leaves(host), jax.tree.leaves(back)):
        assert np.array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------- the config


def test_published_preset_equals_the_catalog_config():
    got = config_from_hf(_published(), name="k-exaone-236b-a23b")
    assert got == CONFIGS["k-exaone-236b-a23b"]
    cut = config_from_hf(
        dict(_published(), num_hidden_layers=5, layer_types=_published()["layer_types"][:5],
             sliding_windows=_published()["sliding_windows"][:5],
             mlp_layer_types=_published()["mlp_layer_types"][:5], num_experts_held=16,
             vocab_size_held=19200), name="k-exaone-236b-a23b-5l-e16")
    assert cut == CONFIGS["k-exaone-236b-a23b-5l-e16"]


@pytest.mark.parametrize("flag,value", [
    ("scoring_func", "softmax"), ("n_group", 8), ("topk_group", 4), ("norm_topk_prob", False),
    ("hidden_act", "gelu"), ("mtp_layer_types", ["sliding_attention"]),
    ("mtp_sliding_windows", [128]), ("num_nextn_predict_layers", 2),
    ("rope_parameters", {"rope_theta": 1e6, "rope_type": "yarn"}),
    ("sliding_window_pattern", "LLGX"), ("sliding_window", 0),
    ("mlp_layer_types", ["sparse"] * 48), ("num_shared_experts", 0),
], ids=lambda v: str(v)[:24])
def test_unimplemented_variants_are_refused_by_name(flag, value):
    with pytest.raises(ValueError, match=flag):
        config_from_hf(dict(_published(), **{flag: value}), name="x")


@pytest.mark.parametrize("over,match", [
    (dict(moe_router="softmax_topk"), "moe_select_bias"),
    (dict(mtp_layers=2), "mtp_layers"),
    (dict(vocab_published=100), "vocab_published"),
    (dict(mtp_layers=1, loop_steps=2, n_experts=0, first_k_dense=0, n_shared_experts=0,
          d_ff_expert=0, sliding_window=None, sliding_window_every=1, rope_sliding_only=False,
          moe_router="softmax", moe_select_bias=True, n_experts_held=0, expert_first=0),
     "mtp_layers"),
])
def test_the_config_refuses_what_it_cannot_hold(over, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(CFG, **over)


def test_checkpoint_round_trip_keeps_the_mtp_tensors_and_cuts_the_shares(tmp_path, whole):
    """export.py writes the whole tiny model under the family's names, MTP
    layer and all; loader.py reads it back leaf for leaf, and into a CUT
    configuration it reads the experts held and the vocabulary's rows held."""
    from bee2bee_tpu.models.export import export_hf
    from bee2bee_tpu.models.loader import _read_safetensors, load_checkpoint

    whole_cfg = dataclasses.replace(WHOLE, vocab_published=0, name="tiny-exaone-whole")
    export_hf(whole, whole_cfg, tmp_path)
    names = set(_read_safetensors(tmp_path / "model.safetensors"))
    for key in ("model.layers.5.eh_proj.weight", "model.layers.5.enorm.weight",
                "model.layers.5.hnorm.weight", "model.layers.5.mlp.experts.15.up_proj.weight",
                "model.layers.5.self_attn.q_norm.weight", "model.layers.0.mlp.up_proj.weight",
                "model.layers.1.mlp.gate.weight", "model.layers.4.post_feedforward_layernorm.weight",
                "lm_head.weight"):
        assert key in names, key
    written = json.loads((tmp_path / "config.json").read_text())
    again = config_from_hf(written, name="tiny-exaone-whole")
    assert dataclasses.replace(again, head_dim_override=16) == whole_cfg
    back = load_checkpoint(tmp_path, again, dtype=jnp.float32)
    flat_a, flat_b = jax.tree.leaves_with_path(whole), jax.tree.leaves_with_path(back)
    assert [p for p, _ in flat_a] == [p for p, _ in flat_b]
    for (path, a), (_, b) in zip(flat_a, flat_b):
        assert np.array_equal(np.asarray(a), np.asarray(b)), path
    cut_cfg = config_from_hf(dict(written, num_experts_held=4, expert_first=4,
                                  vocab_size_held=320), name="cut")
    cut = load_checkpoint(tmp_path, cut_cfg, dtype=jnp.float32)
    assert cut["layers"]["moe"]["w_up"].shape == (4, 4, 64, 24)
    assert cut["mtp"]["block"]["moe"]["w_down"].shape == (1, 4, 24, 64)
    assert cut["tok_embed"].shape == (320, 64) and cut["lm_head"].shape == (64, 320)
    np.testing.assert_array_equal(
        np.asarray(cut["layers"]["moe"]["w_gate"][2, 1]),
        np.asarray(whole["layers"]["moe"]["w_gate"][2, 5]))
    with pytest.raises(ValueError, match="share"):
        export_hf(_share(whole, 4, 4), CFG, tmp_path / "cut")


def test_node_config_passes_the_acceptance_floor_through(tmp_path, monkeypatch):
    from bee2bee_tpu.config import NodeConfig

    assert NodeConfig().engine_config().spec_min_accept == EngineConfig().spec_min_accept
    ec = NodeConfig(spec_tokens=1, spec_min_accept=0).engine_config()
    assert ec.spec_min_accept == 0.0 and ec.spec_tokens == 1
