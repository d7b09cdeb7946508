"""nemotron-h (a layer is ONE branch under ONE norm: a Mamba-2 mixer, NoPE GQA
attention OR a LatentMoE expert layer of ungated relu^2 experts in a latent, the
chip holding a SHARE of the experts and of the vocabulary): the model against
the plain reference on LOGITS, the state and the pool of a pattern whose first
layer owns neither through prefill, chunks and decode, the four shares adding
up, a repeated unit of unlike layers as one scan body, and the converter's
refusals. All at ``tiny-nemotron`` size on the CPU; the engine's half is
``tests/test_nemotron_engine.py`` (two files: one file's worth of jit
executables ages a process into the XLA segfault ``tests/conftest.py``
quarantines)."""

from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bee2bee_tpu.models import core
from bee2bee_tpu.models.config import CONFIGS, ModelConfig, config_from_hf, get_config

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "benchmark"))
import reference_nemotron_h as plain  # noqa: E402  (the benchmark's plain reference)

CFG = get_config("tiny-nemotron")
WHOLE = dataclasses.replace(CFG, n_experts_held=0, expert_first=0)  # every expert held
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
CUT = "nemotron-3-super-120b-a12b-11l-e128"
CUT_FILE = ROOT / "benchmark" / "configs" / f"{CUT}.json"
CHARS = {v: c for c, v in plain.KINDS.items()}


def _whole_params(key=3):
    """Seeded weights with EVERY expert held, nothing hiding behind an init
    value (conv bias, skip, norm scale and every RMSNorm scale random)."""
    p = core.init_params(WHOLE, jax.random.key(key), dtype=jnp.float32)
    k = iter(jax.random.split(jax.random.key(4), 8))
    lay = dict(p["layers"])
    ssm = dict(lay["ssm"])
    ssm["conv_b"] = 0.1 * jax.random.normal(next(k), ssm["conv_b"].shape)
    ssm["D"] = jax.random.normal(next(k), ssm["D"].shape)
    ssm["norm"] = 0.5 + jax.random.uniform(next(k), ssm["norm"].shape)
    lay["ssm"] = ssm
    lay["ln1"] = {"scale": 0.5 + jax.random.uniform(next(k), lay["ln1"]["scale"].shape)}
    # an embedding large enough that every layer's input differs by token
    return dict(p, layers=lay, tok_embed=p["tok_embed"] * 40.0)


def _share(params, first: int, held: int):
    """The chip's share of ``params``' experts: the stacks cut to [first, first + held)."""
    moe = dict(params["layers"]["moe"])
    for n in ("w_up", "w_down"):
        moe[n] = moe[n][:, first:first + held]
    return dict(params, layers=dict(params["layers"], moe=moe))


@pytest.fixture(scope="module")
def whole():
    return _whole_params()


@pytest.fixture(scope="module")
def params(whole):  # tiny-nemotron's own share: experts 4..7
    return _share(whole, CFG.expert_first, CFG.experts_held)


def _ids(rows: int, n: int, seed: int = 0):
    return np.random.RandomState(seed).randint(3, 300, (rows, n)).astype(np.int32)


def _dims(cfg: ModelConfig) -> dict:
    return dict(plain.dims_of_preset(cfg), layer_first=0, rope_theta=10000.0,
                hybrid_override_pattern="".join(CHARS[t] for t in cfg.layer_types))


_PIECES: dict = {}  # (cfg, perturbation) -> the reference's jitted pieces, built once


def _plain_logits(params, cfg, ids, perturb=None, at=None):
    key = (cfg, json.dumps(perturb, sort_keys=True))
    if key not in _PIECES:
        _PIECES[key] = plain.build_forward(_dims(cfg), perturb)
    return np.stack([plain.forward_logits(_dims(cfg), params, ids, t, pieces=_PIECES[key])[0]
                     for t in (range(ids.shape[1]) if at is None else at)], axis=1)


# ------------------------------------------------------------------ the maps


def test_the_derived_maps_follow_three_kinds_of_layer():
    """A pattern whose FIRST layer is an expert layer: it owns neither state
    nor cache, and every stack is as deep as its kind."""
    assert CFG.layer_types[0] == "moe" and CFG.single_branch
    assert (CFG.state_layers, CFG.cache_layers, CFG.n_expert_layers, CFG.n_layers) == (3, 1, 2, 6)
    assert CFG.state_slots == (-1, 0, -1, 1, -1, 2) and CFG.cache_slots == (-1, -1, -1, -1, 0, -1)
    assert CFG.moe_slots == (0, -1, 1, -1, -1, -1)
    assert CFG.layer_windows == (0,) and CFG.expert_share and CFG.shared_ff == 48
    assert CFG.expert_in == 24 and not CFG.gated_mlp and CFG.n_expert_calls == 2
    assert core.pool_layout(CFG) == {"k": (2, 16), "v": (2, 16)}
    assert core.pool_bytes_per_token(CFG) == 1 * 2 * 2 * 2 * 16
    cut = get_config(CUT)
    assert (cut.state_layers, cut.cache_layers, cut.n_expert_layers, cut.experts_held) == (5, 1, 5, 128)
    assert core.pool_bytes_per_token(cut) == 1024
    state = jax.eval_shape(lambda: core.init_ssm_state(cut, 64))
    assert state["ssm"].shape == (5, 64, 128, 64, 128) and state["conv"].shape == (5, 64, 3, 10240)
    pool = jax.eval_shape(lambda: core.init_paged_pool(cut, 3200, 16))
    assert pool["kv"].shape == (1, 3200, 2, 2, 16, 128)
    shapes = jax.eval_shape(lambda: core._init_params(cut, jax.random.key(0), jnp.dtype("bfloat16")))
    lay = shapes["layers"]
    assert sorted(lay) == ["attn", "ln1", "moe", "ssm"]  # no ln2, no second branch
    assert lay["ln1"]["scale"].shape == (11, 4096) and lay["attn"]["wq"].shape == (1, 4096, 4096)
    assert lay["ssm"]["w_in"].shape == (5, 4096, 18560)
    moe = lay["moe"]
    assert sorted(moe) == ["latent_in", "latent_out", "router", "router_bias", "shared", "w_down", "w_up"]
    assert moe["w_up"].shape == (5, 128, 1024, 2688) and moe["w_down"].shape == (5, 128, 2688, 1024)
    assert moe["router"].shape == (5, 4096, 512) and sorted(moe["shared"]) == ["w_down", "w_up"]
    assert moe["shared"]["w_up"].shape == (5, 4096, 5376) and moe["latent_in"].shape == (5, 4096, 1024)
    assert shapes["lm_head"].shape == (4096, 32768) and shapes["tok_embed"].shape == (32768, 4096)
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert 4.64e9 < n < 4.66e9  # 9.3 GB of bf16: the configuration file's arithmetic
    # what a token pays HERE: 22 x 128/512 of an expert's two matrices an expert layer
    per_tok = core.matmul_params_per_token(cut)
    routed = 5 * 22 * 128 / 512 * 2 * 1024 * 2688
    rest = 5 * (4096 * 512 + 2 * 4096 * 5376 + 2 * 4096 * 1024)
    mix = 5 * (4096 * 18560 + 8192 * 4096) + (2 * 4096 * 4096 + 2 * 4096 * 256)
    assert per_tok == int(routed + rest + mix + 4096 * 32768)


def test_layer_units_take_a_repeated_unit_and_leave_runs_of_like_layers_alone():
    cut = get_config(CUT)
    assert cut.layer_units == ((("moe", "mamba"), 0, 5), (("attention",), 10, 1))
    assert CFG.layer_units == ((("moe", "mamba"), 0, 2), (("attention",), 4, 1), (("mamba",), 5, 1))
    # granite's runs come out as they are: a unit of one kind, the counts of layer_runs
    for name in ("tiny-granite", "granite-4.0-h-small-10l-e36", "granite-4.0-h-small"):
        g = get_config(name)
        assert [(u[0], s, n) for u, s, n in g.layer_units] == [r[:3] for r in g.layer_runs]
    assert get_config("tiny-granite").layer_runs == (
        ("mamba", 0, 2, 0), ("attention", 2, 1, 0), ("mamba", 3, 2, 2))
    assert len(get_config("granite-4.0-h-small-10l-e36").layer_units) == 3
    # the published pattern's first eleven layers: M E M E M E M * E M E
    first = dataclasses.replace(cut, layer_types=tuple(
        plain.KINDS[c] for c in "MEMEMEM*EME"))
    assert [u[0] for u in first.layer_units] == [
        ("mamba", "moe"), ("mamba",), ("attention",), ("moe",), ("mamba",), ("moe",)]
    assert sum(len(u) * n for u, _, n in first.layer_units) == 11
    odd = dataclasses.replace(CFG, layer_types=("mamba", "mamba", "attention") * 2)
    assert odd.layer_units == ((("mamba", "mamba", "attention"), 0, 2),)


# ------------------------------------------------------------------ the model


@pytest.mark.parametrize("cfg_name", ["share", "whole"])
def test_forward_matches_the_plain_reference(whole, params, cfg_name):
    """LOGITS against the float32 reference. The tolerance is float32 rounding
    through six layers at logits of std ~1 (measured 4e-6): a bf16 router
    (3.5e-2) or a float8 residual stream (5e-1) fails it by four orders."""
    cfg, p = (CFG, params) if cfg_name == "share" else (WHOLE, whole)
    ids = _ids(3, 21)
    logits, _ = core.forward(p, cfg, ids, None, 0)
    assert logits.shape == (3, 21, 320)  # over the held rows of the vocabulary
    np.testing.assert_allclose(np.asarray(logits), _plain_logits(p, cfg, ids), atol=3e-5)


@pytest.mark.parametrize("perturb,least", [
    ({"activation": "relu"}, 1e-1), ({"activation": "gated"}, 1e-1),
    ({"drop": "shared_expert"}, 1e-1), ({"drop": "routed_scaling_factor"}, 1e-1),
    ({"expert_first": 0}, 1e-1), ({"n_groups": 1}, 1e-1), ({"rope": True}, 1e-2),
    ({"router_dtype": "bfloat16"}, 3e-4), ({"activation_dtype": "float8_e4m3fn"}, 1e-2),
], ids=lambda v: str(v)[:40])
def test_perturbed_reference_differs(params, perturb, least):
    """Each ONE-thing-wrong reference lies far outside the tolerance above."""
    ids = _ids(2, 13)
    logits, _ = core.forward(params, CFG, ids, None, 0)
    ref = _plain_logits(params, CFG, ids, perturb, at=(5, 12))
    assert np.abs(np.asarray(logits)[:, (5, 12)] - ref).max() > least


def test_w_out_before_the_weighting_is_the_same_function(params):
    ids = _ids(2, 9)
    np.testing.assert_allclose(
        _plain_logits(params, CFG, ids, {"w_out": "before_weighting"}, at=(8,)),
        _plain_logits(params, CFG, ids, at=(8,)), atol=2e-5)


def test_the_reference_may_read_the_routers_input_at_the_served_dtype(params):
    """``dims["router_input_dtype"]`` (the cell's reference child sets it to the
    served dtype) rounds what the ROUTER reads and nothing else: absent it is
    float32, and at bfloat16 the logits move by a routing choice at most, far
    less than a bf16 router (operands AND logits rounded) or any other fault."""
    ids = _ids(2, 13)
    dims = dict(_dims(CFG), router_input_dtype="bfloat16")
    plain32 = plain.forward_logits(_dims(CFG), params, ids, 12)[0]
    served = plain.forward_logits(dims, params, ids, 12)[0]
    again = plain.forward_logits(dict(_dims(CFG), router_input_dtype="float32"), params, ids, 12)[0]
    assert np.array_equal(again, plain32)
    assert 0 < np.abs(served - plain32).max() < 0.2
    rounded_router = plain.forward_logits(dims, params, ids, 12, perturb={"router_dtype": "bfloat16"})[0]
    assert np.abs(rounded_router - plain32).max() >= np.abs(served - plain32).max()


def test_the_reference_swaps_the_last_choice_at_one_position(params):
    ids = _ids(2, 9)
    dims = _dims(CFG)
    base, gaps = plain.forward_logits(dims, params, ids, 5)
    assert gaps.shape == (6, 2) and np.isfinite(gaps[[0, 2]]).all()
    assert np.isinf(gaps[[1, 3, 4, 5]]).all()  # only the expert layers route
    swaps = np.zeros((6, 2), bool)
    swaps[0, 1] = True
    swapped, _ = plain.forward_logits(dims, params, ids, 5, swaps)
    np.testing.assert_allclose(swapped[0], base[0], atol=1e-6)  # row 0 untouched
    assert np.abs(swapped[1] - base[1]).max() > 1e-4
    later, _ = plain.forward_logits(dims, params, ids, 8, swaps)  # the swap is AT position 8 now
    assert np.abs(later[1] - plain.forward_logits(dims, params, ids, 8)[0][1]).max() > 1e-4


def test_the_four_shares_add_up(whole):
    """The routed parts that the four shares of an expert layer give (the tiny
    size's experts 0-3, 4-7, 8-11, 12-15; each through W_out, which is linear
    and has no bias) plus the shared expert counted ONCE add up to the uncut
    reference's layer: the program's four shares, and the reference's own."""
    x = jax.random.normal(jax.random.key(9), (2, 7, 64), jnp.float32)
    u = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-5)  # RMS(x; 1)
    moe, slot, dims = whole["layers"]["moe"], 1, _dims(WHOLE)
    lp = jax.tree.map(lambda a: a[slot], moe)
    ones = jnp.ones((6, 64), jnp.float32)

    def ref_branch(d, stack, perturb=None):
        """The reference's expert layer ``slot`` alone: layer(x) - x."""
        layer = plain.build_forward(d, perturb)[1]["moe"]
        with jax.default_matmul_precision("highest"):
            y, _ = layer(x, ones, stack, np.int32(0), np.int32(slot), np.zeros((2,), bool),
                         np.int32(0))
        return np.asarray(y - x)

    full = ref_branch(dims, moe)
    shared = full - ref_branch(dims, moe, {"drop": "shared_expert"})
    out, stats = core._moe_dropless(u, lp, WHOLE)
    assert stats.shape == (3,) and int(stats[2]) == 2 * 7 * 5
    np.testing.assert_allclose(np.asarray(out), full, atol=2e-5)
    ours, theirs = [], []
    for first in (0, 4, 8, 12):
        cfg = dataclasses.replace(WHOLE, n_experts_held=4, expert_first=first)
        out, st = core._moe_dropless(
            u, dict(lp, w_up=lp["w_up"][first:first + 4], w_down=lp["w_down"][first:first + 4]), cfg)
        assert st.shape == (4,) and int(st[2]) + int(st[3]) == 2 * 7 * 5
        ours.append(np.asarray(out) - shared)
        cut = dict(moe, w_up=moe["w_up"][:, first:first + 4], w_down=moe["w_down"][:, first:first + 4])
        theirs.append(ref_branch(dict(dims, n_routed_experts_held=4, expert_first=first), cut) - shared)
    assert min(np.abs(part).max() for part in theirs) > 1e-2  # every share gives something
    np.testing.assert_allclose(sum(ours) + shared, full, atol=3e-5)
    np.testing.assert_allclose(sum(theirs) + shared, full, atol=3e-5)


def _forced_units(monkeypatch, units):
    monkeypatch.setattr(ModelConfig, "layer_units", property(lambda self: units))


def test_a_unit_of_unlike_layers_as_one_scan_body_equals_a_scan_a_layer_to_the_bit(
        params, monkeypatch):
    """(E M) x 2 as ONE scan body against the same layers one scan each (what
    layer_runs alone would give), through prefill and decode over state and
    pool; the unrolled layer list agrees to float32 rounding (XLA:CPU packs
    an unsliced operand's product otherwise)."""
    ids = _ids(2, 17)

    def run(p):
        cache = core.init_paged_pool(CFG, 9, 4, jnp.float32)
        cache.update(core.init_ssm_state(CFG, 2, jnp.float32))
        tables = np.arange(1, 9, dtype=np.int32).reshape(2, 4)
        a, cache = core.forward(p, CFG, ids[:, :12], cache, np.int32(0), block_tables=tables)
        b, cache = core.forward(p, CFG, ids[:, 12:13], cache, np.asarray([12, 12], np.int32),
                                block_tables=tables)
        return np.asarray(a), np.asarray(b), np.asarray(cache["ssm"]), np.asarray(cache["kv"])

    assert [len(u) for u, _, _ in CFG.layer_units] == [2, 1, 1]
    unit = run(params)
    assert _layer_bodies(CFG, params) == 3  # (E M) x 2, *, M
    _forced_units(monkeypatch, tuple(((t,), i, 1) for i, t in enumerate(CFG.layer_types)))
    single = run(params)
    for a, b in zip(unit, single):
        assert np.array_equal(a, b)
    monkeypatch.undo()
    flat = core.unstack_layers(jax.device_get(params), CFG)
    assert [sorted(lp) for lp in flat["layers"]] == [
        ["ln1", "moe"], ["ln1", "ssm"], ["ln1", "moe"], ["ln1", "ssm"], ["attn", "ln1"], ["ln1", "ssm"]]
    for a, b in zip(unit, run(flat)):
        np.testing.assert_allclose(a, b, atol=2e-5)
    back = core.restack_layers(flat)
    assert jax.tree.all(jax.tree.map(lambda x, y: np.array_equal(np.asarray(x), np.asarray(y)),
                                     jax.device_get(params), back))


def _layer_bodies(cfg, params) -> int:
    """Scans at the top of ``cfg``'s traced forward: one a run of layers (the
    chunked scan's and a kernel's own loops lie INSIDE a layer body)."""
    ids = np.zeros((2, 4), np.int32)
    jaxpr = jax.make_jaxpr(lambda p: core.forward(p, cfg, ids, None, 0)[0])(params)
    return sum(e.primitive.name == "scan" for e in jaxpr.jaxpr.eqns)


def test_a_program_holds_two_layer_bodies_and_granites_three():
    for name, bodies in ((CUT, 2), ("granite-4.0-h-small-10l-e36", 3), ("tiny-granite", 3)):
        cfg = get_config(name)
        shapes = jax.eval_shape(
            lambda cfg=cfg: core._init_params(cfg, jax.random.key(0), jnp.dtype("bfloat16")))
        assert _layer_bodies(cfg, shapes) == bodies == len(cfg.layer_units)


@pytest.mark.parametrize("chunks", [(13,), (8, 5), (16, 3)])
def test_prefill_then_decode_matches_the_plain_reference(params, chunks):
    """The served path's programs (prefill in chunks with a padded tail, then
    decode a token at a time through the rows' state and the paged pool, two
    rows of UNEQUAL length) against the reference's full forward on LOGITS."""
    ids = _ids(2, 24, seed=1)
    lens = np.asarray([sum(chunks), sum(chunks) - 2])
    ref = _plain_logits(params, CFG, ids, at=range(8, 24))
    ref = {t: ref[:, i] for i, t in enumerate(range(8, 24))}
    cache = core.init_paged_pool(CFG, 9, 8, jnp.float32)
    cache.update(core.init_ssm_state(CFG, 2, jnp.float32))
    cache["moe_stats"] = jnp.zeros((4,), jnp.int32)
    tables = np.arange(1, 9, dtype=np.int32).reshape(2, 4)
    pos = 0
    for c in chunks:
        real = np.clip(lens - pos, 0, c)
        tok = np.zeros((2, 16), np.int32)
        tok[:, :c] = ids[:, pos:pos + c]
        lg, cache = core.forward(
            params, CFG, tok, cache, np.int32(pos), block_tables=tables,
            paged_write_ceil=lens.astype(np.int32), valid_len=real,
            last_index=np.maximum(real - 1, 0))
        pos += c
    assert lg.shape == (2, 1, CFG.vocab_size)
    for r in range(2):
        np.testing.assert_allclose(np.asarray(lg[r, 0]), ref[int(lens[r]) - 1][r], atol=3e-5)
    hit, _, live, elsewhere = np.asarray(cache["moe_stats"]).tolist()
    # every real position's 5 choices in each of the 2 expert layers, here or elsewhere
    assert live + elsewhere == int(lens.sum()) * 5 * 2 and 0 < live < int(lens.sum()) * 10
    assert hit <= 4 * 2 * len(chunks)
    at = lens.copy()
    for _ in range(4):  # each row decodes from ITS length
        tok = ids[np.arange(2), at][:, None]
        lg, cache = core.forward(params, CFG, tok, cache, at.astype(np.int32), block_tables=tables)
        for r in range(2):
            np.testing.assert_allclose(np.asarray(lg[r, 0]), ref[int(at[r])][r], atol=3e-5)
        at += 1


def test_padded_tail_leaves_the_state_untouched_and_an_expert_layer_has_none(params):
    ids = _ids(1, 16, seed=2)
    cache = core.init_paged_pool(CFG, 5, 8, jnp.float32)
    cache.update(core.init_ssm_state(CFG, 1, jnp.float32))
    assert cache["ssm"].shape[0] == 3 and cache["kv"].shape[0] == 1
    tables = np.arange(1, 5, dtype=np.int32).reshape(1, 4)
    _, full = core.forward(params, CFG, ids[:, :9], cache, np.int32(0), block_tables=tables)
    padded = np.zeros((1, 16), np.int32)
    padded[:, :9] = ids[:, :9]
    _, tail = core.forward(params, CFG, padded, cache, np.int32(0), block_tables=tables,
                           paged_write_ceil=np.int32(9), valid_len=np.asarray([9]))
    np.testing.assert_allclose(np.asarray(tail["ssm"]), np.asarray(full["ssm"]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(tail["conv"]), np.asarray(full["conv"]), atol=1e-5)


def test_a_cache_without_state_is_refused(params):
    with pytest.raises(ValueError, match="recurrent mixer"):
        core.forward(params, CFG, _ids(1, 4), core.init_cache(CFG, 1, 16, jnp.float32), 0)


def test_seeded_weights_get_a_balanced_selection_bias():
    """core.balance_router_bias on a model of layer kinds: a bias an EXPERT
    layer (not a layer), float32, nonzero, and an even load on its own batch."""
    p = core.init_params(CFG, jax.random.key(0), dtype=jnp.float32)
    bias = np.asarray(p["layers"]["moe"]["router_bias"])
    assert bias.shape == (2, 16) and bias.dtype == np.float32 and np.abs(bias).min() > 0
    raw = jax.jit(core._init_params, static_argnums=(0, 2))(CFG, jax.random.key(0), jnp.dtype("float32"))
    assert not np.asarray(raw["layers"]["moe"]["router_bias"]).any()
    for name in ("w_up", "w_down"):
        assert p["layers"]["moe"][name].shape[:2] == (2, 4)  # the share is what is made
        assert np.array_equal(np.asarray(p["layers"]["moe"][name]), np.asarray(raw["layers"]["moe"][name]))
    assert "w_gate" not in p["layers"]["moe"] and "ln2" not in p["layers"]


# ------------------------------------------------------------------ the config


def _published() -> dict:
    if not CATALOG.exists():
        pytest.skip("the model-configs catalog is not on this machine")
    for line in CATALOG.read_text().splitlines():
        row = json.loads(line)
        if row["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16":
            return row["config"]
    pytest.skip("the catalog has no Nemotron 3 Super row")


def _cut_of(published: dict) -> dict:
    return dict(published, layers=11, layer_first=26, n_routed_experts_held=128,
                vocab_size_held=32768, max_position_embeddings=2048, num_nextn_predict_layers=0)


def test_the_served_preset_equals_the_configuration_file_and_the_catalog():
    conf = json.loads(CUT_FILE.read_text())
    assert config_from_hf(conf, name=CUT) == CONFIGS[CUT]
    assert conf["hybrid_override_pattern"][26:37] == "EMEMEMEMEM*"
    pub = _published()
    assert config_from_hf(_cut_of(pub), name=CUT) == CONFIGS[CUT]
    changed = {k for k, v in pub.items() if conf.get(k) != v}
    assert changed == {"max_position_embeddings", "num_nextn_predict_layers"}
    assert set(conf["reduced"]) == {"layers", "n_routed_experts", "vocab_size",
                                    "max_position_embeddings", "num_nextn_predict_layers"}
    got = CONFIGS[CUT]
    assert (got.d_model, got.moe_latent, got.expert_ff, got.shared_ff) == (4096, 1024, 2688, 5376)
    assert (got.n_experts, got.n_experts_per_tok, got.ssm_heads, got.ssm_head_dim) == (512, 22, 128, 64)
    assert (got.ssm_state, got.ssm_groups, got.n_heads, got.n_kv_heads, got.head_dim) == (128, 8, 32, 2, 128)
    want, have = plain.dims_of_preset(got), plain.dims_of_file(conf)
    assert {k: have.get(k) for k in want} == want


@pytest.mark.parametrize("flag,value", [
    ("mamba_proj_bias", True), ("use_bias", True), ("attention_bias", True), ("mlp_bias", True),
    ("use_conv_bias", False), ("n_group", 8), ("topk_group", 4), ("norm_topk_prob", False),
    ("moe_shared_expert_overlap", True), ("sliding_window", 4096), ("mlp_hidden_act", "silu"),
    ("mamba_hidden_act", "gelu"), ("num_nextn_predict_layers", 1), ("n_shared_experts", 2),
    ("residual_in_fp32", True), ("norm_eps", 1e-6), ("moe_latent_size", 0), ("n_groups", 3),
    ("hybrid_override_pattern", "M-" * 44), ("hybrid_override_pattern", "ME*"), ("layers", 89),
], ids=lambda v: str(v)[:24])
def test_unimplemented_variants_are_refused_by_name(flag, value):
    with pytest.raises(ValueError, match=flag):
        config_from_hf(dict(_cut_of(_published()), **{flag: value}), name="x")


def test_an_unknown_kind_of_model_still_names_what_is_covered():
    with pytest.raises(ValueError, match="nemotron_h"):
        config_from_hf({"model_type": "nemotron_h_next"})


@pytest.mark.parametrize("over,match", [
    (dict(layer_types=("moe",) * 6), "layer_types"),
    (dict(layer_types=("moe", "mamba", "attention")), "layer_types"),
    (dict(layer_types=("moe", "mamba", "dense", "mamba", "attention", "mamba")), "layer_types"),
    (dict(moe_router="softmax", moe_select_bias=True, n_experts_held=0, expert_first=0, moe_latent=0),
     "layer_types"),
    (dict(residual_multiplier=0.5), "layer_types"),
    (dict(mtp_layers=1), "mtp_layers"),
    (dict(n_experts_held=14, expert_first=4), "n_experts_held"),
    (dict(moe_latent=-1), "moe_latent"),
])
def test_the_config_refuses_what_the_maps_cannot_hold(over, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(CFG, **over)


def test_a_latent_needs_a_dropless_expert_layer():
    with pytest.raises(ValueError, match="moe_latent"):
        dataclasses.replace(get_config("tiny-mixtral"), moe_latent=8)
