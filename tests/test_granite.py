"""granite-4.0-h (one mixer kind a layer: a Mamba-2 mixer OR NoPE GQA attention,
every layer softmax-top-k experts beside a shared expert, the chip holding a
SHARE of the experts): the model against the plain reference and against
``transformers``' own implementation, the 4-of-5 state and 1-of-5 pool through
prefill, chunks and decode, the shares adding up, and the engine's counters.
All at ``tiny-granite`` size on the CPU."""

from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bee2bee_tpu.engine import EngineConfig, InferenceEngine
from bee2bee_tpu.models import core
from bee2bee_tpu.models.config import CONFIGS, ModelConfig, config_from_hf, get_config

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "benchmark"))
import reference_granite as plain  # noqa: E402  (the benchmark's plain reference)

CFG = get_config("tiny-granite")
WHOLE = dataclasses.replace(CFG, n_experts_held=0, expert_first=0)  # every expert held
ENGINE_KW = dict(max_seq_len=128, dtype="float32", cache_dtype="float32",
                 decode_chunk=4, max_batch=4, prefill_buckets=(16, 32, 64),
                 kv_block_size=8)
# the published config.json (the catalog's numbers) under its own names
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.0078125, "embedding_multiplier": 12,
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 768,
    "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4, "logits_scaling": 16,
    "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
    "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 10, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "num_local_experts": 72, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 1536, "tie_word_embeddings": True,
    "vocab_size": 100352,
}


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
    for ln in ("ln1", "ln2"):
        lay[ln] = {"scale": 0.5 + jax.random.uniform(next(k), lay[ln]["scale"].shape)}
    # an embedding large enough that every layer's input differs by token
    return dict(p, layers=lay, tok_embed=p["tok_embed"] * 40.0)


def _share(params, first: int, held: int):
    """The chip's share of ``params``' experts: the stacks cut to [first, first + held)."""
    moe = dict(params["layers"]["moe"])
    for n in ("w_gate", "w_up", "w_down"):
        moe[n] = moe[n][:, first:first + held]
    return dict(params, layers=dict(params["layers"], moe=moe))


@pytest.fixture(scope="module")
def whole():
    return _whole_params()


@pytest.fixture(scope="module")
def params(whole):  # tiny-granite's own share: experts 4..7
    return _share(whole, CFG.expert_first, CFG.experts_held)


def _ids(rows: int, n: int, seed: int = 0):
    return np.random.RandomState(seed).randint(3, 500, (rows, n)).astype(np.int32)


def _dims(cfg: ModelConfig) -> dict:
    return dict(plain.dims_of_preset(cfg), layer_types=list(cfg.layer_types))


def _plain_logits(params, cfg, ids, perturb=None):
    return np.stack([plain.forward_logits(_dims(cfg), params, ids, t, perturb=perturb)[0]
                     for t in range(ids.shape[1])], axis=1)


# ------------------------------------------------------------------ the model


def test_the_derived_maps_follow_layer_types():
    assert (CFG.state_layers, CFG.cache_layers, CFG.n_layers) == (4, 1, 5)
    assert CFG.state_slots == (0, 1, -1, 2, 3) and CFG.cache_slots == (-1, -1, 0, -1, -1)
    assert CFG.layer_runs == (("mamba", 0, 2, 0), ("attention", 2, 1, 0), ("mamba", 3, 2, 2))
    assert CFG.layer_windows == (0,) and CFG.expert_share and CFG.shared_ff == 40
    cut = get_config("granite-4.0-h-small-10l-e36")
    assert (cut.state_layers, cut.cache_layers, cut.experts_held) == (9, 1, 36)
    assert core.pool_bytes_per_token(cut) == 4096
    state = jax.eval_shape(lambda: core.init_ssm_state(cut, 64))
    assert state["ssm"].shape == (9, 64, 128, 64, 128) and state["conv"].shape == (9, 64, 3, 8448)
    # a pattern that is not one period repeated loads as runs too
    odd = dataclasses.replace(CFG, layer_types=("attention", "mamba", "mamba", "attention", "mamba"))
    assert [r[:3] for r in odd.layer_runs] == [("attention", 0, 1), ("mamba", 1, 2),
                                               ("attention", 3, 1), ("mamba", 4, 1)]
    # falcon-h1 keeps a state as deep as its layers
    assert get_config("falcon-h1-34b-6l").state_layers == 6
    assert jax.eval_shape(lambda: core.init_ssm_state(
        get_config("falcon-h1-34b-6l"), 2))["ssm"].shape[0] == 6


@pytest.mark.parametrize("cfg_name", ["share", "whole"])
def test_forward_matches_the_plain_reference(whole, params, cfg_name):
    cfg, p = (CFG, params) if cfg_name == "share" else (WHOLE, whole)
    ids = _ids(2, 19)
    ours, _ = core.forward(p, cfg, ids, None, 0)
    np.testing.assert_allclose(np.asarray(ours), _plain_logits(p, cfg, ids), atol=3e-5, rtol=1e-4)


@pytest.mark.parametrize("perturb", [
    {"drop": "residual_multiplier"}, {"drop": "shared_expert"}, {"expert_first": 0},
    {"attention_at": 1}, {"activation_dtype": "float8_e4m3fn"},
], ids=lambda p: "-".join(f"{k}={v}" for k, v in p.items()))
def test_perturbed_reference_differs(params, perturb):
    ids = _ids(2, 12)
    ours, _ = core.forward(params, CFG, ids, None, 0)
    theirs = _plain_logits(params, CFG, ids, perturb)
    assert np.abs(np.asarray(ours) - theirs).max() > 100 * 3e-5


def test_the_reference_swaps_the_last_choice_at_one_position(params):
    ids = _ids(2, 10)
    dims = _dims(CFG)
    base, gaps = plain.forward_logits(dims, params, ids, 9)
    swaps = np.zeros((CFG.n_layers, 2), bool)
    swaps[1, 0] = True  # row 0, layer 1
    got, g2 = plain.forward_logits(dims, params, ids, 9, swaps)
    assert np.array_equal(got[1], base[1]) and not np.allclose(got[0], base[0], atol=1e-6)
    assert np.allclose(g2[:2], gaps[:2]) and gaps.shape == (5, 2) and (gaps >= 0).all()
    assert not np.allclose(g2[2:, 0], gaps[2:, 0])  # behind a swap the later gaps move


def test_the_shares_add_up(whole):
    """The parts that the shares [0, 4) and [4, 8) give, the shared expert
    counted once, equal the uncut layer; an assignment to an absent expert
    touches no product and is counted ``elsewhere``."""
    lp = jax.tree.map(lambda a: a[1], whole["layers"]["moe"])
    h = jax.random.normal(jax.random.key(7), (3, 6, CFG.d_model), jnp.float32)
    live = jnp.ones((3, 6), bool).at[2, 4:].set(False)
    full, st_full = core._moe_dropless(h, lp, WHOLE, live=live)
    shared = core._mlp(h, lp["shared"], WHOLE)
    parts, stats = [], []
    for first in (0, 4):
        cfg = dataclasses.replace(CFG, n_experts_held=4, expert_first=first)
        cut = dict(lp, **{n: lp[n][first:first + 4] for n in ("w_gate", "w_up", "w_down")})
        out, st = core._moe_dropless(h, cut, cfg, live=live)
        parts.append(out - shared)
        stats.append(dict(zip(core.moe_stats_names(cfg), np.asarray(st).tolist())))
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1] + shared), np.asarray(full),
                               atol=2e-6)
    n_live = 16 * CFG.n_experts_per_tok  # 16 live positions x 3 choices
    assert [s["live"] + s["elsewhere"] for s in stats] == [n_live, n_live]
    assert stats[0]["live"] == stats[1]["elsewhere"] and stats[0]["live"] + stats[1]["live"] == n_live
    assert dict(zip(core.moe_stats_names(WHOLE), np.asarray(st_full).tolist()))["live"] == n_live
    assert stats[0]["hit"] + stats[1]["hit"] == int(st_full[0]) and 0 < stats[0]["live"] < n_live
    # a token all of whose experts are absent gets the shared expert alone
    topi, _ = core._moe_router(h.reshape(18, -1), lp, CFG)
    away = np.flatnonzero((np.asarray(topi) < 4).all(axis=1) & np.asarray(live).reshape(-1))
    if len(away):
        np.testing.assert_allclose(np.asarray(parts[1]).reshape(18, -1)[away], 0.0, atol=1e-7)
    # dead positions give nothing but count nowhere
    assert np.abs(np.asarray(parts[0] + parts[1])[2, 4:]).max() == 0.0


def test_padded_tail_leaves_a_recurrent_layers_state_untouched(params):
    """A prefill bucket of 16 with 11 real tokens: every recurrent layer's state
    is BIT-FOR-BIT the state after those 11 alone, and the pool is one layer deep."""
    ids = _ids(1, 16, seed=2)
    tables = np.arange(1, 3, dtype=np.int32).reshape(1, 2)

    def run(tok, n):
        cache = core.init_paged_pool(CFG, 4, 8, jnp.float32)
        assert cache["kv"].shape[0] == 1
        cache.update(core.init_ssm_state(CFG, 1, jnp.float32))
        return core.forward(params, CFG, tok, cache, np.int32(0), block_tables=tables,
                            paged_write_ceil=np.int32(n), valid_len=np.asarray([n]),
                            last_index=np.asarray([n - 1]))[1]

    padded, exact = run(ids, 11), run(np.pad(ids[:, :11], ((0, 0), (0, 5))), 11)
    alone = core.init_paged_pool(CFG, 4, 8, jnp.float32)
    alone.update(core.init_ssm_state(CFG, 1, jnp.float32))
    _, alone = core.forward(params, CFG, ids[:, :11], alone, np.int32(0), block_tables=tables)
    for name in ("ssm", "conv"):
        assert padded[name].shape[0] == 4
        assert np.array_equal(np.asarray(padded[name]), np.asarray(exact[name])), name
        np.testing.assert_allclose(np.asarray(padded[name]), np.asarray(alone[name]), atol=1e-6)


@pytest.mark.parametrize("chunks", [(13,), (8, 5), (16, 3)])
def test_prefill_then_decode_matches_full_forward(params, chunks):
    """Prefill (whole, or in chunks that cross the scan's chunk boundary, each
    in a bucket of 16 it does not fill) then decode through the 1-layer pool
    AND the 4-layer state == the cache-less full forward, in LOGITS."""
    ids = _ids(2, 24, seed=1)
    full, _ = core.forward(params, CFG, ids, None, 0)
    cache = core.init_paged_pool(CFG, 16, 8, jnp.float32)
    cache.update(core.init_ssm_state(CFG, 2, jnp.float32))
    cache["moe_stats"] = jnp.zeros((4,), jnp.int32)
    tables = np.arange(1, 9, dtype=np.int32).reshape(2, 4)
    pos, n = 0, sum(chunks)
    for c in chunks:
        tok = np.zeros((2, 16), np.int32)
        tok[:, :c] = ids[:, pos:pos + c]
        lg, cache = core.forward(
            params, CFG, tok, cache, np.int32(pos), block_tables=tables,
            paged_write_ceil=np.int32(n), valid_len=np.asarray([c, c]),
            last_index=np.asarray([c - 1, c - 1]))
        pos += c
    assert lg.shape == (2, 1, CFG.vocab_size)
    np.testing.assert_allclose(np.asarray(lg[:, 0]), np.asarray(full[:, n - 1]), atol=2e-5)
    hit, _, live, elsewhere = np.asarray(cache["moe_stats"]).tolist()
    # every real position's 3 choices in each of 5 layers, here or elsewhere; pads nowhere
    assert live + elsewhere == 2 * n * 3 * 5 and 0 < live < 2 * n * 15 and hit <= 4 * 5 * len(chunks)
    for t in range(n, 24):
        lg, cache = core.forward(params, CFG, ids[:, t:t + 1], cache,
                                 np.asarray([t, t], np.int32), block_tables=tables)
        np.testing.assert_allclose(np.asarray(lg[:, 0]), np.asarray(full[:, t]), atol=2e-5)


def test_unstacked_layers_round_trip_and_give_the_same_logits(params):
    ids = _ids(2, 9)
    host = jax.device_get(params)
    flat = core.unstack_layers(host, CFG)
    assert ["ssm" in lp for lp in flat["layers"]] == [True, True, False, True, True]
    assert all(("attn" in lp) != ("ssm" in lp) for lp in flat["layers"])
    a, _ = core.forward(params, CFG, ids, None, 0)
    b, _ = core.forward(flat, CFG, ids, None, 0)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    back = core.restack_layers(flat)
    assert jax.tree.all(jax.tree.map(lambda x, y: np.array_equal(np.asarray(x), np.asarray(y)),
                                     host, back))


def test_a_cache_without_state_is_refused(params):
    with pytest.raises(ValueError, match="recurrent mixer"):
        core.forward(params, CFG, _ids(1, 4), core.init_cache(CFG, 1, 16, jnp.float32), 0)


def test_center_router_centres_on_the_routers_own_input():
    """Seeded weights: every layer's router answers nothing to the mean of ITS
    input (the pre-FFN norm's output, behind the layer's mixer), and
    smallthinker's rule (the pre-attention norm's) gives what it gave."""
    p = core.init_params(CFG, jax.random.key(0), dtype=jnp.float32)
    raw = jax.jit(core._init_params, static_argnums=(0, 2))(CFG, jax.random.key(0), jnp.dtype("float32"))
    assert p["layers"]["moe"]["router"].shape == raw["layers"]["moe"]["router"].shape == (5, 64, 8)
    assert not np.allclose(np.asarray(p["layers"]["moe"]["router"]),
                           np.asarray(raw["layers"]["moe"]["router"]))
    for name in ("w_up", "w_gate", "w_down"):
        assert p["layers"]["moe"][name].shape[:2] == (5, 4)  # the share is what is made
    # the centring is idempotent: a centred router has no response left to remove
    again = jax.jit(core.center_router, static_argnums=1)(p, CFG)
    np.testing.assert_allclose(np.asarray(again), np.asarray(p["layers"]["moe"]["router"]), atol=2e-6)
    st = get_config("tiny-smallthinker")
    sp = core.init_params(st, jax.random.key(0), dtype=jnp.float32)
    assert sp["layers"]["moe"]["router"].shape == (4, 48, 8)


# ------------------------------------------------------------------ the config


def test_published_preset_equals_the_catalog_config():
    pub = config_from_hf(PUBLISHED, name="granite-4.0-h-small")
    assert pub == CONFIGS["granite-4.0-h-small"]
    cut = dict(PUBLISHED, num_hidden_layers=10, layer_types=PUBLISHED["layer_types"][:10],
               num_local_experts_held=36)
    assert config_from_hf(cut, name="granite-4.0-h-small-10l-e36") == CONFIGS[
        "granite-4.0-h-small-10l-e36"]
    assert pub.attn_scale == 16384.0 and pub.lm_head_multiplier == 0.0625
    assert core.matmul_params_per_token(pub) > core.matmul_params_per_token(CONFIGS[
        "granite-4.0-h-small-10l-e36"])


@pytest.mark.parametrize("flag,value", [
    ("position_embedding_type", "rope"), ("mamba_n_groups", 3), ("attention_bias", True),
    ("mamba_proj_bias", True), ("rope_scaling", {"rope_type": "linear", "factor": 2.0}),
    ("mamba_conv_bias", False), ("hidden_act", "gelu"), ("layer_types", ["mamba"] * 39),
    ("shared_intermediate_size", 0),
])
def test_unimplemented_variants_are_refused_by_name(flag, value):
    with pytest.raises(ValueError, match=flag):
        config_from_hf(dict(PUBLISHED, **{flag: value}))


@pytest.mark.parametrize("over,match", [
    (dict(layer_types=("mamba",) * 5), "layer_types"),
    (dict(layer_types=("mamba", "attention")), "layer_types"),
    (dict(loop_steps=2), "layer_types|loop_steps"),
    (dict(n_experts_held=6, expert_first=4), "n_experts_held"),
    (dict(d_ff_shared=8, n_shared_experts=0), "d_ff_shared"),
])
def test_the_config_refuses_what_the_maps_cannot_hold(over, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(CFG, **over)


def test_softmax_topk_may_read_either_norm():
    assert CFG.moe_router == "softmax_topk" and CFG.moe_router_input == "ffn_norm"
    assert get_config("tiny-smallthinker").moe_router_input == "attn_norm"


# ------------------------------------------------------------------ transformers


def test_transformers_model_with_the_same_weights_gives_the_same_logits():
    """The tie to the published model: ``transformers``' own
    GraniteMoeHybridForCausalLM at tiny size, random init, its weights laid
    into this repo's tree (no loader yet: models/loader.py waits for a
    checkpoint), every expert held."""
    transformers = pytest.importorskip("transformers")
    torch = pytest.importorskip("torch")
    if not hasattr(transformers, "GraniteMoeHybridForCausalLM"):
        pytest.skip("transformers too old for GraniteMoeHybridForCausalLM")
    types = list(CFG.layer_types)
    conf = transformers.GraniteMoeHybridConfig(
        vocab_size=512, hidden_size=64, num_hidden_layers=5, num_attention_heads=4,
        num_key_value_heads=2, intermediate_size=24, shared_intermediate_size=40,
        num_local_experts=8, num_experts_per_tok=3, layer_types=types, mamba_n_heads=8,
        mamba_d_head=16, mamba_d_state=8, mamba_n_groups=1, mamba_d_conv=4, mamba_expand=2,
        mamba_chunk_size=8, mamba_conv_bias=True, mamba_proj_bias=False,
        position_embedding_type="nope", attention_multiplier=0.125, embedding_multiplier=3.0,
        logits_scaling=4.0, residual_multiplier=0.5, tie_word_embeddings=True,
        rms_norm_eps=1e-5, max_position_embeddings=256, initializer_range=0.3)
    torch.manual_seed(0)
    model = transformers.GraniteMoeHybridForCausalLM(conf).eval()
    with torch.no_grad():  # the per-head vectors and norm scales off their init values
        for lyr in model.model.layers:
            lyr.input_layernorm.weight.copy_(torch.rand(64) + 0.5)
            lyr.post_attention_layernorm.weight.copy_(torch.rand(64) + 0.5)
            if hasattr(lyr, "mamba") and lyr.mamba is not None:
                lyr.mamba.A_log.copy_(torch.log(torch.rand(8) * 3 + 0.5))
                lyr.mamba.D.copy_(torch.randn(8))
                lyr.mamba.dt_bias.copy_(torch.randn(8))
                lyr.mamba.norm.weight.copy_(torch.rand(128) + 0.5)
                lyr.mamba.conv1d.bias.copy_(torch.randn(144) * 0.1)
    cfg = config_from_hf(conf.to_dict(), name="tiny-granite")
    assert cfg == WHOLE
    sd = {k: v.numpy() for k, v in model.state_dict().items()}

    def lay(i, name):
        return sd[f"model.layers.{i}.{name}"]

    def stack(rows):
        return jnp.asarray(np.stack(rows))

    mamba = [i for i, t in enumerate(types) if t == "mamba"]
    attn = [i for i, t in enumerate(types) if t == "attention"]
    F, Fs = 24, 40
    inp = [lay(i, "block_sparse_moe.input_linear.weight") for i in range(5)]  # [E, 2F, D]
    sh = [lay(i, "shared_mlp.input_linear.weight") for i in range(5)]  # [2Fs, D]
    params = {
        "tok_embed": jnp.asarray(sd["model.embed_tokens.weight"]),
        "final_norm": {"scale": jnp.asarray(sd["model.norm.weight"])},
        "layers": {
            "ln1": {"scale": stack([lay(i, "input_layernorm.weight") for i in range(5)])},
            "ln2": {"scale": stack([lay(i, "post_attention_layernorm.weight") for i in range(5)])},
            "moe": {
                "router": stack([lay(i, "block_sparse_moe.router.layer.weight").T for i in range(5)]),
                "w_gate": stack([w[:, :F].transpose(0, 2, 1) for w in inp]),
                "w_up": stack([w[:, F:].transpose(0, 2, 1) for w in inp]),
                "w_down": stack([lay(i, "block_sparse_moe.output_linear.weight").transpose(0, 2, 1)
                                 for i in range(5)]),
                "shared": {
                    "w_gate": stack([w[:Fs].T for w in sh]),
                    "w_up": stack([w[Fs:].T for w in sh]),
                    "w_down": stack([lay(i, "shared_mlp.output_linear.weight").T for i in range(5)]),
                },
            },
            "ssm": {
                "w_in": stack([lay(i, "mamba.in_proj.weight").T for i in mamba]),
                "conv_w": stack([lay(i, "mamba.conv1d.weight")[:, 0] for i in mamba]),
                "conv_b": stack([lay(i, "mamba.conv1d.bias") for i in mamba]),
                "dt_bias": stack([lay(i, "mamba.dt_bias") for i in mamba]),
                "A_log": stack([lay(i, "mamba.A_log") for i in mamba]),
                "D": stack([lay(i, "mamba.D") for i in mamba]),
                "norm": stack([lay(i, "mamba.norm.weight") for i in mamba]),
                "w_out": stack([lay(i, "mamba.out_proj.weight").T for i in mamba]),
            },
            "attn": {f"w{n}": stack([lay(i, f"self_attn.{n}_proj.weight").T for i in attn])
                     for n in "qkvo"},
        },
    }
    ids = np.array([[1, 7, 42, 99, 3, 250, 8, 11, 77, 5, 19]], np.int32)  # 11: not a chunk multiple
    ours, _ = core.forward(params, cfg, jnp.asarray(ids), None, jnp.int32(0))
    with torch.no_grad():
        theirs = model(torch.from_numpy(ids.astype(np.int64))).logits.numpy()
    np.testing.assert_allclose(np.asarray(ours, np.float32), theirs, atol=5e-5, rtol=1e-3)
    # ... and the benchmark's plain reference is tied to it with them
    np.testing.assert_allclose(_plain_logits(params, cfg, ids), theirs, atol=5e-5, rtol=1e-3)


# ------------------------------------------------------------------ the engine


def _engine(**over) -> InferenceEngine:
    return InferenceEngine("tiny-granite", engine_config=EngineConfig(**{**ENGINE_KW, **over}))


def _prompt(seed: int, n: int) -> list[int]:
    return [1] + [int(t) for t in np.random.RandomState(seed).randint(3, 259, n - 1)]


def test_engine_greedy_tokens_equal_the_references_and_info_says_the_depths():
    eng = _engine()
    try:
        full = jax.tree.map(jnp.asarray, core.restack_layers(eng.params))
        dims = _dims(eng.model_cfg)
        checked = 0
        for seed, n in ((0, 21), (5, 40), (6, 9)):
            ids = _prompt(seed, n)
            got = eng.generate(list(ids), max_new_tokens=10).token_ids
            for tok in got[:6]:
                ref, _ = plain.forward_logits(dims, full, np.asarray([ids], np.int32), len(ids) - 1)
                assert int(np.argmax(ref[0])) == tok
                ids.append(tok)
                checked += 1
        assert checked >= 6
        info = eng.info
        assert info["state"]["layers"] == 4 and info["state"]["ssm_row_shape"] == [4, 8, 16, 8]
        assert info["state"]["conv_row_shape"] == [4, 3, 144]
        assert info["kv"]["cache_layers"] == 1 and info["kv"]["bytes_per_token"] == 2 * 2 * 16 * 4
        assert eng.scheduler.cache.pool["kv"].shape[0] == 1
        assert eng.scheduler.cache.state["ssm"].shape[0] == 4
    finally:
        eng.close()


@pytest.mark.parametrize("over", [{}, {"prefill_chunk": 16}])
def test_rows_of_a_batch_equal_their_solo_runs(over):
    import threading

    spec = {0: (21, 12), 1: (9, 6), 2: (30, 10), 3: (13, 8)}
    solo_eng = _engine(max_batch=1)
    try:
        solo = {s: solo_eng.generate(_prompt(s, n), max_new_tokens=new).token_ids
                for s, (n, new) in spec.items()}
    finally:
        solo_eng.close()
    eng = _engine(**over)
    got: dict[int, list[int]] = {}

    def run(seed):
        n, new = spec[seed]
        got[seed] = eng.generate(_prompt(seed, n), max_new_tokens=new).token_ids

    try:
        threads = [threading.Thread(target=run, args=(s,)) for s in spec]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert got == solo
    finally:
        eng.close()


def test_counters_count_each_kind_of_layer_and_the_share():
    import bee2bee_tpu.engine.scheduler  # noqa: F401  (registers the metrics)
    from bee2bee_tpu.metrics import get_registry

    reg = get_registry()
    step, calls = reg.get("engine.ssm_step_rows"), reg.get("engine.ssm_step_kernel_calls")
    assign, hit = reg.get("engine.moe_assignments"), reg.get("engine.moe_experts_hit")
    layer_calls = reg.get("engine.moe_layer_calls")
    eng = _engine()
    try:
        was = (step.value(kind="live"), calls.value(), layer_calls.value(), hit.value(),
               {k: assign.value(kind=k) for k in ("live", "elsewhere", "dead")})
        eng.generate(_prompt(0, 21), max_new_tokens=6)
        steps = 5  # the window the budget leaves after the prefill's first token
        assert step.value(kind="live") - was[0] == steps * 4  # 4 recurrent layers of 5
        assert calls.value() - was[1] >= steps * 4
        forwards = (layer_calls.value() - was[2]) // 5
        now = {k: assign.value(kind=k) - was[4][k] for k in was[4]}
        # every live position's 3 choices in 5 layers are here or elsewhere
        assert now["live"] + now["elsewhere"] == (21 + steps) * 3 * 5
        assert now["live"] > 0 and now["elsewhere"] > 0 and now["dead"] == 11 * 15
        assert 0 < hit.value() - was[3] <= forwards * 5 * 4  # of the 4 HELD experts a layer
        state_bytes = sum(a.nbytes for a in jax.tree.leaves(eng.scheduler.cache.state))
        assert reg.get("engine.state_bytes").value() == state_bytes == 4 * (8 * 16 * 8 + 3 * 144) * 4
        eng.introspect.ledger.snapshot()
        assert reg.get("engine.hbm_bytes").value(component="state") == state_bytes
    finally:
        eng.close()
