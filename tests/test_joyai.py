"""JoyAI-LLM-Flash (latent attention, a sigmoid router with a selection bias
over a dropless expert layer, one shared expert, a leading dense layer): the
model against the plain reference and against ``transformers``' DeepSeek-V3
code the published config follows, the served path (prefill in chunks, then
decode through the paged LATENT pool) on logits, the router's properties one
by one, droplessness under the worst imbalance, the loader, the refusals and
the published parameter counts. All at ``tiny-joyai`` size on the CPU."""

from __future__ import annotations

import json
import sys
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bee2bee_tpu.engine import EngineConfig, InferenceEngine, FeatureUnsupported
from bee2bee_tpu.models import core
from bee2bee_tpu.models.config import config_from_hf, get_config
from bee2bee_tpu.ops.ragged import make_ragged_attn_fn

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "benchmark"))
import reference_joyai as plain  # noqa: E402  (the benchmark's plain reference)

CFG = get_config("tiny-joyai")
DIMS = plain.dims_of_preset(CFG)
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
ENGINE_KW = dict(max_seq_len=128, dtype="float32", cache_dtype="float32",
                 decode_chunk=4, max_batch=4, prefill_buckets=(16, 32, 64),
                 kv_block_size=8)
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 7168,
    "kv_lora_rank": 512, "max_position_embeddings": 131072,
    "model_type": "joyai_llm_flash", "moe_intermediate_size": 768, "moe_layer_freq": 1,
    "n_group": 1, "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8, "num_hidden_layers": 40,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
    "rope_theta": 32000000, "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 129280,
}


@pytest.fixture(scope="module")
def params():
    p = core.init_params(CFG, jax.random.key(3), dtype=jnp.float32)
    # nothing may hide behind an init value: the norms' scales random
    k = iter(jax.random.split(jax.random.key(4), 8))

    def off_one(group):
        attn = dict(group["attn"])
        for name in ("q_a_norm", "kv_a_norm"):
            attn[name] = 0.5 + jax.random.uniform(next(k), attn[name].shape)
        return dict(group, attn=attn)

    return dict(p, layers=off_one(p["layers"]), dense_layers=off_one(p["dense_layers"]))


@pytest.fixture(scope="module")
def pieces():
    return plain.build_forward(DIMS)


def _ids(rows: int, n: int, seed: int = 0):
    return np.random.RandomState(seed).randint(3, 500, (rows, n)).astype(np.int32)


def _plain(params, ids, pos, pieces=None, perturb=None):
    return plain.forward_logits(DIMS, params, ids, pos, perturb=perturb, pieces=pieces)[0]


def test_forward_matches_the_plain_reference(params, pieces):
    """The absorbed form (queries taken into the latent space, attention over
    the latent rows) equals the reference's EXPANDED form (per-head keys and
    values built from the latent), and the dropless expert layer the
    reference's dense sum, on logits."""
    ids = _ids(3, 21)
    got, _ = core.forward(params, CFG, ids, None, 0)
    for pos in (0, 5, 20):
        np.testing.assert_allclose(
            np.asarray(got[:, pos]), _plain(params, ids, pos, pieces), atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("perturb", [
    {"drop": "routed_scaling_factor"}, {"drop": "shared_expert"},
    {"drop": "e_score_correction_bias"}, {"bias_in_weights": True}, {"no_k_rope": True},
    {"activation_dtype": "float8_e4m3fn"},
], ids=lambda p: "-".join(f"{k}={v}" for k, v in p.items()))
def test_perturbed_reference_differs(params, pieces, perturb):
    """Each ONE-thing-wrong reference moves the logits by far more than the
    agreement above: the comparison can tell them apart."""
    ids = _ids(2, 19, seed=1)
    right = _plain(params, ids, 18, pieces)
    wrong = _plain(params, ids, 18, perturb=perturb)
    assert np.abs(wrong - right).max() > 100 * 2e-5 * max(1.0, np.abs(right).max())


@pytest.mark.parametrize("step", [0, 1])
def test_the_check_tries_every_admissible_subset_of_swaps(params, pieces, step):
    """The comparison's routing rule (reference_joyai.routed_logits_at): the
    compared position is also computed with every nonempty subset of the
    expert layers swapped 8th <-> 9th there. With near_tie 1.0 every subset
    is admissible in every live row: the subset's logits, recomputed at the
    compared position alone (swapped_logits), equal the whole forward pass
    under those swaps; rows without a served byte at the step stay plain. With
    a near_tie between a row's two gaps only the closer layer may swap, and a
    later layer's gap is judged in the pass that the earlier swap leads to."""
    R, P, n_new = 6, 9, 3
    ids = np.concatenate([_ids(R, P + step, seed=5), np.zeros((R, n_new - step), np.int32)], axis=1)
    owner = np.array([0, 1, -1, 2, 3, -1])
    served = [[65, 66], [65, 66], [65], [65, 66, 67]]
    live = np.array([0, 1, 4] if step else [0, 1, 3, 4])
    seen = {"min_gap": np.inf, "near": []}
    outs = plain.routed_logits_at(DIMS, params, ids, owner, served, P, 1.0, pieces, seen)(step)
    assert len(outs) == 4 and seen["near"][0].tolist() == np.isin(np.arange(R), live).tolist()
    assert 0 < seen["min_gap"] < 1
    pos = P - 1 + step
    dead = np.setdiff1d(np.arange(R), live)
    gaps_under = {}
    for v, layers in ((1, [0]), (2, [1]), (3, [0, 1])):
        swaps = np.zeros((2,) + ids.shape, bool)
        swaps[np.ix_(layers, live, [pos])] = True
        want, gaps = plain.forward_logits(DIMS, params, ids, pos, swaps, pieces=pieces)
        gaps_under[v] = gaps[:, :, pos]
        np.testing.assert_allclose(outs[v][live], want[live], atol=2e-5, rtol=1e-4)
        assert np.abs(outs[v][live] - outs[0][live]).max() > 1e-3  # a swap moves the logits
        np.testing.assert_array_equal(outs[v][dead], outs[0][dead])
    # a threshold between row 0's two plain gaps: only the closer layer may swap alone, and
    # both together only if the later layer is near-tied AFTER the earlier one's swap
    g0 = plain.forward_logits(DIMS, params, ids, pos, pieces=pieces)[1][:, 0, pos]
    nt = float(np.sort(g0).mean())
    outs = plain.routed_logits_at(DIMS, params, ids, owner, served, P, nt, pieces,
                                  {"min_gap": np.inf, "near": []})(step)
    for v, layers in ((1, [0]), (2, [1]), (3, [0, 1])):
        admissible = all(gaps_under[v][l, 0] < nt for l in layers)
        assert np.array_equal(outs[v][0], outs[0][0]) != admissible, (v, g0, nt)


@pytest.mark.parametrize("reader", ["dense", "ragged"])
def test_prefill_in_chunks_then_decode_through_the_latent_pool(params, pieces, reader):
    """The served path on LOGITS: every row prefilled alone in chunks of 8 into
    the paged latent pool (padded tail under the write ceil), then three
    decode steps of one batch whose rows have unequal lengths and whose third
    row is dead (null table), against the reference's full forward pass."""
    attn = make_ragged_attn_fn() if reader == "ragged" else None
    BS, lens = 4, [13, 21, 0, 9]
    pool = core.init_paged_pool(CFG, 40, BS, jnp.float32)
    assert set(pool) == {"latent"} and pool["latent"].shape == (3, 40, 1, BS, 32)
    tables, nxt = np.zeros((4, 8), np.int32), 1
    for b, n in enumerate(lens):
        if n:
            nb = -(-(n + 4) // BS)
            tables[b, :nb] = np.arange(nxt, nxt + nb)
            nxt += nb
    toks = _ids(4, 32, seed=2)
    for b, n in enumerate(lens):
        for pos in range(0, n, 8):
            chunk = toks[b:b + 1, pos:pos + 8].copy()
            chunk[0, min(8, n - pos):] = 0
            _, pool = core.forward(
                params, CFG, chunk, pool, np.int32(pos), attn_fn=attn,
                block_tables=tables[b:b + 1], paged_write_floor=np.int32(0),
                paged_write_ceil=np.int32(n))
    offs = np.asarray(lens, np.int32)
    for step in range(3):
        cur = np.stack([toks[b, lens[b] + step] for b in range(4)])[:, None]
        cache = dict(pool, moe_stats=jnp.zeros((3,), jnp.int32))
        logits, pool = core.forward(params, CFG, cur, cache, offs + step,
                                    attn_fn=attn, block_tables=tables)
        stats = np.asarray(pool.pop("moe_stats"))
        # 3 live rows x 4 experts a token x 2 expert layers; the dead row routes nowhere
        assert stats[2] == 24 and 8 <= stats[0] <= 24
        for b, n in enumerate(lens):
            if n:
                want = _plain(params, toks[b:b + 1, :n + step + 1], n + step, pieces)
                np.testing.assert_allclose(
                    np.asarray(logits[b, 0]), want[0], atol=3e-5, rtol=1e-4)


# ------------------------------------------------------------- the router


def _router_case(scores, bias):
    """_moe_router on one token whose sigmoid scores are ``scores``."""
    s = np.asarray(scores, np.float64)
    logit = np.log(s / (1 - s)).astype(np.float32)  # x = e_0, router row 0 = logit
    E = len(s)
    p = {"router": jnp.zeros((CFG.d_model, E)).at[0].set(logit),
         "router_bias": jnp.asarray(bias, jnp.float32)}
    x = jnp.zeros((1, CFG.d_model)).at[0, 0].set(1.0)
    cfg = get_config("tiny-joyai")
    topi, w = core._moe_router(x, p, cfg)
    return [int(i) for i in topi[0]], np.asarray(w[0], np.float64)


S16 = [0.9, 0.8, 0.7, 0.6, 0.3, 0.2] + [0.1] * 10  # 16 experts, the top 4 clear


def test_router_selects_by_score_plus_bias():
    assert sorted(_router_case(S16, np.zeros(16))[0]) == [0, 1, 2, 3]
    bias = np.zeros(16)
    bias[5] = 0.75  # 0.2 + 0.75 beats 0.9: the bias re-orders the choice
    assert sorted(_router_case(S16, bias)[0]) == [0, 1, 2, 5]


def test_router_weighs_by_the_score_without_the_bias():
    bias = np.zeros(16)
    bias[5] = 0.75
    idx, w = _router_case(S16, bias)
    got = dict(zip(idx, w))
    # weights from s (0.9, 0.2), NOT from s + b (0.9, 0.95)
    assert got[0] / got[5] == pytest.approx(0.9 / 0.2, rel=1e-4)


def test_router_normalises_the_chosen_weights_and_scales_them():
    _, w = _router_case(S16, np.zeros(16))
    assert w.sum() == pytest.approx(CFG.moe_scale, rel=1e-5)  # normalised, then x 2.5
    assert CFG.moe_scale == 2.5 and sorted(w / 2.5) == pytest.approx(
        [0.6 / 3.0, 0.7 / 3.0, 0.8 / 3.0, 0.9 / 3.0], rel=1e-4)  # not raw, not a softmax


def test_router_scores_in_float32_whatever_the_stream_holds():
    """As published the router is never bf16: a bf16 residual stream is taken
    up to float32 BEFORE the router's product, the scores and weights are
    float32, and they equal the float32 router on the same (bf16-valued) input
    bit for bit: nothing inside rounds to bf16."""
    E = CFG.n_experts
    x = jax.random.normal(jax.random.key(11), (5, CFG.d_model), jnp.bfloat16)
    p = {"router": jax.random.normal(jax.random.key(12), (CFG.d_model, E), jnp.float32),
         "router_bias": jnp.zeros((E,), jnp.float32)}
    topi, w = core._moe_router(x, p, CFG)
    assert w.dtype == jnp.float32
    topi32, w32 = core._moe_router(x.astype(jnp.float32), p, CFG)
    np.testing.assert_array_equal(np.asarray(topi), np.asarray(topi32))
    np.testing.assert_array_equal(np.asarray(w), np.asarray(w32))
    # and a bf16 router would NOT give these weights
    s16 = jax.nn.sigmoid(jnp.dot(x, p["router"].astype(jnp.bfloat16)).astype(jnp.float32))
    s32 = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32), p["router"]))
    assert float(jnp.abs(s16 - s32).max()) > 1e-4


# ------------------------------------------------ dropless, the worst imbalance


# ------------------------------------- the seeded selection bias is balanced


def test_a_balanced_bias_spreads_correlated_tokens_over_the_experts():
    """Scores with a large part common to every token (what seeded hidden
    states give a router): under a zero bias 64 tokens pick the same few of
    256 experts; under _balanced_bias every expert gets the mean load on the
    batch it was solved on, and 64 FRESH tokens touch nearly the 1 - (1 - 8 /
    256)^64 = 86.9 % that independent choices would."""
    rng = np.random.RandomState(0)
    E, k = 256, 8
    common = rng.normal(size=(1, E))

    def scores(n):
        return jax.nn.sigmoid(jnp.asarray(common + 0.7 * rng.normal(size=(n, E)), jnp.float32))

    s = scores(4096)
    bias = core._balanced_bias(s, k)

    def load(s, b):
        _, topi = jax.lax.top_k(s + b, k)
        return np.bincount(np.asarray(topi).ravel(), minlength=E)

    before, after = load(s, 0.0), load(s, bias)
    assert before.max() > 8 * before.mean() and (before == 0).sum() > 20
    assert 0.9 * after.mean() < after.min() and after.max() < 1.1 * after.mean()
    fresh = [scores(64) for _ in range(8)]
    touched = lambda b: np.mean([(load(f, b) > 0).mean() for f in fresh])  # noqa: E731
    assert touched(0.0) < 0.5 and touched(bias) > 0.82


def test_seeded_weights_come_with_a_balanced_nonzero_float32_bias(params):
    """init_params balances every expert layer's selection bias on the
    balancing batch (core.balance_router_bias): float32, nonzero, the same for
    the same key, and the routed load on seeded traffic is far more even than
    under a zero bias."""
    bias = params["layers"]["moe"]["router_bias"]
    assert bias.dtype == jnp.float32 and bias.shape == (CFG.n_expert_layers, CFG.n_experts)
    assert float(jnp.abs(bias).min()) > 0.0
    again = core.init_params(CFG, jax.random.key(3), dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(again["layers"]["moe"]["router_bias"]), np.asarray(bias))
    tokens, plen = core._balance_tokens(CFG)
    assert tokens.shape == (32, 256) and tokens[:, 0].tolist() == [1] * 32
    text = bytes(int(t) - 3 for t in tokens[0, 1:plen[0]]).decode()
    assert set(text.split(" ")[:-1]) <= set(core.BALANCE_WORDS)  # (the last word may be cut)

    def busiest(p):  # the busiest expert's load over the mean load
        _, max_load, live = _stats_of(p, tokens)
        return max_load / (live / CFG.n_experts)

    flat = dict(params, layers=dict(params["layers"], moe=dict(
        params["layers"]["moe"], router_bias=jnp.zeros_like(bias))))
    assert busiest(params) < 0.8 * busiest(flat)


def _stats_of(p, tokens):
    """[touched, max_load, live] summed over the expert layers of one
    cache-less forward over ``tokens`` (the prompts' part alone is seeded
    text; the continuation is whatever the batch began with)."""
    R, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (R, T))
    mask = core.attn_mask(CFG, positions, T)
    x = core.embed_tokens(p, CFG, jnp.asarray(tokens), positions)
    total = np.zeros(3, np.int64)
    for group in ("dense_layers", "layers"):
        for i in range(len(jax.tree.leaves(p[group])[0])):
            lp = jax.tree.map(lambda a: a[i], p[group])  # noqa: B023
            x = core.transformer_block(
                lp, CFG, x, positions, mask,
                moe_sink=lambda st: total.__iadd__(np.asarray(st, np.int64)))
    return total


def _one_expert_params(params):
    """Every token chooses experts 0 to 3, whatever it holds."""
    moe = dict(params["layers"]["moe"])
    moe["router"] = jnp.zeros_like(moe["router"])
    moe["router_bias"] = jnp.zeros_like(moe["router_bias"]).at[:, :4].set(1.0)
    return dict(params, layers=dict(params["layers"], moe=moe))


def test_dropless_when_every_token_goes_to_the_same_experts(params, pieces):
    skew = _one_expert_params(params)
    ids = _ids(2, 40, seed=5)  # 80 tokens x 4 = 320 assignments on 4 of 16 experts
    got, _ = core.forward(skew, CFG, ids, None, 0)
    np.testing.assert_allclose(
        np.asarray(got[:, 39]), _plain(skew, ids, 39, pieces), atol=2e-5, rtol=1e-4)
    x = jax.random.normal(jax.random.key(9), (2, 40, CFG.d_model))
    lp = jax.tree.map(lambda a: a[0], skew["layers"]["moe"])
    _, stats = core._moe_dropless(x, lp, CFG)
    assert [int(v) for v in stats] == [4, 80, 320]  # hit, busiest, live: none dropped
    live = jnp.arange(40)[None, :] < jnp.asarray([[40], [7]])
    out, stats = core._moe_dropless(x, lp, CFG, live=live)
    assert [int(v) for v in stats] == [4, 47, 188]  # dead positions are no load
    full, _ = core._moe_dropless(x, lp, CFG)
    np.testing.assert_allclose(np.asarray(out[1, :7]), np.asarray(full[1, :7]), atol=1e-6)


def test_the_leading_layer_is_dense_and_the_next_ones_are_not(params):
    assert "mlp" in params["dense_layers"] and "moe" not in params["dense_layers"]
    assert "moe" in params["layers"] and "mlp" not in params["layers"]
    assert params["dense_layers"]["mlp"]["w_up"].shape == (1, 48, 96)
    assert params["layers"]["moe"]["w_up"].shape == (2, 16, 48, 36)
    assert params["layers"]["moe"]["shared"]["w_up"].shape == (2, 48, 36)
    assert params["layers"]["moe"]["router_bias"].dtype == jnp.float32
    # unstacked (the CPU engine's list): absolute order, unlike trees, and back
    flat = core.unstack_layers(jax.device_get(params))
    assert ["moe" in lp for lp in flat["layers"]] == [False, True, True]
    back = core.restack_layers(flat)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    ids = _ids(1, 9)
    a, _ = core.forward(params, CFG, ids, None, 0)
    b, _ = core.forward(jax.tree.map(jnp.asarray, flat), CFG, ids, None, 0)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_a_latent_block_adds_its_branches_bare(params):
    """``residual_multiplier`` is granite's: a latent-attention block takes no
    notice of one (as before PR 56 put its adds under ``mla.out`` / the FFN's
    part)."""
    import dataclasses

    ids = _ids(1, 9)
    a, _ = core.forward(params, CFG, ids, None, 0)
    half = dataclasses.replace(CFG, residual_multiplier=0.5)
    b, _ = core.forward(params, half, ids, None, 0)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------- configuration and loader


def test_published_preset_equals_the_catalog_config():
    assert config_from_hf(PUBLISHED, name="joyai-llm-flash") == get_config("joyai-llm-flash")
    if CATALOG.exists():
        row = next(json.loads(ln) for ln in CATALOG.read_text().splitlines()
                   if '"JoyAI-LLM-Flash"' in ln)
        assert row["config"] == PUBLISHED
    cut = get_config("joyai-llm-flash-5l")
    assert cut.n_layers == 5 and cut.first_k_dense == 1 and cut.n_expert_layers == 4
    assert core.pool_layout(cut) == {"latent": (1, 576)}
    assert core.pool_bytes_per_token(cut) == 5 * 576 * 2


@pytest.mark.parametrize("key,value", [
    ("n_group", 8), ("topk_group", 4), ("topk_method", "greedy"),
    ("scoring_func", "softmax"), ("moe_layer_freq", 2), ("attention_bias", True),
    ("hidden_act", "gelu"), ("q_lora_rank", None),
    ("rope_scaling", {"type": "yarn", "factor": 40}),
])
def test_unimplemented_variants_are_refused_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        config_from_hf({**PUBLISHED, key: value})


def _count(tree) -> int:
    return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))


def test_parameter_counts_as_published():
    """26.35 M of attention a layer, 4.719 M an expert, 1,239.55 M an expert
    layer, 5,558 M as run (ISSUE 38's arithmetic), from the shapes alone."""
    cut = get_config("joyai-llm-flash-5l")
    shapes = jax.eval_shape(lambda: core._init_params(cut, jax.random.key(0), jnp.bfloat16))
    one = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), tree)
    layer = one(shapes["layers"])
    assert _count(layer["attn"]) == 26_345_472 + 1536 + 512  # five matrices + two norms
    moe = layer["moe"]
    expert = sum(int(np.prod(moe[n].shape[1:])) for n in ("w_gate", "w_up", "w_down"))
    assert expert == 4_718_592
    # attention + 256 experts + the shared one + the router: 1,239.55 M
    matrices = 26_345_472 + 257 * 4_718_592 + 2048 * 256
    assert matrices == 1_239_547_904
    assert _count(layer) == matrices + 256 + 2 * 2048 + 1536 + 512  # + bias, norms
    assert round(_count(shapes) / 1e6) == 5558
    assert core.matmul_params_per_token(cut) < 0.12 * _count(shapes)  # 8 of 256 experts


def _hf_state(cfg, seed: int = 0, extra_layers: int = 1) -> dict:
    """A seeded state dict under the published names, with ``extra_layers``
    next-n layers past the model's own (which the loader must ignore)."""
    rng = np.random.default_rng(seed)
    r = lambda *s: rng.standard_normal(s).astype(np.float32) * 0.1  # noqa: E731
    D, H = cfg.d_model, cfg.n_heads
    qk = cfg.mla_nope_dim + cfg.mla_rope_dim
    st = {"model.embed_tokens.weight": r(cfg.vocab_size, D), "model.norm.weight": r(D) + 1,
          "lm_head.weight": r(cfg.vocab_size, D)}
    for i in range(cfg.n_layers + extra_layers):
        a = f"model.layers.{i}."
        st.update({
            a + "input_layernorm.weight": r(D) + 1,
            a + "post_attention_layernorm.weight": r(D) + 1,
            a + "self_attn.q_a_proj.weight": r(cfg.mla_q_rank, D),
            a + "self_attn.q_a_layernorm.weight": r(cfg.mla_q_rank) + 1,
            a + "self_attn.q_b_proj.weight": r(H * qk, cfg.mla_q_rank),
            a + "self_attn.kv_a_proj_with_mqa.weight": r(cfg.latent_width, D),
            a + "self_attn.kv_a_layernorm.weight": r(cfg.mla_kv_rank) + 1,
            a + "self_attn.kv_b_proj.weight": r(
                H * (cfg.mla_nope_dim + cfg.mla_v_dim), cfg.mla_kv_rank),
            a + "self_attn.o_proj.weight": r(D, H * cfg.mla_v_dim),
        })
        if i < cfg.first_k_dense:
            widths = {"mlp": cfg.d_ff}
        else:
            st[a + "mlp.gate.weight"] = r(cfg.n_experts, D)
            st[a + "mlp.gate.e_score_correction_bias"] = r(cfg.n_experts)
            widths = {"mlp.shared_experts": cfg.expert_ff * cfg.n_shared_experts,
                      **{f"mlp.experts.{e}": cfg.expert_ff for e in range(cfg.n_experts)}}
        for at, F in widths.items():
            st[a + at + ".gate_proj.weight"] = r(F, D)
            st[a + at + ".up_proj.weight"] = r(F, D)
            st[a + at + ".down_proj.weight"] = r(D, F)
        if i >= cfg.n_layers:  # the next-n layer's own tensors
            st[a + "eh_proj.weight"] = r(D, 2 * D)
            st[a + "enorm.weight"] = r(D)
    return st


def test_loader_maps_the_published_names_and_ignores_the_next_n_layer():
    from bee2bee_tpu.models.loader import _convert_joyai

    st = _hf_state(CFG)
    got = _convert_joyai(st, CFG)
    want = core.init_params(CFG, jax.random.key(0), dtype=jnp.float32)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert jax.tree.map(np.shape, got) == jax.tree.map(np.shape, jax.device_get(want))
    # layer 1 is the FIRST expert layer, expert 5's up matrix transposed
    np.testing.assert_array_equal(
        got["layers"]["moe"]["w_up"][0, 5], st["model.layers.1.mlp.experts.5.up_proj.weight"].T)
    np.testing.assert_array_equal(
        got["layers"]["moe"]["router_bias"][1],
        st["model.layers.2.mlp.gate.e_score_correction_bias"])
    np.testing.assert_array_equal(
        got["dense_layers"]["attn"]["wkv_a"][0],
        st["model.layers.0.self_attn.kv_a_proj_with_mqa.weight"].T)


def test_transformers_checkpoint_loads_and_logits_match(tmp_path):
    """The tie to the published code: ``transformers``' DeepseekV3ForCausalLM
    (the layout joyai_llm_flash's config.json follows) at tiny size, saved,
    loaded through loader.py, logits compared."""
    transformers = pytest.importorskip("transformers")
    torch = pytest.importorskip("torch")
    if not hasattr(transformers, "DeepseekV3ForCausalLM"):
        pytest.skip("transformers too old for DeepseekV3ForCausalLM")
    from bee2bee_tpu.models.loader import load_checkpoint

    conf = transformers.DeepseekV3Config(
        vocab_size=512, hidden_size=48, intermediate_size=96, moe_intermediate_size=36,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
        n_shared_experts=1, n_routed_experts=8, routed_scaling_factor=2.5,
        kv_lora_rank=24, q_lora_rank=40, qk_rope_head_dim=8, v_head_dim=20,
        qk_nope_head_dim=12, n_group=1, topk_group=1, num_experts_per_tok=2,
        first_k_dense_replace=1, norm_topk_prob=True, hidden_act="silu",
        max_position_embeddings=64, rms_norm_eps=1e-6, tie_word_embeddings=False,
        rope_theta=10000.0, rope_scaling=None, rope_interleave=True,
        attention_bias=False)
    torch.manual_seed(0)
    model = transformers.DeepseekV3ForCausalLM(conf).eval()
    with torch.no_grad():  # off their init values: the bias re-orders the choice
        for lyr in model.model.layers[1:]:
            lyr.mlp.gate.e_score_correction_bias.copy_(torch.randn(8) * 0.2)
            lyr.mlp.gate.weight.copy_(torch.randn(8, 48))
        for lyr in model.model.layers:
            lyr.self_attn.kv_a_layernorm.weight.copy_(torch.rand(24) + 0.5)
            lyr.self_attn.q_a_layernorm.weight.copy_(torch.rand(40) + 0.5)
    model.save_pretrained(tmp_path)
    d = json.loads((tmp_path / "config.json").read_text())
    d.update(model_type="joyai_llm_flash", topk_method="noaux_tc", scoring_func="sigmoid",
             moe_layer_freq=1)
    cfg = config_from_hf(d, name="tiny-from-transformers")
    loaded = load_checkpoint(tmp_path, cfg, dtype=jnp.float32)
    ids = np.array([[1, 7, 42, 99, 3, 250, 8, 11, 77, 5, 19]], np.int32)
    ours, _ = core.forward(loaded, cfg, jnp.asarray(ids), None, jnp.int32(0))
    with torch.no_grad():
        theirs = model(torch.from_numpy(ids.astype(np.int64))).logits.numpy()
    np.testing.assert_allclose(np.asarray(ours, np.float32), theirs, atol=3e-5, rtol=1e-3)
    low = load_checkpoint(tmp_path, cfg, dtype=jnp.bfloat16, host=True)["layers"]["moe"]
    assert low["router_bias"].dtype == np.float32 and low["w_up"].dtype != np.float32


# ------------------------------------------------------------- the engine


def _engine(**over) -> InferenceEngine:
    return InferenceEngine("tiny-joyai", engine_config=EngineConfig(**{**ENGINE_KW, **over}))


def _prompt(seed: int, n: int) -> list[int]:
    return [1] + [int(t) for t in np.random.RandomState(seed).randint(3, 259, n - 1)]


SPEC = {0: (21, 20), 1: (9, 6), 2: (30, 24), 3: (13, 16), 4: (40, 12)}


@pytest.fixture(scope="module")
def solo():
    """Each test prompt's greedy tokens from an engine that serves it ALONE."""
    eng = _engine(max_batch=1)
    want = {s: eng.generate(_prompt(s, n), max_new_tokens=new).token_ids
            for s, (n, new) in SPEC.items()}
    eng.close()
    return want


def test_engine_decode_matches_full_forward(solo):
    eng = _engine()
    try:
        full = jax.tree.map(jnp.asarray, core.restack_layers(eng.params))
        ids = _prompt(0, 21)
        for tok in solo[0][:8]:
            lg, _ = core.forward(full, eng.model_cfg, np.asarray([ids], np.int32), None, 0)
            assert int(np.argmax(np.asarray(lg[0, -1]))) == tok
            ids.append(tok)
        assert eng.generate(_prompt(0, 21), max_new_tokens=20).token_ids == solo[0]
        kv = eng.info["kv"]
        assert kv["layout"] == {"latent": [1, 32]} and kv["bytes_per_token"] == 3 * 32 * 4
        pool = eng.scheduler.cache.pool
        assert set(pool) == {"latent"} and pool["latent"].shape[2] == 1  # stored once, no V
        eng.introspect.ledger.snapshot()
        from bee2bee_tpu.metrics import get_registry

        assert get_registry().get("engine.hbm_bytes").value(
            component="latent") == pool["latent"].nbytes
    finally:
        eng.close()


@pytest.mark.parametrize("over", [{}, {"attention": "flash"}, {"prefill_chunk": 16}],
                         ids=lambda o: "-".join(f"{k}={v}" for k, v in o.items()) or "default")
def test_rows_admitted_retired_compacted_equal_their_solo_runs(solo, over):
    """Five requests over four rows, admitted at different times, of different
    lengths (rows retire while others decode, holes compact, the bucket
    resizes, a dead row sits in the bucket), the dense and the ragged reader,
    chunked prefill on and off: every row's tokens equal its solo run."""
    eng = _engine(**over)
    got: dict[int, list[int]] = {}

    def run(seed):
        n, new = SPEC[seed]
        got[seed] = eng.generate(_prompt(seed, n), max_new_tokens=new).token_ids

    try:
        first = [threading.Thread(target=run, args=(s,)) for s in (0, 1, 2)]
        for t in first:
            t.start()
        first[1].join()
        later = [threading.Thread(target=run, args=(s,)) for s in (3, 4)]
        for t in later:
            t.start()
        for t in first + later:
            t.join()
        assert got == {s: solo[s] for s in SPEC}
    finally:
        eng.close()


def test_expert_counters_read_what_the_imbalanced_case_implies(params):
    """Every token on experts 0 to 3: a 21-token prompt in a 32 bucket makes
    21 x 4 x 2 live and 11 x 4 x 2 dead assignments and hits 4 experts in each
    of the 2 expert-layer calls; every decode step 1 x 4 x 2 and 4 + 4. A decode
    step at offset o reads o + 1 latent rows in each of the 3 layers."""
    import bee2bee_tpu.engine.scheduler  # noqa: F401  (registers the metrics)
    from bee2bee_tpu.metrics import get_registry

    reg = get_registry()
    assign, hit = reg.get("engine.moe_assignments"), reg.get("engine.moe_experts_hit")
    calls, rows = reg.get("engine.moe_layer_calls"), reg.get("engine.latent_tokens_read")
    eng = InferenceEngine("tiny-joyai", params=_one_expert_params(params),
                          engine_config=EngineConfig(**ENGINE_KW))
    try:
        was = (assign.value(kind="live"), assign.value(kind="dead"), hit.value(),
               calls.value(), rows.value())
        eng.generate(_prompt(0, 21), max_new_tokens=6)
        # a bucket of one row; the window runs the 5 steps the row's budget
        # leaves after the prefill's first token, in chunks of 4 + 1
        steps = 5
        assert eng.scheduler.stats.chunks == 2
        assert assign.value(kind="live") - was[0] == (21 + steps) * 4 * 2
        assert assign.value(kind="dead") - was[1] == 11 * 4 * 2
        assert hit.value() - was[2] == (1 + steps) * 4 * 2
        assert calls.value() - was[3] == (1 + steps) * 2
        assert rows.value() - was[4] == sum(21 + s + 1 for s in range(steps)) * 3
        # one live row, its four assignments on four experts: 1 each, mean 4/16
        assert reg.get("engine.moe_expert_load_max").value() == pytest.approx(4.0)
    finally:
        eng.close()


def test_absorbed_decode_equals_expanded_prefill_on_the_same_tokens(params):
    """The two attention paths are one mathematics: a whole-sequence pass with
    no cache runs EXPANDED (per-head keys and values built from the chunk's
    c_kv), the paged path ABSORBED (queries into the latent space, attention
    over the cached rows). Logits at every position of the decode stretch
    agree to float32 rounding."""
    n, extra, BS = 13, 5, 8
    ids = _ids(1, n + extra, seed=8)
    expanded, _ = core.forward(params, CFG, ids, None, 0)
    pool = core.init_paged_pool(CFG, 8, BS, jnp.float32)
    tables = np.asarray([[1, 2, 3, 0]], np.int32)
    chunk = np.zeros((1, 16), np.int32)
    chunk[0, :n] = ids[0, :n]
    got, pool = core.forward(params, CFG, chunk, pool, np.int32(0), block_tables=tables,
                             paged_write_floor=np.int32(0), paged_write_ceil=np.int32(n))
    np.testing.assert_allclose(np.asarray(got[0, :n]), np.asarray(expanded[0, :n]),
                               atol=3e-5, rtol=1e-4)
    for step in range(extra):
        got, pool = core.forward(params, CFG, ids[:, n + step:n + step + 1], pool,
                                 np.asarray([n + step], np.int32), block_tables=tables)
        np.testing.assert_allclose(np.asarray(got[0, 0]), np.asarray(expanded[0, n + step]),
                                   atol=3e-5, rtol=1e-4)


def test_latent_rows_export_and_import_between_plain_and_lane_aligned_pools():
    """Blocks are blocks: a row's latent pages travel at the published row
    width (32 here) whichever way the pool stores them, into a lane-aligned
    pool (128 lanes) and back, bit for bit; the migration signature names the
    latent layout."""
    import functools

    from bee2bee_tpu.engine.paged import RowCache

    eng = _engine()
    try:
        assert eng._scheduler is None
        BS, W = eng.engine_cfg.kv_block_size, CFG.latent_width
        plain_rc, aligned = RowCache(eng, 4), RowCache(eng, 4)
        aligned.pool = jax.jit(functools.partial(
            core.init_paged_pool, CFG, eng.pool_blocks, BS, jnp.float32, lane_aligned=True))()
        assert aligned.pool["latent"].shape[-1] == 128 != W
        plain_rc.pool = {"latent": jax.random.normal(
            jax.random.key(2), plain_rc.pool["latent"].shape, jnp.float32)}
        n = 2 * BS + 3
        plain_rc.cover(0, n)
        nb, sent = plain_rc.export_row(0, n)
        assert nb == 3 and set(sent) == {"latent"}
        assert sent["latent"].shape == (CFG.n_layers, 1, 3, BS, W)
        aligned.import_row(2, n, sent)
        # stored [L, NB, 1, BS, W]; the wire keeps its block axis at 2
        got = np.asarray(aligned.pool["latent"])[:, aligned.row_blocks[2]].swapaxes(1, 2)
        np.testing.assert_array_equal(got[..., :W], sent["latent"])
        assert not got[..., W:].any()  # pad lanes stay zero
        nb2, back = aligned.export_row(2, n)
        assert nb2 == 3
        np.testing.assert_array_equal(back["latent"], sent["latent"])
        for rc, b in ((plain_rc, 0), (aligned, 2)):
            rc.release(b)
            assert rc.alloc.used_count == 0
    finally:
        eng.close()


REFUSED = {
    "kv_int8": dict(cache_dtype="int8"),
    "spec_ngram": dict(spec_tokens=4),
    "spec_model_drafter": dict(spec_tokens=4, drafter="tiny-llama"),
    "spec_mesh_drafter": dict(spec_tokens=4, drafter="mesh"),
    "multi_lora": dict(max_adapters=2),
    "weight_int8": dict(quantize="int8"),
    "prefix_cache": dict(prefix_cache_entries=4),
}


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_features_not_proven_over_a_latent_pool_are_refused(feature):
    with pytest.raises(FeatureUnsupported) as err:
        _engine(**REFUSED[feature])
    assert err.value.feature == feature and feature in str(err.value)


def test_a_rectangular_cache_is_refused(params):
    with pytest.raises(ValueError, match="latent"):
        core.forward(params, CFG, _ids(1, 4), {"k": jnp.zeros((3, 1, 8, 4, 8))}, 0)
