"""SmallThinker-21BA3B-Instruct (full layers without positions beside roped
window layers, every layer dropless ReGLU experts behind a softmax-top-k router
that reads the pre-attention norm): the model against the benchmark's plain
reference, the served path (prefill in chunks, then decode through the paged
pool, contexts PAST the window) on logits, each one-thing-wrong reference
caught, the router's properties one by one, dead rows and padded tails, the
sigmoid path bit-equal to before, the seeded router's centring, the published
preset, the refusals, the engine and its counters. All at ``tiny-smallthinker``
size on the CPU."""

from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bee2bee_tpu.engine import EngineConfig, FeatureUnsupported, InferenceEngine
from bee2bee_tpu.metrics import get_registry
from bee2bee_tpu.models import core
from bee2bee_tpu.models.config import config_from_hf, get_config
from bee2bee_tpu.ops.grouped import grouped_matmul
from bee2bee_tpu.ops.ragged import make_ragged_attn_fn, read_counts

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "benchmark"))
import reference_smallthinker as plain  # noqa: E402  (the benchmark's plain reference)

CFG = get_config("tiny-smallthinker")
DIMS = plain.dims_of_preset(CFG)
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
ENGINE_KW = dict(max_seq_len=128, dtype="float32", cache_dtype="float32",
                 decode_chunk=4, max_batch=4, prefill_buckets=(16, 32, 64),
                 kv_block_size=8)
PERTURBED = [
    {"no_window": True}, {"rope_full_layers": True}, {"router_input": "ffn_norm"},
    {"activation": "silu"}, {"router_weights": "sigmoid"},
    {"activation_dtype": "float8_e4m3fn"},
]


@pytest.fixture(scope="module")
def params():
    p = core.init_params(CFG, jax.random.key(3), dtype=jnp.float32)
    # nothing may hide behind an init value: the norms' scales random
    k = iter(jax.random.split(jax.random.key(4), 4))
    layers = dict(p["layers"])
    for name in ("ln1", "ln2"):
        layers[name] = {"scale": 0.5 + jax.random.uniform(next(k), layers[name]["scale"].shape)}
    return dict(p, layers=layers)


@pytest.fixture(scope="module")
def pieces():
    return plain.build_forward(DIMS)


def _ids(rows: int, n: int, seed: int = 0):
    return np.random.RandomState(seed).randint(3, 500, (rows, n)).astype(np.int32)


def _plain(params, row, positions, pieces=None, perturb=None):
    return plain.context_logits(
        DIMS, params, row, list(positions), pieces or plain.build_forward(DIMS, perturb))


# ------------------------------------------------------ model vs reference


def test_forward_matches_the_plain_reference(params, pieces):
    """A context of 70 tokens, nearly three windows of 24: the window layers'
    mask and rotation, the full layers' lack of one, the router's input and the
    dropless ReGLU experts equal the reference's dense sum, on logits."""
    ids = _ids(2, 70)
    got, _ = core.forward(params, CFG, ids, None, 0)
    for b in range(2):
        for pos, want in zip((5, 40, 69), _plain(params, ids[b], (5, 40, 69), pieces)):
            np.testing.assert_allclose(np.asarray(got[b, pos]), want, atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("perturb", PERTURBED,
                         ids=lambda p: "-".join(f"{k}={v}" for k, v in p.items()))
def test_perturbed_reference_differs(params, pieces, perturb):
    """Each ONE-thing-wrong reference moves the logits by far more than the
    agreement above: the comparison can tell them apart."""
    row = _ids(1, 60, seed=1)[0]
    right = _plain(params, row, (59,), pieces)[0]
    wrong = _plain(params, row, (59,), perturb=perturb)[0]
    assert np.abs(wrong - right).max() > 100 * 2e-5 * max(1.0, np.abs(right).max())


@pytest.mark.parametrize("reader", ["dense", "ragged"])
def test_prefill_in_chunks_then_decode_through_the_paged_pool(params, pieces, reader):
    """The served path on LOGITS: every row prefilled alone in chunks of 16 into
    the paged pool (padded tail under the write ceil), to contexts PAST the
    window of 24, then three decode steps of one batch whose rows have unequal
    lengths and whose third row is dead (null table), against the reference."""
    attn = make_ragged_attn_fn() if reader == "ragged" else None
    BS, lens = 8, [37, 61, 0, 29]
    pool = core.init_paged_pool(CFG, 40, BS, jnp.float32)
    assert set(pool) == {"kv"} and pool["kv"].shape == (
        CFG.n_layers, 40, 2, CFG.n_kv_heads, BS, CFG.head_dim)
    tables, nxt = np.zeros((4, 16), np.int32), 1
    for b, n in enumerate(lens):
        if n:
            nb = -(-(n + 4) // BS)
            tables[b, :nb] = np.arange(nxt, nxt + nb)
            nxt += nb
    toks = _ids(4, 72, seed=2)
    for b, n in enumerate(lens):
        for pos in range(0, n, 16):
            chunk = toks[b:b + 1, pos:pos + 16].copy()
            chunk[0, min(16, n - pos):] = 0
            _, pool = core.forward(
                params, CFG, chunk, pool, np.int32(pos), attn_fn=attn,
                block_tables=tables[b:b + 1], paged_write_floor=np.int32(0),
                paged_write_ceil=np.int32(n))
    offs = np.asarray(lens, np.int32)
    want = {b: _plain(params, toks[b], range(n, n + 3), pieces) for b, n in enumerate(lens) if n}
    for step in range(3):
        cur = np.stack([toks[b, lens[b] + step] for b in range(4)])[:, None]
        cache = dict(pool, moe_stats=jnp.zeros((3,), jnp.int32))
        logits, pool = core.forward(params, CFG, cur, cache, offs + step,
                                    attn_fn=attn, block_tables=tables)
        stats = np.asarray(pool.pop("moe_stats"))
        # 3 live rows x 3 experts a token x 4 expert layers; the dead row routes nowhere
        assert stats[2] == 36 and 12 <= stats[0] <= 32
        for b in want:
            np.testing.assert_allclose(
                np.asarray(logits[b, 0]), want[b][step], atol=3e-5, rtol=1e-4)


# ------------------------------------------------------------- the router


def _router_case(logits):
    """_moe_router on one token whose router logits are ``logits``."""
    z = np.asarray(logits, np.float32)
    p = {"router": jnp.zeros((CFG.d_model, len(z))).at[0].set(z)}
    x = jnp.zeros((1, CFG.d_model)).at[0, 0].set(1.0)
    topi, w = core._moe_router(x, p, CFG)
    return [int(i) for i in topi[0]], np.asarray(w[0], np.float64)


Z8 = [2.0, -1.0, 0.5, 1.5, -3.0, 0.0, 0.4, -0.2]  # 8 experts, the top 3: 0, 3, 2


def test_router_takes_the_largest_logits_and_softmaxes_those_alone():
    idx, w = _router_case(Z8)
    assert idx == [0, 3, 2]
    e = np.exp(np.asarray([2.0, 1.5, 0.5]))
    assert w == pytest.approx(e / e.sum(), rel=1e-5) and w.sum() == pytest.approx(1.0)


def test_softmax_over_the_chosen_equals_softmax_over_all_then_renormalised():
    """moe_primary_router_apply_softmax + norm_topk_prob: a softmax over all 8,
    the top 3 kept and divided by their sum, is the softmax over those 3."""
    idx, w = _router_case(Z8)
    full = np.exp(np.asarray(Z8, np.float64))
    full /= full.sum()
    assert sorted(np.argsort(-full)[:3]) == sorted(idx)
    assert w == pytest.approx(full[idx] / full[idx].sum(), rel=1e-5)
    # and it is neither the sigmoid-normalised weights nor the raw softmax
    sig = 1 / (1 + np.exp(-np.asarray(Z8)[idx]))
    assert np.abs(w - sig / sig.sum()).max() > 0.02 and np.abs(w - full[idx]).max() > 0.02


def test_router_scores_in_float32_whatever_the_stream_holds():
    E = CFG.n_experts
    x = jax.random.normal(jax.random.key(11), (5, CFG.d_model), jnp.bfloat16)
    p = {"router": jax.random.normal(jax.random.key(12), (CFG.d_model, E), jnp.float32)}
    topi, w = core._moe_router(x, p, CFG)
    topi32, w32 = core._moe_router(x.astype(jnp.float32), p, CFG)
    assert w.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(topi), np.asarray(topi32))
    np.testing.assert_array_equal(np.asarray(w), np.asarray(w32))


def test_the_router_reads_the_pre_attention_norm_not_the_experts_input(params):
    """One block equals attention, then the experts on ln2's output ROUTED by
    ln1's: transformer_block hands the pre-attention norm through. Routed by
    the experts' own input it gives another output."""
    lp = jax.tree.map(lambda a: a[1], params["layers"])
    x = jax.random.normal(jax.random.key(5), (1, 9, CFG.d_model))
    pos = jnp.arange(9)[None]
    mask = core.attn_mask(CFG, pos, 9)
    flag = core.layer_rope_flag(CFG, 1)
    got = core.transformer_block(lp, CFG, x, pos, mask, rope_local=flag)
    mute = dict(lp, moe=dict(lp["moe"], w_down=jnp.zeros_like(lp["moe"]["w_down"])))
    h = core.transformer_block(mute, CFG, x, pos, mask, rope_local=flag)  # x + attention
    a, b = core._norm(x, lp["ln1"], CFG), core._norm(h, lp["ln2"], CFG)
    want = h + core._moe_dropless(b, lp["moe"], CFG, router_x=a)[0]
    other = h + core._moe_dropless(b, lp["moe"], CFG, router_x=b)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert float(jnp.abs(got - other).max()) > 1e-3
    with pytest.raises(ValueError, match="rotates its sliding layers only"):
        core.transformer_block(lp, CFG, x, pos, mask)


# --------------------------------------- dead rows, padded tails, sigmoid path


def test_dead_rows_and_padded_tails_touch_no_expert(params):
    """Positions that are not live are assigned past the last expert: they
    count in no group, hit nothing, and a live position's output does not
    depend on what the dead ones hold."""
    p = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
    x = jax.random.normal(jax.random.key(6), (2, 6, CFG.d_model))
    live = jnp.asarray([[1, 1, 1, 1, 0, 0], [0, 0, 0, 0, 0, 0]], bool)
    out, stats = core._moe_dropless(x, p, CFG, live=live, router_x=x)
    assert int(stats[2]) == 4 * CFG.n_experts_per_tok and int(stats[0]) <= 12
    noise = jnp.where(live[..., None], x, 100.0 * x + 7.0)
    out2, stats2 = core._moe_dropless(noise, p, CFG, live=live, router_x=noise)
    np.testing.assert_array_equal(np.asarray(stats), np.asarray(stats2))
    np.testing.assert_array_equal(np.asarray(out[0, :4]), np.asarray(out2[0, :4]))
    assert float(jnp.abs(out[1]).max()) == 0.0 and float(jnp.abs(out[0, 4:]).max()) == 0.0
    _, none = core._moe_dropless(x, p, CFG, live=jnp.zeros((2, 6), bool), router_x=x)
    assert [int(v) for v in none] == [0, 0, 0]


def test_the_sigmoid_path_is_bit_equal_to_the_layer_as_it_was():
    """tiny-joyai through _moe_dropless against the layer written out as PR 39
    had it (sigmoid scores + bias, silu gate, the router on the experts' own
    input): the new router kind, router input and activation left it alone."""
    cfg = get_config("tiny-joyai")
    E, k, D = cfg.n_experts, cfg.n_experts_per_tok, cfg.d_model
    key = iter(jax.random.split(jax.random.key(8), 8))
    p = {"router": jax.random.normal(next(key), (D, E)),
         "router_bias": 0.1 * jax.random.normal(next(key), (E,)),
         "w_gate": jax.random.normal(next(key), (E, D, cfg.expert_ff)) / 7,
         "w_up": jax.random.normal(next(key), (E, D, cfg.expert_ff)) / 7,
         "w_down": jax.random.normal(next(key), (E, cfg.expert_ff, D)) / 6}
    x = jax.random.normal(next(key), (2, 5, D))
    got, _ = core._moe_dropless(x, p, cfg)
    xf = x.reshape(10, D)
    s = jax.nn.sigmoid(jnp.dot(xf, p["router"], precision=jax.lax.Precision.HIGHEST))
    _, topi = jax.lax.top_k(s + p["router_bias"], k)
    w = jnp.take_along_axis(s, topi, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * cfg.moe_scale
    flat = topi.reshape(-1)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    gs = jnp.zeros((E,), jnp.int32).at[flat].add(1)
    xs = jnp.take(xf, order // k, axis=0)
    gate, up = (grouped_matmul(xs, p[n], gs) for n in ("w_gate", "w_up"))
    y = grouped_matmul(jax.nn.silu(gate) * up, p["w_down"], gs).astype(jnp.float32)
    y = y * jnp.take(w.reshape(-1), order)[:, None]
    inv = jnp.zeros((10 * k,), jnp.int32).at[order].set(jnp.arange(10 * k, dtype=jnp.int32))
    want = jnp.sum(jnp.take(y, inv, axis=0).reshape(10, k, D), axis=1).reshape(2, 5, D)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --------------------------------------------- the seeded router is centred


def test_seeded_weights_come_with_a_centred_router_and_no_bias():
    """init_params removes from every layer's W_r its response to the mean
    router input of the balancing batch, adds NO parameter, and the batch's
    tokens then load the experts far more evenly than the plain seeded draw."""
    raw = jax.jit(core._init_params, static_argnums=(0, 2))(
        CFG, jax.random.key(3), jnp.dtype(jnp.float32))
    p = core.init_params(CFG, jax.random.key(3), dtype=jnp.float32)
    assert set(p["layers"]["moe"]) == {"router", "w_gate", "w_up", "w_down"}
    assert p["layers"]["moe"]["router"].shape == raw["layers"]["moe"]["router"].shape
    tokens, _ = core._balance_tokens(  # center_router's own batch
        CFG, core._CENTER_ROWS, min(core._CENTER_WIDTH, CFG.max_seq_len), prompt_only=True)
    R, T = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(T)[None], (R, T))

    def last_layer_load(tree):
        """(the last layer's router response to its mean input, every expert's
        load over the batch's tokens as a multiple of the mean load)."""
        x = core.embed_tokens(tree, CFG, jnp.asarray(tokens), pos)
        last = CFG.n_layers - 1
        for i in range(last):
            x = core.transformer_block(
                jax.tree.map(lambda v: v[i], tree["layers"]), CFG, x, pos,  # noqa: B023
                core.make_layer_mask(CFG, pos, T)(i), rope_local=core.layer_rope_flag(CFG, i))
        a = core._norm(x, jax.tree.map(lambda v: v[last], tree["layers"]["ln1"]), CFG)
        a = a[:, T // 2:].reshape(R * (T - T // 2), -1)  # the deeper half of every row
        w = tree["layers"]["moe"]["router"][last]
        topi, _ = core._moe_router(a, {"router": w}, CFG)
        load = np.bincount(np.asarray(topi).reshape(-1), minlength=CFG.n_experts)
        return np.asarray(jnp.mean(a, 0) @ w), load / load.mean()

    resp_raw, load_raw = last_layer_load(raw)
    resp, load = last_layer_load(p)
    assert np.abs(resp).max() < 1e-3 * np.abs(resp_raw).max()
    assert load.max() - load.min() < 0.5 * (load_raw.max() - load_raw.min())


# ------------------------------------------------- preset, refusals, counts


def _published() -> dict:
    row = next(json.loads(ln) for ln in CATALOG.read_text().splitlines()
               if "SmallThinker-21BA3B" in ln)
    return row["config"]


@pytest.mark.skipif(not CATALOG.is_file(), reason="no catalog beside the guides")
def test_published_preset_equals_the_catalog_config():
    got = config_from_hf(_published(), name="smallthinker-21b-a3b")
    assert got == get_config("smallthinker-21b-a3b")
    assert got.layer_windows[:8] == (0, 4096, 4096, 4096, 0, 4096, 4096, 4096)
    cut = get_config("smallthinker-21b-a3b-8l")
    assert dataclasses.replace(got, name=cut.name, n_layers=8) == cut
    conf = json.loads((ROOT / "benchmark/configs/smallthinker-21b-a3b-8l.json").read_text())
    assert {k: conf[k] for k in _published()} == _published() and conf["layers"] == 8


HF = {
    "model_name": "smallthinker_x", "hidden_size": 48, "num_hidden_layers": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512,
    "moe_ffn_hidden_size": 36, "moe_num_primary_experts": 8,
    "moe_num_active_primary_experts": 3, "moe_primary_router_apply_softmax": True,
    "norm_topk_prob": True, "rope_theta": 10000, "rope_scaling": None,
    "sliding_window_size": 24, "sliding_window_layout": [0, 1, 1, 1] * 2,
    "rope_layout": [0, 1, 1, 1] * 2, "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
    "max_position_embeddings": 256,
}


@pytest.mark.parametrize("key,value,named", [
    ("sliding_window_layout", [0, 1, 1, 0, 1, 0, 0, 1], "sliding_window_layout"),
    ("rope_layout", [1, 0, 0, 0] * 2, "rope_layout"),
    ("sliding_window_layout", [0] * 8, "mixes no full and windowed"),
    ("moe_primary_router_apply_softmax", False, "moe_primary_router_apply_softmax"),
    ("norm_topk_prob", False, "norm_topk_prob"),
    ("rope_scaling", {"type": "yarn", "factor": 4.0}, "rope_scaling"),
])
def test_unimplemented_variants_are_refused_by_name(key, value, named):
    ok = config_from_hf(HF)
    assert (ok.sliding_window_every, ok.sliding_window_residues) == (4, (1, 2, 3))
    assert ok.rope_sliding_only and ok.moe_router == "softmax_topk" and ok.activation == "reglu"
    d = dict(HF, **{key: value})
    if key == "sliding_window_layout":
        d["rope_layout"] = value
    with pytest.raises(ValueError, match=named):
        config_from_hf(d)


@pytest.mark.parametrize("over,named", [
    # (since PR 51 a softmax_topk router may read either norm: granite's reads
    # the FFN norm's; what is refused of a share is a range outside the experts)
    ({"n_experts_held": 6, "expert_first": 4}, "n_experts_held"),
    ({"sliding_window_every": 1, "sliding_window_residues": (0,)}, "rope_sliding_only"),
    ({"moe_router": "softmax"}, "moe_router_input"),
])
def test_the_config_refuses_what_the_layer_does_not_build(over, named):
    with pytest.raises(ValueError, match=named):
        dataclasses.replace(CFG, **over)


def _count(tree) -> int:
    return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))


def test_parameter_counts_as_published():
    """21.5 B whole (published 21B), 3,966.9 M as run, 11,796,480 B an expert."""
    def shapes(name):
        cfg = get_config(name)
        return jax.eval_shape(lambda: jax.jit(core._init_params, static_argnums=(0, 2))(
            cfg, jax.random.key(0), jnp.dtype(jnp.bfloat16)))

    whole, cut = shapes("smallthinker-21b-a3b"), shapes("smallthinker-21b-a3b-8l")
    assert 21.4e9 < _count(whole) < 21.6e9
    layer = _count(cut["layers"]) // 8
    assert layer == 20_971_520 + 2560 * 64 + 64 * 5_898_240 + 2 * 2560
    assert _count(cut) == 8 * layer + 2 * 151_936 * 2560 + 2560
    assert round(_count(cut) / 1e6, 1) == 3966.9
    moe = json.loads((ROOT / "benchmark/configs/smallthinker-21b-a3b-8l.json").read_text())["moe"]
    assert moe["expert_bytes"] == 3 * 2560 * 768 * 2 == 11_796_480
    assert core.matmul_params_per_token(get_config("smallthinker-21b-a3b-8l")) == 8 * (
        20_971_520 + 2560 * 64 + 6 * 5_898_240) + 151_936 * 2560


REFUSED = {
    "kv_int8": {"cache_dtype": "int8"},
    "weight_int8": {"quantize": "int8"},
    "spec_ngram": {"spec_tokens": 4},
    "spec_model_drafter": {"spec_tokens": 4, "drafter": "tiny-llama"},
    "multi_lora": {"max_adapters": 2},
}


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_features_not_proven_for_dropless_layers_of_two_kinds_are_refused(feature):
    with pytest.raises(FeatureUnsupported) as err:
        InferenceEngine("tiny-smallthinker",
                        engine_config=EngineConfig(**{**ENGINE_KW, **REFUSED[feature]}))
    assert err.value.feature == feature and "tiny-smallthinker" in str(err.value)


# ----------------------------------------------------------------- the engine


def _prompt(seed: int, n: int) -> list[int]:
    return [int(t) for t in np.random.default_rng(seed).integers(3, 259, size=n)]


@pytest.fixture(scope="module")
def solo():
    """Greedy rollouts of three prompts, each alone on the dense path."""
    eng = InferenceEngine("tiny-smallthinker", engine_config=EngineConfig(**ENGINE_KW))
    out = {s: eng.generate(_prompt(s, n), max_new_tokens=m, temperature=0.0).token_ids
           for s, (n, m) in {0: (45, 12), 1: (30, 20), 2: (58, 8)}.items()}
    params = eng.params
    eng.close()
    return out, params


def test_engine_decode_matches_the_reference_past_the_window(solo):
    """The engine's greedy tokens are the reference's argmax, teacher-forced on
    them, at contexts of 45-57 tokens: two windows and more."""
    out, params = solo
    row = np.asarray(_prompt(0, 45) + out[0], np.int32)
    params = core.restack_layers(params) if isinstance(params["layers"], list) else params
    logits = _plain(params, row, range(44, 44 + len(out[0])))
    for step, tok in enumerate(out[0]):
        assert float(logits[step].max() - logits[step][tok]) < 1e-4


@pytest.mark.parametrize("over", [{"attention": "flash"}, {"prefill_chunk": 16},
                                  {"attention": "flash", "prefill_chunk": 16},
                                  {"prefix_cache_entries": 4, "prefill_chunk": 16}],
                         ids=["flash", "chunked", "flash-chunked", "prefix-chunked"])
def test_rows_served_together_equal_their_solo_runs(solo, over):
    out, _ = solo
    eng = InferenceEngine("tiny-smallthinker", engine_config=EngineConfig(**{**ENGINE_KW, **over}))
    chunks = get_registry().counter("engine.prefill_chunks")
    before = chunks.value()
    got = {s: eng.generate(_prompt(s, n), max_new_tokens=m, temperature=0.0).token_ids
           for s, (n, m) in {0: (45, 12), 1: (30, 20), 2: (58, 8)}.items()}
    if "prefix_cache_entries" in over:  # asked again: the shared blocks serve it
        again = eng.generate(_prompt(0, 45), max_new_tokens=12, temperature=0.0).token_ids
        assert again == out[0] and eng.scheduler.stats.prefix_hits == 1
    eng.close()
    assert got == out
    if "prefill_chunk" in over:  # 45, 30 and 58 tokens walk 3 + 2 + 4 chunks of 16
        assert chunks.value() - before >= 9
    else:
        assert chunks.value() == before


def test_tiles_are_counted_a_layer_kind_and_the_window_gauges_follow_the_rows():
    """RowCache.count_tiles counts the MEAN layer's read: one full layer and
    three behind the window of 24, each by ops/ragged.read_counts with its own
    window (the counter took window 0 for every alternating model before).
    note_tokens_held sets the two gauges from the live rows' contexts."""
    eng = InferenceEngine("tiny-smallthinker", engine_config=EngineConfig(
        **{**ENGINE_KW, "attention": "flash"}))
    cache = eng.scheduler.cache
    tables = np.zeros((2, 64), np.int32)  # tiles of 16 pages = 128 tokens
    tables[0, :52], tables[1, :5] = np.arange(1, 53), np.arange(53, 58)
    offsets = np.asarray([410, 33], np.int32)
    kw = dict(heads=CFG.n_kv_heads, group=2, chunk=1, head_dim=16, block_size=8, itemsize=4)
    full = read_counts(tables, offsets, 0, **kw)[:2]
    bound = read_counts(tables, offsets, 24, **kw)[:2]
    assert bound[0] < full[0] and bound[1] == full[1]
    tiles = get_registry().counter("engine.kv_tiles")
    live0, step0 = tiles.value(kind="live"), tiles.value(kind="stepped")
    cache.count_tiles(tables, offsets, 1, calls=4)
    assert tiles.value(kind="live") - live0 == 4 * (full[0] + 3 * bound[0]) // 4  # whole tiles
    assert tiles.value(kind="stepped") - step0 == 4 * full[1]
    cache.note_tokens_held([410, 33, 10])
    held = get_registry().gauge("engine.kv_tokens_held").value()
    behind = get_registry().gauge("engine.kv_tokens_behind_window").value()
    assert held == (410 + 33 + 10) * 4
    assert behind == 3 * ((410 - 24 + 1) + (33 - 24 + 1))
    eng.close()


def test_a_model_whose_window_never_binds_holds_nothing_behind_it():
    eng = InferenceEngine("tiny-llama", engine_config=EngineConfig(**ENGINE_KW))
    assert not eng.scheduler.cache.windowed  # so the scheduler never calls it for this model
    eng.scheduler.cache.note_tokens_held([50, 60])
    assert get_registry().gauge("engine.kv_tokens_behind_window").value() == 0
    assert get_registry().gauge("engine.kv_tokens_held").value() == 110 * eng.model_cfg.n_layers
    eng.close()


# ------------------------------------------ the cell's check: lost bytes, the window


@pytest.mark.parametrize("text,want", [
    ("abcdefgh", list(b"abcdefgh")),
    # every U+FFFD one byte (the positions add up to 8): the whole text is walked
    ("a�b��cde", [97, plain.LOST, 98, plain.LOST, plain.LOST, 99, 100, 101]),
    ("é�abcde", [0xC3, 0xA9, plain.LOST, 97, 98, 99, 100, 101]),
    # a U+FFFD that stands for a truncated sequence (7 positions of 8): cut at the first loss
    ("ab�cdef", [97, 98]),
    ("�abcdef", []),
    ("abc", [97, 98, 99]),  # an early end without a loss stays whole
])
def test_served_bytes_walks_through_a_loss_only_where_every_loss_is_one_byte(text, want):
    assert plain.served_bytes(text, 8) == want


def test_the_lost_class_is_every_token_of_a_byte_that_cannot_stand_alone():
    cls = plain.token_class(plain.LOST, 1000)
    assert len(cls) == sum((t - 3) % 256 >= 0x80 for t in range(3, 1000))
    assert cls.min() == 3 + 0x80 and not set(cls) & set(plain.token_class(97, 1000))


def test_the_walk_goes_on_through_a_lost_byte_and_its_runner_up_opens_a_context():
    """Probe 0's text: 'a', a lost byte, 'b'. At the lost step the reference's
    best lost-class token is 3 + 0x80 and its runner-up 3 + 0x81 lies within
    tol / 4: the SERVED token was the runner-up, and only the context that
    follows it finds 'b' at the top next. The context behind the best token
    ends there as a guess: not wrong, not compared. Probe 1 ('c' behind a
    lost byte whose served token was no candidate at all) ends the same way
    and leaves nothing compared."""
    V, P, tol = 600, 4, 0.5
    tokens = np.zeros((6, P + 3), np.int32)
    owner = np.asarray([0, 1, -1, -1, -1, -1], np.int64)
    a, b, best, second = 3 + 97, 3 + 98, 3 + 0x80, 3 + 0x81

    def logits_at(step, rows):
        out = np.zeros((6, V), np.float32)
        for r in rows:
            if owner[r] == 1:  # lost, then 'c' far down whatever was guessed
                out[r, best if step == 0 else a] = 2.0
            elif step == 0:
                out[r, a] = 2.0
            elif step == 1:
                out[r, best], out[r, second], out[r, 3 + 0x90] = 2.0, 1.9, 1.5
            else:  # 'b' leads only behind the runner-up; behind the best token it is far down
                out[r, b] = 2.0 if tokens[r, P + 1] == second else -3.0
                out[r, 3 + 99] = 1.0
        return out

    served = [[97, plain.LOST, 98], [plain.LOST, 99]]
    res = plain.walk(logits_at, tokens, owner, served, P, 3, V, tol)
    assert res["margins"] == {(0, 0): 0.0, (0, 2): 0.0} and res["checked"] == 2
    assert res["decode_checked"] == 1 and res["lost_walked"] == 2 and res["forks"] == 1
    assert res["guesses_ended"] == 2 and res["worst_margin"] == 0.0
    assert sorted(tokens[owner == 0, P + 1]) == [best, second]


def test_a_known_byte_far_down_behind_a_known_one_ends_the_probe_wrong():
    V, P = 600, 4
    tokens, owner = np.zeros((2, P + 2), np.int32), np.asarray([0, -1], np.int64)

    def logits_at(step, rows):
        out = np.zeros((2, V), np.float32)
        out[rows, 3 + 97 if step == 0 else 3 + 120] = 2.0
        return out

    res = plain.walk(logits_at, tokens, owner, [[97, 98]], P, 2, V, 0.5)
    assert res["margins"] == {(0, 0): 0.0, (0, 1): 2.0} and res["worst_margin"] == 2.0
    assert res["guesses_ended"] == 0 and not res["ok"]


def test_the_window_read_check_passes_the_served_read_and_fails_an_ignored_window():
    """benchmark/reference_smallthinker.window_read at the tiny fixture: the
    program's ragged read under the preset's per-layer window equals the dense
    mask; a reference that ignores the window reads a whole value row off."""
    conf = json.loads((ROOT / "benchmark/tests/fixtures/tiny-smallthinker.json").read_text())
    good = plain.window_read(conf, 60, 8)
    assert good["window_read_ok"] and good["window_read_err"] < 1e-4, good
    # decode rows at the probes' depth, one token past and one inside the window; a chunk
    assert good["window_read_rows"] == [[67, 1], [59, 1], [24, 1], [22, 1], [32, 32]]
    bad = plain.window_read(conf, 60, 8, {"no_window": True})
    assert not bad["window_read_ok"] and bad["window_read_err"] > 0.5, bad
