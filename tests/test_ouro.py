"""Ouro (a looped stack: the same layers ``loop_steps`` times a token, the final
norm after every pass, a layer of cache a (pass, layer)): the model against the
benchmark's plain reference on logits, the served path (prefill in chunks, then
decode through the paged pool, both readers), each one-thing-wrong reference
caught, WHICH cache layer each pass writes, a plain stack's program untouched,
the published preset, the refusals, the engine, its counters and its block
export / import. All at ``tiny-ouro`` size (3 layers x 3 passes) on the CPU."""

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
from bee2bee_tpu.engine.introspect import FlopsModel
from bee2bee_tpu.metrics import get_registry
from bee2bee_tpu.models import core, stages
from bee2bee_tpu.models.config import config_from_hf, get_config
from bee2bee_tpu.ops.ragged import make_ragged_attn_fn
from bee2bee_tpu.parallel import MeshSpec, build_mesh

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "benchmark"))
import reference_ouro as plain  # noqa: E402  (the benchmark's plain reference)

CFG = get_config("tiny-ouro")
L, PASSES = CFG.n_layers, CFG.loop_steps
DIMS = plain.dims_of_preset(CFG)
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
ENGINE_KW = dict(max_seq_len=128, dtype="float32", cache_dtype="float32",
                 decode_chunk=4, max_batch=4, prefill_buckets=(16, 32, 64),
                 kv_block_size=8)
PERTURBED = [{"passes": 2}, {"no_norm_between_passes": True},
             {"pass_reads_previous_cache": True}, {"no_post_norms": True},
             {"activation_dtype": "float8_e4m3fn"}]
# float32 against float32: the two differ by the order of their sums alone
# (logits of std ~1 read 2-6e-6 apart); 3e-5 is five times that and a
# thousandth of the smallest one-thing-wrong reading below
ATOL = 3e-5


@pytest.fixture(scope="module")
def params():
    p = core.init_params(CFG, jax.random.key(3), dtype=jnp.float32)
    # nothing may hide behind an init value: every norm's scale random
    k = iter(jax.random.split(jax.random.key(4), 8))
    layers = dict(p["layers"])
    for name in ("ln1", "ln2", "ln1_post", "ln2_post"):
        layers[name] = {"scale": 0.5 + jax.random.uniform(next(k), layers[name]["scale"].shape)}
    final = {"scale": 0.5 + jax.random.uniform(next(k), p["final_norm"]["scale"].shape)}
    return dict(p, layers=layers, final_norm=final)


def _ids(rows: int, n: int, seed: int = 0):
    return np.random.RandomState(seed).randint(3, 500, (rows, n)).astype(np.int32)


# ------------------------------------------------------ model vs reference


def test_forward_matches_the_plain_reference(params):
    """Three passes of three layers with the norm between them and no second
    norm before the head equal the reference's Python loops, on logits."""
    ids = _ids(2, 40)
    got, _ = core.forward(params, CFG, ids, None, 0)
    want = plain.full_logits(DIMS, params, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL, rtol=1e-4)


@pytest.mark.parametrize("perturb", PERTURBED,
                         ids=lambda p: "-".join(f"{k}={v}" for k, v in p.items()))
def test_perturbed_reference_differs(params, perturb):
    """Each ONE-thing-wrong reference moves the logits by far more than the
    agreement above: the comparison can tell them apart."""
    ids = _ids(1, 40, seed=1)
    right = np.asarray(plain.full_logits(DIMS, params, ids))[0, -1]
    wrong = np.asarray(plain.full_logits(DIMS, params, ids, perturb))[0, -1]
    assert np.abs(wrong - right).max() > 1000 * ATOL


def test_the_head_reads_the_last_passes_norm_and_norms_no_second_time(params):
    x = jax.random.normal(jax.random.key(5), (1, 4, CFG.d_model))
    normed = core._norm(x, params["final_norm"], CFG)
    np.testing.assert_array_equal(np.asarray(core.final_logits(params, CFG, x)),
                                  np.asarray(core.head_logits(params, CFG, normed)))
    assert np.abs(np.asarray(core.head_logits(params, CFG, normed)
                             - core.final_logits(params, CFG, normed))).max() > 1e-2


@pytest.mark.parametrize("reader", ["dense", "ragged"])
def test_prefill_in_chunks_then_decode_through_the_paged_pool(params, reader):
    """The served path on LOGITS: every row prefilled alone in chunks of 16 into
    the paged pool (padded tail under the write ceil), then three decode steps
    of one batch whose rows have unequal lengths and whose third row is dead
    (null table), against the reference's full forward."""
    attn = make_ragged_attn_fn() if reader == "ragged" else None
    BS, lens = 8, [37, 61, 0, 29]
    pool = core.init_paged_pool(CFG, 40, BS, jnp.float32)
    assert set(pool) == {"kv"} and pool["kv"].shape == (
        L * PASSES, 40, 2, CFG.n_kv_heads, BS, CFG.head_dim)
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
    want = {b: np.asarray(plain.full_logits(DIMS, params, toks[b:b + 1, :n + 3]))[0, n:n + 3]
            for b, n in enumerate(lens) if n}
    for step in range(3):
        cur = np.stack([toks[b, lens[b] + step] for b in range(4)])[:, None]
        logits, pool = core.forward(params, CFG, cur, pool, offs + step,
                                    attn_fn=attn, block_tables=tables)
        for b in want:
            np.testing.assert_allclose(
                np.asarray(logits[b, 0]), want[b][step], atol=ATOL, rtol=1e-4)


def test_the_rectangular_cache_is_cache_layers_deep_and_decodes_like_the_full_forward(params):
    ids = _ids(2, 14, seed=3)
    cache = core.init_cache(CFG, 2, 32, jnp.float32)
    assert cache["k"].shape == (L * PASSES, 2, 32, CFG.n_kv_heads, CFG.head_dim)
    full, _ = core.forward(params, CFG, ids, None, 0)
    outs = []
    first, cache = core.forward(params, CFG, ids[:, :9], cache, 0)
    outs.append(first)
    for t in range(9, 14):
        step, cache = core.forward(params, CFG, ids[:, t:t + 1], cache, t)
        outs.append(step)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, 1)), np.asarray(full),
                               atol=ATOL, rtol=1e-4)


@pytest.mark.parametrize("reader", ["dense", "ragged"])
def test_pass_t_writes_cache_layer_t_times_n_layers_plus_l_and_no_other(params, reader):
    """The pool after one prefill holds, at cache layer ``t * n_layers + l``, the K
    and V the reference's pass ``t`` computed in layer ``l`` (so no two indices are
    swapped: 3 x 3 is not symmetric in which is which), and nothing else moved: the
    unmapped blocks keep their sentinel in every cache layer."""
    attn = make_ragged_attn_fn() if reader == "ragged" else None
    BS, n = 8, 13
    pool = {"kv": jnp.full((L * PASSES, 6, 2, CFG.n_kv_heads, BS, CFG.head_dim), 7.0)}
    table = np.asarray([[2, 4, 0, 0]], np.int32)
    ids = _ids(1, n, seed=4)
    _, pool = core.forward(params, CFG, ids, pool, np.int32(0), attn_fn=attn,
                           block_tables=table, paged_write_floor=np.int32(0),
                           paged_write_ceil=np.int32(n))
    kv = np.asarray(pool["kv"])
    assert (kv[:, [1, 3, 5]] == 7.0).all()  # blocks no table maps: untouched in all 9
    # the reference, pass by pass, layer by layer
    embed, layer, norm, _ = plain.build_forward(DIMS)
    x = embed(params["tok_embed"], ids)
    for t in range(PASSES):
        for lay in range(L):
            x, (k, v) = layer(x, params["layers"], np.int32(lay), None)
            stored = kv[t * L + lay][[2, 4]]  # [2 blocks, K|V, Hkv, BS, hd]
            got = stored.transpose(1, 0, 3, 2, 4).reshape(2, 2 * BS, CFG.n_kv_heads, -1)[:, :n]
            np.testing.assert_allclose(got[0], np.asarray(k)[0], atol=1e-5, rtol=1e-4)
            np.testing.assert_allclose(got[1], np.asarray(v)[0], atol=1e-5, rtol=1e-4)
        x = norm(x, params["final_norm"]["scale"])
    # the passes' K differ from one another, so a wrong pass index cannot pass
    assert np.abs(kv[0, 2] - kv[L, 2]).max() > 1e-2 and np.abs(kv[L, 2] - kv[2 * L, 2]).max() > 1e-2


# ----------------------------------------------- a plain stack stays as it was


def _scans(jaxpr) -> list:
    """Nesting of ``scan`` in a jaxpr: a list a scan, holding its inner scans."""
    out = []
    for eqn in jaxpr.eqns:
        inner = []
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    inner += _scans(sub)
        if eqn.primitive.name == "scan":
            out.append(inner)
        else:
            out += inner
    return out


def test_a_plain_stack_has_one_layer_scan_and_no_pass_loop():
    llama = get_config("tiny-llama")
    shapes = jax.eval_shape(lambda: core.init_params(llama, jax.random.key(0)))
    ids = jnp.zeros((2, 8), jnp.int32)
    plain_stack = jax.make_jaxpr(lambda p: core.forward(p, llama, ids, None, 0)[0])(shapes)
    assert _scans(plain_stack.jaxpr) == [[]]  # ONE scan a layer group, nothing around or in it
    shapes = jax.eval_shape(lambda: core.init_params(CFG, jax.random.key(0)))
    looped = jax.make_jaxpr(lambda p: core.forward(p, CFG, ids, None, 0)[0])(shapes)
    assert _scans(looped.jaxpr) == [[[]]]  # the layer scan INSIDE the scan over passes: not unrolled


def test_a_plain_stack_carries_every_scope_of_a_looped_one_but_the_pass_norm():
    """The block's parts are opened for every model (tracing.DEVICE_PARTS);
    what a looped stack alone has is the norm between its passes."""
    def text(cfg):
        shapes = jax.eval_shape(lambda: core.init_params(cfg, jax.random.key(0)))
        return jax.jit(lambda p: core.forward(p, cfg, jnp.zeros((1, 8), jnp.int32), None, 0)[0]
                       ).lower(shapes).as_text(debug_info=True)

    looped, plain = text(CFG), text(get_config("tiny-llama"))
    for scope in ("attn.qkv", "attn.read", "attn.out", "mlp.gate_up", "mlp.down", "head.logits"):
        assert scope in looped and scope in plain, scope
    assert "loop.norm" in looped and "loop.norm" not in plain


def test_the_training_path_differentiates_through_both_loops(params):
    ids = _ids(2, 12, seed=6)

    def loss(p, remat):
        logits, _ = core.forward(p, CFG, ids, None, 0, remat=remat)
        return jnp.mean(jax.nn.logsumexp(logits, -1))

    g0 = jax.grad(loss)(params, False)
    g1 = jax.grad(loss)(params, True)
    assert float(jnp.abs(g0["layers"]["attn"]["wq"]).max()) > 0
    np.testing.assert_allclose(np.asarray(g0["layers"]["mlp"]["w_up"]),
                               np.asarray(g1["layers"]["mlp"]["w_up"]), atol=1e-6, rtol=1e-4)


# ------------------------------------------------- preset, refusals, counts


def _published() -> dict:
    row = next(json.loads(ln) for ln in CATALOG.read_text().splitlines() if '"Ouro-2.6B"' in ln)
    return row["config"]


@pytest.mark.skipif(not CATALOG.is_file(), reason="no catalog beside the guides")
def test_published_preset_equals_the_catalog_config():
    got = config_from_hf(_published(), name="ouro-2.6b")
    assert got == get_config("ouro-2.6b")
    assert (got.n_layers, got.loop_steps, got.cache_layers) == (48, 4, 192)
    assert got.layer_windows == (0,) * 192
    conf = json.loads((ROOT / "benchmark/configs/ouro-2.6b.json").read_text())
    pub = _published()
    assert {k: conf[k] for k in pub if k != "max_position_embeddings"} == {
        k: v for k, v in pub.items() if k != "max_position_embeddings"}
    assert conf["reduced"] == ["max_position_embeddings"]


def _count(tree) -> int:
    return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))


def test_parameter_count_and_a_tokens_work_as_published():
    cfg = get_config("ouro-2.6b")
    shapes = jax.eval_shape(lambda: jax.jit(core._init_params, static_argnums=(0, 2))(
        cfg, jax.random.key(0), jnp.dtype(jnp.bfloat16)))
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert _count(shapes["layers"]) == 48 * layer
    assert _count(shapes) == 48 * layer + 2 * 49_152 * 2048 + 2048 == 2_667_972_608
    # a token's matmul work is FOUR times the layers', the head once
    assert core.matmul_params_per_token(cfg) == 4 * 48 * (layer - 4 * 2048) + 49_152 * 2048
    assert core.pool_bytes_per_token(cfg) == 192 * 2 * 16 * 128 * 2 == 1_572_864
    assert FlopsModel(cfg).attn_flops_per_pos_per_ctx == 4.0 * 192 * 16 * 128
    conf = json.loads((ROOT / "benchmark/configs/ouro-2.6b.json").read_text())
    assert conf["loop"]["layer_bytes"] == 2 * layer and conf["kv"]["n_layers"] == 192
    once = dataclasses.replace(cfg, loop_steps=1)
    assert once.cache_layers == 48 and core.pool_bytes_per_token(once) == 393_216


HF = {
    "model_type": "ouro", "hidden_size": 64, "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 96, "hidden_act": "silu",
    "vocab_size": 512, "rms_norm_eps": 1e-6, "rope_theta": 10000.0, "rope_scaling": None,
    "total_ut_steps": 3, "early_exit_threshold": 1, "use_sliding_window": False,
    "sliding_window": None, "layer_types": ["full_attention"] * 3,
    "tie_word_embeddings": False, "max_position_embeddings": 256,
}


@pytest.mark.parametrize("key,value,named", [
    ("early_exit_threshold", 0.9, "early_exit_threshold"),
    ("use_sliding_window", True, "use_sliding_window"),
    ("rope_scaling", {"type": "yarn", "factor": 4.0}, "rope_scaling"),
    ("layer_types", ["full_attention", "sliding_attention", "full_attention"], "layer_types"),
    ("hidden_act", "gelu", "hidden_act"),
])
def test_unimplemented_variants_are_refused_by_name(key, value, named):
    assert config_from_hf(HF, name="tiny-ouro") == CFG
    with pytest.raises(ValueError, match=named):
        config_from_hf(dict(HF, **{key: value}))


@pytest.mark.parametrize("over", [
    {"loop_steps": 0}, {"sliding_window": 16}, {"n_experts": 4},
    {"ssm_heads": 2, "ssm_head_dim": 8, "ssm_state": 4},
], ids=lambda o: "-".join(o))
def test_the_config_refuses_a_loop_the_forward_does_not_build(over):
    with pytest.raises(ValueError, match="loop_steps"):
        dataclasses.replace(CFG, **over)


REFUSED = {
    "kv_int8": {"cache_dtype": "int8"},
    "weight_int8": {"quantize": "int8"},
    "spec_ngram": {"spec_tokens": 4},
    "spec_model_drafter": {"spec_tokens": 4, "drafter": "tiny-llama"},
    "spec_mesh_drafter": {"spec_tokens": 4, "drafter": "mesh"},
    "multi_lora": {"max_adapters": 2},
    "seq_attention": {"attention": "sp"},
}


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_features_not_proven_for_a_looped_stack_are_refused(feature):
    with pytest.raises(FeatureUnsupported) as err:
        InferenceEngine("tiny-ouro",
                        engine_config=EngineConfig(**{**ENGINE_KW, **REFUSED[feature]}))
    assert err.value.feature == feature and "tiny-ouro" in str(err.value)
    assert "several times a token" in err.value.ground


@pytest.mark.parametrize("axis,feature", [("model", "mesh_model"), ("seq", "seq_attention"),
                                          ("expert", "mesh_expert")])
def test_meshes_not_proven_for_a_looped_stack_are_refused(axis, feature):
    with pytest.raises(FeatureUnsupported) as err:
        InferenceEngine("tiny-ouro", mesh=build_mesh(MeshSpec(**{axis: 2})),
                        engine_config=EngineConfig(**ENGINE_KW))
    assert err.value.feature == feature


def test_pipeline_stages_and_a_looped_drafter_are_refused():
    from bee2bee_tpu.engine.drafter import DraftModel
    from bee2bee_tpu.engine.stage_runner import StageRunner

    with pytest.raises(FeatureUnsupported) as err:
        StageRunner("tiny-ouro", n_stages=3, stage=0)
    assert err.value.feature == "pipeline_stages"
    with pytest.raises(FeatureUnsupported) as err:
        DraftModel("tiny-ouro", spec_tokens=4, batch=2, target_max_seq_len=128)
    assert err.value.feature == "spec_model_drafter"


@pytest.mark.parametrize("path", ["stages", "ring", "pipeline"])
def test_the_paths_that_walk_the_layers_themselves_refuse_a_looped_stack_by_name(path):
    from bee2bee_tpu.parallel import pipeline, ring

    with pytest.raises(ValueError, match="loop_steps"):
        if path == "stages":
            stages.StageSpec.build(CFG, 3, 0)
        elif path == "ring":
            ring.make_sp_forward(CFG, build_mesh(MeshSpec(seq=2)))
        else:
            pipeline.pipeline_apply({}, CFG, None, jnp.zeros((1, 1, 4, CFG.d_model)))


# ----------------------------------------------------------------- the engine


def _prompt(seed: int, n: int) -> list[int]:
    return [int(t) for t in np.random.default_rng(seed).integers(3, 259, size=n)]


ASKS = {0: (45, 12), 1: (30, 20), 2: (58, 8)}


def _serve(eng) -> dict:
    return {s: eng.generate(_prompt(s, n), max_new_tokens=m, temperature=0.0).token_ids
            for s, (n, m) in ASKS.items()}


@pytest.fixture(scope="module")
def solo():
    """Greedy rollouts of three prompts, each alone on the dense path."""
    eng = InferenceEngine("tiny-ouro", engine_config=EngineConfig(**ENGINE_KW))
    info = eng.info
    out, weights = _serve(eng), eng.params
    eng.close()
    return out, weights, info


def test_engine_info_reports_the_caches_layers_and_bytes(solo):
    kv = solo[2]["kv"]
    assert kv["cache_layers"] == L * PASSES == 9
    assert kv["bytes_per_token"] == 9 * 2 * CFG.n_kv_heads * CFG.head_dim * 4  # float32 pool
    assert kv["layout"] == {"k": [4, 16], "v": [4, 16]}


def test_engine_decode_matches_the_reference(solo):
    """The engine's greedy tokens are the reference's argmax, teacher-forced on them."""
    out, weights, _ = solo
    weights = core.restack_layers(weights) if isinstance(weights["layers"], list) else weights
    row = np.asarray(_prompt(0, 45) + out[0], np.int32)[None]
    logits = np.asarray(plain.full_logits(DIMS, weights, row))[0]
    for step, tok in enumerate(out[0]):
        assert float(logits[44 + step].max() - logits[44 + step][tok]) < 1e-4


@pytest.mark.parametrize("over", [{"attention": "flash"}, {"prefill_chunk": 16},
                                  {"attention": "flash", "prefill_chunk": 16},
                                  {"prefix_cache_entries": 4, "prefill_chunk": 16}],
                         ids=["flash", "chunked", "flash-chunked", "prefix-chunked"])
def test_every_served_path_equals_the_solo_dense_runs(solo, over):
    eng = InferenceEngine("tiny-ouro", engine_config=EngineConfig(**{**ENGINE_KW, **over}))
    got = _serve(eng)
    if "prefix_cache_entries" in over:  # asked again: the shared blocks serve all 9 cache layers
        again = eng.generate(_prompt(0, 45), max_new_tokens=12, temperature=0.0).token_ids
        assert again == solo[0][0] and eng.scheduler.stats.prefix_hits == 1
    eng.close()
    assert got == solo[0]


@pytest.mark.parametrize("attention", ["dense", "flash"])
def test_rows_served_together_equal_their_solo_runs(solo, attention):
    """Three streams at once in one batch (unequal lengths, rows ending at unlike
    steps) give each the tokens it got alone."""
    import threading

    eng = InferenceEngine("tiny-ouro",
                          engine_config=EngineConfig(**{**ENGINE_KW, "attention": attention}))
    got: dict = {}

    def ask(s, n, m):
        got[s] = eng.generate(_prompt(s, n), max_new_tokens=m, temperature=0.0).token_ids

    threads = [threading.Thread(target=ask, args=(s, n, m)) for s, (n, m) in ASKS.items()]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    eng.close()
    assert got == solo[0]


def test_loop_passes_and_pages_written_count_loop_steps_times_a_one_pass_twins():
    """The same asks through the looped model and through its one-pass twin (the
    same layers run once): ``engine.loop_passes`` of both kinds and
    ``engine.kv_pages_written`` read exactly ``loop_steps`` times the twin's."""
    reg = get_registry()
    passes, pages = reg.counter("engine.loop_passes"), reg.counter("engine.kv_pages_written")
    calls = reg.counter("engine.prefill_calls")

    def run(model):
        eng = InferenceEngine(model, engine_config=EngineConfig(
            **{**ENGINE_KW, "attention": "flash"}))
        before = (passes.value(kind="prefill"), passes.value(kind="decode"), pages.value(),
                  calls.total())
        res = [eng.generate(_prompt(s, n), max_new_tokens=m, temperature=0.0)
               for s, (n, m) in ASKS.items()]
        eng.close()
        assert [r.finish_reason for r in res] == ["length"] * 3
        after = (passes.value(kind="prefill"), passes.value(kind="decode"), pages.value(),
                 calls.total())
        return [a - b for a, b in zip(after, before)]

    looped = run("tiny-ouro")
    twin = run(dataclasses.replace(CFG, name="tiny-ouro-once", loop_steps=1))
    assert twin[3] == looped[3] == 3 and twin[0] == 3 and twin[1] > 0 and twin[2] > 0
    assert looped[:3] == [PASSES * v for v in twin[:3]]


def _drain(req, base_out=()):
    out = list(base_out)
    while True:
        ev = req.events.get(timeout=60)
        if ev.get("imported"):
            continue
        if ev.get("done"):
            assert ev.get("result") is not None, ev.get("error")
            return out
        out.extend(ev.get("tokens") or [])


def test_a_looped_row_migrates_with_all_its_cache_layers():
    """Checkpoint mid-decode on A, scatter the exported blocks (every one of the 9
    cache layers deep) into B, resume with ZERO prefill: token for token the
    unmigrated rollout; a one-pass peer's signature differs and it refuses them."""
    a = InferenceEngine("tiny-ouro", engine_config=EngineConfig(**ENGINE_KW))
    b = InferenceEngine("tiny-ouro", engine_config=EngineConfig(**ENGINE_KW))
    once = InferenceEngine(dataclasses.replace(CFG, loop_steps=1),
                           engine_config=EngineConfig(**ENGINE_KW))
    try:
        prompt = _prompt(7, 40)
        base = a.generate(prompt, max_new_tokens=24, temperature=0.0).token_ids
        seen = []
        gen = a.generate_stream(prompt, max_new_tokens=24, temperature=0.0)
        for ev in gen:  # (kept referenced: closing the generator cancels the request)
            assert not ev.get("done")
            seen.extend(ev.get("tokens") or [])
            if len(seen) >= 5:
                break
        (req,) = a.scheduler.live_requests()
        snap = a.scheduler.checkpoint(req)
        kv = snap.pop("_kv")
        assert kv["k"].shape[0] == kv["v"].shape[0] == L * PASSES
        assert a.migration_signature()["cache_layers"] == 9
        assert a.migration_signature() == b.migration_signature() != once.migration_signature()
        assert _drain(b.import_generation(snap, kv), snap["out"]) == base
        assert b.scheduler.stats.migrated_in == 1 and b.scheduler.stats.import_reprefills == 0
        with pytest.raises(ValueError, match="shape"):
            once.import_generation(dict(snap, model=once.model_cfg.name), kv)
    finally:
        a.close()
        b.close()
        once.close()
