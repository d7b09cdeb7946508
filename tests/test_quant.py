"""Weight-only int8 quantization (models/quant.py + core.matmul)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bee2bee_tpu.engine import EngineConfig, InferenceEngine
from bee2bee_tpu.models import core, get_config
from bee2bee_tpu.models.quant import (
    dequantize_weight,
    is_quantized,
    quantize_params,
    quantize_weight,
)
from bee2bee_tpu.parallel import MeshSpec, build_mesh

KW = dict(max_seq_len=64, dtype="float32", cache_dtype="float32")


def test_quantize_weight_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    w = rng.standard_normal((2, 32, 16)).astype(np.float32) * 0.05
    qw = quantize_weight(w)
    assert qw["q"].dtype == np.int8 and qw["s"].shape == (2, 16)
    back = dequantize_weight(qw)
    # symmetric int8: error <= scale/2 per element
    assert np.max(np.abs(back - w) / np.maximum(qw["s"][:, None, :], 1e-12)) <= 0.5


def test_quantize_weight_zero_column_safe():
    w = np.zeros((4, 3), np.float32)
    qw = quantize_weight(w)
    assert np.all(qw["q"] == 0)
    np.testing.assert_array_equal(dequantize_weight(qw), 0.0)


def test_quantize_params_targets_only_matmuls():
    cfg = get_config("tiny-llama")
    params = quantize_params(
        jax.device_get(core.init_params(cfg, jax.random.key(0), dtype=jnp.float32))
    )
    assert is_quantized(params["layers"]["attn"]["wq"])
    assert is_quantized(params["layers"]["mlp"]["w_down"])
    assert not is_quantized(params["tok_embed"])  # embeddings stay dense
    assert not isinstance(params["layers"]["ln1"]["scale"], dict)


def test_core_matmul_quantized_close_to_dense():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((3, 32)), jnp.float32)
    w = rng.standard_normal((32, 16)).astype(np.float32) * 0.1
    want = np.asarray(x) @ w
    qw = quantize_weight(w)
    got = core.matmul(x, {"q": jnp.asarray(qw["q"]), "s": jnp.asarray(qw["s"])})
    np.testing.assert_allclose(np.asarray(got), want, atol=0.05, rtol=0.05)


def test_quantized_forward_logits_close():
    """The quality bar: int8 logits stay close to f32 logits."""
    cfg = get_config("tiny-llama")
    params = core.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    qparams = jax.tree.map(
        jnp.asarray, quantize_params(jax.device_get(params)),
    )
    ids = jnp.asarray([[5, 17, 99, 42, 7, 250, 8, 11]], jnp.int32)
    want, _ = core.forward(params, cfg, ids, None, jnp.int32(0))
    got, _ = core.forward(qparams, cfg, ids, None, jnp.int32(0))
    diff = np.abs(np.asarray(got) - np.asarray(want))
    spread = float(np.asarray(want).max() - np.asarray(want).min())
    assert float(diff.max()) < 0.05 * max(spread, 1.0), (
        f"quantized logits drifted: max diff {diff.max():.4f} vs spread {spread:.2f}"
    )


def test_quantize_params_covers_moe_experts():
    """VERDICT r3 item 8: for Mixtral the experts ARE the weights — they
    must quantize (per-expert scales), router stays dense."""
    cfg = get_config("tiny-mixtral")
    params = quantize_params(
        jax.device_get(core.init_params(cfg, jax.random.key(0), dtype=jnp.float32))
    )
    moe = params["layers"]["moe"]
    for k in ("w_up", "w_gate", "w_down"):
        if k in moe:
            assert is_quantized(moe[k]), k
            # weight [L, E, in, out] -> scales [L, E, out]
            assert moe[k]["s"].shape == moe[k]["q"].shape[:2] + moe[k]["q"].shape[-1:]
    assert not is_quantized(moe["router"])  # tiny; stays dense


@pytest.mark.parametrize("impl", ["dense", "routed"])
def test_quantized_moe_forward_logits_close(impl):
    """int8 experts stay close to f32 logits in BOTH MoE formulations."""
    from dataclasses import replace

    cfg = replace(get_config("tiny-mixtral"), moe_impl=impl)
    params = core.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    qparams = jax.tree.map(jnp.asarray, quantize_params(jax.device_get(params)))
    ids = jnp.asarray([[5, 17, 99, 42, 7, 250, 8, 11]], jnp.int32)
    want, _ = core.forward(params, cfg, ids, None, jnp.int32(0))
    got, _ = core.forward(qparams, cfg, ids, None, jnp.int32(0))
    diff = np.abs(np.asarray(got) - np.asarray(want))
    spread = float(np.asarray(want).max() - np.asarray(want).min())
    assert float(diff.max()) < 0.05 * max(spread, 1.0), (
        f"{impl}: max diff {diff.max():.4f} vs spread {spread:.2f}"
    )


def test_quantized_moe_engine_on_expert_mesh():
    """Quantized experts shard over the `expert` axis ({"q","s"} follow
    the weight's rules) and the EP rollout matches single-device."""
    kw = dict(quantize="int8", **KW)
    ref = InferenceEngine("tiny-mixtral", engine_config=EngineConfig(**kw))
    want = ref.generate([5, 17, 99, 42, 7], max_new_tokens=8, temperature=0.0)
    ref.close()

    mesh = build_mesh(MeshSpec(expert=2))
    eng = InferenceEngine("tiny-mixtral", mesh=mesh, engine_config=EngineConfig(**kw))
    wu = eng.params["layers"]["moe"]["w_up"]
    E = wu["q"].shape[1]
    assert {s.data.shape[1] for s in wu["q"].addressable_shards} == {E // 2}
    assert {s.data.shape[1] for s in wu["s"].addressable_shards} == {E // 2}
    got = eng.generate([5, 17, 99, 42, 7], max_new_tokens=8, temperature=0.0)
    eng.close()
    assert got.token_ids == want.token_ids


def test_host_checkpoint_load_for_quantize(tmp_path):
    """quantize='int8' must load checkpoints host-side (the dense model
    never materializes in HBM) and serve identically to the dense load."""
    from bee2bee_tpu.models.loader import load_checkpoint, save_native

    cfg = get_config("tiny-llama")
    params = core.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    save_native(jax.device_get(params), cfg, tmp_path / "ckpt")

    host = load_checkpoint(tmp_path / "ckpt", cfg, dtype=jnp.float32, host=True)
    assert isinstance(jax.tree.leaves(host)[0], np.ndarray)  # not on device

    eng = InferenceEngine(
        "tiny-llama",
        checkpoint_path=str(tmp_path / "ckpt"),
        engine_config=EngineConfig(quantize="int8", **KW),
    )
    r = eng.generate([5, 17, 99], max_new_tokens=4, temperature=0.0)
    eng.close()
    assert r.new_tokens == 4


def test_mesh_join_bf16_still_casts_to_engine_dtype():
    """Regression: ml_dtypes bfloat16 is NOT np.floating — the quant
    pass-through must key on np.integer, or bf16 weights skip the cast."""
    import ml_dtypes

    assert not np.issubdtype(np.dtype(ml_dtypes.bfloat16), np.floating)
    assert not np.issubdtype(np.dtype(ml_dtypes.bfloat16), np.integer)
    assert np.issubdtype(np.int8, np.integer)


def test_engine_serves_quantized():
    eng = InferenceEngine(
        "tiny-llama", engine_config=EngineConfig(quantize="int8", **KW)
    )
    # single-device CPU engines unstack layers (list of per-layer trees);
    # quantized subtrees ride through either layout
    layer0 = eng.params["layers"][0] if isinstance(
        eng.params["layers"], list) else eng.params["layers"]
    assert is_quantized(layer0["attn"]["wq"])
    r = eng.generate([5, 17, 99, 42], max_new_tokens=8, temperature=0.0)
    eng.close()
    assert r.new_tokens == 8


def test_engine_rejects_unknown_quantize():
    with pytest.raises(ValueError, match="only 'int8'"):
        InferenceEngine(
            "tiny-llama", engine_config=EngineConfig(quantize="int4", **KW)
        )


def test_quantized_engine_on_tp_mesh_matches_single_device():
    """Quantized weights shard under TP ({"q","s"} leaves follow the
    weight's partition rules) and the rollout matches single-device."""
    kw = dict(quantize="int8", **KW)
    ref = InferenceEngine("tiny-llama", engine_config=EngineConfig(**kw))
    want = ref.generate([5, 17, 99, 42, 7], max_new_tokens=8, temperature=0.0)
    ref.close()

    mesh = build_mesh(MeshSpec(model=2))
    eng = InferenceEngine("tiny-llama", mesh=mesh, engine_config=EngineConfig(**kw))
    wq = eng.params["layers"]["attn"]["wq"]
    # int8 payload sharded on the out (head) dim; scales follow it
    assert {s.data.shape[-1] for s in wq["q"].addressable_shards} == {
        wq["q"].shape[-1] // 2
    }
    assert {s.data.shape[-1] for s in wq["s"].addressable_shards} == {
        wq["s"].shape[-1] // 2
    }
    got = eng.generate([5, 17, 99, 42, 7], max_new_tokens=8, temperature=0.0)
    eng.close()
    assert got.token_ids == want.token_ids


def test_quantized_mqa_replication():
    """gemma-style MQA on a TP mesh: quantized K/V projections replicate
    whole (the kv_replicated path must see through the /q,/s leaves)."""
    mesh = build_mesh(MeshSpec(model=4))
    eng = InferenceEngine(
        "tiny-gemma", mesh=mesh, engine_config=EngineConfig(quantize="int8", **KW)
    )
    wk = eng.params["layers"]["attn"]["wk"]
    full = wk["q"].shape
    assert {s.data.shape for s in wk["q"].addressable_shards} == {full}  # replicated
    r = eng.generate([5, 17, 99], max_new_tokens=4, temperature=0.0)
    eng.close()
    assert r.new_tokens == 4


@pytest.mark.parametrize("family", ["tiny-gemma3", "tiny-bloom"])
def test_int8_serving_new_architecture_classes(family):
    """int8 weight-only quant through the round-5 trees: the allowlist
    must leave qk-norms / post-norms / embed-norm / alibi constants
    untouched — the quantized engine's greedy rollout must MATCH the
    rollout over the dequantized weights (catches NaN logits and any
    corrupted excluded leaf)."""
    cfg = get_config(family)
    params = core.init_params(cfg, jax.random.key(3), dtype=jnp.float32)
    eng = InferenceEngine(
        family, params=jax.tree.map(lambda a: a, params),
        engine_config=EngineConfig(**KW, prefill_buckets=(16,),
                                   quantize="int8"),
    )
    try:
        r = eng.generate([1, 7, 42, 99], max_new_tokens=5, temperature=0.0)
        assert r.new_tokens == 5
    finally:
        eng.close()
    # reference rollout over the DEQUANTIZED weights — exact same math
    deq = jax.tree.map(lambda a: a, quantize_params(jax.device_get(params)))

    def undo(node):
        if isinstance(node, dict) and "q" in node and "s" in node:
            return jnp.asarray(dequantize_weight(node), jnp.float32)
        if isinstance(node, dict):
            return {k: undo(v) for k, v in node.items()}
        return jnp.asarray(node, jnp.float32)

    deq = undo(deq)
    ids, want = [1, 7, 42, 99], []
    for _ in range(5):
        logits, _ = core.forward(deq, cfg, jnp.asarray([ids], jnp.int32),
                                 None, jnp.int32(0))
        assert bool(jnp.all(jnp.isfinite(logits)))
        t = int(jnp.argmax(logits[0, -1]))
        ids.append(t)
        want.append(t)
    assert r.token_ids == want


def test_quantized_page_write_stores_k_beside_v_in_one_pass():
    """core._quantized_page_write over the page-major int8 leaf
    [NB, 2, Hkv, BS, hd] with scales [NB, 2, Hkv] (PR 44: once over 2 x Hkv
    heads where K and V took a call each): K's half and V's half come out
    bit for bit what a pass over each half alone gives — scales, requantised
    pages and the chunk's slots — for a decode token, a chunk off a page
    edge under a floor and a ceil, and a dead row."""
    from bee2bee_tpu.models.core import _quantized_page_write

    NB, Hkv, BS, hd = 12, 2, 8, 16
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.integers(-127, 128, (NB, 2, Hkv, BS, hd)), jnp.int8)
    scale = jnp.asarray(rng.uniform(0.0, 0.02, (NB, 2, Hkv)), jnp.float32)
    scale = scale.at[5].set(0.0)  # a freshly recycled block
    tables = np.asarray([[1, 2, 3, 0], [4, 5, 6, 0], [0, 0, 0, 0]], np.int32)
    for T, offs, floor, ceil in ((1, [9, 17, 3], None, None),
                                 (11, [5, 3, 0], [7, 0, 0], [14, 12, 0])):
        B = len(offs)
        positions = np.asarray(offs)[:, None] + np.arange(T)[None]
        blk = np.take_along_axis(tables, positions // BS, axis=1)
        if floor is not None:
            blk = np.where(positions >= np.asarray(floor)[:, None], blk, 0)
            blk = np.where(positions < np.asarray(ceil)[:, None], blk, 0)
        slot = positions % BS
        wslot = positions // BS - (np.asarray(offs) // BS)[:, None]
        x = jnp.asarray(rng.standard_normal((B, T, 2, Hkv, hd)) * [[[1.0]], [[3.0]]],
                        jnp.float32)  # V's amax is not K's
        got_pool, got_scale = _quantized_page_write(pool, scale, blk, slot, wslot, x)
        assert got_pool.shape == pool.shape and got_scale.shape == scale.shape
        for half in (0, 1):
            one_pool, one_scale = _quantized_page_write(
                pool[:, half:half + 1], scale[:, half:half + 1], blk, slot, wslot,
                x[:, :, half:half + 1])
            np.testing.assert_array_equal(
                np.asarray(got_pool[:, half]), np.asarray(one_pool[:, 0]))
            np.testing.assert_array_equal(
                np.asarray(got_scale[:, half]), np.asarray(one_scale[:, 0]))
        live = np.unique(blk[blk > 0])
        assert (np.asarray(got_scale)[live] >= np.asarray(scale)[live]).all()
        assert not np.array_equal(np.asarray(got_pool)[live], np.asarray(pool)[live])
        untouched = np.setdiff1d(np.arange(1, NB), live)
        np.testing.assert_array_equal(
            np.asarray(got_pool)[untouched], np.asarray(pool)[untouched])
