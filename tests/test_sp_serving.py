"""Sequence-parallel serving tests (parallel/sp_serving.py): the KV cache
sharded over `seq`, attention merged from per-shard online-softmax
partials — the long-context serving path the reference lacks entirely.

Runs on the conftest's 8-device virtual CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bee2bee_tpu.engine import EngineConfig, InferenceEngine
from bee2bee_tpu.models import core, get_config
from bee2bee_tpu.models.partition import cache_spec
from bee2bee_tpu.parallel import MeshSpec, build_mesh
from bee2bee_tpu.parallel.sp_serving import make_sp_attn_fn, validate_sp_mesh


def _mesh(**axes):
    return build_mesh(MeshSpec(**axes))


def test_sp_attention_matches_dense():
    """The psum-merged partial attention must equal the single-device
    softmax attention bit-for-bit at f32 tolerance, mask and GQA included."""
    mesh = _mesh(seq=4)
    cfg = get_config("tiny-llama")
    rng = np.random.default_rng(0)
    B, T, S = 2, 8, 32
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = jnp.asarray(rng.standard_normal((B, T, H, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, hd)), jnp.float32)
    # serving-shaped mask: query t sees cache positions <= off + t
    off = jnp.asarray([5, 11], jnp.int32)
    q_pos = off[:, None] + jnp.arange(T)[None, :]
    mask = (jnp.arange(S)[None, None, :] <= q_pos[:, :, None])[:, None, :, :]

    want = core._attention(q, k, v, mask, cfg)
    got = make_sp_attn_fn(mesh)(q, k, v, mask, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_sp_attention_fully_masked_rows_are_zero():
    """Rows with no visible cache slots must emit 0, not NaN (the ragged
    batch case: a row at offset 0 decodes while others are mid-sequence)."""
    mesh = _mesh(seq=4)
    cfg = get_config("tiny-llama")
    B, T, S = 1, 4, 16
    q = jnp.ones((B, T, cfg.n_heads, cfg.head_dim), jnp.float32)
    k = jnp.ones((B, S, cfg.n_kv_heads, cfg.head_dim), jnp.float32)
    v = jnp.ones((B, S, cfg.n_kv_heads, cfg.head_dim), jnp.float32)
    mask = jnp.zeros((B, 1, T, S), bool)  # nothing visible
    out = make_sp_attn_fn(mesh)(q, k, v, mask, cfg)
    assert np.all(np.isfinite(np.asarray(out)))
    np.testing.assert_array_equal(np.asarray(out), 0.0)


def _greedy(engine, prompt, n):
    r = engine.generate(prompt, max_new_tokens=n, temperature=0.0)
    return r.token_ids


@pytest.mark.parametrize(
    "axes",
    [
        {"seq": 4},
        {"data": 2, "seq": 2, "model": 2},  # full composition
    ],
    ids=["sp4", "dp2xsp2xtp2"],
)
def test_sp_engine_matches_single_device(axes):
    """End-to-end: the engine on a seq-sharded mesh must produce the same
    greedy rollout as the single-device engine — through the real
    continuous-batching scheduler, prefill buckets and all."""
    prompt = [5, 17, 99, 42, 7, 256, 3, 88, 140, 11]
    kw = dict(
        max_seq_len=64, dtype="float32", cache_dtype="float32", max_batch=2
    )
    ref = InferenceEngine(
        "tiny-llama", engine_config=EngineConfig(**kw)
    )
    want = _greedy(ref, prompt, 16)
    ref.close()
    assert len(want) == 16

    sp = InferenceEngine(
        "tiny-llama",
        mesh=_mesh(**axes),
        engine_config=EngineConfig(attention="sp", **kw),
    )
    got = _greedy(sp, prompt, 16)
    sp.close()
    assert got == want


def test_sp_pool_is_sharded_over_seq():
    """The point of the layout: per-device pool bytes must be 1/n — the
    paged pool's SLOT dim shards over `seq` under attention='sp' ONLY;
    dense/flash keep the pool unsharded (no silent per-step reshard).
    cache_spec (the per-stage pipeline cache) keeps the same contract on
    its capacity dim."""
    from bee2bee_tpu.models.partition import paged_cache_spec

    mesh = _mesh(seq=4)
    cfg = get_config("tiny-llama")
    # [L, NB, 2, Hkv, BS, hd]: kv heads axis 3, slots axis 4
    assert tuple(paged_cache_spec(cfg, mesh, seq_sharded=True)) == (
        None, None, None, "model", "seq", None)
    assert paged_cache_spec(cfg, mesh)[4] is None
    assert cache_spec(cfg, mesh, seq_sharded=True)[2] == "seq"
    assert cache_spec(cfg, mesh)[2] is None
    eng = InferenceEngine(
        "tiny-llama",
        mesh=mesh,
        engine_config=EngineConfig(
            attention="sp", max_seq_len=64, dtype="float32", cache_dtype="float32"
        ),
    )
    pool = eng.new_pool()
    shard_shape = pool["kv"].sharding.shard_shape(pool["kv"].shape)
    # [L, NB, 2, Hkv, BS, hd]: the slot dim is BS/4 per device
    assert shard_shape[4] == pool["kv"].shape[4] // 4
    assert shard_shape[:4] == pool["kv"].shape[:4]
    eng.close()


def test_sp_validation_errors():
    cfg = get_config("tiny-llama")
    with pytest.raises(ValueError, match="seq > 1"):
        validate_sp_mesh(cfg, EngineConfig(attention="sp"), _mesh(model=2))
    with pytest.raises(ValueError, match="divisible by the seq"):
        validate_sp_mesh(
            cfg, EngineConfig(attention="sp", max_seq_len=130), _mesh(seq=4)
        )
    # the pool's slot dim carries the seq sharding: a block size the axis
    # doesn't divide would silently drop the 1/seq pool sharding and
    # crash the first decode's shard_map split — refuse at build
    with pytest.raises(ValueError, match="kv_block_size"):
        validate_sp_mesh(
            cfg,
            EngineConfig(attention="sp", max_seq_len=64, kv_block_size=6),
            _mesh(seq=4),
        )
    # engine constructor runs the validation too
    with pytest.raises(ValueError, match="seq > 1"):
        InferenceEngine(
            "tiny-llama", engine_config=EngineConfig(attention="sp")
        )


def test_sp_long_prompt_spanning_shards():
    """A prompt longer than one cache shard (T > S/n) must prefill
    correctly across shard boundaries."""
    mesh = _mesh(seq=4)
    kw = dict(max_seq_len=64, dtype="float32", cache_dtype="float32")
    prompt = list(np.random.default_rng(1).integers(3, 500, size=40))  # > 64/4
    ref = InferenceEngine("tiny-llama", engine_config=EngineConfig(**kw))
    want = _greedy(ref, prompt, 8)
    ref.close()
    sp = InferenceEngine(
        "tiny-llama", mesh=mesh,
        engine_config=EngineConfig(attention="sp", **kw),
    )
    got = _greedy(sp, prompt, 8)
    sp.close()
    assert got == want
