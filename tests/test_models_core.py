"""Model core correctness: shapes, cache-vs-full-forward equivalence (the
property that makes incremental decoding valid), GQA, MoE, and every config
family init."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bee2bee_tpu.models import CONFIGS, core, get_config


@pytest.fixture(scope="module", params=["tiny-gpt2", "tiny-llama", "tiny-mixtral"])
def model(request):
    cfg = get_config(request.param)
    params = core.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    return cfg, params


def test_get_config_fuzzy_match():
    assert get_config("distilgpt2").name == "distilgpt2"
    assert get_config("meta-llama/Llama-3-8B").name == "llama-3-8b"
    assert get_config("HuggingFaceH4/zephyr-7b-beta").name == "zephyr-7b"
    with pytest.raises(KeyError):
        get_config("definitely-not-a-model")


def test_all_configs_init_tiny():
    # every preset's architecture switches must produce a coherent param tree
    for name in ("tiny-gpt2", "tiny-llama", "tiny-mixtral"):
        cfg = get_config(name)
        params = core.init_params(cfg, jax.random.key(1))
        leaves = jax.tree.leaves(params)
        assert all(jnp.isfinite(x).all() for x in leaves)


def test_full_forward_shapes(model):
    cfg, params = model
    logits, cache = core.forward(params, cfg, jnp.ones((2, 5), jnp.int32), None, 0)
    assert logits.shape == (2, 5, cfg.vocab_size)
    assert cache is None
    assert logits.dtype == jnp.float32


def test_causality(model):
    """Changing a later token must not affect earlier logits."""
    cfg, params = model
    rng = np.random.default_rng(0)
    a = rng.integers(3, cfg.vocab_size, (1, 8)).astype(np.int32)
    b = a.copy()
    b[0, -1] = (b[0, -1] + 7) % cfg.vocab_size
    la, _ = core.forward(params, cfg, jnp.asarray(a), None, 0)
    lb, _ = core.forward(params, cfg, jnp.asarray(b), None, 0)
    np.testing.assert_allclose(la[0, :-1], lb[0, :-1], rtol=1e-5, atol=1e-5)
    assert not np.allclose(la[0, -1], lb[0, -1])


def test_cached_decode_matches_full_forward(model):
    """THE invariant: prefill + step-by-step cached decode must produce the
    same logits as one full no-cache forward pass."""
    cfg, params = model
    rng = np.random.default_rng(1)
    T = 10
    ids = jnp.asarray(rng.integers(3, cfg.vocab_size, (1, T)), jnp.int32)

    full_logits, _ = core.forward(params, cfg, ids, None, 0)

    # prefill the first 4, then decode one token at a time
    cache = core.init_cache(cfg, 1, max_len=32, dtype=jnp.float32)
    pre_logits, cache = core.forward(params, cfg, ids[:, :4], cache, 0)
    np.testing.assert_allclose(pre_logits, full_logits[:, :4], rtol=2e-4, atol=2e-4)
    for t in range(4, T):
        step_logits, cache = core.forward(
            params, cfg, ids[:, t : t + 1], cache, jnp.asarray([t], jnp.int32)
        )
        np.testing.assert_allclose(
            step_logits[:, 0], full_logits[:, t], rtol=2e-4, atol=2e-4,
            err_msg=f"divergence at decode position {t}",
        )


def test_prefill_pad_overwritten_by_decode(model):
    """Pad garbage written past the true length must never leak into decode
    logits: padded prefill + decode == exact-length prefill + decode."""
    cfg, params = model
    rng = np.random.default_rng(2)
    n = 5
    ids = rng.integers(3, cfg.vocab_size, (1, n)).astype(np.int32)
    nxt = jnp.asarray([[7]], jnp.int32)

    # exact-length prefill
    c1 = core.init_cache(cfg, 1, 32, jnp.float32)
    _, c1 = core.forward(params, cfg, jnp.asarray(ids), c1, 0)
    l1, _ = core.forward(params, cfg, nxt, c1, jnp.asarray([n], jnp.int32))

    # padded-to-16 prefill (pad tokens are arbitrary garbage)
    padded = np.full((1, 16), 9, np.int32)
    padded[0, :n] = ids
    c2 = core.init_cache(cfg, 1, 32, jnp.float32)
    _, c2 = core.forward(params, cfg, jnp.asarray(padded), c2, 0)
    l2, _ = core.forward(params, cfg, nxt, c2, jnp.asarray([n], jnp.int32))

    np.testing.assert_allclose(l1, l2, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("model", ["tiny-llama", "tiny-mixtral"])
def test_unstacked_layers_match_stacked(model):
    """core.unstack_layers (the CPU serving fast path — per-layer
    contiguous weights, unrolled loop) must be numerically identical to
    the stacked lax.scan, cached and uncached."""
    cfg = get_config(model)
    params = core.init_params(cfg, jax.random.key(3), dtype=jnp.float32)
    uparams = core.unstack_layers(jax.device_get(params))
    assert isinstance(uparams["layers"], list) and len(uparams["layers"]) == cfg.n_layers

    ids = jnp.asarray([[7, 3, 99, 42, 11]], jnp.int32)
    want, _ = core.forward(params, cfg, ids, None, jnp.int32(0))
    got, _ = core.forward(uparams, cfg, ids, None, jnp.int32(0))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)

    cache_s = core.init_cache(cfg, 1, 32, jnp.float32)
    cache_u = core.init_cache(cfg, 1, 32, jnp.float32)
    w1, cache_s = core.forward(params, cfg, ids, cache_s, jnp.int32(0))
    g1, cache_u = core.forward(uparams, cfg, ids, cache_u, jnp.int32(0))
    np.testing.assert_allclose(np.asarray(g1), np.asarray(w1), atol=1e-5)
    nxt = jnp.asarray([[5]], jnp.int32)
    w2, _ = core.forward(params, cfg, nxt, cache_s, jnp.int32(5))
    g2, _ = core.forward(uparams, cfg, nxt, cache_u, jnp.int32(5))
    np.testing.assert_allclose(np.asarray(g2), np.asarray(w2), atol=1e-5)


def test_engine_unstacks_on_single_device_cpu():
    """On a trivial CPU mesh the engine takes the unstacked fast path
    (the XLA:CPU packed-GEMM issue — docs/PERF.md 'CPU fallback')."""
    from bee2bee_tpu.engine import EngineConfig, InferenceEngine

    eng = InferenceEngine(
        "tiny-llama",
        engine_config=EngineConfig(
            max_seq_len=64, dtype="float32", cache_dtype="float32"
        ),
    )
    try:
        assert isinstance(eng.params["layers"], list)
        r = eng.generate([5, 17, 99], max_new_tokens=4, temperature=0.0)
        assert r.new_tokens == 4
    finally:
        eng.close()


def test_gqa_head_counts():
    cfg = get_config("tiny-llama")
    assert cfg.n_kv_heads < cfg.n_heads  # actually grouped
    params = core.init_params(cfg, jax.random.key(0))
    hd = cfg.head_dim
    assert params["layers"]["attn"]["wk"].shape == (cfg.n_layers, cfg.d_model, cfg.n_kv_heads * hd)
    assert params["layers"]["attn"]["wq"].shape == (cfg.n_layers, cfg.d_model, cfg.n_heads * hd)


def test_moe_router_selects_topk():
    cfg = get_config("tiny-mixtral")
    assert cfg.is_moe
    params = core.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    logits, _ = core.forward(params, cfg, jnp.ones((1, 4), jnp.int32), None, 0)
    assert jnp.isfinite(logits).all()
    # MoE layer params have the expert dim
    assert params["layers"]["moe"]["w_up"].shape[1] == cfg.n_experts


def test_batched_rows_independent(model):
    """Row 0 of a batch must be unaffected by row 1's content."""
    cfg, params = model
    rng = np.random.default_rng(3)
    a = rng.integers(3, cfg.vocab_size, (2, 6)).astype(np.int32)
    b = a.copy()
    b[1] = (b[1] + 11) % cfg.vocab_size
    la, _ = core.forward(params, cfg, jnp.asarray(a), None, 0)
    lb, _ = core.forward(params, cfg, jnp.asarray(b), None, 0)
    np.testing.assert_allclose(la[0], lb[0], rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------- routed MoE


def test_routed_moe_matches_dense_at_full_capacity():
    """With capacity >= N (no drops), the routed dispatch must equal the
    dense all-experts formulation exactly (VERDICT r2 task #7 acceptance)."""
    from bee2bee_tpu.models.config import get_config

    dense_cfg = get_config("tiny-mixtral")
    routed_cfg = get_config(
        "tiny-mixtral", moe_impl="routed",
        moe_capacity_factor=float(dense_cfg.n_experts),  # C = N: nothing drops
    )
    params = core.init_params(dense_cfg, jax.random.key(0), dtype=jnp.float32)
    ids = jnp.asarray(
        np.random.default_rng(0).integers(3, dense_cfg.vocab_size, (2, 12)), jnp.int32
    )
    want, _ = core.forward(params, dense_cfg, ids, None, jnp.int32(0))
    got, _ = core.forward(params, routed_cfg, ids, None, jnp.int32(0))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_routed_moe_capacity_drops_are_finite():
    """Tokens past expert capacity drop (combine weight 0) — outputs stay
    finite and within range, never NaN."""
    from bee2bee_tpu.models.config import get_config

    cfg = get_config("tiny-mixtral", moe_impl="routed", moe_capacity_factor=0.25)
    params = core.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    ids = jnp.asarray(
        np.random.default_rng(1).integers(3, cfg.vocab_size, (2, 16)), jnp.int32
    )
    logits, _ = core.forward(params, cfg, ids, None, jnp.int32(0))
    assert np.isfinite(np.asarray(logits)).all()


def test_routed_moe_on_expert_mesh_matches_single_device():
    """Routed MoE under EP sharding: the dispatch/combine einsums become
    collectives over the `expert` axis; numerics must not change."""
    from bee2bee_tpu.models import partition
    from bee2bee_tpu.models.config import get_config
    from bee2bee_tpu.parallel import MeshSpec, build_mesh

    cfg = get_config("tiny-mixtral", moe_impl="routed", moe_capacity_factor=4.0)
    mesh = build_mesh(MeshSpec(expert=4, model=2))
    params = core.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    ids = jnp.asarray(
        np.random.default_rng(2).integers(3, cfg.vocab_size, (1, 8)), jnp.int32
    )
    want, _ = core.forward(params, cfg, ids, None, jnp.int32(0))
    sharded = partition.shard_params(params, mesh, cfg=cfg)
    got = jax.jit(lambda p, x: core.forward(p, cfg, x, None, jnp.int32(0))[0])(
        sharded, ids
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_routed_moe_is_differentiable():
    """The dispatch path (one_hot/cumsum/einsum) must carry gradients —
    the dryrun trains a routed tiny-mixtral."""
    from bee2bee_tpu.models.config import get_config

    cfg = get_config("tiny-mixtral", moe_impl="routed", moe_capacity_factor=2.0)
    params = core.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    ids = jnp.asarray(
        np.random.default_rng(3).integers(3, cfg.vocab_size, (2, 8)), jnp.int32
    )

    def loss(p):
        logits, _ = core.forward(p, cfg, ids, None, jnp.int32(0))
        tgt = ids[:, 1:]
        lp = jax.nn.log_softmax(logits[:, :-1])
        return -jnp.mean(jnp.take_along_axis(lp, tgt[..., None], axis=-1))

    grads = jax.grad(loss)(params)
    gnorms = [float(jnp.linalg.norm(g)) for g in jax.tree.leaves(grads)]
    assert all(np.isfinite(g) for g in gnorms)
    wup_g = grads["layers"]["moe"]["w_up"]
    assert float(jnp.abs(wup_g).sum()) > 0  # experts actually received grads


def test_routed_moe_groups_match_ungrouped_at_full_capacity():
    """Grouped dispatch with per-group full capacity still equals dense;
    a group size that forces padding (g=5 over N=24) must not change
    valid-token outputs."""
    from bee2bee_tpu.models.config import get_config

    dense_cfg = get_config("tiny-mixtral")
    routed = get_config(
        "tiny-mixtral", moe_impl="routed",
        moe_capacity_factor=float(dense_cfg.n_experts), moe_group_size=5,
    )
    params = core.init_params(dense_cfg, jax.random.key(0), dtype=jnp.float32)
    ids = jnp.asarray(
        np.random.default_rng(4).integers(3, dense_cfg.vocab_size, (2, 12)), jnp.int32
    )
    want, _ = core.forward(params, dense_cfg, ids, None, jnp.int32(0))
    got, _ = core.forward(params, routed, ids, None, jnp.int32(0))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_moe_impl_validated():
    from bee2bee_tpu.models.config import get_config

    with pytest.raises(ValueError, match="moe_impl"):
        get_config("tiny-mixtral", moe_impl="Routed")


def test_large_family_configs_resolve_and_validate():
    """The bigger members of supported families: fuzzy names resolve, and
    each fits its natural serving mesh (divisibility check — the configs
    must actually serve, not just exist)."""
    from bee2bee_tpu.models import get_config
    from bee2bee_tpu.models.partition import validate_divisibility
    from bee2bee_tpu.parallel import MeshSpec, build_mesh

    cases = {
        "google/gemma-7b": "gemma-7b",
        "mistralai/Mistral-7B-v0.1": "mistral-7b",
        "meta-llama/Meta-Llama-3-70B": "llama-3-70b",
    }
    mesh8 = build_mesh(MeshSpec(data=1, model=8))
    for query, want in cases.items():
        cfg = get_config(query)
        assert cfg.name == want, (query, cfg.name)
        validate_divisibility(cfg, mesh8)  # must not raise
    # bare family names resolve to the family DEFAULT, not the biggest
    assert get_config("llama-3").name == "llama-3-8b"
    assert get_config("gemma").name == "gemma-2b"
    # gemma-7b's 256-dim heads: attention width independent of d_model
    g7 = get_config("gemma-7b")
    assert g7.head_dim == 256 and g7.n_heads * g7.head_dim == 4096
    # mistral-7b is zephyr's architecture under its own name (one source)
    from dataclasses import asdict
    z, m = asdict(get_config("zephyr-7b")), asdict(get_config("mistral-7b"))
    z.pop("name"), m.pop("name")
    assert z == m
    # forward math smoke on a shrunken llama-3-70b-shaped config
    import jax
    import jax.numpy as jnp

    from bee2bee_tpu.models import core

    cfg = get_config("llama-3-70b", d_model=128, n_layers=2, n_heads=8,
                     n_kv_heads=2, d_ff=256, vocab_size=512)
    params = core.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    logits, _ = core.forward(
        params, cfg, jnp.asarray([[1, 5, 9]], jnp.int32), None, jnp.int32(0)
    )
    assert logits.shape == (1, 3, 512)


# ---- q / k / v read where they lie (PR 48): in a call of at most
# core.QKV_IN_PLACE_ROWS rows a barrier keeps the three products apart from
# the head split; in a call of more the compiler folds the split into them,
# as it did in every call before


def _int8(params):
    from bee2bee_tpu.models.quant import quantize_params

    return jax.tree.map(jnp.asarray, quantize_params(jax.device_get(params)))


def _one_lora_row(cfg, B):
    """A pool of two slots (0 = the null adapter) on wq and wv; row 1 alone
    carries the adapter."""
    rng, L, D, r = np.random.default_rng(3), cfg.n_layers, cfg.d_model, 4
    widths = {"wq": cfg.n_heads * cfg.head_dim, "wv": cfg.n_kv_heads * cfg.head_dim}
    factors = {}
    for name, n in widths.items():
        a = rng.normal(0, 0.3, (L, 2, D, r)).astype(np.float32)
        b = rng.normal(0, 0.3, (L, 2, r, n)).astype(np.float32)
        a[:, 0], b[:, 0] = 0, 0
        factors[name] = {"a": jnp.asarray(a), "b": jnp.asarray(b)}
    ids = np.zeros(B, np.int32)
    ids[1] = 1
    return dict(adapters=factors, adapter_ids=ids,
                adapter_scales=jnp.asarray([0.0, 2.0], jnp.float32))


@pytest.mark.parametrize("name,variant", [
    ("tiny-gpt2", "plain"),  # MHA, q / k / v biases after the barrier
    ("tiny-llama", "plain"),  # GQA 4 / 2
    ("tiny-llama", "int8"),  # the barrier follows the scale
    ("tiny-llama", "lora"),  # the barrier follows the row's delta
])
def test_the_qkv_barrier_leaves_the_logits_as_they_were(monkeypatch, name, variant):
    """The same call under the boundary (the barrier) and over it (the head
    split folded into the products, the program of every call before PR 48):
    the logits agree to the tolerance of the cached-against-full tests above."""
    cfg = get_config(name)
    params = core.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    if variant == "int8":
        params = _int8(params)
    B, T = 4, 16
    ids = np.random.default_rng(1).integers(3, cfg.vocab_size, (B, T)).astype(np.int32)
    kw = _one_lora_row(cfg, B) if variant == "lora" else {}

    def run(barrier):
        fn = jax.jit(lambda p, x: core.forward(p, cfg, x, None, jnp.int32(0), **kw)[0])
        assert ("optimization_barrier" in fn.lower(params, ids).as_text()) == barrier
        return np.asarray(fn(params, ids))

    kept = run(True)
    monkeypatch.setattr(core, "QKV_IN_PLACE_ROWS", B * T - 1)
    np.testing.assert_allclose(kept, run(False), rtol=2e-4, atol=2e-4)
    if variant == "lora":  # and the adapter row is not the base model's
        base = np.asarray(core.forward(params, cfg, ids, None, jnp.int32(0))[0])
        assert np.abs(kept[1] - base[1]).max() > 1e-2
        np.testing.assert_allclose(kept[0], base[0], rtol=2e-4, atol=2e-4)


def test_the_qkv_barrier_is_differentiable(monkeypatch):
    """Training calls of few rows run through the barrier: it has a transpose
    rule, and the gradient is that of the many-row program."""
    cfg = get_config("tiny-llama")
    params = core.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    ids = jnp.asarray(np.random.default_rng(2).integers(3, cfg.vocab_size, (4, 8)), jnp.int32)

    def grads(barrier):
        def loss(p):  # a fresh function a form: a trace is cached by function
            logits = core.forward(p, cfg, ids, None, jnp.int32(0), remat=True)[0]
            return jnp.mean(jax.nn.logsumexp(logits, axis=-1))

        fn = jax.jit(jax.grad(loss))
        assert ("optimization_barrier" in fn.lower(params).as_text()) == barrier
        return fn(params)

    kept = grads(True)
    monkeypatch.setattr(core, "QKV_IN_PLACE_ROWS", 0)
    without = grads(False)
    for leaf in ("wq", "wk", "wv"):
        g = np.asarray(kept["layers"]["attn"][leaf])
        assert np.abs(g).max() > 0
        np.testing.assert_allclose(
            g, np.asarray(without["layers"]["attn"][leaf]), rtol=2e-4, atol=1e-6)
