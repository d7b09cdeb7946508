"""Feature composition: the serving knobs must work TOGETHER, not just
alone — each combination pinned to the plain single-device rollout."""

import numpy as np
import pytest

from bee2bee_tpu.engine import EngineConfig, InferenceEngine
from bee2bee_tpu.parallel import MeshSpec, build_mesh

KW = dict(max_seq_len=128, dtype="float32", cache_dtype="float32")
PROMPT = list(np.random.default_rng(9).integers(3, 500, size=40))


def _rollout(engine, n=8):
    r = engine.generate(PROMPT, max_new_tokens=n, temperature=0.0)
    engine.close()
    return r.token_ids


@pytest.fixture(scope="module")
def baseline():
    return _rollout(InferenceEngine("tiny-llama", engine_config=EngineConfig(**KW)))


def test_sp_with_prefix_cache_and_chunked_prefill(baseline):
    eng = InferenceEngine(
        "tiny-llama",
        mesh=build_mesh(MeshSpec(seq=4)),
        engine_config=EngineConfig(
            attention="sp", prefix_cache_entries=4, prefill_chunk=16, **KW
        ),
    )
    first = eng.generate(PROMPT, max_new_tokens=8, temperature=0.0).token_ids
    second = eng.generate(PROMPT, max_new_tokens=8, temperature=0.0).token_ids
    assert eng.scheduler.stats.prefix_hits == 1  # cache worked under SP
    eng.close()
    assert first == baseline and second == baseline


def test_quantize_with_prefix_cache_and_chunks():
    """int8 changes logits slightly, so pin quantized-combo rollouts to
    the quantized-baseline rollout instead of the f32 one."""
    qkw = dict(quantize="int8", **KW)
    want = _rollout(InferenceEngine("tiny-llama", engine_config=EngineConfig(**qkw)))
    eng = InferenceEngine(
        "tiny-llama",
        engine_config=EngineConfig(
            prefix_cache_entries=4, prefill_chunk=16, **qkw
        ),
    )
    first = eng.generate(PROMPT, max_new_tokens=8, temperature=0.0).token_ids
    second = eng.generate(PROMPT, max_new_tokens=8, temperature=0.0).token_ids
    assert eng.scheduler.stats.prefix_hits == 1
    eng.close()
    assert first == want and second == want


def test_quantize_with_sp_mesh():
    qkw = dict(quantize="int8", **KW)
    want = _rollout(InferenceEngine("tiny-llama", engine_config=EngineConfig(**qkw)))
    got = _rollout(
        InferenceEngine(
            "tiny-llama",
            mesh=build_mesh(MeshSpec(data=2, seq=2, model=2)),
            engine_config=EngineConfig(attention="sp", **qkw),
        )
    )
    assert got == want


def test_quantize_with_tp_flash_mesh():
    """int8 + the pallas flash kernel + TP (interpret mode on CPU)."""
    qkw = dict(quantize="int8", **KW)
    want = _rollout(InferenceEngine("tiny-llama", engine_config=EngineConfig(**qkw)))
    got = _rollout(
        InferenceEngine(
            "tiny-llama",
            mesh=build_mesh(MeshSpec(model=2)),
            engine_config=EngineConfig(attention="flash", **qkw),
        )
    )
    assert got == want

def test_penalties_with_prefix_cache_and_chunked_prefill():
    """Penalized rows + prefix-cache admission + chunked prefill compose:
    the cached-prefix path must still build the FULL prompt bincount
    (counts come from req.ids, not from what was prefilled). The prompt
    loops so the greedy continuation provably repeats — a random prompt
    can make any penalty an invisible no-op."""
    loop = [7, 8] * 20
    eng = InferenceEngine(
        "tiny-llama",
        engine_config=EngineConfig(
            prefix_cache_entries=4, prefill_chunk=16, **KW
        ),
    )
    plain = eng.generate(loop, max_new_tokens=12, temperature=0.0).token_ids
    assert np.bincount(plain).max() >= 3  # the loop actually loops
    # second request hits the prefix cache AND carries penalties
    pen = eng.generate(
        loop, max_new_tokens=12, temperature=0.0, repetition_penalty=5.0,
    ).token_ids
    assert eng.scheduler.stats.prefix_hits >= 1
    assert pen != plain  # penalty applied despite the cached prefix
    # and a third plain request is unaffected by the penalized one
    again = eng.generate(loop, max_new_tokens=12, temperature=0.0).token_ids
    eng.close()
    assert again == plain


def test_penalties_with_quantize_int8():
    """int8 weights + occurrence penalties: the counts tensor and the
    quantized matmuls share the decode graph."""
    eng = InferenceEngine(
        "tiny-llama",
        engine_config=EngineConfig(quantize="int8", **KW),
    )
    a = eng.generate(PROMPT, max_new_tokens=8, temperature=0.0,
                     frequency_penalty=100.0).token_ids
    b = eng.generate(PROMPT, max_new_tokens=8, temperature=0.0,
                     frequency_penalty=100.0).token_ids
    eng.close()
    assert a == b  # deterministic
    assert np.bincount(a).max() <= 2  # the tax bit


def test_min_p_with_sp_mesh(baseline):
    """min_p rides the seq-sharded serving path (per-row arrays reach the
    sampler regardless of attention impl)."""
    eng = InferenceEngine(
        "tiny-llama",
        mesh=build_mesh(MeshSpec(seq=4)),
        engine_config=EngineConfig(attention="sp", **KW),
    )
    pinned = eng.generate(
        PROMPT, max_new_tokens=8, temperature=2.0, min_p=1.0
    ).token_ids
    eng.close()
    assert pinned == baseline  # min_p=1 degrades to greedy == baseline


def test_lora_with_quantize_int8():
    """LoRA merge happens BEFORE int8 quantization: the quantized engine
    serves the finetuned weights (engine.__init__ ordering)."""
    import jax

    from bee2bee_tpu.models import get_config
    from bee2bee_tpu.train.lora import LoraConfig, init_lora, save_adapters

    cfg = get_config("tiny-llama")
    lcfg = LoraConfig(rank=4, alpha=64.0, targets=("wq", "wv"))
    adapters = init_lora(cfg, lcfg, jax.random.key(5))
    adapters = jax.tree.map(lambda x: x + 0.05, adapters)  # visible delta
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        p = f"{d}/a.npz"
        save_adapters(p, adapters, lcfg)
        eng = InferenceEngine(
            "tiny-llama",
            engine_config=EngineConfig(quantize="int8", **KW),
            lora_path=p,
        )
        merged = eng.generate(PROMPT, max_new_tokens=8, temperature=0.0).token_ids
        eng.close()
    base_q = _rollout(InferenceEngine(
        "tiny-llama", engine_config=EngineConfig(quantize="int8", **KW)
    ))
    assert merged != base_q  # the adapters actually reached the int8 weights


# ---- a recurrent-state model (falcon-h1) and the features that assume "a
# row's cache is its K/V pages": each either carries the state or is refused
# when the engine (stage runner, drafter) is BUILT, by a typed error naming it.
# The config-only refusals live in tests/test_falcon_h1.py::REFUSED.


def _refused(feature, build):
    from bee2bee_tpu.engine import FeatureUnsupported

    with pytest.raises(FeatureUnsupported) as err:
        build()
    assert err.value.feature == feature and "tiny-falcon-h1" in str(err.value)


@pytest.mark.parametrize("feature,mesh,over", [
    ("mesh_model", MeshSpec(model=2), {}),
    ("seq_attention", MeshSpec(seq=2), {"attention": "sp"}),
    ("seq_attention", MeshSpec(seq=2), {}),
])
def test_recurrent_model_refuses_mesh_axes_that_do_not_shard_its_state(feature, mesh, over):
    _refused(feature, lambda: InferenceEngine(
        "tiny-falcon-h1", mesh=build_mesh(mesh), engine_config=EngineConfig(**over, **KW)))


def test_recurrent_model_refuses_pipeline_stages_and_the_drafter_seat():
    from bee2bee_tpu.engine.drafter import DraftModel
    from bee2bee_tpu.engine.stage_runner import StageRunner

    _refused("pipeline_stages", lambda: StageRunner(
        "tiny-falcon-h1", n_stages=2, stage=0, max_seq_len=64, dtype="float32"))
    _refused("spec_model_drafter", lambda: DraftModel(
        "tiny-falcon-h1", spec_tokens=4, batch=2, target_max_seq_len=64, dtype="float32"))


def test_recurrent_model_serves_with_int8_kv_and_int8_weights():
    """What does NOT assume pages-only state keeps working: the quantized
    pool and weight-only int8 compose with the recurrent state."""
    want = _rollout(InferenceEngine("tiny-falcon-h1", engine_config=EngineConfig(
        quantize="int8", **KW)))
    got = _rollout(InferenceEngine("tiny-falcon-h1", engine_config=EngineConfig(
        quantize="int8", prefill_chunk=16, **KW)))
    assert got == want
    r = _rollout(InferenceEngine("tiny-falcon-h1", engine_config=EngineConfig(
        **{**KW, "cache_dtype": "int8"})))
    assert len(r) == 8


# ---- a latent-attention model (JoyAI-LLM-Flash): what is not proven over a
# LATENT pool (one [c_kv | k_rope] row a token, no per-head K/V) is refused by
# name when the engine (stage runner, drafter) is BUILT. The config-only
# refusals live in tests/test_joyai.py::REFUSED.


def _latent_refused(feature, build):
    from bee2bee_tpu.engine import FeatureUnsupported

    with pytest.raises(FeatureUnsupported) as err:
        build()
    assert err.value.feature == feature and "tiny-joyai" in str(err.value)


@pytest.mark.parametrize("feature,mesh,over", [
    ("mesh_model", MeshSpec(model=2), {}),
    ("seq_attention", MeshSpec(seq=2), {"attention": "sp"}),
    ("seq_attention", MeshSpec(seq=2), {}),
    ("mesh_expert", MeshSpec(expert=2), {}),
])
def test_latent_model_refuses_mesh_axes_it_is_not_partitioned_over(feature, mesh, over):
    _latent_refused(feature, lambda: InferenceEngine(
        "tiny-joyai", mesh=build_mesh(mesh), engine_config=EngineConfig(**over, **KW)))


@pytest.mark.parametrize("feature,over", [
    ("kv_int8", {"cache_dtype": "int8"}),
    ("weight_int8", {"quantize": "int8"}),
    ("spec_ngram", {"spec_tokens": 4}),
    ("spec_model_drafter", {"spec_tokens": 4, "drafter": "tiny-llama"}),
    ("multi_lora", {"max_adapters": 2}),
    ("prefix_cache", {"prefix_cache_entries": 4}),
])
def test_latent_model_refuses_config_features_by_name(feature, over):
    _latent_refused(feature, lambda: InferenceEngine(
        "tiny-joyai", engine_config=EngineConfig(**{**KW, **over})))


def test_latent_model_refuses_pipeline_stages_and_the_drafter_seat():
    from bee2bee_tpu.engine.drafter import DraftModel
    from bee2bee_tpu.engine.stage_runner import StageRunner

    _latent_refused("pipeline_stages", lambda: StageRunner(
        "tiny-joyai", n_stages=3, stage=0, max_seq_len=64, dtype="float32"))
    _latent_refused("spec_model_drafter", lambda: DraftModel(
        "tiny-joyai", spec_tokens=4, batch=2, target_max_seq_len=64, dtype="float32"))


def test_latent_model_serves_with_chunked_prefill_penalties_and_the_ragged_reader():
    """What does not look inside a page keeps working over the latent pool."""
    want = _rollout(InferenceEngine("tiny-joyai", engine_config=EngineConfig(**KW)))
    got = _rollout(InferenceEngine("tiny-joyai", engine_config=EngineConfig(
        prefill_chunk=16, **KW)))
    assert got == want and len(want) == 8
    flash = _rollout(InferenceEngine("tiny-joyai", engine_config=EngineConfig(
        **{**KW, "attention": "flash"})))
    assert len(flash) == 8


# ---- a model of dropless expert layers over a K/V pool whose layers are of
# two attention kinds (SmallThinker): what is not proven for it is refused by
# name when the engine (stage runner, drafter) is BUILT. The config-only
# refusals live in tests/test_smallthinker.py::REFUSED.


def _dropless_refused(feature, build):
    from bee2bee_tpu.engine import FeatureUnsupported

    with pytest.raises(FeatureUnsupported) as err:
        build()
    assert err.value.feature == feature and "tiny-smallthinker" in str(err.value)


@pytest.mark.parametrize("feature,mesh,over", [
    ("mesh_model", MeshSpec(model=2), {}),
    ("seq_attention", MeshSpec(seq=2), {"attention": "sp"}),
    ("seq_attention", MeshSpec(seq=2), {}),
    ("mesh_expert", MeshSpec(expert=2), {}),
])
def test_dropless_model_refuses_mesh_axes_it_is_not_partitioned_over(feature, mesh, over):
    _dropless_refused(feature, lambda: InferenceEngine(
        "tiny-smallthinker", mesh=build_mesh(mesh), engine_config=EngineConfig(**over, **KW)))


def test_dropless_model_refuses_pipeline_stages_and_the_drafter_seat():
    from bee2bee_tpu.engine.drafter import DraftModel
    from bee2bee_tpu.engine.stage_runner import StageRunner

    _dropless_refused("pipeline_stages", lambda: StageRunner(
        "tiny-smallthinker", n_stages=2, stage=0, max_seq_len=64, dtype="float32"))
    _dropless_refused("spec_model_drafter", lambda: DraftModel(
        "tiny-smallthinker", spec_tokens=4, batch=2, target_max_seq_len=64, dtype="float32"))


def test_dropless_model_serves_with_chunked_prefill_the_prefix_cache_and_the_ragged_reader():
    """What does not look inside an expert keeps working: contexts of 48 tokens,
    two windows of 24, in chunks, from shared blocks, through the kernel."""
    want = _rollout(InferenceEngine("tiny-smallthinker", engine_config=EngineConfig(**KW)))
    eng = InferenceEngine("tiny-smallthinker", engine_config=EngineConfig(
        prefix_cache_entries=4, prefill_chunk=16, **KW))
    first = eng.generate(PROMPT, max_new_tokens=8, temperature=0.0).token_ids
    second = eng.generate(PROMPT, max_new_tokens=8, temperature=0.0).token_ids
    assert eng.scheduler.stats.prefix_hits == 1
    eng.close()
    assert first == want and second == want and len(want) == 8
    flash = _rollout(InferenceEngine("tiny-smallthinker", engine_config=EngineConfig(
        **{**KW, "attention": "flash", "prefill_chunk": 16})))
    assert flash == want
