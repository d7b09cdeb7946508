"""falcon-h1 (a Mamba-2 mixer in parallel with attention in every block):
the model against the plain reference and against ``transformers``' own
implementation, the chunked scan against the token recurrence, and the
engine's second kind of row state (recurrent, beside the paged pool) through
admission, chunked prefill, compaction, a bucket resize, a dead row and the
re-prefill migration rung. All at ``tiny-falcon-h1`` size on the CPU."""

from __future__ import annotations

import dataclasses
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
from bee2bee_tpu.models.config import CONFIGS, config_from_hf, get_config

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "benchmark"))
import reference_falcon_h1 as plain  # noqa: E402  (the benchmark's plain reference)

CFG = get_config("tiny-falcon-h1")
ENGINE_KW = dict(max_seq_len=128, dtype="float32", cache_dtype="float32",
                 decode_chunk=4, max_batch=4, prefill_buckets=(16, 32, 64),
                 kv_block_size=8)


@pytest.fixture(scope="module")
def params():
    p = core.init_params(CFG, jax.random.key(3), dtype=jnp.float32)
    # nothing may hide behind an init value: bias, skip and norm scale random
    k = iter(jax.random.split(jax.random.key(4), 4))
    ssm = dict(p["layers"]["ssm"])
    ssm["conv_b"] = 0.1 * jax.random.normal(next(k), ssm["conv_b"].shape)
    ssm["D"] = jax.random.normal(next(k), ssm["D"].shape)
    ssm["norm"] = 0.5 + jax.random.uniform(next(k), ssm["norm"].shape)
    return dict(p, layers=dict(p["layers"], ssm=ssm))


def _ids(rows: int, n: int, seed: int = 0):
    return np.random.RandomState(seed).randint(3, 500, (rows, n)).astype(np.int32)


def _plain_logits(params, ids, perturb=None):
    embed, layer, head = plain.build_forward(plain.dims_of_preset(CFG), perturb)
    with jax.default_matmul_precision("highest"):
        x = embed(params["tok_embed"], ids)
        for i in range(CFG.n_layers):
            x = layer(x, params["layers"], np.int32(i))
        return np.stack([head(x[:, t], params["final_norm"]["scale"], params["lm_head"])
                         for t in range(ids.shape[1])], axis=1)


# ------------------------------------------------------------------ the model


def test_forward_matches_the_plain_reference(params):
    """(a) core.forward, chunked scan and all, against the token-by-token
    float32 reference. Tolerance: both are float32 end to end, so only the
    order of summation differs; logits have std ~0.5 here."""
    ids = _ids(3, 21)
    ours, _ = core.forward(params, CFG, ids, None, 0)
    np.testing.assert_allclose(np.asarray(ours), _plain_logits(params, ids), atol=2e-5)


@pytest.mark.parametrize("perturb", [
    {"state_dtype": "bfloat16"}, {"drop_multiplier": "key_multiplier"},
    {"drop_multiplier": "ssm_multipliers"}, {"drop_multiplier": "mlp_multipliers"},
])
def test_perturbed_reference_differs(params, perturb):
    """The reference's own perturbations (the builder's proof that the
    tolerance discriminates) do change its logits."""
    ids = _ids(2, 21)
    assert np.abs(_plain_logits(params, ids, perturb) - _plain_logits(params, ids)).max() > 1e-4


@pytest.mark.parametrize("T", [1, 5, 8, 16, 19, 24])
def test_chunked_scan_equals_token_recurrence(params, T):
    """(c) lengths that are and are not multiples of the chunk (8), and one
    shorter than it: the mixer over T positions at once == T one-step calls,
    outputs and carried state alike."""
    lp = jax.tree.map(lambda a: a[0], params["layers"]["ssm"])
    u = jax.random.normal(jax.random.key(T), (2, T, CFG.d_model), jnp.float32)
    zero = jax.tree.map(lambda a: a[0], core.init_ssm_state(CFG, 2, jnp.float32))
    at_once, st_once = core.ssm_mixer(lp, CFG, u, zero)
    st, outs = zero, []
    for t in range(T):
        o, st = core.ssm_mixer(lp, CFG, u[:, t:t + 1], st)
        outs.append(o)
    np.testing.assert_allclose(np.asarray(at_once), np.concatenate(outs, 1), atol=1e-5)
    for name in ("ssm", "conv"):
        np.testing.assert_allclose(np.asarray(st_once[name]), np.asarray(st[name]), atol=1e-5)
    if T >= 2:  # a stateless pass is the same pass from zero state
        np.testing.assert_allclose(np.asarray(core.ssm_mixer(lp, CFG, u)[0]),
                                   np.asarray(at_once), atol=1e-6)


def test_padded_tail_leaves_the_state_untouched(params):
    """A prefill bucket's pad positions: the state after a bucket of 16 with
    11 real tokens is BIT-FOR-BIT the state after those 11 alone."""
    lp = jax.tree.map(lambda a: a[0], params["layers"]["ssm"])
    u = jax.random.normal(jax.random.key(0), (2, 16, CFG.d_model), jnp.float32)
    zero = jax.tree.map(lambda a: a[0], core.init_ssm_state(CFG, 2, jnp.float32))
    _, padded = core.ssm_mixer(lp, CFG, u, zero, valid_len=jnp.asarray([11, 16]))
    _, exact = core.ssm_mixer(lp, CFG, u[:1, :11], jax.tree.map(lambda a: a[:1], zero))
    for name in ("ssm", "conv"):
        assert np.array_equal(np.asarray(padded[name][0]), np.asarray(exact[name][0])), name


@pytest.mark.parametrize("chunks", [(13,), (8, 5), (16, 3)])
def test_prefill_then_decode_matches_full_forward(params, chunks):
    """(b) prefill (whole, or in chunks that cross the scan's chunk boundary,
    each in a bucket of 16 it does not fill) then decode through the paged
    pool AND the state == the cache-less full forward."""
    ids = _ids(2, 24, seed=1)
    full, _ = core.forward(params, CFG, ids, None, 0)
    BS, NB = 8, 16
    cache = core.init_paged_pool(CFG, NB, BS, jnp.float32)
    cache.update(core.init_ssm_state(CFG, 2, jnp.float32))
    tables = np.arange(1, 9, dtype=np.int32).reshape(2, 4)
    pos = 0
    n = sum(chunks)
    for c in chunks:
        tok = np.zeros((2, 16), np.int32)
        tok[:, :c] = ids[:, pos:pos + c]
        lg, cache = core.forward(
            params, CFG, tok, cache, np.int32(pos), block_tables=tables,
            paged_write_ceil=np.int32(n), valid_len=np.asarray([c, c]),
            last_index=np.asarray([c - 1, c - 1]))
        pos += c
    assert lg.shape == (2, 1, CFG.vocab_size)  # the last position's logits only
    np.testing.assert_allclose(np.asarray(lg[:, 0]), np.asarray(full[:, n - 1]), atol=2e-5)
    for t in range(n, 24):
        lg, cache = core.forward(params, CFG, ids[:, t:t + 1], cache,
                                 np.asarray([t, t], np.int32), block_tables=tables)
        np.testing.assert_allclose(np.asarray(lg[:, 0]), np.asarray(full[:, t]), atol=2e-5)


def test_a_cache_without_state_is_refused(params):
    with pytest.raises(ValueError, match="recurrent mixer"):
        core.forward(params, CFG, _ids(1, 4), core.init_cache(CFG, 1, 16, jnp.float32), 0)


def test_published_preset_equals_the_catalog_config():
    """(g) config_from_hf on the published config.json (the catalog row's
    ``config``, copied into the benchmark's configuration file)."""
    conf = json.loads((ROOT / "benchmark/configs/falcon-h1-34b-6l.json").read_text())
    got = config_from_hf(conf, name="falcon-h1-34b")
    want = dataclasses.replace(CONFIGS["falcon-h1-34b"],
                               max_seq_len=conf["max_position_embeddings"])
    assert got == want and got.n_layers == 72
    cut = CONFIGS["falcon-h1-34b-6l"]
    assert dataclasses.replace(cut, n_layers=72, name="falcon-h1-34b") == CONFIGS["falcon-h1-34b"]
    assert cut.n_layers == conf["layers"] == 6
    # 430.1 M a block (attention 31.46 + mixer 68.3 + MLP 330.3), W_in and W_out counted
    per_block = (core.matmul_params_per_token(cut) - cut.d_model * cut.vocab_size) / 6
    assert per_block == 31_457_280 + 5120 * 9248 + 4096 * 5120 + 3 * 5120 * 21504


@pytest.mark.parametrize("flag,value", [
    ("mamba_rms_norm", False), ("mamba_norm_before_gate", True),
    ("attn_layer_indices", [0, 2]), ("mamba_proj_bias", True), ("attention_bias", True),
    ("mlp_bias", True), ("projectors_bias", True), ("mamba_conv_bias", False),
    ("rope_scaling", {"rope_type": "linear", "factor": 2.0}),
])
def test_unimplemented_variants_are_refused_by_name(flag, value):
    conf = json.loads((ROOT / "benchmark/configs/falcon-h1-34b-6l.json").read_text())
    with pytest.raises(ValueError, match=flag):
        config_from_hf({**conf, flag: value})


def test_transformers_checkpoint_loads_and_logits_match(tmp_path):
    """(e) the tie to the published model: ``transformers``' own
    FalconH1ForCausalLM at tiny size, saved, loaded through loader.py."""
    transformers = pytest.importorskip("transformers")
    torch = pytest.importorskip("torch")
    if not hasattr(transformers, "FalconH1ForCausalLM"):
        pytest.skip("transformers too old for FalconH1ForCausalLM")
    from bee2bee_tpu.models.loader import load_checkpoint

    conf = transformers.FalconH1Config(
        vocab_size=512, hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, intermediate_size=128,
        max_position_embeddings=64, mamba_d_ssm=32, mamba_n_heads=4, mamba_d_head=8,
        mamba_n_groups=2, mamba_d_state=16, mamba_d_conv=4, mamba_chunk_size=4,
        mamba_rms_norm=True, mamba_norm_before_gate=False, tie_word_embeddings=False,
        rope_theta=1e6, embedding_multiplier=2.0, lm_head_multiplier=0.5,
        attention_in_multiplier=0.9, attention_out_multiplier=0.7, key_multiplier=0.6,
        ssm_in_multiplier=0.8, ssm_out_multiplier=0.75, mlp_multipliers=[0.85, 0.65],
        ssm_multipliers=[0.9, 0.8, 0.7, 0.6, 0.5])
    torch.manual_seed(0)
    model = transformers.FalconH1ForCausalLM(conf).eval()
    with torch.no_grad():  # the per-head vectors off their init values
        for lyr in model.model.layers:
            lyr.mamba.A_log.copy_(torch.log(torch.rand(4) * 3 + 0.5))
            lyr.mamba.D.copy_(torch.randn(4))
            lyr.mamba.dt_bias.copy_(torch.randn(4))
            lyr.mamba.norm.weight.copy_(torch.rand(32) + 0.5)
            lyr.mamba.conv1d.bias.copy_(torch.randn(96) * 0.1)
    model.save_pretrained(tmp_path)
    cfg = config_from_hf(json.loads((tmp_path / "config.json").read_text()))
    loaded = load_checkpoint(tmp_path, cfg, dtype=jnp.float32)
    ids = np.array([[1, 7, 42, 99, 3, 250, 8, 11, 77, 5, 19]], np.int32)  # 11: not a chunk multiple
    ours, _ = core.forward(loaded, cfg, jnp.asarray(ids), None, jnp.int32(0))
    with torch.no_grad():
        theirs = model(torch.from_numpy(ids.astype(np.int64))).logits.numpy()
    np.testing.assert_allclose(np.asarray(ours, np.float32), theirs, atol=3e-5, rtol=1e-3)
    # bf16 load keeps the decay vectors float32
    low = load_checkpoint(tmp_path, cfg, dtype=jnp.bfloat16, host=True)["layers"]["ssm"]
    assert low["A_log"].dtype == np.float32 and low["w_in"].dtype != np.float32


# ------------------------------------------------- the engine's state manager


def _engine(**over) -> InferenceEngine:
    return InferenceEngine("tiny-falcon-h1", engine_config=EngineConfig(**{**ENGINE_KW, **over}))


def _prompt(seed: int, n: int) -> list[int]:
    return [1] + [int(t) for t in np.random.RandomState(seed).randint(3, 259, n - 1)]


@pytest.fixture(scope="module")
def solo():
    """Each test prompt's greedy tokens from an engine that serves it ALONE."""
    eng = _engine(max_batch=1)
    want = {}
    for seed, n, new in ((0, 21, 20), (1, 9, 6), (2, 30, 24), (3, 13, 16), (4, 40, 12)):
        want[seed] = eng.generate(_prompt(seed, n), max_new_tokens=new).token_ids
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
        info = eng.info
        assert info["state"]["ssm_row_shape"] == [2, 4, 8, 16]
        assert info["state"]["conv_row_shape"] == [2, 3, 96] and info["state"]["ssm_dtype"] == "float32"
        assert info["introspect"]["hbm"]["components"]["state"] > 0  # not in workspace_other
    finally:
        eng.close()


@pytest.mark.parametrize("over", [{}, {"prefill_chunk": 16}])
def test_rows_admitted_retired_compacted_resized_equal_their_solo_runs(solo, over):
    """(d) five requests over four rows, admitted at different times, of
    different lengths (so rows retire while others decode, holes compact and
    the bucket resizes), with chunked prefill on and off: every row's tokens
    equal its solo run. A dead row of the bucket never changes a live one."""
    eng = _engine(**over)
    got: dict[int, list[int]] = {}
    spec = {0: (21, 20), 1: (9, 6), 2: (30, 24), 3: (13, 16), 4: (40, 12)}

    def run(seed):
        n, new = spec[seed]
        got[seed] = eng.generate(_prompt(seed, n), max_new_tokens=new).token_ids

    try:
        first = [threading.Thread(target=run, args=(s,)) for s in (0, 1, 2)]
        for t in first:
            t.start()
        first[1].join()  # the short one retires: a hole, then a compaction
        later = [threading.Thread(target=run, args=(s,)) for s in (3, 4)]
        for t in later:
            t.start()
        for t in first + later:
            t.join()
        assert got == {s: solo[s] for s in spec}
        st = eng.scheduler
        assert st.cache.state["ssm"].shape[1] == st._bsz  # the state follows the bucket
    finally:
        eng.close()


def test_state_counters_and_gauges():
    """The counters count what was dispatched: real and pad positions of the
    prefill bucket, live and dead rows x steps x layers of decode windows."""
    import bee2bee_tpu.engine.scheduler  # noqa: F401  (registers the metrics)
    from bee2bee_tpu.metrics import get_registry

    reg = get_registry()
    scan, step = reg.get("engine.ssm_scan_tokens"), reg.get("engine.ssm_step_rows")
    calls = reg.get("engine.ssm_step_kernel_calls")
    eng = _engine()
    try:
        was = {k: (scan.value(kind=k), step.value(kind=j))
               for k, j in (("real", "live"), ("pad", "dead"))}
        calls_was = calls.value()
        eng.generate(_prompt(0, 21), max_new_tokens=6)  # bucket 32: 21 real + 11 pad
        assert scan.value(kind="real") - was["real"][0] == 21
        assert scan.value(kind="pad") - was["pad"][0] == 11
        # the 5 steps the budget leaves after the prefill's first token (chunks
        # of decode_chunk 4 + 1: the window ends with its row), 2 layers, 1 row
        assert eng.scheduler.stats.chunks == 2
        assert step.value(kind="live") - was["real"][1] == 5 * 2
        assert step.value(kind="dead") - was["pad"][1] == 0  # a bucket of one row
        assert calls.value() - calls_was == 5 * 2  # one kernel call a layer a step
        assert reg.get("engine.state_rows").value() == 1
        state_bytes = sum(a.nbytes for a in jax.tree.leaves(eng.scheduler.cache.state))
        assert reg.get("engine.state_bytes").value() == state_bytes == 2 * (4 * 8 * 16 + 3 * 96) * 4
        eng.introspect.ledger.snapshot()  # the ledger's gauges refresh on a read
        assert reg.get("engine.hbm_bytes").value(component="state") == state_bytes
    finally:
        eng.close()


def test_migration_takes_the_reprefill_rung_and_next_tokens_are_equal(solo):
    """A recurrent row's snapshot ships no blocks (its pages are not its whole
    state): the importer re-prefills prompt + accepted tokens, which rebuilds
    K/V AND state, and the imported row's next tokens equal the donor's."""
    a, b = _engine(), _engine()
    try:
        seen = []
        gen = a.generate_stream(_prompt(2, 30), max_new_tokens=24)  # held: closing it cancels
        for ev in gen:
            assert not ev.get("done")
            seen.extend(ev.get("tokens") or [])
            if len(seen) >= 6:
                break
        (req,) = a.scheduler.live_requests()
        snap = a.scheduler.checkpoint(req)
        assert "_kv" not in snap and snap["kv_blocks"] == 0 and snap["out"]
        req2 = b.import_generation(dict(snap))
        out = list(snap["out"])
        while True:
            ev = req2.events.get(timeout=60)
            if ev.get("done"):
                assert ev.get("result") is not None, ev.get("error")
                break
            out.extend(ev.get("tokens") or [])
        assert out == solo[2]
        assert b.scheduler.stats.import_reprefills == 1
    finally:
        a.close()
        b.close()


REFUSED = {
    "prefix_cache": dict(prefix_cache_entries=4),
    "spec_ngram": dict(spec_tokens=4),
    "spec_model_drafter": dict(spec_tokens=4, drafter="tiny-llama"),
    "spec_mesh_drafter": dict(spec_tokens=4, drafter="mesh"),
    "multi_lora": dict(max_adapters=2),
    "prefill_chunk": dict(prefill_chunk=48),
}


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_config_features_that_cannot_carry_the_state_are_refused(feature):
    with pytest.raises(FeatureUnsupported) as err:
        _engine(**REFUSED[feature])
    assert err.value.feature == feature and feature in str(err.value)
