"""nemotron-h on the served path: the engine's programs (``RowCache`` state three
layers deep, a pool ONE layer deep, expert stacks two deep under six layers)
against the plain reference, rows of a batch against their solo runs, and the
counters of a model whose expert layers are a KIND of layer. The model's own
tests are ``tests/test_nemotron.py`` (two files on purpose: see there)."""

from __future__ import annotations

import sys
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bee2bee_tpu.engine import EngineConfig, InferenceEngine
from bee2bee_tpu.models import core, support

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "benchmark"))
import reference_nemotron_h as plain  # noqa: E402  (the benchmark's plain reference)

ENGINE_KW = dict(max_seq_len=128, dtype="float32", cache_dtype="float32",
                 decode_chunk=4, max_batch=4, prefill_buckets=(16, 32, 64),
                 kv_block_size=8)
CHARS = {v: c for c, v in plain.KINDS.items()}


def _engine(**over) -> InferenceEngine:
    return InferenceEngine("tiny-nemotron", engine_config=EngineConfig(**{**ENGINE_KW, **over}))


def _prompt(seed: int, n: int) -> list[int]:
    return [1] + [int(t) for t in np.random.RandomState(seed).randint(3, 259, n - 1)]


def _dims(cfg) -> dict:
    return dict(plain.dims_of_preset(cfg), layer_first=0,
                hybrid_override_pattern="".join(CHARS[t] for t in cfg.layer_types))


def test_engine_greedy_tokens_equal_the_references_and_info_says_the_depths():
    eng = _engine()
    try:
        full = jax.tree.map(jnp.asarray, core.restack_layers(eng.params))
        dims = _dims(eng.model_cfg)
        pieces = plain.build_forward(dims)
        checked = 0
        for seed, n in ((0, 21), (5, 40), (6, 9)):
            ids = _prompt(seed, n)
            got = eng.generate(list(ids), max_new_tokens=10).token_ids
            for tok in got[:6]:
                ref, _ = plain.forward_logits(dims, full, np.asarray([ids], np.int32),
                                              len(ids) - 1, pieces=pieces)
                assert ref.shape == (1, 320) and int(np.argmax(ref[0])) == tok
                ids.append(tok)
                checked += 1
        assert checked >= 6
        info = eng.info
        assert info["state"]["layers"] == 3 and info["state"]["ssm_row_shape"] == [3, 8, 16, 8]
        assert info["state"]["conv_row_shape"] == [3, 3, 160]
        assert info["kv"]["cache_layers"] == 1 and info["kv"]["bytes_per_token"] == 2 * 2 * 16 * 4
        assert eng.scheduler.cache.pool["kv"].shape[0] == 1
        assert eng.scheduler.cache.state["ssm"].shape[0] == 3
    finally:
        eng.close()


@pytest.mark.parametrize("over", [{}, {"prefill_chunk": 16}])
def test_rows_of_a_batch_equal_their_solo_runs(over):
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
        assert step.value(kind="live") - was[0] == steps * 3  # 3 recurrent layers of 6
        assert calls.value() - was[1] >= steps * 3
        # an expert-layer call is counted for the TWO expert layers, not for six
        assert (layer_calls.value() - was[2]) % 2 == 0
        forwards = (layer_calls.value() - was[2]) // 2
        assert 1 + 1 <= forwards <= 1 + steps
        now = {k: assign.value(kind=k) - was[4][k] for k in was[4]}
        # every live position's 5 choices in 2 layers are here or elsewhere
        assert now["live"] + now["elsewhere"] == (21 + steps) * 5 * 2
        assert now["live"] > 0 and now["elsewhere"] > 0 and now["dead"] == 11 * 10
        assert 0 < hit.value() - was[3] <= forwards * 2 * 4  # of the 4 HELD experts a layer
        state_bytes = sum(a.nbytes for a in jax.tree.leaves(eng.scheduler.cache.state))
        assert reg.get("engine.state_bytes").value() == state_bytes == 3 * (8 * 16 * 8 + 3 * 160) * 4
    finally:
        eng.close()


@pytest.mark.parametrize("over,feature", [
    (dict(prefix_cache_entries=4), "prefix_cache"), (dict(spec_tokens=2), "spec_ngram"),
    (dict(cache_dtype="int8"), "kv_int8"), (dict(quantize="int8"), "weight_int8"),
])
def test_the_engine_refuses_by_name_what_is_not_built_for_it(over, feature):
    with pytest.raises(support.FeatureUnsupported, match=feature) as err:
        _engine(**over)
    assert err.value.feature == feature and "ONE branch" in err.value.ground
