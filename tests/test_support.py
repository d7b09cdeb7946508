"""What each kind of model refuses is ONE table (models/support.py): every
row raises what it says, a plain model is refused nothing, the engine names
the features a configuration turns on, the first refusal is the table's
first, and docs/MODELS.md prints the table as it is. No engine is built."""

from __future__ import annotations

import pathlib
import re
from types import SimpleNamespace

import pytest

from bee2bee_tpu.engine import EngineConfig, FeatureUnsupported, InferenceEngine
from bee2bee_tpu.models import support
from bee2bee_tpu.models.config import get_config

PRESET = {  # a tiny model of each kind
    "recurrent_state": "tiny-falcon-h1",
    "latent_pool": "tiny-joyai",
    "dropless_routed": "tiny-smallthinker",
    "looped_stack": "tiny-ouro",
    "layer_kinds": "tiny-granite",
    "mtp_layer": "tiny-exaone",
    "single_branch": "tiny-nemotron",
}
DETAIL = dict(prefill_chunk=48, max_seq_len=128)
ROWS = [(ground, feature, why)
        for ground, rows in support.REFUSED.items() for feature, why in rows]
NO_MESH = SimpleNamespace(shape={})


def test_the_table_has_a_row_set_for_every_ground_and_no_pair_twice():
    assert [name for name, _, _ in support.GROUNDS] == list(support.REFUSED) == list(PRESET)
    assert len({(g, f) for g, f, _ in ROWS}) == len(ROWS) >= 40
    for name, has, _ in support.GROUNDS:  # the presets are of ONE kind each
        assert [g for g, tiny in PRESET.items() if has(get_config(tiny))] == [name]


@pytest.mark.parametrize("ground,feature,why", ROWS, ids=[f"{g}-{f}" for g, f, _ in ROWS])
def test_every_row_raises_what_it_says(ground, feature, why):
    cfg = get_config(PRESET[ground])
    sentence = {name: s for name, _, s in support.GROUNDS}[ground]
    with pytest.raises(FeatureUnsupported) as err:
        support.require(cfg, feature, **DETAIL)
    assert (err.value.feature, err.value.ground) == (feature, sentence)
    assert str(err.value) == (f"{feature} is not supported for {cfg.name!r}: "
                              f"{sentence}, and {why.format(**DETAIL)}")
    assert isinstance(err.value, ValueError)


def test_a_plain_model_is_refused_nothing():
    features = sorted({f for _, f, _ in ROWS})
    assert support.require(get_config("tiny-llama"), *features, **DETAIL) is None
    # ... and a kind of model only what its own rows name
    assert support.require(get_config("tiny-ouro"), "prefix_cache", "kv_export") is None


IN_USE = {
    "kv_int8": (dict(cache_dtype="int8"), {}),
    "weight_int8": (dict(quantize="int8"), {}),
    "spec_ngram": (dict(spec_tokens=4), {}),
    "spec_model_drafter": (dict(spec_tokens=4, drafter="tiny-llama"), {}),
    "spec_mesh_drafter": (dict(spec_tokens=4, drafter="mesh"), {}),
    "seq_attention": (dict(attention="sp"), {}),
    "mesh_model": ({}, {"model": 2}),
    "mesh_expert": ({}, {"expert": 2}),
    "multi_lora": (dict(max_adapters=2), {}),
    "prefix_cache": (dict(prefix_cache_entries=4), {}),
    "prefill_chunk": (dict(prefill_chunk=48), {}),
}
# what comes with a feature: a drafter needs spec_tokens, "mesh" is a drafter
IMPLIED = {"spec_model_drafter": {"spec_ngram"},
           "spec_mesh_drafter": {"spec_ngram", "spec_model_drafter"}}


def test_the_engine_names_each_feature_for_the_configuration_that_turns_it_on():
    in_use = InferenceEngine._features_in_use
    assert in_use(EngineConfig(), NO_MESH, 128) == set()
    assert in_use(EngineConfig(prefill_chunk=32), NO_MESH, 128) == set()  # it divides
    assert in_use(EngineConfig(), SimpleNamespace(shape={"seq": 2}), 128) == {"seq_attention"}
    for feature, (over, axes) in IN_USE.items():
        got = in_use(EngineConfig(**over), SimpleNamespace(shape=axes), 128)
        assert got == {feature} | IMPLIED.get(feature, set()), feature
    # every feature the engine can name is a feature of the table, and the
    # table's others are asked about where they are built
    asked_elsewhere = {"pipeline_stages", "kv_export", "pipeline_stage_split",
                       "pipeline_trunk", "ring_forward",
                       # (of a published config.json: config._nemotron_h_from_hf)
                       "mtp_module", "mlp_alone_layer"}
    assert set(IN_USE) | asked_elsewhere == {f for _, f, _ in ROWS}
    # a model with a multi-token-prediction layer speculates with THAT: no
    # kind refuses spec_mtp, and the n-gram floor is not what spec_tokens asks
    own = get_config("tiny-exaone")
    assert in_use(EngineConfig(spec_tokens=1), NO_MESH, 128, own) == {"spec_mtp"}
    assert in_use(EngineConfig(), NO_MESH, 128, own) == set()
    assert in_use(EngineConfig(spec_tokens=1, drafter="tiny-llama"), NO_MESH, 128, own) == {
        "spec_mtp", "spec_model_drafter"}
    assert "spec_mtp" not in {f for _, f, _ in ROWS}
    assert support.require(own, "spec_mtp", "prefill_chunk", **DETAIL) is None


@pytest.mark.parametrize("model,features,first", [
    ("tiny-ouro", ("multi_lora", "kv_int8"), "kv_int8"),
    ("tiny-joyai", ("weight_int8", "multi_lora"), "multi_lora"),
    ("tiny-falcon-h1", ("spec_ngram", "spec_model_drafter", "spec_mesh_drafter"),
     "spec_mesh_drafter"),
    ("tiny-smallthinker", ("pipeline_stages", "spec_ngram"), "spec_ngram"),
    ("tiny-granite", ("weight_int8", "mesh_expert", "kv_export"), "mesh_expert"),
    ("granite-4.0-h-small-10l-e36", ("kv_int8", "prefix_cache"), "prefix_cache"),
    ("tiny-exaone", ("kv_export", "spec_ngram", "mesh_expert"), "spec_ngram"),
    ("k-exaone-236b-a23b-5l-e16", ("weight_int8", "prefix_cache"), "prefix_cache"),
    ("k-exaone-236b-a23b", ("pipeline_stages", "spec_model_drafter"), "spec_model_drafter"),
    ("tiny-nemotron", ("weight_int8", "mesh_expert", "kv_export"), "mesh_expert"),
    ("nemotron-3-super-120b-a12b-11l-e128", ("kv_int8", "prefix_cache"), "prefix_cache"),
])
def test_of_two_refused_features_the_table_s_first_is_raised(model, features, first):
    with pytest.raises(FeatureUnsupported) as err:
        support.require(get_config(model), *features)
    assert err.value.feature == first


def test_the_document_prints_the_table():
    """docs/MODELS.md "What each kind of model refuses": grounds across,
    features down, the sentence in the cell and nothing in any other."""
    text = (pathlib.Path(__file__).parent.parent / "docs" / "MODELS.md").read_text()
    section = text.split("**What each kind of model refuses**", 1)[1]
    table = [[c.strip() for c in line.strip().strip("|").split("|")]
             for line in section.splitlines() if line.startswith("|")]
    head, body = table[0], table[2:]
    grounds = [re.sub(r"`", "", h) for h in head[1:]]
    assert grounds == list(support.REFUSED)
    printed = {(g, row[0].strip("`")): cell
               for row in body for g, cell in zip(grounds, row[1:]) if cell}
    assert printed == {(g, f): why for g, f, why in ROWS}
