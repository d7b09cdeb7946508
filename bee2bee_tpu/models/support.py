"""What each kind of model cannot run yet: ONE table.

A kind of model is a GROUND: a property of its ModelConfig that some serving
features stumble on. ``REFUSED`` lists, for each ground, the features that
are not proven for it and why; ``require`` is the one place that raises.
The engine, the stage runner, the drafter, the pool's export and the paths
that walk the layers themselves all ask here, at construction: none of them
may be silently wrong. A new kind of model adds one entry to ``GROUNDS`` and
its rows to ``REFUSED`` (docs/MODELS.md "What each kind of model refuses"
prints the table; tests/test_support.py holds the two together).
"""

from __future__ import annotations

from .config import ModelConfig


class FeatureUnsupported(ValueError):
    """A feature that is not proven for a kind of model was asked for with
    such a model: ``feature`` names it, ``ground`` is the sentence of the
    model's property it stumbles on (GROUNDS)."""

    def __init__(self, feature: str, model: str, why: str, ground: str):
        self.feature, self.ground = feature, ground
        super().__init__(
            f"{feature} is not supported for {model!r}: {ground}, and {why}")


# (name, does the model have it, the sentence a refusal carries), in the
# order they are asked: a model with two grounds is refused by the first.
GROUNDS = (
    # falcon-h1's Mamba-2 mixer: rollback is not free for a recurrence, and
    # a row's pages are not its complete state
    ("recurrent_state", lambda cfg: cfg.has_ssm and not _layer_kinds(cfg),
     "its rows carry recurrent state beside their K/V pages"),
    # latent attention caches one [c_kv | k_rope] row a token
    # (core.pool_layout)
    ("latent_pool", lambda cfg: cfg.has_mla,
     "its rows cache latent rows (no per-head K/V)"),
    # smallthinker: a plain K/V pool under dropless expert layers
    ("dropless_routed", lambda cfg: (cfg.moe_dropless and not cfg.has_mla
                                     and not _layer_kinds(cfg)
                                     and not cfg.mtp_layers),
     "its every layer is a dropless expert layer routed from the "
     "pre-attention norm"),
    # ouro: a layer of cache a (pass, layer) (cfg.cache_layers)
    ("looped_stack", lambda cfg: cfg.loop_steps > 1,
     "its layers run several times a token with a cache of their own in "
     "every pass"),
    # granite-4.0-h: a recurrent mixer OR attention a layer (cfg.layer_types:
    # a state as deep as the one kind, a pool as deep as the other) under
    # dropless expert layers of which the chip may hold a share
    ("layer_kinds", lambda cfg: _layer_kinds(cfg) and not cfg.single_branch,
     "its layers hold one mixer kind each (recurrent state in some, K/V "
     "pages in the others) under dropless expert layers, of whose experts "
     "the chip may hold a share"),
    # K-EXAONE: attention in every layer (a window in some), dropless expert
    # layers of which the chip may hold a share behind a leading dense layer,
    # and a multi-token-prediction layer, the model's OWN drafter (the engine's
    # ``mtp`` tier, the one speculation proven for it)
    ("mtp_layer", lambda cfg: cfg.mtp_layers > 0,
     "its layers are attention (a window in some) under dropless expert "
     "layers, of whose experts the chip may hold a share, and it drafts with "
     "a multi-token-prediction layer of its own"),
    # nemotron-h: ``layer_types``' third kind, a layer of ONE branch under ONE
    # norm (a recurrent mixer, attention OR an expert layer of ungated
    # experts in a latent); state, pool and expert stacks each as deep as
    # their kind (cfg.single_branch)
    ("single_branch", lambda cfg: cfg.single_branch,
     "its layers are ONE branch each (a recurrent mixer, attention or a "
     "dropless expert layer, whose experts may live in a latent and of which "
     "the chip may hold a share): recurrent state in some, K/V pages in one "
     "kind, neither in the expert layers"),
)


def _layer_kinds(cfg: ModelConfig) -> bool:
    return (bool(cfg.layer_types) or cfg.expert_share) and not cfg.mtp_layers


_NO_ROLLBACK = "a rejected draft cannot be rolled back out of the state"
_WALKS_ONCE = ("the final norm comes after every pass (cfg.loop_steps), and "
               "this path walks the layers once; use core.forward")

# ground -> ((feature, why), ...): within a ground, the first row among the
# features asked for is the refusal raised. A ``why`` may name
# {prefill_chunk} and {max_seq_len} (require's ``detail``).
REFUSED = {
    "recurrent_state": (
        ("prefix_cache", "a pinned block holds K/V only — the state at the "
         "prefix's end would have to be snapshotted"),
        ("spec_mesh_drafter", _NO_ROLLBACK),
        ("spec_model_drafter", _NO_ROLLBACK),
        ("spec_ngram", _NO_ROLLBACK),
        ("seq_attention", "the state is not sharded over a seq axis"),
        ("mesh_model", "the mixer's heads and state are not sharded over a "
         "model axis (--mesh-shape model:N)"),
        ("multi_lora", "the mixer's projections have no adapter path"),
        ("prefill_chunk", "a chunk of {prefill_chunk} does not divide "
         "max_seq_len {max_seq_len}, so the last window would re-feed tokens "
         "the state already absorbed"),
        ("pipeline_stages", "a stage's per-microbatch cache holds K/V only"),
        ("kv_export", "the state has no export format yet"),
    ),
    "latent_pool": (
        ("kv_int8", "the requantising page write keeps a scale a K/V head: a "
         "latent row has none"),
        ("spec_mesh_drafter", "the verify forward over latent rows is not "
         "tested"),
        ("spec_model_drafter", "the verify forward over latent rows is not "
         "tested (as the drafter: the drafter's rectangular cache holds K/V)"),
        ("spec_ngram", "the verify forward over latent rows is not tested"),
        ("seq_attention", "the sp partials read per-head K/V"),
        ("mesh_model", "the one latent row a token is read by every head: "
         "the read is not partitioned over a model axis (--mesh-shape "
         "model:N)"),
        ("mesh_expert", "the dropless expert layer's grouped product is not "
         "partitioned over an expert axis"),
        ("multi_lora", "the latent projections have no adapter path"),
        ("weight_int8", "the absorbed products read W_kvb unquantised"),
        ("prefix_cache", "a shared latent block under a resumed prefill is "
         "not tested"),
        ("pipeline_stages", "a stage's per-microbatch cache is rectangular "
         "K/V"),
    ),
    "dropless_routed": (
        ("kv_int8", "the int8 pool's per-layer slices under a window that "
         "binds are not tested"),
        ("weight_int8", "the grouped product reads the expert stacks "
         "unquantised"),
        ("spec_mesh_drafter", "the verify forward is not tested with it"),
        ("spec_model_drafter", "the verify forward is not tested with it (as "
         "the drafter: the drafter's loop is not tested with it)"),
        ("spec_ngram", "the verify forward is not tested with it"),
        ("seq_attention", "the sp partials know no window"),
        ("mesh_model", "the dropless expert layer's grouped product is not "
         "partitioned over a model axis (--mesh-shape model:N)"),
        ("mesh_expert", "the dropless expert layer's grouped product is not "
         "partitioned over an expert axis"),
        ("multi_lora", "adapters are not tested with it"),
        ("pipeline_stages", "a stage's loop reads a layer's experts sliced "
         "out of the stack and is not tested"),
    ),
    "looped_stack": (
        ("kv_int8", "the int8 pool's per-layer slices inside the pass loop "
         "are not tested"),
        ("weight_int8", "quantised weights read once a pass are not tested"),
        ("spec_mesh_drafter", "the verify forward is not tested with it"),
        ("spec_model_drafter", "the verify forward is not tested with it (as "
         "the drafter: the drafter builds a cut stack and runs it once)"),
        ("spec_ngram", "the verify forward is not tested with it"),
        ("seq_attention", "the sp path's cache is not indexed by pass"),
        ("mesh_model", "the pass loop around a sharded pool is not tested "
         "(--mesh-shape model:N)"),
        ("mesh_expert", "it has no experts to place on an expert axis"),
        ("multi_lora", "the adapter stacks are [n_layers, ...] and the pass "
         "loop does not hand them round again"),
        ("pipeline_stages", "a stage's layers would have to come round once "
         "a pass"),
        ("pipeline_stage_split", _WALKS_ONCE),
        ("pipeline_trunk", _WALKS_ONCE),
        ("ring_forward", _WALKS_ONCE),
    ),
    "layer_kinds": (
        ("prefix_cache", "a pinned block holds K/V only — the recurrent "
         "layers' state at the prefix's end would have to be snapshotted"),
        ("spec_mesh_drafter", _NO_ROLLBACK),
        ("spec_model_drafter", _NO_ROLLBACK),
        ("spec_ngram", _NO_ROLLBACK),
        ("seq_attention", "the state is not sharded over a seq axis"),
        ("mesh_model", "neither the mixer's heads and state nor the grouped "
         "product are partitioned over a model axis (--mesh-shape model:N)"),
        ("mesh_expert", "a share of the experts is a property of the "
         "configuration (n_experts_held): the exchange of the partial sums "
         "between the chips of a layer is not built, and the grouped product "
         "is not partitioned over an expert axis"),
        ("multi_lora", "neither the mixer's projections nor the expert "
         "layers have an adapter path"),
        ("prefill_chunk", "a chunk of {prefill_chunk} does not divide "
         "max_seq_len {max_seq_len}, so the last window would re-feed tokens "
         "the state already absorbed"),
        ("pipeline_stages", "a stage's per-microbatch cache holds K/V only, "
         "as deep as the stage's layers"),
        ("kv_export", "the state has no export format yet"),
        ("kv_int8", "the int8 pool's per-layer slices indexed by a layer's "
         "cache slot are not tested"),
        ("weight_int8", "the grouped product reads the expert stacks "
         "unquantised"),
        ("pipeline_stage_split", "a stage's walk reads every layer's mixer "
         "at the layer's own index, not its slot of its kind; use "
         "core.forward"),
        ("pipeline_trunk", "the trunk's walk reads every layer's mixer at "
         "the layer's own index, not its slot of its kind; use core.forward"),
        ("ring_forward", "the ring's walk reads every layer's mixer at the "
         "layer's own index, not its slot of its kind; use core.forward"),
    ),
    "mtp_layer": (
        ("prefix_cache", "a pinned block holds the MTP layer's K/V too, and "
         "its row at a prefix's last position is made with the token that "
         "FOLLOWS the prefix"),
        ("spec_mesh_drafter", "the verify forward with a drafter other than "
         "the model's own layer is not tested with it"),
        ("spec_model_drafter", "the verify forward with a drafter other than "
         "the model's own layer is not tested with it (as the drafter: the "
         "drafter's loop is not tested with it)"),
        ("spec_ngram", "the verify forward with a drafter other than the "
         "model's own layer is not tested with it"),
        ("kv_int8", "the int8 pool's per-layer slices under a window that "
         "binds and under the MTP layer's writes are not tested"),
        ("weight_int8", "the grouped product reads the expert stacks "
         "unquantised"),
        ("seq_attention", "the sp partials know no window"),
        ("mesh_model", "the dropless expert layer's grouped product is not "
         "partitioned over a model axis (--mesh-shape model:N)"),
        ("mesh_expert", "a share of the experts is a property of the "
         "configuration (n_experts_held): the exchange of the partial sums "
         "between the chips of a layer is not built, and the grouped product "
         "is not partitioned over an expert axis"),
        ("multi_lora", "adapters are not tested with it"),
        ("pipeline_stages", "a stage's loop reads a layer's experts sliced "
         "out of the stack, knows no MTP layer and is not tested"),
        ("kv_export", "a row's blocks hold the MTP layer's K/V, whose export "
         "and import are not tested"),
        ("pipeline_stage_split", "a stage's walk knows no MTP layer; use "
         "core.forward"),
        ("pipeline_trunk", "the trunk's walk knows no MTP layer; use "
         "core.forward"),
        ("ring_forward", "the ring's walk knows no MTP layer; use "
         "core.forward"),
    ),
    "single_branch": (
        # (the first two are properties of a published config.json, asked
        # about where it is read: config._nemotron_h_from_hf)
        ("mtp_module", "the published multi-token-prediction module (an "
         "attention layer and an expert layer, each of one branch, with "
         "weights shared across draft steps) is not built: core.mtp_forward "
         "runs ONE block of two branches and the engine's mtp tier drafts one "
         "token a step; a cut configuration leaves it out "
         "(num_nextn_predict_layers 0) and says so in `reduced`"),
        ("mlp_alone_layer", "a dense MLP-alone layer ('-' in "
         "hybrid_override_pattern) is a fourth kind of layer, which "
         "layer_types does not name"),
        ("prefix_cache", "a pinned block holds K/V only — the recurrent "
         "layers' state at the prefix's end would have to be snapshotted"),
        ("spec_mesh_drafter", _NO_ROLLBACK),
        ("spec_model_drafter", _NO_ROLLBACK),
        ("spec_ngram", _NO_ROLLBACK),
        ("seq_attention", "the state is not sharded over a seq axis"),
        ("mesh_model", "neither the mixer's heads and state nor the grouped "
         "product are partitioned over a model axis (--mesh-shape model:N)"),
        ("mesh_expert", "a share of the experts is a property of the "
         "configuration (n_experts_held): the exchange of the partial latent "
         "sums between the chips of a layer is not built, and the grouped "
         "product is not partitioned over an expert axis"),
        ("multi_lora", "neither the mixer's projections, the latent "
         "projections nor the expert layers have an adapter path"),
        ("prefill_chunk", "a chunk of {prefill_chunk} does not divide "
         "max_seq_len {max_seq_len}, so the last window would re-feed tokens "
         "the state already absorbed"),
        ("pipeline_stages", "a stage's per-microbatch cache holds K/V only, "
         "as deep as the stage's layers, and its loop knows no layer without "
         "attention"),
        ("kv_export", "the state has no export format yet"),
        ("kv_int8", "the int8 pool's per-layer slices indexed by a layer's "
         "cache slot are not tested"),
        ("weight_int8", "the grouped product reads the expert stacks "
         "unquantised, and the latent projections are not tested quantised"),
        ("pipeline_stage_split", "a stage's walk reads every layer's branch "
         "at the layer's own index, not its slot of its kind, and runs two "
         "branches a layer; use core.forward"),
        ("pipeline_trunk", "the trunk's walk reads every layer's branch at "
         "the layer's own index, not its slot of its kind, and runs two "
         "branches a layer; use core.forward"),
        ("ring_forward", "the ring's walk reads every layer's branch at the "
         "layer's own index, not its slot of its kind, and runs two branches "
         "a layer; use core.forward"),
    ),
}


def why(ground: str, feature: str) -> str:
    """The sentence of ``REFUSED``'s row (ground, feature): for a refusal
    raised where no ModelConfig exists yet (a converter of config.json)."""
    return dict(REFUSED[ground])[feature]


def require(cfg: ModelConfig, *features: str, **detail):
    """Raise FeatureUnsupported for the first of ``features`` that ``cfg``'s
    kind of model refuses — grounds in their order, a ground's rows in
    theirs — else return. ``detail`` fills the numbers a ``why`` names."""
    for name, has, ground in GROUNDS:
        if not has(cfg):
            continue
        for feature, why in REFUSED[name]:
            if feature in features:
                raise FeatureUnsupported(
                    feature, cfg.name, why.format(**detail), ground)
