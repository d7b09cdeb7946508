"""Model configurations: one dataclass drives the shared transformer core.

Preset registry covers the BASELINE.md measurement ladder (distilgpt2,
gemma-2b, llama-3-8b, zephyr-7b, mixtral-8x7b) plus tiny variants for tests.
HF checkpoint names map onto these presets by fuzzy match, mirroring the
reference's model-tag matching (reference services.py:136-151).
"""

from __future__ import annotations

import json
import logging
from dataclasses import dataclass, fields, replace
from pathlib import Path

logger = logging.getLogger("bee2bee_tpu.models.config")


@dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    max_seq_len: int = 2048
    # architecture switches
    pos_embedding: str = "rope"  # "rope" | "learned" | "alibi" (bloom:
    # linear attention-score bias per head, no embedding-side positions) |
    # "nope" (granite-4.0-h: no positional encoding anywhere)
    norm: str = "rmsnorm"  # "rmsnorm" | "layernorm"
    norm_bias: bool = True  # layernorm only: mpt ships weight-only norms
    activation: str = "silu"  # "silu" (gated) | "gelu" (tanh approx, gpt2/
    # phi) | "gelu_exact" (erf — gpt-neox) | "geglu" | "reglu" (gated by a
    # ReLU: smallthinker's sparse experts) | "relu2" (relu(x)^2, NO gate
    # matrix: nemotron-h's experts and shared expert)
    use_bias: bool = False  # attn/mlp biases (gpt2 style)
    qkv_bias: bool = False  # bias on q/k/v ONLY (qwen2 style; no bo/mlp bias)
    qk_norm: bool = False  # per-head RMSNorm on q and k before rope
    # (qwen3 style; learned [head_dim] scales)
    qk_norm_full: bool = False  # with qk_norm: normalize the WHOLE q/k
    # projection width instead of per head (olmo2: [H*hd]/[Hkv*hd] scales)
    tie_embeddings: bool = True
    rope_theta: float = 10000.0
    # frequency-domain RoPE scaling, encoded as a hashable tuple:
    #   ("linear", factor)  — position-interpolation fine-tunes
    #   ("llama3", factor, low_freq_factor, high_freq_factor,
    #    original_max_position_embeddings)  — llama-3.1+ checkpoints
    #   ("yarn", factor, attention_factor, beta_fast, beta_slow,
    #    original_max_position_embeddings, truncate)  — NTK-by-parts
    #    long-context fine-tunes (attention_factor resolved at parse time,
    #    incl. the deepseek mscale variants)
    rope_scaling: tuple | None = None
    norm_eps: float = 1e-5
    logits_softcap: float | None = None
    embedding_scale: bool = False  # gemma multiplies embeds by sqrt(d_model)
    norm_plus_one: bool = False  # gemma checkpoints store rmsnorm as (1 + w)
    # phi/gpt-neox-style switches
    rotary_pct: float = 1.0  # fraction of head_dim that rotates (phi-2: 0.4)
    rope_style: str = "half"  # "half": rotate (first, second) halves of the
    # rotary dims as a block (llama/neox/phi); "interleaved": rotate
    # adjacent pairs (x[2i], x[2i+1]) — gpt-j's rotate_every_two
    mlp_bias: bool = False  # biases on the MLP matmuls ONLY (gpt-j: fc_in/
    # fc_out carry biases while the attention projections have none)
    lm_head_bias: bool = False  # untied lm_head carries a bias (phi)
    # sliding-window attention (mistral): each query attends to at most
    # the last `sliding_window` positions. None = full causal. Supported
    # by the dense attention path (engine validates flash/sp against it).
    sliding_window: int | None = None
    # with sliding_window set: layers whose layer_idx % sliding_window_every
    # falls in sliding_window_residues window, the rest attend fully.
    # 1 = every layer (mistral); every=2/residues=(0,) = gemma-2's
    # alternation; every=6/residues=(0,1,2,3,4) = gemma-3's 5-local-1-global
    sliding_window_every: int = 1
    sliding_window_residues: tuple = (0,)
    # gemma-3: SLIDING layers rotate with this theta and NO rope_scaling;
    # global layers use rope_theta + rope_scaling. None = one rope for all
    local_rope_theta: float | None = None
    # smallthinker: ONLY the sliding layers rotate (rope_theta, no scaling);
    # the full layers carry no positional encoding at all (NoPE). The same
    # is_sliding_layer rule decides a layer's window and its rotation
    rope_sliding_only: bool = False
    # gemma-2 attention extras
    attn_logit_softcap: float | None = None  # tanh cap on attention scores
    attn_scale: float | None = None  # score denominator becomes
    # sqrt(attn_scale) instead of sqrt(head_dim) (query_pre_attn_scalar)
    post_norms: bool = False  # gemma-2: extra norms on the attn and mlp
    # OUTPUTS before they join the residual (4 norms per block)
    no_pre_norms: bool = False  # olmo2: NO ln1/ln2 pre-norms — the
    # post-output norms (post_norms must be set) are the only block norms
    parallel_block: bool = False  # x + attn(ln(x)) + mlp(ln'(x)) parallel
    # residual (phi/gpt-neox); sequential pre-norm blocks otherwise
    parallel_norms: int = 1  # parallel blocks only: 1 = attn and mlp share
    # ln1 (phi); 2 = mlp gets its own ln2 (gpt-neox use_parallel_residual)
    # MoE
    n_experts: int = 0  # 0 = dense
    n_experts_per_tok: int = 2
    # "dense": all experts on all tokens, weight-masked — the exact
    # reference formulation (correctness baseline, 4x routed FLOPs at
    # top-2-of-8). "routed": GShard-style capacity-grouped dispatch; only
    # routed tokens hit each expert, tokens past capacity drop.
    moe_impl: str = "dense"  # "dense" | "routed"
    moe_capacity_factor: float = 1.25  # routed: C = ceil(g*k/E * factor)
    # routed dispatch runs per GROUP of this many tokens (GShard grouping):
    # capacity — and so the [*, g, E, C] dispatch tensor — stays O(group
    # size), not O(batch*seq). Groups route independently.
    moe_group_size: int = 512

    # bloom: LayerNorm over the embeddings before block 0
    embedding_norm: bool = False

    # falcon-h1: a Mamba-2 (SSD) mixer IN PARALLEL with attention in every
    # block — both read ln1's output and their outputs are summed before
    # the one residual add. ssm_heads == 0 means "no mixer". Sizes under
    # the names of the published config.json: mamba_n_heads, mamba_d_head,
    # mamba_d_state, mamba_n_groups (B and C are shared by the heads of a
    # group), mamba_d_conv (causal depthwise conv width), mamba_chunk_size
    # (the chunked scan's block; prefill only — decode is the recurrence)
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 128
    # falcon-h1's muP multipliers (all 1.0 = inert): embeddings, logits,
    # the attention branch's input/output, k before the rotation, the
    # mixer's input/output, (gate pre-activation, down output) of the MLP
    # and the five zones (z, x, B, C, dt) of the mixer's in-projection
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    mlp_multipliers: tuple = (1.0, 1.0)
    ssm_multipliers: tuple = (1.0, 1.0, 1.0, 1.0, 1.0)

    # JoyAI-LLM-Flash / DeepSeek-V3 style latent attention (MLA), under the
    # published config.json names: q_lora_rank, kv_lora_rank,
    # qk_nope_head_dim, qk_rope_head_dim, v_head_dim. mla_kv_rank == 0
    # means "plain attention". A token caches ONE row of mla_kv_rank +
    # mla_rope_dim numbers a layer (the normed c_kv and the rotated k_rope
    # shared by every head): no per-head K, no V (core._mla_attention)
    mla_q_rank: int = 0
    mla_kv_rank: int = 0
    mla_nope_dim: int = 0
    mla_rope_dim: int = 0
    mla_v_dim: int = 0
    # the same family's expert layer: moe_router "sigmoid" scores each
    # expert with sigmoid(x W_r), SELECTS the top k of score + a learned
    # per-expert bias (e_score_correction_bias), weighs them by the score
    # WITHOUT the bias, normalised over the k and times moe_scale
    # (routed_scaling_factor); n_shared_experts always-on experts of the
    # routed width join the sum; d_ff_expert is the experts' width apart
    # from the dense layers' d_ff (0 = d_ff); the first first_k_dense layers
    # are dense MLPs (first_k_dense_replace). A sigmoid router always takes
    # the DROPLESS expert layer (core._moe_dropless): moe_impl is not
    # consulted for it. "softmax_topk" (smallthinker) takes the same
    # dropless layer with no bias: the top k of the LOGITS x W_r, weighed by
    # a softmax over those k alone (= softmax over all, then renormalised)
    moe_router: str = "softmax"  # "softmax" | "sigmoid" | "softmax_topk"
    moe_scale: float = 1.0
    n_shared_experts: int = 0
    d_ff_expert: int = 0
    first_k_dense: int = 0
    # smallthinker: the router reads the block's PRE-ATTENTION norm output
    # ("attn_norm": the router sits before attention), not the pre-FFN one
    # the experts read ("ffn_norm"). Dropless expert layers only
    moe_router_input: str = "ffn_norm"  # "ffn_norm" | "attn_norm"
    # ouro (looped language models): the WHOLE stack of n_layers runs
    # loop_steps times a token with the same weights (the published
    # total_ut_steps), the model's one final norm after every pass, and
    # every pass keeps K/V of its own: cache_layers below is what sizes a
    # cache, n_layers stays the count of WEIGHT layers. 1 = a plain stack
    loop_steps: int = 1
    # granite-4.0-h (granitemoehybrid): a layer's token mixer is ONE of a
    # recurrent mixer or attention, by config.json's ``layer_types`` ("mamba"
    # / "attention", one entry a layer). () = every layer alike, and ssm_heads
    # then means a mixer BESIDE attention in every block (falcon-h1). The
    # state is as deep as the recurrent layers (state_layers), every cache as
    # deep as the attention layers (cache_layers); a layer finds its own by
    # state_slots / cache_slots, and n_layers sizes neither
    layer_types: tuple = ()
    # the chip's share of every layer's routed experts (a dropless expert
    # layer only): it holds n_experts_held of them from expert_first on
    # (0 = all). The router keeps n_experts outputs and n_experts_per_tok
    # choices; an assignment to an expert held elsewhere is computed
    # elsewhere: no product here, weight zero, counted apart. Nothing stands
    # in for the absent chips or their exchange
    n_experts_held: int = 0
    expert_first: int = 0
    # the shared expert's own width (granite's shared_intermediate_size);
    # 0 = n_shared_experts experts of the routed width
    d_ff_shared: int = 0
    # granite: BOTH residual adds of a block take their branch times this
    residual_multiplier: float = 1.0
    # K-EXAONE (exaone_moe): a sigmoid router with NO selection bias (the
    # top k of the scores themselves; seeded weights are evened by
    # core.center_router, a rule on the weights, as a softmax-top-k router's).
    # True = joyai's noaux_tc bias (``router_bias``)
    moe_select_bias: bool = True
    # multi-token-prediction layers behind the trunk (the published
    # num_nextn_predict_layers; 0 = none, 1 = what is built): ``u = W_eh
    # [RMS_e(Emb(x_{t+1})); RMS_h(h_t)]``, one block of the trunk's kind
    # (full attention; NoPE where the full layers carry none; an expert layer
    # where the trunk has them) over a cache layer of its OWN behind the
    # trunk's (cache_layers counts it), then the trunk's final norm and head:
    # the model's own drafter (core.mtp_forward, the engine's ``mtp`` tier)
    mtp_layers: int = 0
    # a chip that holds a SLICE of the vocabulary: vocab_size is then the
    # rows HELD here (ids 0 .. vocab_size - 1 of the embedding and the head:
    # logits and sampling are over the slice) and this the published size
    # (0 = vocab_size is the whole vocabulary)
    vocab_published: int = 0
    # nemotron-h: ``layer_types`` may name a THIRD kind, "moe": a layer is
    # then ONE branch under ONE norm, ``x + branch(norm(x))`` with the branch
    # a mixer, attention OR an expert layer (single_branch); an "moe" layer
    # owns neither state nor cache, and the expert stacks are as deep as the
    # "moe" layers (moe_slots). moe_latent (the published moe_latent_size; 0 =
    # the experts read the model's width): the routed experts live in a
    # latent of this width, ``z = h W_in`` before the dispatch and ``W_out``
    # once on the weighted sum; router and shared expert read the full width
    moe_latent: int = 0

    def __post_init__(self):
        # json lists (the native-checkpoint model_config.json round-trip)
        # back to hashable tuples: cfg is a static jit argument
        object.__setattr__(self, "mlp_multipliers", tuple(self.mlp_multipliers))
        object.__setattr__(self, "ssm_multipliers", tuple(self.ssm_multipliers))
        if len(self.mlp_multipliers) != 2 or len(self.ssm_multipliers) != 5:
            raise ValueError(
                f"mlp_multipliers needs 2 entries and ssm_multipliers 5, got "
                f"{self.mlp_multipliers!r} / {self.ssm_multipliers!r}"
            )
        if self.ssm_heads:
            if min(self.ssm_head_dim, self.ssm_state, self.ssm_conv,
                   self.ssm_chunk) < 1 or self.ssm_heads % self.ssm_groups:
                raise ValueError(
                    f"ssm mixer needs positive head/state/conv/chunk sizes "
                    f"and ssm_groups dividing ssm_heads, got heads="
                    f"{self.ssm_heads} head_dim={self.ssm_head_dim} state="
                    f"{self.ssm_state} groups={self.ssm_groups} conv="
                    f"{self.ssm_conv} chunk={self.ssm_chunk}"
                )
        if self.sliding_window_residues != (0,):
            object.__setattr__(self, "sliding_window_residues",
                               tuple(self.sliding_window_residues))
        if self.rope_scaling is not None:
            # normalize a json list back to the hashable tuple form (the
            # native-checkpoint model_config.json round-trip)
            object.__setattr__(self, "rope_scaling", tuple(self.rope_scaling))
            kind = self.rope_scaling[0]
            want = {"linear": 2, "llama3": 5, "yarn": 7}.get(kind)
            if want is None or len(self.rope_scaling) != want:
                raise ValueError(
                    f"rope_scaling={self.rope_scaling!r}: expected "
                    f"('linear', factor), ('llama3', factor, low_freq, "
                    f"high_freq, original_max_pos), or ('yarn', factor, "
                    f"attention_factor, beta_fast, beta_slow, "
                    f"original_max_pos, truncate)"
                )
        if self.no_pre_norms and not self.post_norms:
            raise ValueError(
                "no_pre_norms requires post_norms — the block would have "
                "ZERO normalization otherwise (olmo2 sets both)"
            )
        if self.pos_embedding not in ("rope", "learned", "alibi", "nope"):
            raise ValueError(
                f"pos_embedding={self.pos_embedding!r} must be 'rope', "
                f"'learned', 'alibi' or 'nope'"
            )
        if self.rope_style not in ("half", "interleaved"):
            # a typo here would silently rotate the wrong way (core._rope
            # has no else-error) — fail like moe_impl does
            raise ValueError(
                f"rope_style={self.rope_style!r} must be 'half' or 'interleaved'"
            )
        if self.moe_impl not in ("dense", "routed"):
            raise ValueError(
                f"moe_impl={self.moe_impl!r} must be 'dense' or 'routed'"
            )
        if self.moe_group_size < 1:
            raise ValueError(f"moe_group_size={self.moe_group_size} must be >= 1")
        if self.moe_router not in ("softmax", "sigmoid", "softmax_topk"):
            raise ValueError(
                f"moe_router={self.moe_router!r} must be 'softmax', 'sigmoid' "
                "or 'softmax_topk'"
            )
        if self.moe_router_input not in ("ffn_norm", "attn_norm") or (
            self.moe_router_input == "attn_norm"
            and (self.moe_router == "softmax" or self.no_pre_norms
                 or self.parallel_block)
        ):
            raise ValueError(
                f"moe_router_input={self.moe_router_input!r} must be "
                "'ffn_norm', or 'attn_norm' on a dropless expert layer "
                "(moe_router 'sigmoid' / 'softmax_topk') of a sequential "
                "pre-norm block"
            )
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if self.layer_types:
            kinds = set(self.layer_types)
            if (len(self.layer_types) != self.n_layers
                    or not {"mamba", "attention"} <= kinds
                    or not kinds <= {"mamba", "attention", "moe"}
                    or not self.ssm_heads):
                raise ValueError(
                    f"layer_types={self.layer_types!r} must name each of the "
                    f"{self.n_layers} layers 'mamba' or 'attention' (or, a "
                    "layer of one branch, 'moe'), hold at least one of each "
                    "of the two (the state and the pool are as deep as their "
                    "kinds) and come with the mixer's sizes (ssm_heads)"
                )
            if (self.loop_steps > 1 or self.mla_kv_rank or self.first_k_dense
                    or self.parallel_block or self.no_pre_norms
                    or self.sliding_window):
                raise ValueError(
                    "layer_types (one mixer kind a layer) is built for "
                    "sequential pre-norm blocks with plain full attention: no "
                    "looped stack, latent attention, leading dense layers, "
                    "parallel block or sliding window"
                )
            if "moe" in kinds and (
                    self.moe_router == "softmax" or not self.n_experts
                    or self.post_norms or self.moe_router_input != "ffn_norm"
                    or self.residual_multiplier != 1.0):
                raise ValueError(
                    f"layer_types={self.layer_types!r} names 'moe' layers (a "
                    "layer of ONE branch): they are dropless expert layers "
                    "(moe_router 'sigmoid' / 'softmax_topk', n_experts) fed "
                    "their own norm, with no post norm and no residual "
                    "multiplier"
                )
        if self.moe_latent and (self.moe_latent < 0 or not self.moe_dropless):
            raise ValueError(
                f"moe_latent={self.moe_latent} is the width a dropless expert "
                "layer's routed experts live in (moe_router 'sigmoid' / "
                "'softmax_topk')")
        if self.n_experts_held or self.expert_first:
            held = self.n_experts_held or self.n_experts
            if (self.moe_router == "softmax" or held < 1
                    or self.expert_first < 0
                    or self.expert_first + held > self.n_experts):
                raise ValueError(
                    f"n_experts_held={self.n_experts_held} from expert_first="
                    f"{self.expert_first} must be a range of the "
                    f"{self.n_experts} experts of a dropless expert layer "
                    "(moe_router 'sigmoid' / 'softmax_topk')"
                )
        if not self.moe_select_bias and self.moe_router != "sigmoid":
            raise ValueError(
                "moe_select_bias=False is a sigmoid router's switch (the "
                f"other routers have no selection bias), got moe_router="
                f"{self.moe_router!r}")
        if self.mtp_layers not in (0, 1) or (self.mtp_layers and (
                self.has_ssm or self.mla_kv_rank or self.loop_steps > 1
                or self.layer_types or self.parallel_block
                or self.pos_embedding not in ("rope", "nope"))):
            raise ValueError(
                f"mtp_layers={self.mtp_layers} must be 0 or 1, and a "
                "multi-token-prediction layer is built for a plain stack of "
                "attention layers with K/V pages: no recurrent mixer, latent "
                "attention, looped stack, layer_types or parallel block")
        if self.vocab_published and self.vocab_published < self.vocab_size:
            raise ValueError(
                f"vocab_published={self.vocab_published} is the WHOLE "
                f"vocabulary of which vocab_size={self.vocab_size} rows are "
                "held here")
        if self.d_ff_shared and not self.n_shared_experts:
            raise ValueError(
                f"d_ff_shared={self.d_ff_shared} needs a shared expert "
                "(n_shared_experts)")
        if self.rope_sliding_only and not (
            self.sliding_window and self.sliding_window_every > 1
            and self.pos_embedding == "rope"
            and self.local_rope_theta is None
        ):
            raise ValueError(
                "rope_sliding_only needs a rope model whose layers alternate "
                "(sliding_window with sliding_window_every > 1) and one theta"
            )
        if self.mla_kv_rank and min(self.mla_q_rank, self.mla_nope_dim,
                                    self.mla_rope_dim, self.mla_v_dim) < 1:
            raise ValueError(
                "latent attention needs positive mla_q_rank / mla_nope_dim / "
                f"mla_rope_dim / mla_v_dim beside mla_kv_rank, got "
                f"{self.mla_q_rank}/{self.mla_nope_dim}/{self.mla_rope_dim}/"
                f"{self.mla_v_dim}"
            )
        if self.mla_kv_rank and self.mla_rope_dim % 2:
            raise ValueError(f"mla_rope_dim={self.mla_rope_dim} must be even")
        if self.loop_steps < 1 or (self.loop_steps > 1 and (
                self.ssm_heads or self.mla_kv_rank or self.n_experts
                or self.sliding_window)):
            raise ValueError(
                f"loop_steps={self.loop_steps} must be >= 1, and a looped "
                "stack (loop_steps > 1) is built for dense full-attention "
                "blocks only: no recurrent mixer, latent attention, experts "
                "or sliding window (core.forward's pass loop indexes a "
                "plain K/V cache by pass and layer)"
            )
        if not 0 <= self.first_k_dense <= self.n_layers or (
            (self.first_k_dense or self.n_shared_experts or self.d_ff_expert)
            and not self.n_experts
        ):
            raise ValueError(
                f"first_k_dense={self.first_k_dense} / n_shared_experts="
                f"{self.n_shared_experts} / d_ff_expert={self.d_ff_expert} "
                f"need an expert model of at least first_k_dense layers "
                f"(n_experts={self.n_experts}, n_layers={self.n_layers})"
            )

    # families where attention width != d_model (gemma-7b: 16 heads of 256
    # over d_model 3072) set this; None derives d_model // n_heads
    head_dim_override: int | None = None

    @property
    def head_dim(self) -> int:
        if self.head_dim_override is not None:
            return self.head_dim_override
        return self.d_model // self.n_heads

    @property
    def rotary_dim(self) -> int:
        """Head dims that actually rotate: floor-to-even rotary_pct *
        head_dim (HF's int() truncation) — THE one formula core._rope and
        the exporters share."""
        if self.rotary_pct >= 1.0:
            return self.head_dim
        return max(2, int(self.head_dim * self.rotary_pct) // 2 * 2)

    @property
    def cache_layers(self) -> int:
        """Layers of CACHE a token holds: one a (pass, weight layer) of a
        looped stack, pass ``t``'s layer ``l`` at index ``t * n_layers + l``
        (core.forward). THE one number that sizes a pool, a rectangular
        cache, a block's bytes and a cached token's attention work; equal to
        n_layers for every plain stack. Under ``layer_types`` only the
        attention layers cache anything (cache_slots)."""
        if self.layer_types:
            return self.layer_types.count("attention")
        # (a multi-token-prediction block's K/V: the layer behind the trunk's)
        return self.n_layers * self.loop_steps + self.mtp_layers

    @property
    def state_layers(self) -> int:
        """Layers of recurrent STATE a row holds (core.init_ssm_state's
        leading axis): every layer where the mixer runs beside attention in
        every block, the "mamba" layers under ``layer_types``, 0 without a
        mixer."""
        if not self.ssm_heads:
            return 0
        return (self.layer_types.count("mamba") if self.layer_types
                else self.n_layers)

    def _slots(self, kind: str) -> tuple:
        n, out = 0, []
        for t in self.layer_types:
            out.append(n if t == kind else -1)
            n += t == kind
        return tuple(out)

    @property
    def state_slots(self) -> tuple:
        """Layer -> its slot of the state (-1: the layer has no mixer). The
        identity where every layer has one."""
        if self.layer_types:
            return self._slots("mamba")
        return tuple(range(self.n_layers)) if self.ssm_heads else ()

    @property
    def cache_slots(self) -> tuple:
        """Layer -> its layer of a cache (-1: the layer has no attention).
        The identity for a plain stack (a looped stack adds the pass's base:
        core.forward)."""
        if self.layer_types:
            return self._slots("attention")
        return tuple(range(self.n_layers))

    @property
    def single_branch(self) -> bool:
        """A layer is ONE branch under ONE norm (nemotron-h): a mixer,
        attention OR an expert layer, by ``layer_types``' three kinds."""
        return "moe" in self.layer_types

    @property
    def moe_slots(self) -> tuple:
        """Layer -> its slot of the expert stacks (-1: the layer has no
        expert layer), where the expert layers are a KIND of layer
        (single_branch); elsewhere an expert layer's slot is its place behind
        the leading dense layers."""
        return self._slots("moe")

    @property
    def kind_slots(self) -> dict:
        """Layer kind -> (layer -> its slot among the layers of that kind)."""
        return {"mamba": self.state_slots, "attention": self.cache_slots,
                "moe": self.moe_slots}

    @property
    def layer_runs(self) -> tuple:
        """``layer_types`` as RUNS of like layers, in order: (kind, first
        layer, count, the first layer's slot of its kind); a pattern need not
        be periodic. What core.forward scans is layer_units."""
        runs, slots = [], self.kind_slots
        for i, t in enumerate(self.layer_types):
            if runs and runs[-1][0] == t:
                runs[-1][2] += 1
            else:
                runs.append([t, i, 1, slots[t][i]])
        return tuple(tuple(r) for r in runs)

    @property
    def layer_units(self) -> tuple:
        """``layer_types`` as runs of a repeated UNIT (a short tuple of
        kinds), in order: (unit, first layer, repeats). core.forward scans a
        run at a time with the whole unit as the scan's body, so a pattern
        that ALTERNATES (nemotron-h's ``E M E M E M E M E M *``: ("moe",
        "mamba") x 5, ("attention",) x 1) is two bodies and not eleven. At
        each layer the unit that covers the most layers with at least two
        repeats is taken, the shortest among equals, else the layer's own
        kind: runs of like layers (granite's ``m m m m m a m m m m``) come out
        as layer_runs has them, a unit of one kind. A unit is at most four
        layers: a longer body (granite's period of ten, four times over) is no
        shorter a program than its runs."""
        types, units, i = self.layer_types, [], 0
        while i < len(types):
            best, covered = 1, 0
            for p in range(1, min(4, (len(types) - i) // 2) + 1):
                r = 1
                while types[i + r * p:i + (r + 1) * p] == types[i:i + p]:
                    r += 1
                if r >= 2 and p * r > covered:
                    best, covered = p, p * r
            # (a run of like layers is the unit of one that covers it whole:
            # no longer unit of one kind covers more, and ties go to the shorter)
            r = max(covered // best, 1)
            units.append((types[i:i + best], i, r))
            i += best * r
        return tuple(units)

    @property
    def layer_windows(self) -> tuple:
        """Every layer's sliding window on host integers (0 = it attends
        fully): core.is_sliding_layer's rule, for what is counted per layer
        KIND (the ragged read's tiles, the tokens behind a window)."""
        w = int(self.sliding_window or 0)
        if self.layer_types:  # one entry a CACHE layer: the attention layers'
            return (0,) * self.cache_layers
        return tuple(
            w if i % self.sliding_window_every in self.sliding_window_residues
            else 0 for i in range(self.n_layers)) * self.loop_steps + (
                (0,) * self.mtp_layers)  # an MTP block attends fully

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def has_mla(self) -> bool:
        """Latent attention: a token caches one [c_kv | k_rope] row a layer
        (core.init_paged_pool's ``latent`` leaf) instead of per-head K/V."""
        return self.mla_kv_rank > 0

    @property
    def latent_width(self) -> int:
        """Numbers a token caches a layer under latent attention."""
        return self.mla_kv_rank + self.mla_rope_dim

    @property
    def moe_dropless(self) -> bool:
        """The sigmoid- and softmax-top-k-routed expert layers compute every
        chosen assignment (core._moe_dropless); the softmax presets keep
        moe_impl's two."""
        return self.n_experts > 0 and self.moe_router != "softmax"

    @property
    def gated_mlp(self) -> bool:
        """The MLP / an expert has a gate matrix beside up and down."""
        return self.activation in ("silu", "geglu", "reglu")  # not "relu2"

    @property
    def expert_ff(self) -> int:
        return self.d_ff_expert or self.d_ff

    @property
    def n_expert_layers(self) -> int:
        if self.single_branch:  # the "moe" layers alone
            return self.layer_types.count("moe")
        return self.n_layers - self.first_k_dense if self.n_experts else 0

    @property
    def expert_in(self) -> int:
        """The width a routed expert reads and writes: the latent's
        (moe_latent) or the model's."""
        return self.moe_latent or self.d_model

    @property
    def n_expert_calls(self) -> int:
        """Expert-layer calls of ONE forward that runs everything the model
        has: the trunk's expert layers and an MTP block's."""
        return self.n_expert_layers + (self.mtp_layers if self.n_experts else 0)

    @property
    def has_ssm(self) -> bool:
        """A recurrent mixer runs in some or all layers (beside attention in
        every block, or INSTEAD of it in the "mamba" layers of
        ``layer_types``): each row then owns a slot of recurrent state,
        state_layers deep, beside its K/V pages (core.init_ssm_state)."""
        return self.ssm_heads > 0

    @property
    def experts_held(self) -> int:
        """Routed experts a layer holds HERE (all of them without a share)."""
        return self.n_experts_held or self.n_experts

    @property
    def expert_share(self) -> bool:
        """Does this chip hold only a share of every layer's experts?"""
        return 0 < self.experts_held < self.n_experts

    @property
    def shared_ff(self) -> int:
        """The shared expert's width (0 = none)."""
        return self.d_ff_shared or self.n_shared_experts * self.expert_ff

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Channels through the causal conv: x, then B and C of every group."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def ssm_proj_dim(self) -> int:
        """The in-projection's width: gate z, [x; B; C], dt (one a head)."""
        return self.ssm_inner + self.ssm_conv_dim + self.ssm_heads


def _gpt2(name, d_model, n_layers, n_heads, d_ff=None, vocab=50257, max_pos=1024):
    return ModelConfig(
        name=name,
        vocab_size=vocab,
        d_model=d_model,
        n_layers=n_layers,
        n_heads=n_heads,
        n_kv_heads=n_heads,
        d_ff=d_ff or 4 * d_model,
        max_seq_len=max_pos,
        pos_embedding="learned",
        norm="layernorm",
        activation="gelu",
        use_bias=True,
        tie_embeddings=True,
    )


CONFIGS: dict[str, ModelConfig] = {
    # -- test-sized --
    "tiny-gpt2": _gpt2("tiny-gpt2", d_model=64, n_layers=2, n_heads=4, vocab=512, max_pos=256),
    "tiny-llama": ModelConfig(
        name="tiny-llama", vocab_size=512, d_model=64, n_layers=2, n_heads=4,
        n_kv_heads=2, d_ff=128, max_seq_len=256,
    ),
    "tiny-llama-4l": ModelConfig(  # 4 layers: pipeline splits deeper than
        # 2 stages (layer_ranges caps n_stages at n_layers) — the
        # pipeline_interleave bench/test topology at 4 stages
        name="tiny-llama-4l", vocab_size=512, d_model=64, n_layers=4,
        n_heads=4, n_kv_heads=2, d_ff=128, max_seq_len=256,
    ),
    "tiny-mixtral": ModelConfig(
        name="tiny-mixtral", vocab_size=512, d_model=64, n_layers=2, n_heads=4,
        n_kv_heads=2, d_ff=128, max_seq_len=256, n_experts=4, n_experts_per_tok=2,
    ),
    "tiny-gemma": ModelConfig(  # MQA (one kv head): the KV-replication path
        name="tiny-gemma", vocab_size=512, d_model=64, n_layers=2, n_heads=4,
        n_kv_heads=1, d_ff=128, max_seq_len=256, activation="geglu",
        embedding_scale=True, norm_plus_one=True, norm_eps=1e-6,
    ),
    "tiny-qwen3": ModelConfig(  # llama arch + per-head q/k RMSNorm
        name="tiny-qwen3", vocab_size=512, d_model=64, n_layers=2, n_heads=4,
        n_kv_heads=2, d_ff=128, max_seq_len=256, qk_norm=True,
        rope_theta=1000000.0, norm_eps=1e-6, tie_embeddings=False,
    ),
    "tiny-mistral": ModelConfig(  # llama arch + sliding-window attention,
        # window deliberately smaller than the test prompts so the windowed
        # mask is actually exercised against HF's implementation
        name="tiny-mistral", vocab_size=512, d_model=64, n_layers=2, n_heads=4,
        n_kv_heads=2, d_ff=128, max_seq_len=256, sliding_window=4,
    ),
    "tiny-gemma2": ModelConfig(  # gemma-2: post-norms, attn softcap,
        # query scale override, ALTERNATING local/global attention
        # (window 4 < the 8-token test prompts, every 2nd layer)
        name="tiny-gemma2", vocab_size=512, d_model=64, n_layers=2,
        n_heads=4, n_kv_heads=2, d_ff=128, max_seq_len=256,
        activation="geglu", embedding_scale=True, norm_plus_one=True,
        norm_eps=1e-6, post_norms=True, attn_logit_softcap=50.0,
        logits_softcap=30.0, attn_scale=32.0, sliding_window=4,
        sliding_window_every=2,
    ),
    "tiny-gemma3": ModelConfig(  # gemma-3: gemma-2 post-norms + (1+w)
        # per-head qk-norm + DUAL rope (local 10k on sliding layers,
        # global theta + linear scaling on the rest) + 2-local-1-global
        # pattern (period 3 keeps a 3-layer tiny model exercising both)
        name="tiny-gemma3", vocab_size=512, d_model=64, n_layers=3,
        n_heads=4, n_kv_heads=2, d_ff=128, max_seq_len=256,
        activation="geglu", embedding_scale=True, norm_plus_one=True,
        norm_eps=1e-6, post_norms=True, qk_norm=True, attn_scale=32.0,
        rope_theta=1000000.0, local_rope_theta=10000.0,
        rope_scaling=("linear", 8.0), sliding_window=4,
        sliding_window_every=3, sliding_window_residues=(0, 1),
    ),
    "tiny-qwen": ModelConfig(  # qwen2 style: llama arch + q/k/v-only bias
        name="tiny-qwen", vocab_size=512, d_model=64, n_layers=2, n_heads=4,
        n_kv_heads=2, d_ff=128, max_seq_len=256, qkv_bias=True,
        rope_theta=1000000.0,
    ),
    # gpt-bigcode / starcoder style: gpt2 block + MQA, tanh gelu
    "tiny-bigcode": ModelConfig(
        name="tiny-bigcode", vocab_size=512, d_model=64, n_layers=2,
        n_heads=4, n_kv_heads=1, d_ff=128, max_seq_len=256,
        pos_embedding="learned", norm="layernorm", activation="gelu",
        use_bias=True, tie_embeddings=True,
    ),
    "starcoder-15b": ModelConfig(
        # bigcode/starcoderbase: 48 128-dim heads with ONE kv head over a
        # gpt2-style learned-position block, 8k context
        name="starcoder-15b", vocab_size=49152, d_model=6144, n_layers=40,
        n_heads=48, n_kv_heads=1, d_ff=24576, max_seq_len=8192,
        pos_embedding="learned", norm="layernorm", activation="gelu",
        use_bias=True, tie_embeddings=True,
    ),
    # -- BASELINE ladder --
    "distilgpt2": _gpt2("distilgpt2", d_model=768, n_layers=6, n_heads=12),
    "gpt2": _gpt2("gpt2", d_model=768, n_layers=12, n_heads=12),
    "gemma-2b": ModelConfig(
        # head_dim = 2048/8 = 256, matching gemma's 256-dim heads
        name="gemma-2b", vocab_size=256000, d_model=2048, n_layers=18, n_heads=8,
        n_kv_heads=1, d_ff=16384, max_seq_len=8192, activation="geglu",
        embedding_scale=True, norm_eps=1e-6, norm_plus_one=True,
    ),
    "llama-3-8b": ModelConfig(
        name="llama-3-8b", vocab_size=128256, d_model=4096, n_layers=32, n_heads=32,
        n_kv_heads=8, d_ff=14336, max_seq_len=8192, rope_theta=500000.0,
        tie_embeddings=False,
    ),
    "zephyr-7b": ModelConfig(  # mistral-7b architecture (HuggingFaceH4/zephyr-7b-beta)
        name="zephyr-7b", vocab_size=32000, d_model=4096, n_layers=32, n_heads=32,
        sliding_window=4096,
        n_kv_heads=8, d_ff=14336, max_seq_len=4096, tie_embeddings=False,
    ),
    "mixtral-8x7b": ModelConfig(
        name="mixtral-8x7b", vocab_size=32000, d_model=4096, n_layers=32, n_heads=32,
        n_kv_heads=8, d_ff=14336, max_seq_len=8192, tie_embeddings=False,
        n_experts=8, n_experts_per_tok=2,
    ),
    # -- qwen2 family (llama arch + q/k/v bias, 1e6 rope theta) --
    "qwen2-0.5b": ModelConfig(
        name="qwen2-0.5b", vocab_size=151936, d_model=896, n_layers=24,
        n_heads=14, n_kv_heads=2, d_ff=4864, max_seq_len=32768,
        qkv_bias=True, rope_theta=1000000.0, norm_eps=1e-6,
    ),
    "qwen2-7b": ModelConfig(
        name="qwen2-7b", vocab_size=152064, d_model=3584, n_layers=28,
        n_heads=28, n_kv_heads=4, d_ff=18944, max_seq_len=32768,
        qkv_bias=True, rope_theta=1000000.0, norm_eps=1e-6,
        tie_embeddings=False,
    ),
    # -- qwen3 family (llama arch + per-head q/k RMSNorm, no qkv bias) --
    "qwen3-8b": ModelConfig(
        name="qwen3-8b", vocab_size=151936, d_model=4096, n_layers=36,
        n_heads=32, n_kv_heads=8, d_ff=12288, max_seq_len=40960,
        qk_norm=True, rope_theta=1000000.0, norm_eps=1e-6,
        tie_embeddings=False,
    ),
    "tiny-qwen3moe": ModelConfig(  # qwen3 qk-norm + qwen3_moe expert names
        name="tiny-qwen3moe", vocab_size=512, d_model=64, n_layers=2,
        n_heads=4, n_kv_heads=2, d_ff=32, max_seq_len=256, qk_norm=True,
        rope_theta=1000000.0, norm_eps=1e-6, tie_embeddings=False,
        n_experts=4, n_experts_per_tok=2,
    ),
    "qwen3-30b-a3b": ModelConfig(
        # Qwen/Qwen3-30B-A3B: 128 experts, 8 active, 768-wide experts,
        # per-head qk-norm, head_dim 128 over d_model 2048
        name="qwen3-30b-a3b", vocab_size=151936, d_model=2048, n_layers=48,
        n_heads=32, n_kv_heads=4, d_ff=768, max_seq_len=40960,
        qk_norm=True, rope_theta=1000000.0, norm_eps=1e-6,
        tie_embeddings=False, head_dim_override=128,
        n_experts=128, n_experts_per_tok=8,
    ),
    # -- larger members of the already-supported families --
    "gemma-2-9b": ModelConfig(
        # google/gemma-2-9b: 16 256-dim heads over d_model 3584 (override),
        # alternating 4096-window/global layers, softcapped scores+logits
        name="gemma-2-9b", vocab_size=256000, d_model=3584, n_layers=42,
        n_heads=16, n_kv_heads=8, d_ff=14336, max_seq_len=8192,
        activation="geglu", embedding_scale=True, norm_plus_one=True,
        norm_eps=1e-6, head_dim_override=256, post_norms=True,
        attn_logit_softcap=50.0, logits_softcap=30.0, attn_scale=256.0,
        sliding_window=4096, sliding_window_every=2,
    ),
    "gemma-3-4b": ModelConfig(
        # google/gemma-3-4b (text config): 8 256-dim heads over d_model
        # 2304, 5-local-1-global 1024-token windows, dual rope (local 10k;
        # global 1M with linear-8 scaling), 128k context
        name="gemma-3-4b", vocab_size=262208, d_model=2304, n_layers=34,
        n_heads=8, n_kv_heads=4, d_ff=9216, max_seq_len=131072,
        activation="geglu", embedding_scale=True, norm_plus_one=True,
        norm_eps=1e-6, head_dim_override=256, post_norms=True,
        qk_norm=True, attn_scale=256.0, rope_theta=1000000.0,
        local_rope_theta=10000.0, rope_scaling=("linear", 8.0),
        sliding_window=1024, sliding_window_every=6,
        sliding_window_residues=(0, 1, 2, 3, 4),
    ),
    "gemma-7b": ModelConfig(
        # attention width 4096 != d_model 3072: heads are 256-dim like
        # gemma-2b's, hence the explicit head_dim_override
        name="gemma-7b", vocab_size=256000, d_model=3072, n_layers=28, n_heads=16,
        n_kv_heads=16, d_ff=24576, max_seq_len=8192, activation="geglu",
        embedding_scale=True, norm_eps=1e-6, norm_plus_one=True,
        head_dim_override=256,
    ),
    "llama-3-70b": ModelConfig(
        name="llama-3-70b", vocab_size=128256, d_model=8192, n_layers=80,
        n_heads=64, n_kv_heads=8, d_ff=28672, max_seq_len=8192,
        rope_theta=500000.0, tie_embeddings=False,
    ),
}

# zephyr IS mistral-7b architecture — one definition, two names (drift-proof)
CONFIGS["mistral-7b"] = replace(CONFIGS["zephyr-7b"], name="mistral-7b")
# llama-3.1: same weights-shape as llama-3 + the llama3 rope-scaling
# schedule over a 128k window (config.json: rope_scaling.rope_type=llama3)
CONFIGS["llama-3.1-8b"] = replace(
    CONFIGS["llama-3-8b"], name="llama-3.1-8b", max_seq_len=131072,
    rope_scaling=("llama3", 8.0, 1.0, 4.0, 8192),
)

CONFIGS["tiny-phi"] = ModelConfig(  # parallel blocks + partial rotary
    name="tiny-phi", vocab_size=512, d_model=64, n_layers=2, n_heads=4,
    n_kv_heads=4, d_ff=128, max_seq_len=256, activation="gelu",
    norm="layernorm", use_bias=True, tie_embeddings=False,
    rotary_pct=0.4, parallel_block=True, lm_head_bias=True,
)
CONFIGS["tiny-gptj"] = ModelConfig(  # interleaved rotary + mlp-only bias
    name="tiny-gptj", vocab_size=512, d_model=64, n_layers=2, n_heads=4,
    n_kv_heads=4, d_ff=128, max_seq_len=256, activation="gelu",
    norm="layernorm", tie_embeddings=False, mlp_bias=True,
    rotary_pct=0.5, rope_style="interleaved", parallel_block=True,
    lm_head_bias=True,
)
CONFIGS["gpt-j-6b"] = ModelConfig(
    # EleutherAI/gpt-j-6b: parallel block sharing one layernorm,
    # interleaved rotary over 64 of 256 head dims, bias-free attention
    # with biased MLP and lm_head
    name="gpt-j-6b", vocab_size=50400, d_model=4096, n_layers=28,
    n_heads=16, n_kv_heads=16, d_ff=16384, max_seq_len=2048,
    activation="gelu", norm="layernorm", tie_embeddings=False,
    mlp_bias=True, rotary_pct=0.25, rope_style="interleaved",
    parallel_block=True, lm_head_bias=True,
)
CONFIGS["tiny-bloom"] = ModelConfig(  # ALiBi attention (no rotary/learned
    # positions), embedding LayerNorm before block 0, biased everything
    name="tiny-bloom", vocab_size=512, d_model=64, n_layers=2, n_heads=4,
    n_kv_heads=4, d_ff=256, max_seq_len=256, pos_embedding="alibi",
    norm="layernorm", activation="gelu", use_bias=True,
    tie_embeddings=True, embedding_norm=True,
)
CONFIGS["bloom-7b1"] = ModelConfig(
    # bigscience/bloom-7b1: 30 layers x 32 heads, ALiBi, 250k vocab
    name="bloom-7b1", vocab_size=250880, d_model=4096, n_layers=30,
    n_heads=32, n_kv_heads=32, d_ff=16384, max_seq_len=2048,
    pos_embedding="alibi", norm="layernorm", activation="gelu",
    use_bias=True, tie_embeddings=True, embedding_norm=True,
)
CONFIGS["tiny-mpt"] = ModelConfig(  # mpt style: ALiBi + weight-only
    # layernorms + zero linear biases + exact gelu, sequential blocks
    name="tiny-mpt", vocab_size=512, d_model=64, n_layers=2, n_heads=4,
    n_kv_heads=4, d_ff=256, max_seq_len=256, pos_embedding="alibi",
    norm="layernorm", norm_bias=False, activation="gelu_exact",
    tie_embeddings=True,
)
CONFIGS["mpt-7b"] = ModelConfig(
    # mosaicml/mpt-7b: 32 heads (power of two — the bloom slope formula
    # applies exactly), expansion ratio 4, no biases anywhere
    name="mpt-7b", vocab_size=50432, d_model=4096, n_layers=32,
    n_heads=32, n_kv_heads=32, d_ff=16384, max_seq_len=2048,
    pos_embedding="alibi", norm="layernorm", norm_bias=False,
    activation="gelu_exact", tie_embeddings=True,
)
CONFIGS["tiny-falcon"] = ModelConfig(  # falcon-7b shape: MQA + bias-free
    # parallel block sharing ONE layernorm, exact-erf gelu, tied head
    name="tiny-falcon", vocab_size=512, d_model=64, n_layers=2, n_heads=4,
    n_kv_heads=1, d_ff=128, max_seq_len=256, activation="gelu_exact",
    norm="layernorm", tie_embeddings=True, parallel_block=True,
)
CONFIGS["falcon-7b"] = ModelConfig(
    # tiiuae/falcon-7b: 71 64-dim heads with ONE kv head (multi_query),
    # parallel attn+mlp sharing input_layernorm, no linear biases, tied
    # embeddings, full rotary
    name="falcon-7b", vocab_size=65024, d_model=4544, n_layers=32,
    n_heads=71, n_kv_heads=1, d_ff=18176, max_seq_len=2048,
    activation="gelu_exact", norm="layernorm", tie_embeddings=True,
    parallel_block=True,
)
CONFIGS["tiny-neox"] = ModelConfig(  # dual-norm parallel residual
    name="tiny-neox", vocab_size=512, d_model=64, n_layers=2, n_heads=4,
    n_kv_heads=4, d_ff=128, max_seq_len=256, activation="gelu_exact",
    norm="layernorm", use_bias=True, tie_embeddings=False,
    rotary_pct=0.25, parallel_block=True, parallel_norms=2,
)
CONFIGS["pythia-1.4b"] = ModelConfig(
    # EleutherAI/pythia-1.4b (GPT-NeoX arch): parallel residual with
    # separate attn/mlp norms, rotary over the first quarter of head dims
    name="pythia-1.4b", vocab_size=50304, d_model=2048, n_layers=24,
    n_heads=16, n_kv_heads=16, d_ff=8192, max_seq_len=2048,
    activation="gelu_exact", norm="layernorm", use_bias=True,
    tie_embeddings=False, rotary_pct=0.25, parallel_block=True,
    parallel_norms=2,
)
CONFIGS["gpt-neox-20b"] = ModelConfig(
    name="gpt-neox-20b", vocab_size=50432, d_model=6144, n_layers=44,
    n_heads=64, n_kv_heads=64, d_ff=24576, max_seq_len=2048,
    activation="gelu_exact", norm="layernorm", use_bias=True,
    tie_embeddings=False, rotary_pct=0.25, parallel_block=True,
    parallel_norms=2,
)
CONFIGS["tiny-olmo2"] = ModelConfig(
    # olmo2 style: POST-norm-only blocks + full-width q/k RMSNorm
    name="tiny-olmo2", vocab_size=512, d_model=64, n_layers=2, n_heads=4,
    n_kv_heads=2, d_ff=128, max_seq_len=256, tie_embeddings=False,
    post_norms=True, no_pre_norms=True, qk_norm=True, qk_norm_full=True,
)
CONFIGS["olmo2-7b"] = ModelConfig(
    # allenai/OLMo-2-1124-7B: fully-open 7B, rope theta 5e5, 100k vocab
    name="olmo2-7b", vocab_size=100352, d_model=4096, n_layers=32,
    n_heads=32, n_kv_heads=32, d_ff=11008, max_seq_len=4096,
    rope_theta=500000.0, norm_eps=1e-6, tie_embeddings=False,
    post_norms=True, no_pre_norms=True, qk_norm=True, qk_norm_full=True,
)
CONFIGS["tiny-stablelm"] = ModelConfig(
    # stablelm-2 style: llama tensor layout with BIASED layernorms,
    # partial rotary 0.25, gated silu, untied head
    name="tiny-stablelm", vocab_size=512, d_model=64, n_layers=2,
    n_heads=4, n_kv_heads=2, d_ff=128, max_seq_len=256, norm="layernorm",
    rotary_pct=0.25, tie_embeddings=False,
)
CONFIGS["stablelm-2-1.6b"] = ModelConfig(
    # stabilityai/stablelm-2-1_6b ships use_qkv_bias=true (the qwen-style
    # per-projection q/k/v biases are a defining stablelm-2 feature)
    name="stablelm-2-1.6b", vocab_size=100352, d_model=2048, n_layers=24,
    n_heads=32, n_kv_heads=32, d_ff=5632, max_seq_len=4096,
    norm="layernorm", rotary_pct=0.25, qkv_bias=True, tie_embeddings=False,
)
CONFIGS["phi-3-mini"] = ModelConfig(
    # microsoft/Phi-3-mini-4k-instruct: llama-branch arch behind fused
    # qkv_proj/gate_up_proj tensors (loader._convert_phi3 un-fuses),
    # 2047-token sliding window on every layer. The 128k variants use
    # longrope scaling, which config_from_hf refuses (unimplemented).
    name="phi-3-mini", vocab_size=32064, d_model=3072, n_layers=32,
    n_heads=32, n_kv_heads=32, d_ff=8192, max_seq_len=4096,
    tie_embeddings=False, sliding_window=2047,
)
CONFIGS["phi-2"] = ModelConfig(
    # microsoft/phi-2: 2.7B, parallel attn+mlp blocks sharing one
    # layernorm, partial rotary over the first 32 of 80 head dims,
    # untied lm_head with bias
    name="phi-2", vocab_size=51200, d_model=2560, n_layers=32, n_heads=32,
    n_kv_heads=32, d_ff=10240, max_seq_len=2048, activation="gelu",
    norm="layernorm", use_bias=True, tie_embeddings=False,
    rotary_pct=0.4, parallel_block=True, lm_head_bias=True,
)


_FALCON_H1_34B = dict(
    # tiiuae/Falcon-H1-34B-Instruct config.json: every block runs a Mamba-2
    # mixer (32 heads x 128, state 256, 2 groups, conv 4, chunk 128) in
    # parallel with GQA 20/4 x 128 attention, then a SwiGLU MLP; untied
    # head over 261,120 tokens; fourteen muP multipliers
    vocab_size=261120, d_model=5120, n_heads=20, n_kv_heads=4, d_ff=21504,
    head_dim_override=128, max_seq_len=262144, rope_theta=1e11,
    norm_eps=1e-5, tie_embeddings=False,
    ssm_heads=32, ssm_head_dim=128, ssm_state=256, ssm_groups=2,
    ssm_conv=4, ssm_chunk=128,
    embedding_multiplier=5.656854249492381, lm_head_multiplier=0.0078125,
    attention_in_multiplier=1.0, attention_out_multiplier=0.0375,
    key_multiplier=0.011048543456039804, ssm_in_multiplier=0.25,
    ssm_out_multiplier=0.08838834764831845,
    mlp_multipliers=(0.1767766952966369, 0.011160714285714284),
    ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738),
)
CONFIGS["falcon-h1-34b"] = ModelConfig(
    name="falcon-h1-34b", n_layers=72, **_FALCON_H1_34B)
CONFIGS["falcon-h1-34b-6l"] = ModelConfig(
    # the served cut of the benchmark (benchmark/configs/falcon-h1-34b-6l
    # .json): six of the 72 identical blocks, every width, head and the
    # whole vocabulary; the other 66 blocks would lie on further chips
    name="falcon-h1-34b-6l", n_layers=6, **_FALCON_H1_34B)
CONFIGS["tiny-falcon-h1"] = ModelConfig(
    # every mechanism at CPU-test size: 2 groups, head size != state size
    # (a transposed [.., head_dim, state] axis fails loudly), a chunk
    # smaller than the test prompts, all fourteen multipliers off 1.0
    name="tiny-falcon-h1", vocab_size=512, d_model=64, n_layers=2, n_heads=4,
    n_kv_heads=2, d_ff=128, max_seq_len=256, tie_embeddings=False,
    rope_theta=1e6,
    ssm_heads=4, ssm_head_dim=8, ssm_state=16, ssm_groups=2, ssm_conv=4,
    ssm_chunk=8,
    embedding_multiplier=2.0, lm_head_multiplier=0.5,
    attention_in_multiplier=0.9, attention_out_multiplier=0.7,
    key_multiplier=0.6, ssm_in_multiplier=0.8, ssm_out_multiplier=0.75,
    mlp_multipliers=(0.85, 0.65),
    ssm_multipliers=(0.9, 0.8, 0.7, 0.6, 0.5),
)


_JOYAI_LLM_FLASH = dict(
    # jdopensource/JoyAI-LLM-Flash config.json (model_type joyai_llm_flash,
    # 48B-A2.7B): MLA with 32 heads (q rank 1536, latent 512 + a 64-wide
    # roped key shared by the heads, 128 nope / 128 value a head), one
    # leading dense layer 7168 wide, then expert layers of 256 experts 768
    # wide, top-8 behind a sigmoid router with a selection bias, weights
    # normalised and scaled by 2.5, one shared expert; untied head over
    # 129,280 tokens. The next-n (multi-token prediction) layer is NOT built
    vocab_size=129280, d_model=2048, n_heads=32, n_kv_heads=32, d_ff=7168,
    head_dim_override=64, max_seq_len=131072, rope_theta=32000000.0,
    rope_style="interleaved", norm_eps=1e-6, tie_embeddings=False,
    mla_q_rank=1536, mla_kv_rank=512, mla_nope_dim=128, mla_rope_dim=64,
    mla_v_dim=128,
    n_experts=256, n_experts_per_tok=8, moe_router="sigmoid", moe_scale=2.5,
    n_shared_experts=1, d_ff_expert=768, first_k_dense=1,
)
CONFIGS["joyai-llm-flash"] = ModelConfig(
    name="joyai-llm-flash", n_layers=40, **_JOYAI_LLM_FLASH)
CONFIGS["joyai-llm-flash-5l"] = ModelConfig(
    # the served cut of the benchmark (benchmark/configs/joyai-llm-flash-5l
    # .json): the leading dense layer and four expert layers with ALL 256
    # experts, every width and the whole vocabulary: the first of ten
    # pipeline stages of four layers, with the final norm and head added
    name="joyai-llm-flash-5l", n_layers=5, **_JOYAI_LLM_FLASH)
CONFIGS["tiny-joyai"] = ModelConfig(
    # every mechanism at CPU-test size, no width a multiple of 128: 3
    # layers of which 1 dense, 16 experts top-4, 1 shared, latent 24 + 8
    name="tiny-joyai", vocab_size=512, d_model=48, n_layers=3, n_heads=4,
    n_kv_heads=4, d_ff=96, head_dim_override=8, max_seq_len=256,
    rope_theta=10000.0, rope_style="interleaved", norm_eps=1e-6,
    tie_embeddings=False,
    mla_q_rank=40, mla_kv_rank=24, mla_nope_dim=12, mla_rope_dim=8,
    mla_v_dim=20,
    n_experts=16, n_experts_per_tok=4, moe_router="sigmoid", moe_scale=2.5,
    n_shared_experts=1, d_ff_expert=36, first_k_dense=1,
)


_SMALLTHINKER_21B = dict(
    # PowerInfer/SmallThinker-21BA3B-Instruct config.json (model_name
    # smallthinker_21b_instruct, 21B-A3B): GQA 28/4 x 128; layers l % 4 == 0
    # attend fully with NO positional encoding, the other three of four
    # through a 4,096 window with RoPE at theta 1.5e6; every layer 64 ReGLU
    # experts 768 wide, top-6, the router fed the PRE-ATTENTION norm's
    # output, weights a softmax over the chosen six; no bias anywhere, no
    # shared expert; untied head over 151,936 tokens
    vocab_size=151936, d_model=2560, n_heads=28, n_kv_heads=4, d_ff=768,
    head_dim_override=128, max_seq_len=16384, rope_theta=1500000.0,
    norm_eps=1e-6, tie_embeddings=False, activation="reglu",
    sliding_window=4096, sliding_window_every=4,
    sliding_window_residues=(1, 2, 3), rope_sliding_only=True,
    n_experts=64, n_experts_per_tok=6, moe_router="softmax_topk",
    moe_router_input="attn_norm",
)
CONFIGS["smallthinker-21b-a3b"] = ModelConfig(
    name="smallthinker-21b-a3b", n_layers=52, **_SMALLTHINKER_21B)
CONFIGS["smallthinker-21b-a3b-8l"] = ModelConfig(
    # the served cut of the benchmark (benchmark/configs/smallthinker-21b-
    # a3b-8l.json): two whole periods (F W W W F W W W) with all 64 experts,
    # every width and the whole vocabulary: the first of seven pipeline
    # stages, with the final norm and head added
    name="smallthinker-21b-a3b-8l", n_layers=8, **_SMALLTHINKER_21B)
CONFIGS["tiny-smallthinker"] = ModelConfig(
    # every mechanism at CPU-test size: 4 layers = one period, a window of
    # 24 that contexts of the tests pass, 8 experts top-3
    name="tiny-smallthinker", vocab_size=512, d_model=48, n_layers=4,
    n_heads=4, n_kv_heads=2, d_ff=36, head_dim_override=16, max_seq_len=256,
    rope_theta=10000.0, norm_eps=1e-6, tie_embeddings=False,
    activation="reglu", sliding_window=24, sliding_window_every=4,
    sliding_window_residues=(1, 2, 3), rope_sliding_only=True,
    n_experts=8, n_experts_per_tok=3, moe_router="softmax_topk",
    moe_router_input="attn_norm",
)


_GRANITE_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
_GRANITE_4_H_SMALL = dict(
    # ibm-granite/granite-4.0-h-small config.json (model_type
    # granitemoehybrid, 32B-A9B): a layer's mixer is a Mamba-2 mixer (128
    # heads x 64, state 128, 1 group, conv 4 with bias, chunk 256) OR GQA
    # 32/8 x 128 attention with NO positional encoding and scores times
    # 1/128, in a period of ten (m m m m m a m m m m); every layer 72 SwiGLU
    # experts 768 wide, top-10 by logit, weights a softmax over the ten, the
    # router fed the post-mixer norm, beside one shared SwiGLU expert 1,536
    # wide; both residual adds times 0.22; embeddings times 12, a tied head
    # over 100,352 tokens with logits / 16
    vocab_size=100352, d_model=4096, n_heads=32, n_kv_heads=8, d_ff=768,
    max_seq_len=131072, pos_embedding="nope",
    norm_eps=1e-5, tie_embeddings=True, attn_scale=16384.0,
    ssm_heads=128, ssm_head_dim=64, ssm_state=128, ssm_groups=1, ssm_conv=4,
    ssm_chunk=256,
    embedding_multiplier=12.0, lm_head_multiplier=0.0625,
    residual_multiplier=0.22,
    n_experts=72, n_experts_per_tok=10, moe_router="softmax_topk",
    n_shared_experts=1, d_ff_shared=1536,
)
CONFIGS["granite-4.0-h-small"] = ModelConfig(
    # as published: 40 layers, every expert held (64 GB of bf16: it loads
    # only where it fits)
    name="granite-4.0-h-small", n_layers=40,
    layer_types=_GRANITE_PERIOD * 4, **_GRANITE_4_H_SMALL)
CONFIGS["granite-4.0-h-small-10l-e36"] = ModelConfig(
    # the served cut of the benchmark (benchmark/configs/granite-4.0-h-small-
    # 10l-e36.json): one period of ten layers, every width and the whole
    # vocabulary, experts 0-35 of every layer's 72: the first chip of the
    # first of four two-chip pipeline stages, with the final norm and head
    # added; experts 36-71 lie on the partner chip
    name="granite-4.0-h-small-10l-e36", n_layers=10,
    layer_types=_GRANITE_PERIOD, n_experts_held=36, **_GRANITE_4_H_SMALL)
CONFIGS["tiny-granite"] = ModelConfig(
    # every mechanism at CPU-test size: m m a m m (two runs of unlike length
    # round the one attention layer), 8 experts top-3 of which 4 are held
    # from the 4th on, a shared expert of another width, head size != state
    # size, a chunk shorter than the test prompts, every scalar off 1
    name="tiny-granite", vocab_size=512, d_model=64, n_layers=5, n_heads=4,
    n_kv_heads=2, d_ff=24, max_seq_len=256,
    pos_embedding="nope", norm_eps=1e-5, tie_embeddings=True, attn_scale=64.0,
    ssm_heads=8, ssm_head_dim=16, ssm_state=8, ssm_groups=1, ssm_conv=4,
    ssm_chunk=8,
    embedding_multiplier=3.0, lm_head_multiplier=0.25,
    residual_multiplier=0.5,
    layer_types=("mamba", "mamba", "attention", "mamba", "mamba"),
    n_experts=8, n_experts_per_tok=3, moe_router="softmax_topk",
    n_experts_held=4, expert_first=4, n_shared_experts=1, d_ff_shared=40,
)


_K_EXAONE_236B = dict(
    # LGAI-EXAONE/K-EXAONE-236B-A23B config.json (model_type exaone_moe,
    # 236B-A23B): GQA 64/8 x 128 with per-head RMSNorm on q and k; layers in
    # periods of four (L L L G): three behind a 128-token window WITH RoPE
    # (theta 1e6), one full WITHOUT positions; a block norms its branches'
    # OUTPUTS, not their inputs (EXAONE 4.0's placement); layer 0 a dense
    # SwiGLU MLP 18,432 wide, every other layer 128 SwiGLU experts 2,048 wide,
    # top-8 by sigmoid score with no selection bias, weights normalised x 2.5,
    # beside one shared expert; one multi-token-prediction layer; an untied
    # head over 153,600 tokens
    d_model=6144, n_heads=64, n_kv_heads=8, head_dim_override=128, d_ff=18432,
    max_seq_len=262144, qk_norm=True, rope_theta=1000000.0, norm_eps=1e-5,
    tie_embeddings=False, no_pre_norms=True, post_norms=True,
    sliding_window=128, sliding_window_every=4,
    sliding_window_residues=(0, 1, 2), rope_sliding_only=True,
    n_experts=128, n_experts_per_tok=8, moe_router="sigmoid",
    moe_select_bias=False, moe_scale=2.5, n_shared_experts=1,
    d_ff_expert=2048, first_k_dense=1, mtp_layers=1,
)
CONFIGS["k-exaone-236b-a23b"] = ModelConfig(
    # as published: 48 layers, every expert, the whole vocabulary (472 GB of
    # bf16: it loads only where it fits)
    name="k-exaone-236b-a23b", n_layers=48, vocab_size=153600,
    **_K_EXAONE_236B)
CONFIGS["k-exaone-236b-a23b-5l-e16"] = ModelConfig(
    # the served cut of the benchmark (benchmark/configs/k-exaone-236b-a23b-
    # 5l-e16.json): layer_types[:5] = L L L G L (layer 0 dense), every width,
    # experts 0-15 of every layer's 128 and rows 0-19,199 of the embedding and
    # the head: one chip's share of the eight that share each layer, the first
    # stage's first chip with the final norm, the head and the MTP layer added
    name="k-exaone-236b-a23b-5l-e16", n_layers=5, vocab_size=19200,
    vocab_published=153600, n_experts_held=16, **_K_EXAONE_236B)
CONFIGS["tiny-exaone"] = ModelConfig(
    # every mechanism at CPU-test size: L(dense) L L G L + one MTP layer,
    # window 8 (shorter than the test prompts), 16 experts top-4 of which 4
    # are held from the 4th on, a shared expert, a sliced untied vocabulary
    name="tiny-exaone", vocab_size=320, vocab_published=512, d_model=64,
    n_layers=5, n_heads=4, n_kv_heads=2, head_dim_override=16, d_ff=96,
    max_seq_len=256, qk_norm=True, rope_theta=10000.0, norm_eps=1e-5,
    tie_embeddings=False, no_pre_norms=True, post_norms=True,
    sliding_window=8, sliding_window_every=4,
    sliding_window_residues=(0, 1, 2), rope_sliding_only=True,
    n_experts=16, n_experts_per_tok=4, moe_router="sigmoid",
    moe_select_bias=False, moe_scale=2.5, n_shared_experts=1, d_ff_expert=24,
    first_k_dense=1, n_experts_held=4, expert_first=4, mtp_layers=1,
)


_NEMOTRON_PERIOD = ("moe", "mamba") * 5 + ("attention",)
_NEMOTRON_3_SUPER = dict(
    # nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16 config.json (model_type
    # nemotron_h, 120B-A12B): a layer is ONE branch under ONE norm, by the
    # character of hybrid_override_pattern a Mamba-2 mixer (M: 128 heads x 64,
    # state 128, 8 groups, conv 4 with bias, chunk 128), GQA 32/2 x 128
    # attention with no positional encoding (*) or a LatentMoE expert layer
    # (E: 512 experts of TWO matrices 1,024 x 2,688 x 1,024 with relu^2
    # between, in a latent 1,024 wide, top-22 by sigmoid score + selection
    # bias, weights normalised x 5, beside one shared expert 5,376 wide at the
    # model's width); an untied head over 131,072 tokens. Its multi-token-
    # prediction module is not built (models/support.py)
    d_model=4096, n_heads=32, n_kv_heads=2, d_ff=2688, pos_embedding="nope",
    norm_eps=1e-5, tie_embeddings=False, activation="relu2",
    ssm_heads=128, ssm_head_dim=64, ssm_state=128, ssm_groups=8, ssm_conv=4,
    ssm_chunk=128,
    n_experts=512, n_experts_per_tok=22, moe_router="sigmoid", moe_scale=5.0,
    n_shared_experts=1, d_ff_expert=2688, d_ff_shared=5376, moe_latent=1024,
)
CONFIGS["nemotron-3-super-120b-a12b-11l-e128"] = ModelConfig(
    # the served cut of the benchmark (benchmark/configs/nemotron-3-super-
    # 120b-a12b-11l-e128.json): hybrid_override_pattern[26:37] = E M E M E M E
    # M E M *, one whole period, every width, experts 0-127 of every expert
    # layer's 512 and rows 0-32,767 of the embedding and the head: the first
    # of the four chips that share the layers of one pipeline stage, with the
    # embedding, the final norm and its slice of the head added
    name="nemotron-3-super-120b-a12b-11l-e128", n_layers=11,
    vocab_size=32768, vocab_published=131072, max_seq_len=2048,
    layer_types=_NEMOTRON_PERIOD, n_experts_held=128, **_NEMOTRON_3_SUPER)
CONFIGS["tiny-nemotron"] = ModelConfig(
    # every mechanism at CPU-test size: E M E M * M (a unit of two kinds
    # twice, then two runs of one; the FIRST layer an expert layer), 16
    # experts top-5 in a latent of 24 of which 4 are held from the 4th on, a
    # shared expert of another width, 2 groups, a chunk shorter than the test
    # prompts, a sliced untied vocabulary
    name="tiny-nemotron", vocab_size=320, vocab_published=512, d_model=64,
    n_layers=6, n_heads=4, n_kv_heads=2, d_ff=40, max_seq_len=256,
    pos_embedding="nope", norm_eps=1e-5, tie_embeddings=False,
    activation="relu2",
    ssm_heads=8, ssm_head_dim=16, ssm_state=8, ssm_groups=2, ssm_conv=4,
    ssm_chunk=8,
    layer_types=("moe", "mamba", "moe", "mamba", "attention", "mamba"),
    n_experts=16, n_experts_per_tok=5, moe_router="sigmoid", moe_scale=2.5,
    n_shared_experts=1, d_ff_expert=40, d_ff_shared=48, moe_latent=24,
    n_experts_held=4, expert_first=4,
)


def _neox_act(hidden_act: str) -> str:
    if hidden_act in ("gelu_new", "gelu_pytorch_tanh", "gelu_fast"):
        return "gelu"
    if hidden_act == "gelu":
        return "gelu_exact"
    raise ValueError(
        f"gpt_neox hidden_act {hidden_act!r} is not supported by the native "
        f"core (gelu variants only)"
    )


def _parse_rope_scaling(d: dict, default_max_pos: int = 2048) -> tuple | None:
    """HF rope_scaling dict → cfg.rope_scaling tuple, or raise for
    schedules the core doesn't implement (yarn/longrope/dynamic) — every
    rotary family must route through this, or an extended-context
    fine-tune serves with unscaled rotations, silently wrong at every
    position."""
    rs = d.get("rope_scaling")
    if not rs:
        return None
    rtype = rs.get("rope_type") or rs.get("type")
    if rtype == "llama3":
        return ("llama3", float(rs["factor"]), float(rs["low_freq_factor"]),
                float(rs["high_freq_factor"]),
                int(rs["original_max_position_embeddings"]))
    if rtype == "linear":
        return ("linear", float(rs["factor"]))
    if rtype == "yarn":
        import math as _math

        factor = float(rs["factor"])
        af = rs.get("attention_factor")
        if af is None:
            # HF's inference rule, incl. the deepseek mscale variants
            def get_mscale(scale, ms=1.0):
                return 1.0 if scale <= 1 else 0.1 * ms * _math.log(scale) + 1.0

            ms, msad = rs.get("mscale"), rs.get("mscale_all_dim")
            af = (get_mscale(factor, ms) / get_mscale(factor, msad)
                  if ms and msad else get_mscale(factor))
        orig = (rs.get("original_max_position_embeddings")
                or d.get("max_position_embeddings", default_max_pos))
        return ("yarn", factor, float(af),
                float(rs.get("beta_fast") or 32),
                float(rs.get("beta_slow") or 1),
                int(orig), bool(rs.get("truncate", True)))
    if rtype in ("default", None):
        return None
    raise ValueError(
        f"rope_scaling type {rtype!r} is not supported by the native core "
        f"(llama3/linear/yarn only); serve via the ollama/remote backends"
    )


def _falcon_h1_from_hf(d: dict, nm: str) -> ModelConfig:
    """falcon_h1 (tiiuae/Falcon-H1-*): parallel Mamba-2 mixer + attention
    blocks. What core.ssm_mixer does not implement is refused BY NAME —
    a guessed variant under a real model's name would serve wrong logits
    with no signal."""
    published = {  # flag -> the one value the implementation covers
        "mamba_rms_norm": True, "mamba_norm_before_gate": False,
        "mamba_conv_bias": True, "mamba_proj_bias": False,
        "attention_bias": False, "mlp_bias": False, "projectors_bias": False,
        "mamba_use_mlp": True,
    }
    defaults = {"mamba_rms_norm": False, "mamba_norm_before_gate": True}
    for flag, want in published.items():
        got = d.get(flag, defaults.get(flag, want))
        if bool(got) != want:
            raise ValueError(
                f"falcon_h1 config with {flag}={got!r} is not implemented "
                f"(only {flag}={want!r}, the published Falcon-H1 setting)"
            )
    if d.get("attn_layer_indices") is not None:
        raise ValueError(
            "falcon_h1 config with attn_layer_indices set is not "
            "implemented (every block runs attention beside its mixer)"
        )
    if d.get("rope_scaling") is not None:
        raise ValueError(
            f"falcon_h1 config with rope_scaling={d['rope_scaling']!r} is "
            "not implemented (the published models use none)"
        )
    if d.get("hidden_act", "silu") != "silu":
        raise ValueError(
            f"falcon_h1 config with hidden_act={d['hidden_act']!r} is not "
            "implemented (silu only)"
        )
    D, H = d["hidden_size"], d["num_attention_heads"]
    inner = d.get("mamba_d_ssm")
    if inner is None:
        inner = int(d.get("mamba_expand", 2) * D)
    heads = d["mamba_n_heads"]
    d_head = d.get("mamba_d_head", "auto")
    if d_head in (None, "auto"):
        d_head = inner // heads
    if d_head * heads != inner:
        raise ValueError(
            f"falcon_h1 config: mamba_n_heads {heads} x mamba_d_head "
            f"{d_head} != mamba_d_ssm {inner}"
        )
    hd = d.get("head_dim") or D // H
    return ModelConfig(
        name=nm, vocab_size=d["vocab_size"], d_model=D,
        n_layers=d["num_hidden_layers"], n_heads=H,
        n_kv_heads=d.get("num_key_value_heads") or H,
        d_ff=d["intermediate_size"],
        head_dim_override=None if hd * H == D else hd,
        max_seq_len=d.get("max_position_embeddings", 8192),
        rope_theta=float(d.get("rope_theta", 100000.0)),
        norm_eps=d.get("rms_norm_eps", 1e-5),
        tie_embeddings=d.get("tie_word_embeddings", False),
        ssm_heads=heads, ssm_head_dim=d_head,
        ssm_state=d.get("mamba_d_state", 256),
        ssm_groups=d.get("mamba_n_groups", 1),
        ssm_conv=d.get("mamba_d_conv", 4),
        ssm_chunk=d.get("mamba_chunk_size", 256),
        embedding_multiplier=float(d.get("embedding_multiplier", 1.0)),
        lm_head_multiplier=float(d.get("lm_head_multiplier", 1.0)),
        attention_in_multiplier=float(d.get("attention_in_multiplier", 1.0)),
        attention_out_multiplier=float(d.get("attention_out_multiplier", 1.0)),
        key_multiplier=float(d.get("key_multiplier", 1.0)),
        ssm_in_multiplier=float(d.get("ssm_in_multiplier", 1.0)),
        ssm_out_multiplier=float(d.get("ssm_out_multiplier", 1.0)),
        mlp_multipliers=tuple(d.get("mlp_multipliers") or (1.0, 1.0)),
        ssm_multipliers=tuple(d.get("ssm_multipliers") or (1.0,) * 5),
    )


def _joyai_from_hf(d: dict, nm: str) -> ModelConfig:
    """joyai_llm_flash (jdopensource/JoyAI-LLM-Flash; the DeepSeek-V3
    layout): latent attention, a sigmoid router with a selection bias, a
    shared expert, leading dense layers. What core does not implement is
    refused BY NAME. ``num_nextn_predict_layers`` is read past: the
    next-token logits do not depend on the next-n layer, which is not
    built (the loader skips its tensors)."""
    published = {  # key -> the one value the implementation covers
        "n_group": 1, "topk_group": 1, "topk_method": "noaux_tc",
        "scoring_func": "sigmoid", "rope_scaling": None, "moe_layer_freq": 1,
        "attention_bias": False, "hidden_act": "silu",
    }
    for key, want in published.items():
        got = d.get(key, want)
        if got != want:
            raise ValueError(
                f"joyai_llm_flash config with {key}={got!r} is not "
                f"implemented (only {key}={want!r}, the published setting)"
            )
    if d.get("q_lora_rank") is None:
        raise ValueError(
            "joyai_llm_flash config with q_lora_rank=None is not implemented "
            "(the query goes through its low-rank pair)"
        )
    if not d.get("norm_topk_prob", True):
        raise ValueError(
            "joyai_llm_flash config with norm_topk_prob=False is not "
            "implemented (the chosen weights are normalised)"
        )
    if not d.get("rope_interleave", True):
        raise ValueError(
            "joyai_llm_flash config with rope_interleave=False is not "
            "implemented (RoPE pairs are (2i, 2i+1))"
        )
    H = d["num_attention_heads"]
    return ModelConfig(
        name=nm, vocab_size=d["vocab_size"], d_model=d["hidden_size"],
        n_layers=d["num_hidden_layers"], n_heads=H,
        n_kv_heads=d.get("num_key_value_heads") or H,
        d_ff=d["intermediate_size"],
        head_dim_override=d.get("head_dim") or d["qk_rope_head_dim"],
        max_seq_len=d.get("max_position_embeddings", 4096),
        rope_theta=float(d.get("rope_theta", 10000.0)),
        rope_style="interleaved", norm_eps=d.get("rms_norm_eps", 1e-6),
        tie_embeddings=d.get("tie_word_embeddings", False),
        mla_q_rank=d["q_lora_rank"], mla_kv_rank=d["kv_lora_rank"],
        mla_nope_dim=d["qk_nope_head_dim"], mla_rope_dim=d["qk_rope_head_dim"],
        mla_v_dim=d["v_head_dim"],
        n_experts=d["n_routed_experts"],
        n_experts_per_tok=d["num_experts_per_tok"], moe_router="sigmoid",
        moe_scale=float(d.get("routed_scaling_factor", 1.0)),
        n_shared_experts=d.get("n_shared_experts") or 0,
        d_ff_expert=d["moe_intermediate_size"],
        first_k_dense=d.get("first_k_dense_replace", 0),
    )


CONFIGS["ouro-2.6b"] = ModelConfig(
    # ByteDance/Ouro-2.6B config.json (model_type ouro, arXiv:2510.25741):
    # 48 dense layers, MHA 16 x 128 with RoPE at theta 1e6 over the whole
    # head, a "sandwich" block (RMSNorm before each branch AND on its
    # output), SwiGLU 5632 wide, untied head over 49,152 tokens — and the
    # whole stack runs total_ut_steps = 4 times a token with the same
    # weights, the final norm after every pass, K/V kept a (pass, layer):
    # 192 cache layers. The exit gate is not built (early_exit_threshold 1:
    # no token leaves before the last pass; docs/MODELS.md)
    name="ouro-2.6b", vocab_size=49152, d_model=2048, n_layers=48,
    n_heads=16, n_kv_heads=16, d_ff=5632, head_dim_override=128,
    max_seq_len=65536, rope_theta=1000000.0, norm_eps=1e-6,
    tie_embeddings=False, post_norms=True, loop_steps=4,
)
CONFIGS["tiny-ouro"] = ModelConfig(
    # the loop at CPU-test size: 3 layers x 3 passes (unlike counts, so a
    # swapped (pass, layer) index cannot pass by symmetry), 4 heads x 16
    name="tiny-ouro", vocab_size=512, d_model=64, n_layers=3, n_heads=4,
    n_kv_heads=4, d_ff=96, head_dim_override=16, max_seq_len=256,
    rope_theta=10000.0, norm_eps=1e-6, tie_embeddings=False,
    post_norms=True, loop_steps=3,
)


def _layer_period(sliding: set, n_layers: int, longest: int):
    """(every, residues) of the shortest period, at most ``longest`` layers,
    under which the layers of ``sliding`` are those whose index modulo
    ``every`` is in ``residues`` (sliding_window_every / _residues), or None."""
    for p in range(1, longest + 1):
        residues = tuple(sorted({i % p for i in sliding}))
        if all((i % p in residues) == (i in sliding) for i in range(n_layers)):
            return p, residues
    return None


def _smallthinker_from_hf(d: dict, nm: str) -> ModelConfig:
    """smallthinker (PowerInfer/SmallThinker-*; ``model_name
    smallthinker_*``): full layers with no positional encoding beside
    windowed layers with RoPE, ReGLU experts behind a softmax-top-k router
    that reads the pre-attention norm. What core does not implement is
    refused BY NAME: a layout that is not periodic, a ``rope_layout`` that
    is not the ``sliding_window_layout``'s twin (one is_sliding_layer rule
    decides both), sigmoid routing, unnormalised weights, rope scaling."""
    L = d["num_hidden_layers"]
    layout = list(d.get("sliding_window_layout") or [0] * L)
    if len(layout) != L or list(d.get("rope_layout") or layout) != layout:
        raise ValueError(
            f"smallthinker config with rope_layout={d.get('rope_layout')!r} "
            f"beside sliding_window_layout={layout!r} is not implemented "
            f"(both must list {L} layers and be alike: a layer rotates iff "
            "it windows)"
        )
    sliding = {i for i, w in enumerate(layout) if w}
    found = _layer_period(sliding, L, min(L // 2, 12))  # at least two periods
    if found is None:
        raise ValueError(
            f"smallthinker config with sliding_window_layout={layout!r} is "
            "not implemented (no period of at most 12 layers, repeated at "
            "least twice, describes it)"
        )
    p, residues = found
    if not sliding or len(sliding) == L:
        raise ValueError(
            f"smallthinker config with sliding_window_layout={layout!r} is "
            "not implemented (it mixes no full and windowed layers)"
        )
    for key, want in {"moe_primary_router_apply_softmax": True,
                      "norm_topk_prob": True, "rope_scaling": None,
                      "moe_enable_secondary_experts": False}.items():
        got = d.get(key, want)
        if got != want:
            raise ValueError(
                f"smallthinker config with {key}={got!r} is not implemented "
                f"(only {key}={want!r}, the published setting)"
            )
    H = d["num_attention_heads"]
    return ModelConfig(
        name=nm, vocab_size=d["vocab_size"], d_model=d["hidden_size"],
        n_layers=L, n_heads=H, n_kv_heads=d.get("num_key_value_heads") or H,
        d_ff=d["moe_ffn_hidden_size"],
        head_dim_override=d.get("head_dim") or d["hidden_size"] // H,
        max_seq_len=d.get("max_position_embeddings", 16384),
        rope_theta=float(d.get("rope_theta", 10000.0)),
        norm_eps=d.get("rms_norm_eps", 1e-6),
        tie_embeddings=d.get("tie_word_embeddings", False),
        activation="reglu", sliding_window=d["sliding_window_size"],
        sliding_window_every=p, sliding_window_residues=residues,
        rope_sliding_only=True,
        n_experts=d["moe_num_primary_experts"],
        n_experts_per_tok=d["moe_num_active_primary_experts"],
        moe_router="softmax_topk", moe_router_input="attn_norm",
    )


def _ouro_from_hf(d: dict, nm: str) -> ModelConfig:
    """ouro (ByteDance/Ouro-*; looped language models): a dense sandwich-norm
    stack run ``total_ut_steps`` times a token. What core does not build is
    refused BY NAME: leaving the loop early (``early_exit_threshold`` < 1:
    the exit gate is not built), a sliding window, rope scaling, a layer
    kind other than full attention."""
    if float(d.get("early_exit_threshold", 1.0)) < 1.0:
        raise ValueError(
            f"ouro config with early_exit_threshold="
            f"{d['early_exit_threshold']!r} is not implemented (only 1, the "
            "published setting: every token runs every pass; the exit gate "
            "that lets a token leave the loop early is not built)"
        )
    if d.get("use_sliding_window"):
        raise ValueError(
            "ouro config with use_sliding_window=True is not implemented "
            "(only False, the published setting: a looped stack indexes a "
            "full-attention cache by pass and layer)"
        )
    if d.get("rope_scaling") is not None:
        raise ValueError(
            f"ouro config with rope_scaling={d['rope_scaling']!r} is not "
            "implemented (only null, the published setting)"
        )
    odd = sorted({t for t in d.get("layer_types") or [] if t != "full_attention"})
    if odd:
        raise ValueError(
            f"ouro config with layer_types entries {odd} is not implemented "
            "(only 'full_attention', the published setting)"
        )
    if d.get("hidden_act", "silu") != "silu":
        raise ValueError(
            f"ouro config with hidden_act={d['hidden_act']!r} is not "
            "implemented (only 'silu', the published setting)"
        )
    H = d["num_attention_heads"]
    return ModelConfig(
        name=nm, vocab_size=d["vocab_size"], d_model=d["hidden_size"],
        n_layers=d["num_hidden_layers"], n_heads=H,
        n_kv_heads=d.get("num_key_value_heads") or H,
        d_ff=d["intermediate_size"],
        head_dim_override=d.get("head_dim") or d["hidden_size"] // H,
        max_seq_len=d.get("max_position_embeddings", 65536),
        rope_theta=float(d.get("rope_theta", 10000.0)),
        norm_eps=d.get("rms_norm_eps", 1e-6),
        tie_embeddings=d.get("tie_word_embeddings", False),
        post_norms=True, loop_steps=int(d.get("total_ut_steps", 1)),
    )


def _granite_hybrid_from_hf(d: dict, nm: str) -> ModelConfig:
    """granitemoehybrid (ibm-granite/granite-4.0-h-*): a Mamba-2 mixer OR
    NoPE GQA attention a layer (``layer_types``), every layer softmax-top-k
    experts beside a shared expert. What core does not build is refused BY
    NAME. ``num_local_experts_held`` / ``expert_first`` (not published keys:
    a cut configuration's own) give the chip's share of the experts."""
    published = {  # key -> the one value the implementation covers
        "position_embedding_type": "nope", "attention_bias": False,
        "mamba_proj_bias": False, "mamba_conv_bias": True,
        "rope_scaling": None, "hidden_act": "silu",
        "normalization_function": "rmsnorm",
    }
    for key, want in published.items():
        got = d.get(key, want)
        if got != want:
            raise ValueError(
                f"granitemoehybrid config with {key}={got!r} is not "
                f"implemented (only {key}={want!r}, the published setting)"
            )
    heads, groups = d["mamba_n_heads"], d.get("mamba_n_groups", 1)
    if heads % groups:
        raise ValueError(
            f"granitemoehybrid config with mamba_n_groups={groups} is not "
            f"implemented (it must divide mamba_n_heads {heads})"
        )
    L = d["num_hidden_layers"]
    types = tuple(d.get("layer_types") or ())
    if len(types) != L or not set(types) <= {"mamba", "attention"}:
        raise ValueError(
            f"granitemoehybrid config with layer_types={list(types)!r} is "
            f"not implemented (it must name each of the {L} layers 'mamba' "
            "or 'attention')"
        )
    if not d.get("num_local_experts") or not d.get("shared_intermediate_size"):
        raise ValueError(
            "granitemoehybrid config with num_local_experts="
            f"{d.get('num_local_experts')!r} / shared_intermediate_size="
            f"{d.get('shared_intermediate_size')!r} is not implemented "
            "(every layer routes experts beside a shared expert)"
        )
    D, H = d["hidden_size"], d["num_attention_heads"]
    inner = int(d.get("mamba_expand", 2) * D)
    d_head = d.get("mamba_d_head", "auto")
    if d_head in (None, "auto"):
        d_head = inner // heads
    if d_head * heads != inner:
        raise ValueError(
            f"granitemoehybrid config: mamba_n_heads {heads} x mamba_d_head "
            f"{d_head} != mamba_expand x hidden_size {inner}"
        )
    hd = d.get("head_dim") or D // H
    return ModelConfig(
        name=nm, vocab_size=d["vocab_size"], d_model=D, n_layers=L,
        n_heads=H, n_kv_heads=d.get("num_key_value_heads") or H,
        d_ff=d["intermediate_size"],  # read as ONE expert's width
        head_dim_override=None if hd * H == D else hd,
        max_seq_len=d.get("max_position_embeddings", 131072),
        pos_embedding="nope", norm_eps=d.get("rms_norm_eps", 1e-5),
        tie_embeddings=d.get("tie_word_embeddings", True),
        # scores times attention_multiplier = scores / sqrt(attn_scale)
        attn_scale=1.0 / float(d["attention_multiplier"]) ** 2
        if d.get("attention_multiplier") else None,
        ssm_heads=heads, ssm_head_dim=d_head,
        ssm_state=d.get("mamba_d_state", 128), ssm_groups=groups,
        ssm_conv=d.get("mamba_d_conv", 4),
        ssm_chunk=d.get("mamba_chunk_size", 256),
        embedding_multiplier=float(d.get("embedding_multiplier", 1.0)),
        lm_head_multiplier=1.0 / float(d.get("logits_scaling", 1.0)),
        residual_multiplier=float(d.get("residual_multiplier", 1.0)),
        layer_types=types,
        n_experts=d["num_local_experts"],
        n_experts_per_tok=d["num_experts_per_tok"], moe_router="softmax_topk",
        n_experts_held=d.get("num_local_experts_held") or 0,
        expert_first=d.get("expert_first") or 0,
        n_shared_experts=1, d_ff_shared=d["shared_intermediate_size"],
    )


def _exaone_moe_from_hf(d: dict, nm: str) -> ModelConfig:
    """exaone_moe (LGAI-EXAONE/K-EXAONE-*): window layers with RoPE beside
    full layers without positions (``layer_types`` / ``sliding_windows``, in
    the period ``sliding_window_pattern`` spells), sigmoid-routed experts
    beside a shared expert behind ``first_k_dense_replace`` dense layers, and
    ``num_nextn_predict_layers`` multi-token-prediction layers. What core does
    not build is refused BY NAME. ``num_experts_held`` / ``expert_first`` /
    ``vocab_size_held`` (not published keys: a cut configuration's own) give
    the chip's share of the experts and of the vocabulary's rows."""
    L = d["num_hidden_layers"]
    rope = d.get("rope_parameters") or {}
    published = {  # key -> the one value the implementation covers
        "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1,
        "norm_topk_prob": True, "hidden_act": "silu",
        "mtp_layer_types": ["full_attention"], "mtp_sliding_windows": [0],
    }
    if not d.get("num_nextn_predict_layers"):
        published.pop("mtp_layer_types"), published.pop("mtp_sliding_windows")
    for key, want in published.items():
        got = d.get(key, want)
        if got != want:
            raise ValueError(
                f"exaone_moe config with {key}={got!r} is not implemented "
                f"(only {key}={want!r}, the published setting)"
            )
    if rope.get("rope_type", "default") != "default" or d.get("rope_scaling"):
        raise ValueError(
            f"exaone_moe config with rope_parameters={rope!r} / rope_scaling="
            f"{d.get('rope_scaling')!r} is not implemented (only rope_type="
            "'default', no scaling)"
        )
    if d.get("num_nextn_predict_layers", 0) not in (0, 1):
        raise ValueError(
            "exaone_moe config with num_nextn_predict_layers="
            f"{d['num_nextn_predict_layers']!r} is not implemented (0 or 1)"
        )
    pattern = str(d.get("sliding_window_pattern") or "")
    window = d.get("sliding_window") or 0
    types = list(d.get("layer_types") or ())
    names = {"L": "sliding_attention", "G": "full_attention"}
    if (not window or set(pattern) != {"L", "G"} or len(types) != L
            or types != [names[pattern[i % len(pattern)]] for i in range(L)]
            or list(d.get("sliding_windows") or [
                window if t == "sliding_attention" else 0 for t in types
            ]) != [window if t == "sliding_attention" else 0 for t in types]):
        raise ValueError(
            f"exaone_moe config with layer_types={types!r} / sliding_windows="
            f"{d.get('sliding_windows')!r} is not implemented (the {L} layers "
            f"must repeat sliding_window_pattern={pattern!r} of L and G, the "
            f"L layers behind sliding_window={window!r})"
        )
    k_dense = d.get("first_k_dense_replace", 0)
    mlps = list(d.get("mlp_layer_types")
                or ["dense"] * k_dense + ["sparse"] * (L - k_dense))
    if mlps != ["dense"] * k_dense + ["sparse"] * (L - k_dense):
        raise ValueError(
            f"exaone_moe config with mlp_layer_types={mlps!r} is not "
            f"implemented (first_k_dense_replace={k_dense} dense layers, then "
            "sparse ones)"
        )
    if not d.get("num_experts") or not d.get("num_shared_experts"):
        raise ValueError(
            f"exaone_moe config with num_experts={d.get('num_experts')!r} / "
            f"num_shared_experts={d.get('num_shared_experts')!r} is not "
            "implemented (every sparse layer routes experts beside a shared "
            "expert)"
        )
    D, H = d["hidden_size"], d["num_attention_heads"]
    hd = d.get("head_dim") or D // H
    held = d.get("vocab_size_held") or 0
    return ModelConfig(
        name=nm, vocab_size=held or d["vocab_size"],
        vocab_published=d["vocab_size"] if held else 0, d_model=D,
        n_layers=L, n_heads=H, n_kv_heads=d.get("num_key_value_heads") or H,
        d_ff=d["intermediate_size"],
        head_dim_override=None if hd * H == D else hd,
        max_seq_len=d.get("max_position_embeddings", 262144),
        qk_norm=True, rope_theta=float(rope.get("rope_theta", 1000000.0)),
        norm_eps=d.get("rms_norm_eps", 1e-5),
        tie_embeddings=d.get("tie_word_embeddings", False),
        no_pre_norms=True, post_norms=True,
        sliding_window=window, sliding_window_every=len(pattern),
        sliding_window_residues=tuple(
            i for i, c in enumerate(pattern) if c == "L"),
        rope_sliding_only=True,
        n_experts=d["num_experts"],
        n_experts_per_tok=d["num_experts_per_tok"], moe_router="sigmoid",
        moe_select_bias=False,
        moe_scale=float(d.get("routed_scaling_factor", 1.0)),
        n_shared_experts=d["num_shared_experts"],
        d_ff_expert=d["moe_intermediate_size"], first_k_dense=k_dense,
        n_experts_held=d.get("num_experts_held") or 0,
        expert_first=d.get("expert_first") or 0,
        mtp_layers=d.get("num_nextn_predict_layers", 0),
    )


def _nemotron_h_from_hf(d: dict, nm: str) -> ModelConfig:
    """nemotron_h (nvidia/NVIDIA-Nemotron-3-*): a layer is ONE branch under
    ONE norm, by the character of ``hybrid_override_pattern`` a Mamba-2 mixer
    (``M``), attention without positions (``*``) or a LatentMoE expert layer
    (``E``: sigmoid-routed relu^2 experts of two matrices in a latent
    ``moe_latent_size`` wide, beside a shared expert at the model's width).
    What core does not build is refused BY NAME: a dense MLP-alone layer
    (``-``), the multi-token-prediction module, every bias. ``layers`` /
    ``layer_first`` (the slice of the pattern that runs),
    ``n_routed_experts_held`` / ``expert_first`` and ``vocab_size_held`` (not
    published keys: a cut configuration's own) give the chip's share of the
    depth, the experts and the vocabulary's rows."""
    published = {  # key -> the one value the implementation covers
        "mamba_proj_bias": False, "use_bias": False, "attention_bias": False,
        "mlp_bias": False, "use_conv_bias": True, "n_group": 1,
        "topk_group": 1, "norm_topk_prob": True,
        "moe_shared_expert_overlap": False, "sliding_window": None,
        "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
        "n_shared_experts": 1, "residual_in_fp32": False,
        "num_nextn_predict_layers": 0,
    }
    from .support import why  # (support imports this module)

    for key, want in published.items():
        got = d.get(key, want)
        if got != want:
            raise ValueError(
                f"nemotron_h config with {key}={got!r} is not implemented "
                f"(only {key}={want!r}"
                + (f": {why('single_branch', 'mtp_module')})"
                   if key == "num_nextn_predict_layers" else ")")
            )
    eps = d.get("layer_norm_epsilon", 1e-5)
    if d.get("norm_eps", eps) != eps:
        raise ValueError(
            f"nemotron_h config with norm_eps={d['norm_eps']!r} beside "
            f"layer_norm_epsilon={eps!r} is not implemented (one epsilon for "
            "every norm)")
    pattern = str(d.get("hybrid_override_pattern") or "")
    kinds = {"M": "mamba", "E": "moe", "*": "attention"}
    if len(pattern) != d["num_hidden_layers"] or not set(pattern) <= set(kinds):
        raise ValueError(
            f"nemotron_h config with hybrid_override_pattern={pattern!r} is "
            f"not implemented (one of M, E, * for each of the "
            f"{d['num_hidden_layers']} layers; "
            f"{why('single_branch', 'mlp_alone_layer')})")
    first, L = d.get("layer_first") or 0, d.get("layers") or len(pattern)
    if first < 0 or L < 1 or first + L > len(pattern):
        raise ValueError(
            f"nemotron_h config with layers={L} from layer_first={first} is "
            f"not a slice of the {len(pattern)} published layers")
    heads, groups = d["mamba_num_heads"], d.get("n_groups", 1)
    D, H = d["hidden_size"], d["num_attention_heads"]
    if heads % groups or heads * d["mamba_head_dim"] != d.get("expand", 2) * D:
        raise ValueError(
            f"nemotron_h config: n_groups={groups} must divide "
            f"mamba_num_heads {heads}, and mamba_num_heads x mamba_head_dim "
            f"{d['mamba_head_dim']} equal expand x hidden_size")
    if not d.get("n_routed_experts") or not d.get("moe_latent_size"):
        raise ValueError(
            f"nemotron_h config with n_routed_experts="
            f"{d.get('n_routed_experts')!r} / moe_latent_size="
            f"{d.get('moe_latent_size')!r} is not implemented (an E layer "
            "routes experts in a latent)")
    hd = d.get("head_dim") or D // H
    held = d.get("vocab_size_held") or 0
    return ModelConfig(
        name=nm, vocab_size=held or d["vocab_size"],
        vocab_published=d["vocab_size"] if held else 0, d_model=D, n_layers=L,
        n_heads=H, n_kv_heads=d.get("num_key_value_heads") or H,
        d_ff=d["intermediate_size"],  # a '-' layer's width: none is built
        head_dim_override=None if hd * H == D else hd,
        max_seq_len=d.get("max_position_embeddings", 262144),
        # the Nemotron-H block applies no rotation: rope_theta and
        # partial_rotary_factor are carried keys
        pos_embedding="nope", norm_eps=eps,
        tie_embeddings=d.get("tie_word_embeddings", False),
        activation="relu2",
        ssm_heads=heads, ssm_head_dim=d["mamba_head_dim"],
        ssm_state=d.get("ssm_state_size", 128), ssm_groups=groups,
        ssm_conv=d.get("conv_kernel", 4), ssm_chunk=d.get("chunk_size", 128),
        layer_types=tuple(kinds[c] for c in pattern[first:first + L]),
        n_experts=d["n_routed_experts"],
        n_experts_per_tok=d["num_experts_per_tok"], moe_router="sigmoid",
        moe_scale=float(d.get("routed_scaling_factor", 1.0)),
        n_shared_experts=1, d_ff_expert=d["moe_intermediate_size"],
        d_ff_shared=d["moe_shared_expert_intermediate_size"],
        moe_latent=d["moe_latent_size"],
        n_experts_held=d.get("n_routed_experts_held") or 0,
        expert_first=d.get("expert_first") or 0,
    )


def config_from_hf(d: dict, name: str | None = None) -> ModelConfig:
    """Synthesize a ModelConfig from an HF ``config.json`` dict — the
    any-checkpoint path: a checkpoint whose architecture is NOT in the
    preset registry can still be served natively, the way the reference
    serves any HF causal LM via AutoModelForCausalLM (reference
    services.py:39-52, hf.py:23-32). Inverse of export.hf_config_dict;
    covers the gpt2 / llama / mistral / qwen2 / gemma / mixtral / phi /
    gpt-neox / gpt-j layouts (the dominant open-model shapes)."""
    mt = d.get("model_type")
    nm = name or d.get("_name_or_path") or f"{mt}-checkpoint"
    if mt == "gpt2":
        return ModelConfig(
            name=nm, vocab_size=d["vocab_size"], d_model=d["n_embd"],
            n_layers=d["n_layer"], n_heads=d["n_head"], n_kv_heads=d["n_head"],
            d_ff=d.get("n_inner") or 4 * d["n_embd"],
            max_seq_len=d.get("n_positions", 1024), pos_embedding="learned",
            norm="layernorm", activation="gelu", use_bias=True,
            tie_embeddings=True,
            norm_eps=d.get("layer_norm_epsilon", 1e-5),
        )
    if mt == "gpt_bigcode":
        H = d["n_head"]
        return ModelConfig(
            name=nm, vocab_size=d["vocab_size"], d_model=d["n_embd"],
            n_layers=d["n_layer"], n_heads=H,
            n_kv_heads=1 if d.get("multi_query", True) else H,
            d_ff=d.get("n_inner") or 4 * d["n_embd"],
            max_seq_len=d.get("n_positions", 1024), pos_embedding="learned",
            norm="layernorm",
            # same gelu-dialect map (and refusal of non-gelu) as gpt_neox:
            # an exact-gelu checkpoint must not silently run tanh-approx
            activation=_neox_act(d.get("activation_function",
                                       "gelu_pytorch_tanh")),
            use_bias=True,
            tie_embeddings=d.get("tie_word_embeddings", True),
            norm_eps=d.get("layer_norm_epsilon", 1e-5),
        )
    if mt == "gptj":
        hd = d["n_embd"] // d["n_head"]
        return ModelConfig(
            name=nm, vocab_size=d["vocab_size"], d_model=d["n_embd"],
            n_layers=d["n_layer"], n_heads=d["n_head"], n_kv_heads=d["n_head"],
            d_ff=d.get("n_inner") or 4 * d["n_embd"],
            max_seq_len=d.get("n_positions", 2048), activation="gelu",
            norm="layernorm", tie_embeddings=False, mlp_bias=True,
            rotary_pct=d.get("rotary_dim", hd) / hd, rope_style="interleaved",
            parallel_block=True, lm_head_bias=True,
            norm_eps=d.get("layer_norm_epsilon", 1e-5),
        )
    if mt == "gpt_neox":
        return ModelConfig(
            name=nm, vocab_size=d["vocab_size"], d_model=d["hidden_size"],
            n_layers=d["num_hidden_layers"], n_heads=d["num_attention_heads"],
            n_kv_heads=d["num_attention_heads"], d_ff=d["intermediate_size"],
            max_seq_len=d.get("max_position_embeddings", 2048),
            # HF "gelu" is the exact erf form; the tanh approximations are
            # spelled gelu_new / gelu_pytorch_tanh. Anything else must
            # fail loudly — a silently substituted nonlinearity serves
            # garbage with no error
            activation=_neox_act(d.get("hidden_act", "gelu")),
            norm="layernorm", use_bias=True,
            tie_embeddings=d.get("tie_word_embeddings", False),
            rotary_pct=d.get("rotary_pct", 1.0),
            rope_theta=d.get("rotary_emb_base", 10000.0),
            rope_scaling=_parse_rope_scaling(d),
            parallel_block=d.get("use_parallel_residual", True),
            parallel_norms=2, norm_eps=d.get("layer_norm_eps", 1e-5),
        )
    if mt == "mpt":
        ac = d.get("attn_config") or {}
        if not ac.get("alibi", True):
            raise ValueError(
                "mpt without alibi (learned-pos variant) is not supported "
                "by the native core; serve via the ollama/remote backends"
            )
        if ac.get("clip_qkv") or ac.get("softmax_scale"):
            raise ValueError(
                "mpt clip_qkv / custom softmax_scale are not supported by "
                "the native core"
            )
        H = d["n_heads"]
        if H & (H - 1):
            # MPT's non-power-of-two slope interleave differs from the
            # bloom formula core.alibi_slopes implements — refuse rather
            # than attend with wrong biases
            raise ValueError(
                f"mpt with non-power-of-two n_heads={H} is not supported "
                f"(ALiBi slope schedule differs)"
            )
        return ModelConfig(
            name=nm, vocab_size=d["vocab_size"], d_model=d["d_model"],
            n_layers=d["n_layers"], n_heads=H, n_kv_heads=H,
            d_ff=int(d.get("expansion_ratio", 4)) * d["d_model"],
            max_seq_len=d.get("max_seq_len", 2048), pos_embedding="alibi",
            norm="layernorm", norm_bias=False, activation="gelu_exact",
            tie_embeddings=d.get("tie_word_embeddings", True),
            norm_eps=d.get("layer_norm_epsilon", 1e-5),
        )
    if mt == "bloom":
        if d.get("apply_residual_connection_post_layernorm"):
            # HF adds the post-LN hidden states to the residual under this
            # flag; our blocks always use the pre-LN input — serving such
            # a checkpoint would diverge at every layer, silently
            raise ValueError(
                "bloom apply_residual_connection_post_layernorm=true is "
                "not supported by the native core; serve via the "
                "ollama/remote backends"
            )
        H = d["n_head"]
        return ModelConfig(
            name=nm, vocab_size=d["vocab_size"], d_model=d["hidden_size"],
            n_layers=d["n_layer"], n_heads=H, n_kv_heads=H,
            d_ff=4 * d["hidden_size"],  # BloomConfig has no n_inner field
            # ALiBi has no positional table — context is bounded only by
            # the serving cache; seq_length is the training length the
            # wild checkpoints carry (2048 for the bloom releases)
            max_seq_len=d.get("seq_length", 2048),
            pos_embedding="alibi", norm="layernorm",
            activation="gelu", use_bias=True,
            tie_embeddings=d.get("tie_word_embeddings", True),
            embedding_norm=True, norm_eps=d.get("layer_norm_epsilon", 1e-5),
        )
    if mt == "falcon":
        if d.get("alibi"):
            raise ValueError(
                "falcon alibi checkpoints are not supported by the native "
                "core (rotary only); serve via the ollama/remote backends"
            )
        if d.get("new_decoder_architecture"):
            raise ValueError(
                "falcon new_decoder_architecture (grouped-KV interleave, "
                "falcon-40b/180b) is not supported by the native core yet"
            )
        if not d.get("parallel_attn", True):
            raise ValueError(
                "falcon parallel_attn=false (sequential blocks) is not "
                "supported by the native falcon path"
            )
        if d.get("bias"):
            # our falcon layout is bias-free (like every released falcon);
            # loading a bias=true checkpoint would silently zero every
            # linear bias — refuse, don't drop
            raise ValueError(
                "falcon bias=true checkpoints are not supported by the "
                "native core; serve via the ollama/remote backends"
            )
        H, D = d["num_attention_heads"], d["hidden_size"]
        return ModelConfig(
            name=nm, vocab_size=d["vocab_size"], d_model=D,
            n_layers=d["num_hidden_layers"], n_heads=H,
            n_kv_heads=1 if d.get("multi_query", True) else H,
            d_ff=d.get("ffn_hidden_size") or 4 * D,
            max_seq_len=d.get("max_position_embeddings", 2048),
            activation="gelu_exact", norm="layernorm",
            tie_embeddings=d.get("tie_word_embeddings", True),
            rope_theta=d.get("rope_theta", 10000.0),
            rope_scaling=_parse_rope_scaling(d), parallel_block=True,
            norm_eps=d.get("layer_norm_epsilon", 1e-5),
        )
    if mt == "phi":
        return ModelConfig(
            name=nm, vocab_size=d["vocab_size"], d_model=d["hidden_size"],
            n_layers=d["num_hidden_layers"], n_heads=d["num_attention_heads"],
            n_kv_heads=d.get("num_key_value_heads") or d["num_attention_heads"],
            d_ff=d["intermediate_size"],
            max_seq_len=d.get("max_position_embeddings", 2048),
            activation="gelu", norm="layernorm", use_bias=True,
            tie_embeddings=False,
            rotary_pct=d.get("partial_rotary_factor", 1.0),
            rope_theta=d.get("rope_theta", 10000.0),
            rope_scaling=_parse_rope_scaling(d), parallel_block=True,
            lm_head_bias=True, norm_eps=d.get("layer_norm_eps", 1e-5),
        )
    if mt == "qwen3_moe":
        if not d.get("norm_topk_prob", False):
            # our routing renormalizes the top-k weights (softmax over the
            # selected logits == softmax-all + renorm); without the renorm
            # the weighting differs — refuse, don't serve drifted mixtures
            raise ValueError(
                "qwen3_moe with norm_topk_prob=false is not supported by "
                "the native core (routing weights would differ)"
            )
        if d.get("decoder_sparse_step", 1) != 1 or d.get("mlp_only_layers"):
            raise ValueError(
                "qwen3_moe with dense interleaved layers "
                "(decoder_sparse_step != 1 / mlp_only_layers) is not "
                "supported by the native core"
            )
        if d.get("attention_bias"):
            raise ValueError(
                "qwen3_moe attention_bias=true is not supported by the "
                "native core (o_proj bias)"
            )
        H = d["num_attention_heads"]
        # Qwen3MoeConfig has NO head_dim parameter — transformers falls
        # back to hidden_size // num_attention_heads when absent (unlike
        # dense Qwen3Config's 128 default)
        hd = d.get("head_dim")
        kw3: dict = dict(
            name=nm, vocab_size=d["vocab_size"], d_model=d["hidden_size"],
            n_layers=d["num_hidden_layers"], n_heads=H,
            # class default is 4, NOT n_heads (the family-default rule)
            n_kv_heads=d.get("num_key_value_heads", 4),
            # expert width, not the (unused) dense intermediate_size
            d_ff=d["moe_intermediate_size"],
            max_seq_len=d.get("max_position_embeddings", 32768),
            rope_theta=d.get("rope_theta", 10000.0),
            rope_scaling=_parse_rope_scaling(d, 32768),
            norm_eps=d.get("rms_norm_eps", 1e-6),
            tie_embeddings=d.get("tie_word_embeddings", False),
            qk_norm=True,
            n_experts=d["num_experts"],
            n_experts_per_tok=d.get("num_experts_per_tok", 8),
        )
        if d.get("use_sliding_window") and d.get("sliding_window"):
            # unlike dense qwen, Qwen3Moe modeling never reads
            # max_window_layers — it windows EVERY layer when enabled
            kw3["sliding_window"] = d["sliding_window"]
        if hd and hd != d["hidden_size"] // H:
            kw3["head_dim_override"] = hd
        return ModelConfig(**kw3)
    if mt == "olmo2":
        if d.get("attention_bias"):
            # same refuse-don't-drop rule as the llama branch: the o_proj
            # bias has no slot in our layout
            raise ValueError(
                "olmo2 checkpoints with attention_bias=true are not "
                "supported by the native core; serve via the ollama/remote "
                "backends"
            )
        H = d["num_attention_heads"]
        return ModelConfig(
            name=nm, vocab_size=d["vocab_size"], d_model=d["hidden_size"],
            n_layers=d["num_hidden_layers"], n_heads=H,
            n_kv_heads=d.get("num_key_value_heads") or H,
            d_ff=d["intermediate_size"],
            max_seq_len=d.get("max_position_embeddings", 2048),
            rope_theta=d.get("rope_theta", 10000.0),
            rope_scaling=_parse_rope_scaling(d),
            norm_eps=d.get("rms_norm_eps", 1e-5),
            tie_embeddings=d.get("tie_word_embeddings", False),
            # olmo2 blocks norm only their OUTPUTS, and RMS-normalize the
            # WHOLE q/k projection before the head reshape
            post_norms=True, no_pre_norms=True,
            qk_norm=True, qk_norm_full=True,
        )
    if mt == "stablelm":
        if d.get("use_parallel_residual"):
            raise ValueError(
                "stablelm use_parallel_residual=true is not supported by "
                "the native core's stablelm path"
            )
        if d.get("qk_layernorm"):
            raise ValueError(
                "stablelm qk_layernorm=true (per-head LayerNorm) is not "
                "supported by the native core"
            )
        H = d["num_attention_heads"]
        return ModelConfig(
            name=nm, vocab_size=d["vocab_size"], d_model=d["hidden_size"],
            n_layers=d["num_hidden_layers"], n_heads=H,
            n_kv_heads=d.get("num_key_value_heads") or H,
            d_ff=d["intermediate_size"],
            max_seq_len=d.get("max_position_embeddings", 4096),
            norm="layernorm",  # biased LNs over the llama tensor layout
            rotary_pct=d.get("partial_rotary_factor", 0.25),
            rope_theta=d.get("rope_theta", 10000.0),
            rope_scaling=_parse_rope_scaling(d, 4096),
            qkv_bias=d.get("use_qkv_bias", False),
            tie_embeddings=d.get("tie_word_embeddings", False),
            norm_eps=d.get("layer_norm_eps", 1e-5),
        )
    if mt == "phi3":
        # architecturally a llama-branch model (the loader un-fuses
        # qkv_proj / gate_up_proj); partial rotary + optional window
        H = d["num_attention_heads"]
        return ModelConfig(
            name=nm, vocab_size=d["vocab_size"], d_model=d["hidden_size"],
            n_layers=d["num_hidden_layers"], n_heads=H,
            n_kv_heads=d.get("num_key_value_heads") or H,
            d_ff=d["intermediate_size"],
            max_seq_len=d.get("max_position_embeddings", 4096),
            rope_theta=d.get("rope_theta", 10000.0),
            rope_scaling=_parse_rope_scaling(d, 4096),  # longrope refuses
            rotary_pct=d.get("partial_rotary_factor", 1.0),
            norm_eps=d.get("rms_norm_eps", 1e-5),
            tie_embeddings=d.get("tie_word_embeddings", False),
            sliding_window=d.get("sliding_window"),
        )
    if mt == "falcon_h1":
        return _falcon_h1_from_hf(d, nm)
    if mt == "joyai_llm_flash":
        return _joyai_from_hf(d, nm)
    if mt == "smallthinker" or str(d.get("model_name", "")).startswith(
            "smallthinker_"):
        return _smallthinker_from_hf(d, name or d.get("_name_or_path")
                                     or d.get("model_name") or nm)
    if mt == "ouro":
        return _ouro_from_hf(d, nm)
    if mt == "granitemoehybrid":
        return _granite_hybrid_from_hf(d, nm)
    if mt == "exaone_moe":
        return _exaone_moe_from_hf(d, nm)
    if mt == "nemotron_h":
        return _nemotron_h_from_hf(d, nm)
    if mt == "gemma3":
        raise ValueError(
            "gemma3 multimodal configs are not supported; extract the "
            "text_config (model_type gemma3_text) or serve via the "
            "ollama/remote backends"
        )
    if mt == "gemma3_text":
        L = d["num_hidden_layers"]
        types = d.get("layer_types")
        if types:
            sliding = {i for i, t in enumerate(types)
                       if t == "sliding_attention"}
            # recover a periodic (every, residues) description; gemma-3
            # ships 5-local-1-global (period 6)
            found = _layer_period(sliding, len(types), min(len(types), 12))
            if found is None:
                raise ValueError(
                    "gemma3 layer_types pattern is not periodic; cannot "
                    "represent it"
                )
            every, res = found
        else:
            # no layer_types (older transformers writers): the pattern key
            # is sliding_window_pattern (Gemma3TextConfig default 6),
            # is_sliding = (i+1) % pattern != 0 — i.e. every pattern-th
            # layer is global, the rest are local. Hardcoding 5-local-1-
            # global here would silently mis-mask (and mis-rope) any
            # checkpoint shipping a non-default pattern.
            pattern = int(d.get("sliding_window_pattern") or 6)
            every = max(pattern, 1)
            res = tuple(r for r in range(every) if (r + 1) % every != 0)
        window = d.get("sliding_window", 4096)
        if not res:
            # no sliding layers at all (e.g. a long-context fine-tune):
            # every-1 + the window set would make make_layer_mask window
            # EVERY layer — disable the window instead
            window, every, res = None, 1, ()
        return ModelConfig(
            name=nm, vocab_size=d["vocab_size"], d_model=d["hidden_size"],
            n_layers=L, n_heads=d["num_attention_heads"],
            n_kv_heads=d.get("num_key_value_heads")
            or d["num_attention_heads"],
            d_ff=d["intermediate_size"],
            max_seq_len=d.get("max_position_embeddings", 131072),
            activation="geglu", embedding_scale=True, norm_plus_one=True,
            post_norms=True, qk_norm=True,
            attn_scale=d.get("query_pre_attn_scalar", 256),
            attn_logit_softcap=d.get("attn_logit_softcapping"),
            logits_softcap=d.get("final_logit_softcapping"),
            rope_theta=d.get("rope_theta", 1000000.0),
            local_rope_theta=d.get("rope_local_base_freq", 10000.0),
            rope_scaling=_parse_rope_scaling(d, 131072),
            norm_eps=d.get("rms_norm_eps", 1e-6),
            tie_embeddings=d.get("tie_word_embeddings", True),
            # every/residues stay decoupled from the window: even with the
            # window disabled they still drive the local/global ROPE split
            sliding_window=window,
            sliding_window_every=every,
            sliding_window_residues=res,
            **({"head_dim_override": hd} if (
                hd := d.get("head_dim", 256)
            ) and hd != d["hidden_size"] // d["num_attention_heads"]
               else {}),
        )
    if mt in ("llama", "mistral", "qwen2", "qwen3", "gemma", "gemma2",
              "mixtral"):
        n_heads = d["num_attention_heads"]
        # transformers serializes config.json as a DIFF against each
        # Config class's defaults — absent keys mean the FAMILY default
        # (values introspected from the installed transformers; a wrong
        # fallback here silently drifts every norm / truncates context)
        gemma_like = mt in ("gemma", "gemma2")
        hd = d.get("head_dim",
                   {"gemma": 256, "gemma2": 256, "qwen3": 128}.get(mt))
        default_maxpos = {"llama": 2048, "mistral": 131072,
                          "mixtral": 131072, "qwen2": 32768,
                          "qwen3": 32768, "gemma": 8192, "gemma2": 8192}[mt]
        kw: dict = dict(
            name=nm, vocab_size=d["vocab_size"], d_model=d["hidden_size"],
            n_layers=d["num_hidden_layers"], n_heads=n_heads,
            n_kv_heads=d.get("num_key_value_heads") or n_heads,
            d_ff=d["intermediate_size"],
            max_seq_len=d.get("max_position_embeddings", default_maxpos),
            rope_theta=d.get("rope_theta",
                             1000000.0 if mt == "mixtral" else 10000.0),
            # every family defaults rms_norm_eps=1e-6 EXCEPT mixtral (1e-5)
            norm_eps=d.get("rms_norm_eps",
                           1e-5 if mt == "mixtral" else 1e-6),
            tie_embeddings=d.get("tie_word_embeddings", gemma_like),
            qkv_bias=mt == "qwen2",
            qk_norm=mt == "qwen3",
        )
        if (scaling := _parse_rope_scaling(d, default_maxpos)) is not None:
            kw["rope_scaling"] = scaling
        if d.get("attention_bias"):
            # HF attention_bias puts biases on q/k/v AND o_proj; our
            # llama-branch layout carries q/k/v biases only (qwen2 style),
            # so the o_proj bias would be silently dropped — refuse rather
            # than serve offset logits
            raise ValueError(
                "llama-family checkpoints with attention_bias=true are not "
                "supported by the native core (o_proj bias); serve via the "
                "ollama/remote backends"
            )
        if hd and hd != d["hidden_size"] // n_heads:
            kw["head_dim_override"] = hd
        if mt == "mistral":
            # an ABSENT key means MistralConfig's class default (4096) —
            # the same "config.json is a diff against class defaults" rule
            # gemma-2 follows below; an explicit null stays disabled
            window = d.get("sliding_window", 4096)
            if window:
                kw["sliding_window"] = window
        elif mt == "mixtral" and d.get("sliding_window"):
            # MixtralConfig's class default is null — absent means off
            kw["sliding_window"] = d["sliding_window"]
        if (mt in ("qwen2", "qwen3") and d.get("use_sliding_window")
                and d.get("sliding_window")):
            mwl = int(d.get("max_window_layers") or 0)
            if mwl <= 0:
                kw["sliding_window"] = d["sliding_window"]
            elif mwl >= int(d["num_hidden_layers"]):
                # HF windows only layers >= max_window_layers, so a cap at
                # (or past) the layer count windows NOTHING — full
                # attention is bit-exact, not a compromise: stay silent
                pass
            else:
                # HF windows only layers >= max_window_layers; our config
                # windows EVERY layer, so a partial-window checkpoint
                # (max_window_layers > 0) is served full-attention instead —
                # exact for prompts within the window and matches HF on the
                # majority (first) layers, vs. silently wrong everywhere.
                # Say so at serve time: this is a fidelity compromise.
                logger.warning(
                    "%s: dropping the partial sliding-window schedule "
                    "(sliding_window=%s, max_window_layers=%s) — serving "
                    "full attention on every layer; long-context logits "
                    "will diverge from HF beyond the window",
                    nm, d.get("sliding_window"), d.get("max_window_layers"),
                )
        if mt in ("gemma", "gemma2"):
            act = d.get("hidden_activation") or d.get("hidden_act") or "gelu_pytorch_tanh"
            kw.update(
                activation="geglu" if act.startswith("gelu") else act,
                embedding_scale=True, norm_plus_one=True,
            )
        if mt == "gemma2":
            # transformers serializes config.json as a DIFF against class
            # defaults — an absent key means the Gemma2Config DEFAULT
            # (50/30/256/4096), NOT disabled; an explicit null stays None
            window = d.get("sliding_window", 4096)
            kw.update(
                post_norms=True,
                attn_logit_softcap=d.get("attn_logit_softcapping", 50.0),
                logits_softcap=d.get("final_logit_softcapping", 30.0),
                attn_scale=d.get("query_pre_attn_scalar", 256),
                # HF Gemma2: is_sliding = not bool(layer_idx % 2) — even
                # layers window, odd attend fully
                sliding_window=window,
                sliding_window_every=2 if window else 1,
            )
        if mt == "mixtral":
            kw.update(n_experts=d["num_local_experts"],
                      n_experts_per_tok=d.get("num_experts_per_tok", 2))
        return ModelConfig(**kw)
    raise ValueError(
        f"unsupported model_type {mt!r} in config.json — native serving "
        f"covers gpt2/llama/mistral/qwen2/gemma/mixtral/phi/gpt_neox/gptj/"
        f"falcon_h1/joyai_llm_flash/smallthinker/ouro/granitemoehybrid/"
        f"exaone_moe/nemotron_h; "
        f"other architectures can be served via the ollama/remote backends"
    )


def config_for_checkpoint(path: str | Path, name: str | None = None) -> ModelConfig:
    """Resolve a checkpoint DIRECTORY to a ModelConfig from its own
    metadata: a native save (model_config.json, our field names) or an HF
    checkpoint (config.json). This is what lets ``serve-tpu --model auto
    --checkpoint <dir>`` serve architectures with no registry entry."""
    path = Path(path)
    native = path / "model_config.json"
    if native.exists():
        d = json.loads(native.read_text())
        known = {f.name for f in fields(ModelConfig)}
        unknown = sorted(set(d) - known)
        if unknown:
            # a checkpoint saved by a newer version may carry architecture
            # switches this build doesn't know; dropping them silently
            # would serve wrong logits with no signal
            logger.warning(
                "%s: ignoring unknown model_config.json keys %s — if these "
                "are architecture switches from a newer writer, the served "
                "logits will diverge",
                native, unknown,
            )
        return ModelConfig(**{k: v for k, v in d.items() if k in known})
    hf = path / "config.json"
    if hf.exists():
        return config_from_hf(json.loads(hf.read_text()), name=name)
    raise FileNotFoundError(
        f"no model_config.json or config.json under {path} — cannot "
        f"synthesize a model config for this checkpoint"
    )


def resolve_model_config(model, checkpoint_path: str | None = None) -> ModelConfig:
    """THE model-resolution rule shared by the engine and the pipeline
    stage runner: a ModelConfig passes through; a registry name resolves
    via get_config; an unknown name (or the 'auto' sentinel) with a
    checkpoint falls back to the checkpoint's own config
    (config_for_checkpoint) — the reference's AutoModel any-checkpoint
    capability."""
    if isinstance(model, ModelConfig):
        return model
    try:
        return get_config(model or "auto")
    except KeyError:
        if not checkpoint_path:
            raise
        return config_for_checkpoint(
            checkpoint_path,
            name=None if model in (None, "", "auto") else model,
        )


def get_config(name: str, **overrides) -> ModelConfig:
    """Resolve a model name to a config, with the reference's both-ways fuzzy
    match (`services.py:136-151`): exact key, else substring either way."""
    key = name.lower().strip()
    if key in CONFIGS:
        cfg = CONFIGS[key]
    else:
        short = key.split("/")[-1]
        flat = lambda s: s.replace("-", "").replace("_", "").replace(".", "")
        # tiny-* test presets never match a real checkpoint name unless the
        # query itself says "tiny"
        pool = {
            k: c for k, c in CONFIGS.items()
            if "tiny" in short or not k.startswith("tiny-")
        }
        # tiers: exact short name > key contained in query > query contained
        # in key. Tie-breaks differ by direction: when the KEY is inside the
        # query (tier 2), the longest key is the most specific match; when
        # the QUERY is inside several keys (tier 3, e.g. "llama-3" matching
        # both -8b and -70b), the SHORTEST key is the family default — the
        # longest would silently resolve a bare family name to its biggest
        # member
        tiers = (
            ([k for k in pool if k == short or flat(k) == flat(short)], max),
            ([k for k in pool if flat(k) in flat(short)], max),
            ([k for k in pool if flat(short) in flat(k)], min),
        )
        hit = next(((t, pick) for t, pick in tiers if t), None)
        if hit is None:
            raise KeyError(f"no model config matches {name!r}; known: {sorted(CONFIGS)}")
        t, pick = hit
        cfg = pool[pick(t, key=len)]
    return replace(cfg, **overrides) if overrides else cfg
