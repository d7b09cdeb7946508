"""The transformer core: init + forward for every supported family.

Design (TPU-first, not a port of reference hf.py):

- **Stacked layer params + `lax.scan`**: all per-layer weights carry a
  leading `n_layers` dim and the layer loop is a `lax.scan`, so XLA traces
  one layer body regardless of depth — compile time and HLO size are O(1)
  in n_layers.
- **Single forward for prefill and decode**: the same function handles a
  [B, T] chunk against a fixed-capacity KV cache at a given offset. T=1 is
  the decode step; T=bucket is prefill. Static shapes everywhere — the
  cache is preallocated at `max_seq_len`, masking handles validity.
- **GQA by construction**: K/V heads are repeated via reshape-broadcast
  (no materialized repeat when XLA fuses).
- **bfloat16 compute, f32 accumulations** where it matters (attention
  logits, softmax, norms, router logits).

The param tree is a flat-ish nested dict; see init_params for the schema.
Partition rules over the same paths live in partition.py.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.grouped import grouped_matmul
from ..ops.ssm_step import ssm_state_step, ssm_state_step_xla
from .config import ModelConfig

Params = dict[str, Any]


# ---------------------------------------------------------------- init


def _dense_init(key, shape, scale=None, dtype=jnp.float32):
    # fan-in is the second-to-last dim: layer-stacked weights are [L, in, out]
    fan_in = shape[-2] if len(shape) > 1 else shape[0]
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    # the barrier keeps the scale multiply out of the sampler's fusion, so
    # the values are bit-identical whether this runs eagerly or inside
    # init_params' one jitted program (checked on the CPU backend; seeded
    # tests pin near-tie greedy tokens to these exact weights)
    return lax.optimization_barrier(jax.random.normal(key, shape, dtype)) * scale


def matmul_params_per_token(cfg: ModelConfig) -> int:
    """Matmul weight elements each token position streams through one
    forward — the ``2·N`` half of the engine economics plane's FLOPs
    model (engine/introspect.py): every counted element costs one
    multiply + one add per position.

    Counted: q/k/v/o projections, the dense MLP (gated → 3 matrices), the
    lm head (tied or not — the logits matmul runs either way), and for
    MoE the router plus only the ``n_experts_per_tok`` ACTIVE experts —
    what a routed token actually pays, matching the "routed" impl (the
    "dense" correctness impl physically computes all E experts, but MFU
    is defined on the model's useful math, not an impl's redundancy).
    Excluded: embeddings lookup, norms, biases, rope — O(D) noise next
    to the O(D²) terms. A recurrent mixer (falcon-h1) adds its W_in and
    W_out."""
    D, F, L = cfg.d_model, cfg.d_ff, cfg.n_layers
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if cfg.has_mla:  # the five matrices of core._mla_attention
        attn = (
            D * cfg.mla_q_rank
            + cfg.mla_q_rank * H * (cfg.mla_nope_dim + cfg.mla_rope_dim)
            + D * cfg.latent_width
            + cfg.mla_kv_rank * H * (cfg.mla_nope_dim + cfg.mla_v_dim)
            + H * cfg.mla_v_dim * D
        )
    else:
        attn = D * (H * hd) + 2 * D * (Hkv * hd) + (H * hd) * D
    gated = cfg.gated_mlp
    mlp_one = (3 if gated else 2) * D * F
    if cfg.is_moe:
        # (experts in a latent, cfg.moe_latent, read and write ITS width; the
        # two projections into and out of it are paid once a token)
        one = (3 if gated else 2) * cfg.expert_in * cfg.expert_ff
        # under an expert share a token pays HERE for its choices held here
        routed = cfg.n_experts_per_tok * cfg.experts_held / cfg.n_experts
        moe = (D * cfg.n_experts + routed * one
               + (3 if gated else 2) * D * cfg.shared_ff
               + 2 * D * cfg.moe_latent)
        # leading dense layers (first_k_dense) pay the dense MLP instead
        mlps = cfg.first_k_dense * mlp_one + cfg.n_expert_layers * moe
    else:
        mlps = L * mlp_one
    # falcon-h1: the mixer's in- and out-projection (the scan itself is
    # O(inner * state) a token — under 1 % of the block — and not counted)
    ssm = D * cfg.ssm_proj_dim + cfg.ssm_inner * D if cfg.has_ssm else 0
    if cfg.layer_types:  # a layer pays for ONE mixer kind
        return int(cfg.cache_layers * attn + cfg.state_layers * ssm + mlps
                   + D * cfg.vocab_size)
    # a looped stack streams every layer loop_steps times a token, the head once
    return int(cfg.loop_steps * (L * (attn + ssm) + mlps) + D * cfg.vocab_size)


def init_params(cfg: ModelConfig, key, dtype=jnp.bfloat16,
                out_shardings=None) -> Params:
    """Random-init params with the layout the whole framework shares.

    The init runs as ONE jitted program, so with ``out_shardings`` (a
    matching pytree of shardings — partition.param_shardings over
    ``jax.eval_shape`` of this function) every device generates only its
    own shard: a 7B model on a model:4 mesh never lands whole on device 0.
    The values depend on (cfg, key, dtype) alone, not on the sharding.

    Schema (leading L = n_layers stacked dim):
      tok_embed [V, D]; pos_embed [P, D] (learned-pos only);
      final_norm {scale[D], (bias[D])}; lm_head [D, V] (untied only,
      + lm_head_bias [V] when cfg.lm_head_bias — phi)
      layers/
        ln1.scale|bias [L, D]
        attn: wq [L, D, H*hd], wk|wv [L, D, Hkv*hd], wo [L, H*hd, D]
              (+ bq, bk, bv [L, ...], bo [L, D] when use_bias)
        ln2.scale|bias [L, D] (absent for shared-norm parallel blocks — phi)
        dense mlp: w_up [L, D, F], w_down [L, F, D], (w_gate [L, D, F])
                   (+ b_up [L, F], b_down [L, D])
        moe: router [L, D, E], experts w_up|w_gate [L, E, D, F],
             w_down [L, E, F, D]
        ssm (cfg.has_ssm — falcon-h1's Mamba-2 mixer): w_in [L, D, proj]
             (proj = inner z + [x; B; C] + heads dt), conv_w [L, C, K],
             conv_b [L, C], norm [L, inner], w_out [L, inner, D] in
             ``dtype``; dt_bias, A_log, D [L, heads] ALWAYS float32
        attn under latent attention (cfg.has_mla) instead: wq_a [L, D, qr],
             q_a_norm [L, qr], wq_b [L, qr, H*(nope+rope)], wkv_a
             [L, D, kvr+rope], kv_a_norm [L, kvr], wkv_b [L, kvr,
             H*(nope+v)], wo [L, H*v, D]
        moe under the sigmoid router (cfg.moe_router "sigmoid") also: router_bias
             [L, E] ALWAYS float32 (balanced on seeded traffic as training
             balances it, balance_router_bias: nonzero, so selection and
             weight differ), shared {w_gate, w_up [L, D, Fs], w_down
             [L, Fs, D]}; expert matrices are cfg.expert_ff wide
      under cfg.layer_types (granite-4.0-h: ONE mixer kind a layer) ``layers``
        holds ln1 / ln2 / moe stacked over all L layers, ``ssm`` over the
        cfg.state_layers "mamba" layers alone and ``attn`` over the
        cfg.cache_layers "attention" layers alone, each in layer order: a
        layer finds its mixer by cfg.state_slots / cfg.cache_slots. Under an
        expert share (cfg.n_experts_held) the expert stacks are [L, held,
        ...], the router stays [L, D, E]; the shared expert is cfg.shared_ff
        wide
      under cfg.single_branch (nemotron-h: a layer is ONE branch under ONE
        norm, ``layer_types``' third kind "moe") ``layers`` holds ln1 alone
        over all L layers, ``ssm`` / ``attn`` as above and ``moe`` over the
        cfg.n_expert_layers "moe" layers alone (cfg.moe_slots): no ln2, no
        second branch. With cfg.moe_latent the expert matrices are [.., Eh,
        Dl, F] / [.., Eh, F, Dl] beside ``latent_in`` [.., D, Dl] and
        ``latent_out`` [.., Dl, D]; an ungated activation ("relu2") has no
        ``w_gate``, in the experts or in ``shared``
      mtp/ (cfg.mtp_layers, K-EXAONE's multi-token-prediction layer): enorm /
        hnorm {scale [D]}, eh_proj [2 D, D], block: ONE layer of the trunk's
        schema stacked [1, ...] (an expert layer where the trunk has them);
        the embedding, the final norm and the head are the trunk's
      dense_layers/ (cfg.first_k_dense > 0 only): the LEADING dense
        layers, the same schema with a dense mlp, stacked [k, ...];
        ``layers`` then holds the n_layers - k expert layers. Layers of
        unlike trees go through forward's loop as groups of like layers.
    """
    # cfg and dtype are static arguments of the one module-level function,
    # so repeated inits of a config reuse its trace (a fresh partial or
    # lambda per call would retrace every time)
    params = jax.jit(
        _init_params, static_argnums=(0, 2), out_shardings=out_shardings
    )(cfg, key, jnp.dtype(dtype))
    if cfg.moe_dropless:
        # a noaux_tc router is TRAINED to an even load by its selection
        # bias; seeded weights get theirs the same way (balance_router_bias).
        # A router with no bias gets the same even load by a rule on its
        # weights (center_router): no parameter is added
        name, rule = (("router_bias", balance_router_bias)
                      if cfg.moe_router == "sigmoid" and cfg.moe_select_bias
                      else ("router", center_router))
        moe = params["layers"]["moe"]
        sharding = (None if out_shardings is None
                    else out_shardings["layers"]["moe"][name])
        if cfg.mtp_layers:  # (the trunk's routers, the MTP block's)
            sharding = (None if out_shardings is None else (
                sharding, out_shardings["mtp"]["block"]["moe"][name]))
        new = jax.jit(rule, static_argnums=1, out_shardings=sharding)(
            params, cfg)
        if cfg.mtp_layers:
            new, new_mtp = new
            block = params["mtp"]["block"]
            params = dict(params, mtp=dict(params["mtp"], block=dict(
                block, moe=dict(block["moe"], **{name: new_mtp}))))
        params = dict(params, layers=dict(
            params["layers"], moe=dict(moe, **{name: new})))
    return params


def _init_params(cfg: ModelConfig, key, dtype) -> Params:
    D, F, V, L = cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.n_layers
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    keys = iter(jax.random.split(key, 32))

    def dense(shape, scale=None):
        return _dense_init(next(keys), shape, scale, dtype)

    # a router without a bias is evened on what a token has of its OWN
    # (center_router): at unit scale a seeded token's own part stays visible
    # behind thousands of context tokens (at 0.02 every row 6k deep held
    # nearly the same state and 32 rows hit 62 % of a layer's experts, PR 43)
    # (a sigmoid router without a selection bias is evened the same way)
    embed_std = 1.0 if (cfg.moe_router == "softmax_topk"
                        or not cfg.moe_select_bias) else 0.02
    if cfg.tie_embeddings and cfg.moe_dropless:
        # a TIED head reads a token's own embedding back: behind the
        # embedding multiplier m the input's own logit stands 12 s sqrt(D) /
        # rms(x) deviations over the rest at std s, and every row would
        # repeat its last token. At 0.75 / (m sqrt(D)) (granite: 2^-10) it is
        # one logit among the others; the first norm restores the scale
        embed_std = 0.75 / (cfg.embedding_multiplier * math.sqrt(D))
    params: Params = {
        "tok_embed": _dense_init(next(keys), (V, D), scale=embed_std, dtype=dtype),
    }
    if cfg.pos_embedding == "learned":
        params["pos_embed"] = _dense_init(next(keys), (cfg.max_seq_len, D), 0.02, dtype)
    if cfg.embedding_norm:
        params["embed_norm"] = {"scale": jnp.ones((D,), dtype)}
        if cfg.norm == "layernorm" and cfg.norm_bias:
            params["embed_norm"]["bias"] = jnp.zeros((D,), dtype)

    def layer_group(L, moe_layers, La=None, Ls=None, Lm=None):
        """One group of ``L`` like layers (dense MLP or expert layers);
        under cfg.layer_types ``La`` of them hold attention and ``Ls`` a
        mixer (default: all), under cfg.single_branch ``Lm`` an expert layer
        (``L`` then counts the one norm a layer)."""
        La, Ls = (L if La is None else La), (L if Ls is None else Ls)
        Lm = L if Lm is None else Lm
        if cfg.has_mla:
            qk = cfg.mla_nope_dim + cfg.mla_rope_dim
            attn = {
                "wq_a": dense((La, D, cfg.mla_q_rank)),
                "q_a_norm": jnp.ones((La, cfg.mla_q_rank), dtype),
                "wq_b": dense((La, cfg.mla_q_rank, H * qk)),
                "wkv_a": dense((La, D, cfg.latent_width)),
                "kv_a_norm": jnp.ones((La, cfg.mla_kv_rank), dtype),
                "wkv_b": dense((La, cfg.mla_kv_rank,
                                H * (cfg.mla_nope_dim + cfg.mla_v_dim))),
                "wo": dense((La, H * cfg.mla_v_dim, D)),
            }
        else:
            attn = {
                "wq": dense((La, D, H * hd)),
                "wk": dense((La, D, Hkv * hd)),
                "wv": dense((La, D, Hkv * hd)),
                "wo": dense((La, H * hd, D), scale=1.0 / math.sqrt(H * hd)),
            }
        layers: Params = {"attn": attn}
        if cfg.use_bias or cfg.qkv_bias:
            layers["attn"]["bq"] = jnp.zeros((La, H * hd), dtype)
            layers["attn"]["bk"] = jnp.zeros((La, Hkv * hd), dtype)
            layers["attn"]["bv"] = jnp.zeros((La, Hkv * hd), dtype)
        if cfg.qk_norm:  # qwen3: per-head scales; olmo2: full-width scales
            qn = (H * hd, Hkv * hd) if cfg.qk_norm_full else (hd, hd)
            layers["attn"]["q_norm"] = jnp.ones((La, qn[0]), dtype)
            layers["attn"]["k_norm"] = jnp.ones((La, qn[1]), dtype)
        if cfg.use_bias:  # qwen2 (qkv_bias) has NO output-projection bias
            layers["attn"]["bo"] = jnp.zeros((La, D), dtype)
        if not cfg.no_pre_norms:  # olmo2 blocks norm only their OUTPUTS
            layers["ln1"] = {"scale": jnp.ones((L, D), dtype)}
            if (not cfg.parallel_block or cfg.parallel_norms == 2) and (
                    not cfg.single_branch):
                # sequential blocks AND neox-style dual-norm parallel blocks
                # have ln2; only phi's shared-norm parallel blocks and a
                # layer of one branch drop it
                layers["ln2"] = {"scale": jnp.ones((L, D), dtype)}
        if cfg.post_norms:  # gemma-2: norms on the attn/mlp outputs too
            # a looped stack's SEEDED output norms start at 1 / sqrt(the
            # branches a token passes): at scale 1 every one of ouro's 384
            # adds a unit vector, attention's near-uniform average over the
            # context outweighs a token's own part at once and every row of a
            # batch emits the SAME greedy token whatever its prompt (PR 46,
            # on the chip: 4 distinct tokens in 16 rows x 24 steps; at this
            # scale 84, as unlike prompts should give)
            post = (1.0 / math.sqrt(2 * cfg.cache_layers)
                    if cfg.loop_steps > 1 else 1.0)
            layers["ln1_post"] = {"scale": jnp.full((L, D), post, dtype)}
            layers["ln2_post"] = {"scale": jnp.full((L, D), post, dtype)}
        if cfg.norm == "layernorm" and cfg.norm_bias:
            for ln in ("ln1", "ln2", "ln1_post", "ln2_post"):
                if ln in layers:
                    layers[ln]["bias"] = jnp.zeros((L, D), dtype)
        gated = cfg.gated_mlp
        if moe_layers:
            # (Eh: the experts held HERE; the router keeps every output)
            E, Eh, Fe = cfg.n_experts, cfg.experts_held, cfg.expert_ff
            De = cfg.expert_in  # the experts' own width: a latent's or D
            moe = {
                "router": dense((Lm, D, E)),
                "w_up": dense((Lm, Eh, De, Fe)),
                "w_down": dense((Lm, Eh, Fe, De), scale=1.0 / math.sqrt(Fe)),
            }
            if gated:
                moe["w_gate"] = dense((Lm, Eh, De, Fe))
            if cfg.moe_router == "sigmoid" and cfg.moe_select_bias:
                # float32 always; init_params sets it (balance_router_bias)
                moe["router_bias"] = jnp.zeros((Lm, E), jnp.float32)
            if cfg.n_shared_experts:
                Fs = cfg.shared_ff
                # (the keys' order is the seeded weights': gate, up, down)
                shared = {"w_gate": dense((Lm, D, Fs))} if gated else {}
                shared["w_up"] = dense((Lm, D, Fs))
                shared["w_down"] = dense(
                    (Lm, Fs, D), scale=1.0 / math.sqrt(Fs))
                moe["shared"] = shared
            if cfg.moe_latent:
                moe["latent_in"] = dense((Lm, D, De))
                moe["latent_out"] = dense((Lm, De, D))
            layers["moe"] = moe
        else:
            mlp = {
                "w_up": dense((L, D, F)),
                "w_down": dense((L, F, D), scale=1.0 / math.sqrt(F)),
            }
            if gated:
                mlp["w_gate"] = dense((L, D, F))
            if cfg.use_bias or cfg.mlp_bias:
                mlp["b_up"] = jnp.zeros((L, F), dtype)
                mlp["b_down"] = jnp.zeros((L, D), dtype)
            layers["mlp"] = mlp

        if cfg.has_ssm:
            Hs, K, C = cfg.ssm_heads, cfg.ssm_conv, cfg.ssm_conv_dim
            # what random-normal does not suit, by the Mamba-2 convention:
            # A_log = log(1..heads) (S4D-real), D = 1, dt_bias = the inverse
            # softplus of dt drawn log-uniform in [1e-3, 1e-1], norm scale 1.
            # The three per-head vectors stay float32 whatever the dtype:
            # exp(A_log) and softplus(dt + dt_bias) set every step's decay
            dt = jnp.exp(
                jax.random.uniform(next(keys), (Ls, Hs), jnp.float32)
                * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3)
            )
            layers["ssm"] = {
                "w_in": dense((Ls, D, cfg.ssm_proj_dim)),
                "conv_w": dense((Ls, C, K), scale=1.0 / math.sqrt(K)),
                "conv_b": jnp.zeros((Ls, C), dtype),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.broadcast_to(
                    jnp.log(jnp.arange(1, Hs + 1, dtype=jnp.float32)), (Ls, Hs)),
                "D": jnp.ones((Ls, Hs), jnp.float32),
                "norm": jnp.ones((Ls, cfg.ssm_inner), dtype),
                "w_out": dense((Ls, cfg.ssm_inner, D)),
            }

        return layers

    k_dense = cfg.first_k_dense
    if cfg.layer_types:
        params["layers"] = layer_group(
            L, cfg.is_moe, La=cfg.cache_layers, Ls=cfg.state_layers,
            Lm=cfg.n_expert_layers if cfg.single_branch else None)
    else:
        params["layers"] = layer_group(L - k_dense, cfg.is_moe)
    if k_dense:
        params["dense_layers"] = layer_group(k_dense, False)
    params["final_norm"] = {"scale": jnp.ones((D,), dtype)}
    if cfg.norm == "layernorm" and cfg.norm_bias:
        params["final_norm"]["bias"] = jnp.zeros((D,), dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense((D, V))
        if cfg.lm_head_bias:  # phi: untied head carries a bias
            params["lm_head_bias"] = jnp.zeros((V,), dtype)
    if cfg.mtp_layers:
        # keys of its own: the trunk's weights are what they are without it
        keys = iter(jax.random.split(jax.random.fold_in(key, 1), 16))
        params["mtp"] = {
            "enorm": {"scale": jnp.ones((D,), dtype)},
            "hnorm": {"scale": jnp.ones((D,), dtype)},
            "eh_proj": dense((2 * D, D)),
            "block": layer_group(cfg.mtp_layers, cfg.is_moe),
        }
    return params


# ---------------------------------------------------------------- ops


def _norm(x, p, cfg: ModelConfig):
    xf = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        xf = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + cfg.norm_eps)
    else:
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        xf = (xf - mean) * lax.rsqrt(var + cfg.norm_eps)
    out = xf.astype(x.dtype) * p["scale"]
    if "bias" in p:
        out = out + p["bias"]
    return out


def scale_rope_freqs(freqs, scaling: tuple | None, theta: float | None = None,
                     rot: int | None = None):
    """Frequency-domain RoPE scaling (cfg.rope_scaling).

    "linear": all frequencies divided by the factor — position
    interpolation. "llama3" (llama-3.1+): long wavelengths (> original
    context / low_freq_factor) get the full division, short wavelengths
    (< original / high_freq_factor) stay untouched, the band between
    interpolates. "yarn": NTK-by-parts — a linear ramp over the rotary
    DIMENSIONS (not wavelengths) between full interpolation and no
    scaling, with the ramp bounds derived from beta_fast/beta_slow
    rotations at the original context (theta and rot required). All must
    match transformers' _compute_*_parameters exactly or every position's
    rotation drifts. The yarn attention_factor (cos/sin magnitude) is
    applied in _rope, not here."""
    if scaling is None:
        return freqs
    if scaling[0] == "linear":
        return freqs / scaling[1]
    if scaling[0] == "yarn":
        if theta is None or rot is None:
            raise ValueError(
                "yarn rope scaling needs theta and rot (the ramp bounds "
                "are dimension- and base-dependent)"
            )
        _, factor, _af, beta_fast, beta_slow, orig, truncate = scaling

        def corr_dim(n_rot):
            return (rot * math.log(orig / (n_rot * 2 * math.pi))
                    ) / (2 * math.log(theta))

        low, high = corr_dim(beta_fast), corr_dim(beta_slow)
        if truncate:
            low, high = math.floor(low), math.ceil(high)
        low, high = max(low, 0), min(high, rot - 1)
        if low == high:
            high += 0.001
        ramp = jnp.clip(
            (jnp.arange(rot // 2, dtype=jnp.float32) - low) / (high - low),
            0.0, 1.0,
        )
        extrap = 1.0 - ramp  # 1 = keep the base frequency (extrapolation)
        return (freqs / factor) * (1.0 - extrap) + freqs * extrap
    _, factor, low_f, high_f, orig = scaling
    low_wavelen = orig / low_f
    high_wavelen = orig / high_f
    wavelen = 2.0 * math.pi / freqs
    smooth = (orig / wavelen - low_f) / (high_f - low_f)
    smoothed = (1.0 - smooth) * freqs / factor + smooth * freqs
    return jnp.where(
        wavelen > low_wavelen, freqs / factor,
        jnp.where(wavelen < high_wavelen, freqs, smoothed),
    )


def _qk_rmsnorm(x, scale, eps: float):
    """Per-head RMSNorm over head_dim (qwen3's q_norm/k_norm).
    x: [B, T, H, hd]; scale: [hd] (shared across heads)."""
    xf = x.astype(jnp.float32)
    xf = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return xf.astype(x.dtype) * scale


def _rope(x, positions, theta: float, rot: int | None = None,
          style: str = "half", scaling: tuple | None = None):
    """Rotary embedding. x: [B, T, H, hd]; positions: [B, T].

    rot < hd rotates only the FIRST rot dims and passes the tail through
    unchanged (phi/gpt-neox/gpt-j partial rotary; cfg.rotary_dim is the
    one place the count is derived). style="half" rotates the (first,
    second) halves of the rotary block together (llama/neox/phi);
    "interleaved" rotates adjacent pairs (x[2i], x[2i+1]) — gpt-j's
    rotate_every_two. Both share the same per-pair frequencies."""
    hd = x.shape[-1]
    rot = hd if rot is None else rot
    xr, tail = x[..., :rot], x[..., rot:]
    freqs = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    freqs = scale_rope_freqs(freqs, scaling, theta=theta, rot=rot)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, T, rot/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    xf = xr.astype(jnp.float32)
    if style == "interleaved":
        x1 = xf[..., 0::2]  # [B, T, H, rot/2]
        x2 = xf[..., 1::2]
        r1 = x1 * cos - x2 * sin
        r2 = x2 * cos + x1 * sin
        out = jnp.stack([r1, r2], axis=-1).reshape(xf.shape)
    else:
        x1, x2 = jnp.split(xf, 2, axis=-1)
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    if scaling is not None and scaling[0] == "yarn":
        # yarn's attention temperature: HF multiplies cos AND sin by the
        # attention_factor, i.e. the whole rotated block scales (the
        # non-rotary tail stays untouched)
        out = out * scaling[2]
    out = out.astype(x.dtype)
    return out if rot == hd else jnp.concatenate([out, tail], axis=-1)


def _activate(up, gate, cfg: ModelConfig):
    if cfg.activation == "silu":
        return jax.nn.silu(gate) * up
    if cfg.activation == "geglu":
        return jax.nn.gelu(gate, approximate=True) * up
    if cfg.activation == "reglu":  # smallthinker's sparse experts
        return jax.nn.relu(gate) * up
    if cfg.activation == "gelu_exact":  # gpt-neox: erf, not tanh approx
        return jax.nn.gelu(up, approximate=False)
    if cfg.activation == "relu2":  # nemotron-h: relu(x)^2, no gate
        return jnp.square(jax.nn.relu(up))
    return jax.nn.gelu(up, approximate=True)


def alibi_slopes(n_heads: int) -> list[float]:
    """Per-head ALiBi slopes (the train-short-test-long bias of bloom/
    mpt): geometric sequence 2^(-8i/n) for power-of-two head counts, with
    HF's interpolation for the remainder otherwise — must match
    transformers' build_alibi_tensor exactly or logits drift."""
    n = 2 ** math.floor(math.log2(n_heads))
    base = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
    slopes = [base ** (i + 1) for i in range(n)]
    if n < n_heads:
        extra_base = 2.0 ** (-(2.0 ** -(math.log2(2 * n) - 3)))
        slopes += [extra_base ** (2 * i + 1) for i in range(n_heads - n)]
    return slopes


def _attention(q, k, v, mask, cfg: ModelConfig):
    """q: [B, T, H, hd]; k, v: [B, S, Hkv, hd]; mask: [B, 1, T, S] bool."""
    B, T, H, hd = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    group = H // Hkv
    q = q.reshape(B, T, Hkv, group, hd)
    logits = jnp.einsum("btkgh,bskh->bkgts", q, k).astype(jnp.float32)
    # gemma-2 overrides the score denominator (query_pre_attn_scalar)
    logits = logits / math.sqrt(cfg.attn_scale or hd)
    if cfg.attn_logit_softcap:  # gemma-2: tanh cap BEFORE masking
        c = cfg.attn_logit_softcap
        logits = jnp.tanh(logits / c) * c
    if cfg.pos_embedding == "alibi":
        # + slope_h * key_position: softmax is shift-invariant per query
        # row, so the absolute-position form equals the relative -m*(i-j)
        # bias (and is exactly what HF bloom adds); masked slots are
        # overwritten below, so cache positions work unchanged
        slopes = jnp.asarray(alibi_slopes(H), jnp.float32).reshape(Hkv, group)
        logits = logits + (slopes[None, :, :, None, None]
                           * jnp.arange(S, dtype=jnp.float32))
    # mask [B,1,T,S] -> broadcast over (kv_head, group) dims
    logits = jnp.where(mask[:, :, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgts,bskh->btkgh", probs, v)
    return out.reshape(B, T, H * hd)


def _quantized_page_write(pool, scale, blk, slot, wslot, x):
    """Quantize-on-write scatter for ONE layer of an int8 paged pool —
    the models/quant.py symmetric amax recipe at (page, K or V, kv-head)
    granularity, K and V in one pass.

    ``pool`` [NB, 2, Hkv, BS, hd] int8; ``scale`` [NB, 2, Hkv] f32;
    ``blk``/``slot`` [B, T] the position→(page, slot) map WITH the
    write-floor/ceil null redirects already applied (so CoW donor pages
    are never touched — redirected positions land in the null block 0);
    ``wslot`` [B, T] each position's index into the chunk's page window
    (positions // BS - offset // BS); ``x`` [B, T, 2, Hkv, hd] the chunk's
    freshly projected K beside its V, as a page holds them.

    A page's scale is a RUNNING MAX over its tenancy: a write that
    raises the page's amax requantizes the page's existing int8 content
    under the grown scale (bounded re-rounding noise — at most one
    re-round per scale growth; scales never shrink until the allocator
    recycles the block and the scheduler zeroes its scale entry, so a
    recycled block's previous tenant can never inflate the new one).
    Touched pages are deduplicated through the chunk's page window
    before the gather/rescatter, so per-step requantization traffic is
    O(pages written) — one page per row on decode — not O(T) full-page
    copies. Returns (new_pool, new_scale)."""
    NB, _, _, BS, _ = pool.shape
    B, T = blk.shape
    # a T-position chunk at an arbitrary slot offset straddles at most
    # this many pages — the window the touched-page dedup scatters into
    # (wslot values are < P by construction: (off+T-1)//BS - off//BS)
    P = (T + BS - 2) // BS + 1
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1) * (1.0 / 127.0)  # [B, T, 2, Hkv]
    # scatter-max per (page, half, head): redirected positions only ever
    # grow the null block's scale (garbage page by design)
    cand = jnp.zeros(scale.shape, jnp.float32).at[blk].max(amax)
    new_scale = jnp.maximum(scale, cand)
    safe = jnp.where(new_scale > 0.0, new_scale, 1.0)
    # dedup touched pages: window slot w holds ONE page id (rows own
    # disjoint blocks; fully redirected slots keep the null block 0),
    # so the page gather/rescatter below moves each page once
    pg_blk = jnp.zeros((B, P), jnp.int32).at[
        jnp.arange(B, dtype=jnp.int32)[:, None], wslot
    ].max(blk)
    # requantize existing content under the (possibly) grown scale —
    # but ONLY when some page actually grew (lax.cond, a real branch):
    # steady-state decode (token amax under the page's running max, the
    # common case once a page warms up) skips the page read-modify-write
    # entirely and pays just the slot scatter, like the bf16 path.
    # Inside the taken branch, ratio == 1 where unchanged (rint(int *
    # 1.0) is exact), < 1 where grown, 0 for a freshly reset page
    # (scale 0 → stale bytes zeroed before the new tenant's first read)
    def _requant(p):
        ratio = scale / safe  # [NB, 2, Hkv]
        pages = p[pg_blk].astype(jnp.float32)  # [B, P, 2, Hkv, BS, hd]
        rq = jnp.clip(
            jnp.rint(pages * ratio[pg_blk][..., None, None]), -127, 127
        ).astype(jnp.int8)
        return p.at[pg_blk].set(rq)

    out = lax.cond(jnp.any(cand > scale), _requant, lambda p: p, pool)
    # quantize the chunk's values under the new page scales and scatter
    # into their slots (distinct (page, slot) pairs except the null block)
    q = jnp.clip(
        jnp.rint(xf / safe[blk][..., None]), -127, 127
    ).astype(jnp.int8)
    return out.at[blk, :, :, slot].set(q), new_scale


def matmul(x, w):
    """x @ w where w may be an int8 weight-only quantized subtree
    {"q": int8 [..., in, out], "s": f32 [..., out]} (models/quant.py).
    Per-out-channel scales commute with the dot, so dequant applies to
    the OUTPUT — XLA fuses the int8 convert into the operand read and
    the weights stream from HBM at half the bf16 bytes."""
    if isinstance(w, dict) and "q" in w:
        return (x @ w["q"].astype(x.dtype)) * w["s"].astype(x.dtype)
    return x @ w


def _lora_rows(ab, ids, scale):
    """Gather one layer's per-ROW adapter factors: ``ab`` is the pool's
    stacked {"a": [N, din, r], "b": [N, r, dout]} slice for this layer,
    ``ids`` [B] each row's pool slot (0 = the reserved null adapter,
    all-zero factors), ``scale`` [N] each slot's alpha/rank scaling.
    Returns (a [B, din, r], b [B, r, dout], s [B])."""
    return ab["a"][ids], ab["b"][ids], scale[ids]


def lora_matmul(x, w, name, lora):
    """The multi-adapter serving hook around ``matmul``: base projection
    plus each row's low-rank delta ``s * (x @ A) @ B`` (adapters/pool.py
    holds the stacked factors; train/lora.py defines the merge math this
    must agree with). ``lora`` is None (plain matmul — the trace is
    byte-identical to the pre-adapter graph) or {"ab": per-layer target
    dict, "ids": [B], "scale": [N]}; a target absent from the pool passes
    through untouched. Rows mapped to slot 0 gather the null adapter's
    zero factors, so adapter-less rows in a mixed batch stay exact (the
    batch-level skip for ALL-baseline batches lives in the scheduler,
    same per-row gating discipline as spec decode). The rank-r einsums
    run in f32 like merge_lora's delta, then cast back — x is [B, T, din]
    everywhere this is called (the batch dim is the row identity)."""
    out = matmul(x, w)
    ab = None if lora is None else lora["ab"].get(name)
    if ab is None:
        return out
    a, b, s = _lora_rows(ab, lora["ids"], lora["scale"])
    xf = x.astype(jnp.float32)
    h = jnp.einsum("btd,bdr->btr", xf, a.astype(jnp.float32))
    delta = jnp.einsum("btr,bro->bto", h, b.astype(jnp.float32))
    return out + (delta * s[:, None, None]).astype(out.dtype)


def expert_einsum(spec, x, w, s_expand):
    """Expert-weight einsum with optional int8 quantization.

    MoE expert weights are [E, in, out] (per-layer slice); their scales
    are [E, out] (models/quant.py, amax over the in dim), which commute
    with the contraction exactly as in matmul(). `s_expand` reshapes the
    scale to broadcast against the einsum OUTPUT (the out/expert dims
    land in different positions per formulation — dense puts E next to
    last, routed inserts a capacity dim)."""
    if isinstance(w, dict) and "q" in w:
        out = jnp.einsum(spec, x, w["q"].astype(x.dtype))
        return out * s_expand(w["s"].astype(out.dtype))
    return jnp.einsum(spec, x, w)


def _mlp(x, p, cfg: ModelConfig, lora=None):
    with jax.named_scope("mlp.gate_up"):
        up = lora_matmul(x, p["w_up"], "w_up", lora)
        if "b_up" in p:
            up = up + p["b_up"]
        gate = lora_matmul(x, p["w_gate"], "w_gate", lora) if "w_gate" in p else None
        gate_mult, down_mult = cfg.mlp_multipliers  # falcon-h1's muP pair
        if gate is not None and gate_mult != 1.0:
            gate = gate * jnp.asarray(gate_mult, gate.dtype)
        h = _activate(up, gate, cfg)
    with jax.named_scope("mlp.down"):
        out = lora_matmul(h, p["w_down"], "w_down", lora)
        if "b_down" in p:
            out = out + p["b_down"]
        if down_mult != 1.0:
            out = out * jnp.asarray(down_mult, out.dtype)
    return out


_HI = lax.Precision.HIGHEST  # float32 products that must stay float32 on
# the TPU's MXU (its default would round both operands to bf16): the mixer's
# scan, the expert router


def _moe_routed(x, p, cfg: ModelConfig):
    """Top-k expert MLP, GShard-style routed dispatch (static shapes).

    Tokens are split into GROUPS of cfg.moe_group_size; each group routes
    independently into per-expert capacity buffers [G, E, C, D] via a
    dispatch one-hot, each expert runs its MLP on only its buffers, and a
    combine einsum scatters weighted outputs back — k/E of the dense
    formulation's expert FLOPs. Grouping keeps capacity — and the
    [G, g, E, C] dispatch tensor — O(group size), not O(batch*seq): the
    ungrouped formulation is quadratic in token count and OOMs real
    sequence lengths. C = ceil(g*k/E * capacity factor); assignments past
    an expert's per-group capacity drop (combine weight zero), token-
    index-major priority; trailing pad tokens consume no capacity.
    Everything is einsum/one_hot/cumsum — no gather/scatter, fully
    differentiable, and the sharded-E einsums become all-to-alls over the
    `expert` mesh axis under the partitioner.
    """
    B, T, D = x.shape
    E, k = cfg.n_experts, cfg.n_experts_per_tok
    N = B * T
    g = min(cfg.moe_group_size, N)
    G = -(-N // g)  # ceil: last group padded with dead tokens
    Np = G * g
    C = min(g, int(math.ceil(g * k / E * cfg.moe_capacity_factor)))

    xf = x.reshape(N, D)
    valid = jnp.ones((N,), jnp.float32)
    if Np != N:
        xf = jnp.pad(xf, ((0, Np - N), (0, 0)))
        valid = jnp.pad(valid, (0, Np - N))
    xg = xf.reshape(G, g, D)
    vg = valid.reshape(G, g)

    logits = jnp.einsum("gnd,de->gne", xg, p["router"]).astype(jnp.float32)
    topv, topi = lax.top_k(logits, k)
    topp = jax.nn.softmax(topv, axis=-1)  # [G, g, k] renormalized

    oh = jax.nn.one_hot(topi, E, dtype=jnp.float32)  # [G, g, k, E]
    oh = oh * vg[:, :, None, None]  # pad tokens take no capacity
    ohf = oh.reshape(G, g * k, E)  # token-major, slot-minor priority
    pos_all = jnp.cumsum(ohf, axis=1) - ohf  # per-group running count
    # exact small integers in f32; one_hot wants integer positions
    pos = jnp.sum(pos_all * ohf, axis=-1).astype(jnp.int32)  # [G, g*k]
    keep = (pos < C).astype(jnp.float32)
    slot = jax.nn.one_hot(pos, C, dtype=jnp.float32) * keep[..., None]
    disp = (ohf[..., None] * slot[:, :, None, :]).reshape(G, g, k, E, C)
    combine = jnp.sum(disp * topp[..., None, None], axis=2)  # [G, g, E, C]
    disp_tok = jnp.sum(disp, axis=2)  # [G, g, E, C] 0/1

    xe = jnp.einsum("gnec,gnd->gecd", disp_tok.astype(x.dtype), xg)
    # out [G,E,C,*]: scales [E,*] broadcast as [E,1,*] over the C dim
    s_ec = lambda s: s[:, None, :]  # noqa: E731
    up = expert_einsum("gecd,edf->gecf", xe, p["w_up"], s_ec)
    gate = (
        expert_einsum("gecd,edf->gecf", xe, p["w_gate"], s_ec)
        if "w_gate" in p
        else None
    )
    h = _activate(up, gate, cfg)
    ye = expert_einsum("gecf,efd->gecd", h, p["w_down"], s_ec)  # [G, E, C, D]
    out = jnp.einsum("gnec,gecd->gnd", combine.astype(ye.dtype), ye)
    return out.reshape(Np, D)[:N].reshape(B, T, D)


def _moe(x, p, cfg: ModelConfig):
    """Top-k expert MLP, dense-einsum formulation.

    Every token computes logits over E experts; the top-k probs are
    renormalized and all experts run on all tokens with a weight mask —
    the XLA-friendly dense formulation (no gather/scatter, static shapes).
    Expert-parallel sharding splits the E dim across the `expert` mesh axis
    and XLA turns the weighted sum into a reduce over that axis.
    cfg.moe_impl="routed" switches to the capacity-grouped dispatch that
    only pays the routed FLOPs (_moe_routed); dense stays the reference
    check.
    """
    if cfg.moe_impl == "routed":
        return _moe_routed(x, p, cfg)
    B, T, D = x.shape
    E, k = cfg.n_experts, cfg.n_experts_per_tok
    logits = (x @ p["router"]).astype(jnp.float32)  # [B, T, E]
    topv, topi = lax.top_k(logits, k)
    topp = jax.nn.softmax(topv, axis=-1)  # renormalized over the top-k
    # dense per-expert weight [B, T, E]: scatter top-k probs via one-hot
    weights = jnp.sum(jax.nn.one_hot(topi, E, dtype=jnp.float32) * topp[..., None], axis=-2)
    # out [B,T,E,*]: scales [E,*] align with the trailing dims directly
    s_id = lambda s: s  # noqa: E731
    up = expert_einsum("btd,edf->btef", x, p["w_up"], s_id)
    if "w_gate" in p:
        gate = expert_einsum("btd,edf->btef", x, p["w_gate"], s_id)
    else:
        gate = None
    h = _activate(up, gate, cfg)  # [B, T, E, F]
    out = expert_einsum("btef,efd->bted", h, p["w_down"], s_id)
    return jnp.einsum("bted,bte->btd", out, weights.astype(out.dtype))


MOE_STATS = ("hit", "max_load", "live")  # the entries of a forward's
# ``moe_stats`` int32 [3], each summed over its expert layers: experts with at
# least one live assignment, the busiest expert's assignments, live assignments
# — of the experts held HERE; under an expert share (moe_stats_names) a fourth,
# "elsewhere": live assignments whose expert another chip holds


def moe_stats_names(cfg: ModelConfig) -> tuple:
    """The entries of ``cfg``'s ``moe_stats`` vector."""
    return MOE_STATS + (("elsewhere",) if cfg.expert_share else ())


def _router_logits(xf, p):
    """``x W_r`` [N, E] in float32 from a float32 product."""
    return jnp.dot(
        xf.astype(jnp.float32), p["router"].astype(jnp.float32), precision=_HI)


def _router_scores(xf, p):
    """``sigmoid(x W_r)`` [N, E] in float32 from a float32 product."""
    return jax.nn.sigmoid(_router_logits(xf, p))


def _split_expert_stack(moe: Params):
    """A stacked expert group's tree as (the rest, {w_gate, w_up, w_down}):
    the three [L, E, ...] stacks (two under an ungated activation) stay out
    of a layer scan's xs and are read in place (_moe_dropless's ``experts`` /
    ``layer``)."""
    names = ("w_gate", "w_up", "w_down")
    return ({n: a for n, a in moe.items() if n not in names},
            {n: moe[n] for n in names if n in moe})


def _moe_router(xf, p, cfg: ModelConfig):
    """The dropless layer's router on ``xf`` [N, D], float32 throughout.
    Returns (topi [N, k] int32, w [N, k] f32).

    "sigmoid": scores ``s = sigmoid(x W_r)``; the k experts with the
    largest ``s + bias`` are chosen; their weights are ``s`` WITHOUT the
    bias, divided by their sum and times cfg.moe_scale.
    "softmax_topk" (no bias): the k largest LOGITS ``z = x W_r`` are chosen
    and weighed by a softmax over those k alone — what a softmax over all
    the experts gives once the chosen weights are renormalised."""
    if cfg.moe_router == "softmax_topk":
        z, topi = lax.top_k(_router_logits(xf, p), cfg.n_experts_per_tok)
        return topi.astype(jnp.int32), jax.nn.softmax(z, axis=-1) * cfg.moe_scale
    s = _router_scores(xf, p)
    # (no selection bias, cfg.moe_select_bias False: the top k of the scores)
    _, topi = lax.top_k(
        s + p["router_bias"] if "router_bias" in p else s,
        cfg.n_experts_per_tok)
    w = jnp.take_along_axis(s, topi, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * cfg.moe_scale
    return topi.astype(jnp.int32), w


# The words of the repo's synthetic traffic (chip_smoke.py, the benchmark's
# load generator): what seeded weights are balanced on. A router's even load
# holds on the text it was balanced on, as a trained one's does.
BALANCE_WORDS = ("mesh", "node", "token", "cache", "block", "shard", "queue",
                 "route", "draft", "batch", "page", "head", "layer", "chip",
                 "ring", "tile")
_BALANCE_ROWS, _BALANCE_WIDTH = 32, 256  # the balancing batch: rows x tokens
_BALANCE_PASSES, _BALANCE_STEPS = 4, 64


def _balance_tokens(cfg: ModelConfig, rows: int = _BALANCE_ROWS,
                    width: int = _BALANCE_WIDTH, prompt_only: bool = False):
    """The balancing batch (tokens [R, T] int32, prompt lengths [R]): a row
    is BOS + seeded BALANCE_WORDS text through the byte tokenizer's map
    (token = byte + 3), 32 to 192 tokens of it, then a continuation, seeded
    random to begin with; ``prompt_only``: the text fills the row. The same
    for every key: a constant of the program."""
    import numpy as np

    rng = np.random.RandomState(0)
    R, T, V = rows, width, cfg.vocab_size
    plen = np.full((R,), T) if prompt_only else 32 + (np.arange(R) * 160) // R
    tokens = rng.randint(3, V, (R, T))
    for r in range(R):
        text = " ".join(rng.choice(BALANCE_WORDS, T // 4)).encode()
        tokens[r, 0] = 1
        tokens[r, 1:plen[r]] = 3 + np.frombuffer(
            text[:plen[r] - 1], np.uint8).astype(np.int64) % max(V - 3, 1)
    return tokens.astype(np.int32), plen.astype(np.int32)


def _balanced_bias(s, k: int):
    """The selection bias [E] under which the top ``k`` of ``s + bias``
    load every expert alike over the rows of ``s`` [N, E] (scores in (0,
    1), float32): noaux_tc's own rule — the bias of an expert over the mean
    load goes down, under it up — run until it rests."""
    N, E = s.shape

    def step(i, b):
        _, topi = lax.top_k(s + b, k)
        # counted in integers: the sum's order cannot reach the bias
        load = jnp.zeros((E,), jnp.int32).at[topi.reshape(-1)].add(1) * (
            E / (N * k))  # 1 = the mean load
        rate = 0.03 * jnp.minimum(1.0, 4.0 * (1.0 - i / _BALANCE_STEPS))
        return b - rate * jnp.clip(load - 1.0, -2.0, 2.0)

    return lax.fori_loop(0, _BALANCE_STEPS, step, jnp.zeros((E,), jnp.float32))


def balance_router_bias(params: Params, cfg: ModelConfig):
    """``router_bias`` [L, E] float32 for seeded weights: every expert
    layer's selection bias balanced, layer after layer, on the balancing
    batch — what a noaux_tc router's training does to it, and what makes
    64 decoding rows touch 1 - (1 - k/E)^64 of a layer's experts. Seeded
    hidden states share a large common part across tokens (the mean of the
    context's values), which a zero or random bias lets pick the same few
    experts for every row (measured: 30 % of 256 touched a step, not 87).

    A layer's bias is solved from its own router scores (_balanced_bias)
    and used for the layers after it. The batch's continuations are the
    model's OWN greedy tokens, as served contexts hold: each of the
    _BALANCE_PASSES passes takes the argmax at every position as the next
    position's token (a Jacobi step of greedy decoding) and balances anew;
    the last pass's bias is returned."""
    tokens, plen = _balance_tokens(cfg)
    R, T = tokens.shape
    prompt = jnp.arange(T)[None, :] < jnp.asarray(plen)[:, None]
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (R, T))
    mask = attn_mask(cfg, positions, T)
    moe = params["layers"]["moe"]
    rest, stack = _split_expert_stack(moe)
    layers = dict(params["layers"], moe=rest)

    def layer(x, xs):
        lp, i = xs
        x = x + _mla_attention(
            lp["attn"], cfg, _norm(x, lp["ln1"], cfg), positions, mask)
        h2 = _norm(x, lp["ln2"], cfg)
        s = _router_scores(h2.reshape(R * T, -1), lp["moe"])
        bias = _balanced_bias(s, cfg.n_experts_per_tok)
        out, _ = _moe_dropless(h2, dict(lp["moe"], router_bias=bias), cfg,
                               experts=stack, layer=i)
        return x + out, bias

    def continued(x, tokens):
        """The batch for the next pass: behind its prompt every row goes on
        with the model's own greedy tokens of this pass."""
        # a row's logits at a time: [T, V] float32, never [R, T, V]
        greedy = lax.map(
            lambda xr: jnp.argmax(final_logits(params, cfg, xr[None])[0], -1), x)
        shifted = jnp.concatenate([tokens[:, :1], greedy[:, :-1]], axis=1)
        return jnp.where(prompt, tokens, shifted.astype(tokens.dtype))

    def one_pass(_, carry):
        tokens = carry[1]
        x = embed_tokens(params, cfg, tokens, positions)
        for i in range(cfg.first_k_dense):
            x = transformer_block(
                jax.tree.map(lambda a: a[i], params["dense_layers"]), cfg,  # noqa: B023
                x, positions, mask)
        x, bias = lax.scan(
            layer, x, (layers, jnp.arange(cfg.n_layers - cfg.first_k_dense)))
        return bias, continued(x, tokens)

    def layer_of_a_kind(x, xs):
        """One mixer kind a layer (cfg.layer_types): the block as forward
        runs it, stateless; an expert layer's bias is solved where the block
        reads its router (_moe_dropless's ``router_fix``)."""
        lp, i = xs
        got = []

        def fix(rx, p):
            got.append(_balanced_bias(_router_scores(rx, p),
                                      cfg.n_experts_per_tok))
            return dict(p, router_bias=got[0])

        x = transformer_block(
            lp, cfg, x, positions, mask,
            moe_kw={"experts": stack, "layer": _slot_of(cfg.moe_slots, i)
                    if cfg.single_branch else i, "router_fix": fix})
        return x, got[0] if got else None

    def one_pass_of_kinds(_, carry):
        tokens = carry[1]
        x = embed_tokens(params, cfg, tokens, positions)
        x, bias = _scan_layer_runs(cfg, layers, layer_of_a_kind, x)
        return jnp.concatenate(bias, axis=0), continued(x, tokens)

    bias, _ = lax.fori_loop(
        0, _BALANCE_PASSES,
        one_pass_of_kinds if cfg.layer_types else one_pass,
        (jnp.zeros_like(moe["router_bias"]), jnp.asarray(tokens)))
    return bias


# center_router's balancing batch: rows x tokens of TEXT (no random
# continuation), of which the deeper half of every row is averaged
_CENTER_ROWS, _CENTER_WIDTH = 16, 1024


def center_router(params: Params, cfg: ModelConfig):
    """``router`` [L, D, E] for seeded weights of a router WITHOUT a bias
    (cfg.moe_router "softmax_topk"): from every layer's ``W_r`` its response
    to the MEAN router input of the balancing batch is removed,
    ``W_r - m (m^T W_r) / (m^T m)``, layer after layer (a layer's mean is
    taken behind the layers already centred). Seeded hidden states share a
    large common part across tokens (balance_router_bias), growing with
    depth, which a plain random router turns into the same few experts for
    every row; with the mean's response gone the logits are what a token has
    of its own, and 32 decoding rows touch about 1 - (1 - k/E)^32 of a
    layer's experts, as a trained router's even load does. No parameter is
    added: the served weights and a reference's are the same arrays.

    The batch must show the common part as SERVED contexts hold it, to a
    degree: the mean is taken over the deeper half of 16 rows of 1,024 tokens
    of the load generator's words alone. Measured on the chip at the
    published widths, the experts 32 rows hit 4,096 tokens deep (PR 43): no
    rule 44.6 %, joyai's batch (32 x 256, prompts of 32-192 tokens then random
    tokens, every position) 69.0 %, text only 69.0 %, its deeper half 75.9 %,
    this batch 87.7 %; removing the top 2-16 principal directions of the
    router input's second moment instead of the mean 86.9-88.7 % (not worth
    an eigendecomposition a layer).

    The mean is taken of the router's OWN input, whichever norm feeds it:
    the pre-attention norm's output (cfg.moe_router_input "attn_norm",
    smallthinker: computed here, ahead of the block) or the pre-FFN norm's
    ("ffn_norm", granite: known only behind the layer's mixer, so the block
    centres the matrix where it reads it, _moe_dropless's ``router_fix``).
    A model's leading dense layers (cfg.first_k_dense) run first and route
    nothing, as do the layers of another kind where an expert layer is a kind
    of layer (cfg.single_branch). With a multi-token-prediction layer (cfg.mtp_layers) the MTP
    block's router is centred too, behind the centred trunk, on what
    mtp_forward feeds it; returns (the trunk's [L, D, E], the MTP block's
    [1, D, E])."""
    width = min(_CENTER_WIDTH, cfg.max_seq_len)
    # the combine holds rows x width x k float32 rows of d_model at once: no
    # more of them than smallthinker's batch (k = 6) makes, whatever the k
    # (granite's k = 10: 512 tokens a row, and its contexts end at 704)
    while _CENTER_ROWS * width * cfg.n_experts_per_tok > _CENTER_ROWS * _CENTER_WIDTH * 6:
        width //= 2
    tokens, _ = _balance_tokens(cfg, _CENTER_ROWS, width, prompt_only=True)
    R, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (R, T))
    layer_mask = make_layer_mask(cfg, positions, T)
    rest, stack = _split_expert_stack(params["layers"]["moe"])

    def centred(w, m):
        w32 = w.astype(jnp.float32)
        return (w32 - jnp.outer(m, jnp.dot(m, w32, precision=_HI))
                / jnp.dot(m, m)).astype(w.dtype)

    def stack_index(i):  # a layer's place in the EXPERT layers' stack
        if cfg.single_branch:
            return _slot_of(cfg.moe_slots, i)
        return i - cfg.first_k_dense if cfg.first_k_dense else i

    def deep_mean(a):  # [R, T, D] -> [D] over the deeper half of every row
        return jnp.mean(a.astype(jnp.float32)[:, T // 2:].reshape(
            R * (T - T // 2), -1), axis=0)

    def layer(x, xs):
        lp, i = xs
        w = centred(lp["moe"]["router"],
                    deep_mean(_norm(x, lp["ln1"], cfg)))
        x = transformer_block(
            dict(lp, moe=dict(lp["moe"], router=w)), cfg, x, positions,
            layer_mask(i), rope_local=layer_rope_flag(cfg, i),
            moe_kw={"experts": stack, "layer": stack_index(i)})
        return x, w

    def centring():
        """(_moe_dropless's ``router_fix`` that centres the matrix on the
        router's own input where the block reads it, [the centred matrix])."""
        got = []

        def fix(rx, p):
            got.append(centred(p["router"], deep_mean(rx.reshape(R, T, -1))))
            return dict(p, router=got[0])

        return fix, got

    def layer_behind_mixer(x, xs):
        lp, i = xs
        fix, got = centring()
        x = transformer_block(
            lp, cfg, x, positions, layer_mask(i),
            rope_local=layer_rope_flag(cfg, i),
            moe_kw={"experts": stack, "layer": stack_index(i),
                    "router_fix": fix})
        return x, got[0] if got else None  # (a layer that routes nothing)

    x = embed_tokens(params, cfg, jnp.asarray(tokens), positions)
    layers = dict(params["layers"], moe=rest)
    if not cfg.layer_types:
        body = (layer if cfg.moe_router_input == "attn_norm"
                else layer_behind_mixer)
        k_dense = cfg.first_k_dense
        for i in range(k_dense):  # the leading dense layers route nothing
            x = transformer_block(
                jax.tree.map(lambda a: a[i], params["dense_layers"]), cfg,  # noqa: B023
                x, positions, layer_mask(i), rope_local=layer_rope_flag(cfg, i))
        x, routers = lax.scan(
            body, x, (layers, jnp.arange(k_dense, cfg.n_layers)))
        if not cfg.mtp_layers:
            return routers
        # the MTP block's router, behind the centred trunk: its input is
        # what mtp_forward makes of the trunk's last hidden state and the
        # NEXT token's embedding
        fix, got = centring()
        behind = cfg.n_layers  # (mtp_forward's block, without a cache)
        transformer_block(
            jax.tree.map(lambda a: a[0], params["mtp"]["block"]), cfg,
            mtp_input(params, cfg, x, jnp.roll(jnp.asarray(tokens), -1, axis=1),
                      positions),
            positions, layer_mask(behind),
            rope_local=layer_rope_flag(cfg, behind),
            moe_kw={"router_fix": fix})
        return routers, got[0][None]
    # one mixer kind a layer: a scan a run of like layers (core.forward)
    return jnp.concatenate(
        _scan_layer_runs(cfg, layers, layer_behind_mixer, x)[1], axis=0)


def _moe_dropless(x, p, cfg: ModelConfig, live=None, experts=None, layer=None,
                  router_x=None, router_fix=None):
    """The expert layer behind a sigmoid or softmax-top-k router (_moe_router),
    DROPLESS: every chosen assignment is computed, whatever the imbalance.
    Returns (out [B, T, D], stats int32 [3] as MOE_STATS names them).
    ``router_x`` [B, T, D] is what the ROUTER reads where that is not ``x``
    (cfg.moe_router_input "attn_norm": the block's pre-attention norm); the
    experts read ``x``.

    The N x k assignments are sorted by expert (``moe.dispatch``: one
    argsort, a bincount for the group sizes, a row gather), the three
    expert matrices are grouped products over the sorted rows
    (``moe.experts``: ops/grouped.py, which visits only experts that got a
    row and reads each visited expert's matrix from where it lies). The way
    back (``moe.combine``) gathers the product's OWN rows, in the dtype it
    left them (bf16 on the served path), by the inverse permutation, choice
    by choice: [k, N, D], every token's j-th choice in slab j. One float32
    expression then converts them, selects zero where an assignment's sorted
    row lies past the last group (``inv >= n_live``: a dead position, an
    expert held elsewhere; such a row may hold NaN), weighs them by the
    router's weights as the router gave them and sums a token's k choices in
    the router's order, so no float32 array of the sorted rows is ever made.
    The shared expert is added to that float32 sum (``moe.shared``), cast
    once.

    ``live`` [B, T] bool: positions that are real. A dead row of a batch
    bucket or a prefill bucket's padded tail is assigned to a pad group
    past the last expert: it sorts last, belongs to no group of the
    product and is masked on the way back, so it neither touches an expert
    nor counts as load. ``experts`` (default ``p``) holds w_gate / w_up /
    w_down; with ``layer`` (a traced scalar: forward's layer scan) they are
    the STACKED [L, E, ...] arrays, read in place: the stack is viewed as
    L*E groups of which only this layer's E get rows, so no layer's
    experts are sliced out of the stack (a 0.8 GB copy a matrix a layer
    otherwise).

    Under an expert SHARE (cfg.expert_share) the stacks hold the
    cfg.experts_held experts from cfg.expert_first on: the router still
    scores every expert and takes its k, an assignment to an expert held
    ELSEWHERE joins the pad group (no product, masked) and is counted
    in a fourth stat, and the layer returns the held experts' part plus the
    shared expert: what this chip would hand to the exchange with its
    partners, which is not built. ``router_fix(router input [N, D], W_r) ->
    p`` replaces the router's parameters before they are read (center_router's
    matrix, balance_router_bias's selection bias).

    Experts in a LATENT (``latent_in`` / ``latent_out`` in ``p``: nemotron-h's
    LatentMoE, cfg.moe_latent): ``z = x W_in`` [N, Dl] is made BEFORE the
    dispatch, so the sorted rows, the grouped products and the combine run Dl
    wide, and ``W_out`` is applied ONCE to the weighted float32 sum (it is
    linear and has no bias); router and shared expert read ``x`` at the
    model's width. Both projections run under ``moe.experts`` in scopes of
    their own (``latent.in`` / ``latent.out``: tracing.DEVICE_NESTED). An
    ungated activation (cfg.gated_mlp False: "relu2") has no ``w_gate``: two
    grouped products an expert."""
    B, T, D = x.shape
    k = cfg.n_experts_per_tok
    E = cfg.experts_held  # the groups of the product: the experts held HERE
    N, M = B * T, B * T * k
    xf = x.reshape(N, D)
    experts = p if experts is None else experts

    with jax.named_scope("moe.router"):
        rx = xf if router_x is None else router_x.reshape(N, D)
        if router_fix is not None:
            p = router_fix(rx, p)
        topi, w = _moe_router(rx, p, cfg)

    xe = xf  # what the routed experts read
    if "latent_in" in p:
        with jax.named_scope("moe.experts"), jax.named_scope("latent.in"):
            xe = matmul(xf, p["latent_in"])
    De = xe.shape[-1]

    with jax.named_scope("moe.dispatch"):
        flat = topi.reshape(M)
        elsewhere = None
        if cfg.expert_share:
            flat = flat - cfg.expert_first
            away = (flat < 0) | (flat >= E)
            if live is not None:
                away = away & jnp.repeat(live.reshape(N), k)
            elsewhere = jnp.sum(away)
            flat = jnp.where(away, E, flat)
        if live is not None:
            flat = jnp.where(jnp.repeat(live.reshape(N), k), flat, E)
        order = jnp.argsort(flat, stable=True).astype(jnp.int32)  # [M]
        gs = jnp.zeros((E + 1,), jnp.int32).at[flat].add(1)[:E]
        n_live = jnp.sum(gs)  # the sorted rows before it belong to a group
        xs = jnp.take(xe, order // k, axis=0)  # [M, De]
        if layer is not None:
            L = experts["w_up"].shape[0]
            sizes = lax.dynamic_update_slice(
                jnp.zeros((L * E,), jnp.int32), gs, (layer * E,))
            stack = lambda a: a.reshape(L * E, *a.shape[2:])  # noqa: E731
        else:
            sizes, stack = gs, lambda a: a  # noqa: E731

    with jax.named_scope("moe.experts"):
        gate = (grouped_matmul(xs, stack(experts["w_gate"]), sizes)
                if "w_gate" in experts else None)
        up = grouped_matmul(xs, stack(experts["w_up"]), sizes)
        # (rows past the last group hold whatever the buffer held, through
        # all three products: a row's product reads no other row)
        y = grouped_matmul(
            _activate(up, gate, cfg), stack(experts["w_down"]), sizes)

    with jax.named_scope("moe.combine"):
        # (assignment -> its sorted row, laid [k, N]: gathered choice-major
        # the rows view as [k, N, D] for nothing, where [N, k, D] is a copy
        # on the chip, k being no multiple of a tile's rows)
        inv = jnp.zeros((M,), jnp.int32).at[order].set(
            jnp.arange(M, dtype=jnp.int32)).reshape(N, k).T
        yg = jnp.take(y, inv.reshape(M), axis=0).reshape(k, N, De)
        # (a select, never a 0 / 1 multiplier: a masked row may hold NaN)
        kept = jnp.where((inv < n_live)[..., None], yg.astype(jnp.float32), 0.0)
        out = jnp.sum(kept * w.T[..., None], axis=0)

    if "latent_out" in p:  # back to the model's width, once, in float32
        with jax.named_scope("moe.experts"), jax.named_scope("latent.out"):
            out = jnp.dot(out.astype(x.dtype), p["latent_out"].astype(x.dtype),
                          preferred_element_type=jnp.float32)

    if "shared" in p:
        with jax.named_scope("moe.shared"):
            out = out + _mlp(xf, p["shared"], cfg).astype(jnp.float32)

    with jax.named_scope("moe.combine"):
        stats = jnp.stack(
            [jnp.sum(gs > 0), jnp.max(gs), n_live]
            + ([] if elsewhere is None else [elsewhere])).astype(jnp.int32)
        return out.astype(x.dtype).reshape(B, T, D), stats


# ------------------------------------------- latent attention (MLA)


def _latent_attention(q_lat, lat, mask, v_width: int, sm_scale: float):
    """Dense attention over latent rows: ``q_lat`` [B, T, H, W] (the
    absorbed query beside its rotated part), ``lat`` [B, S, W] the cached
    [c_kv | k_rope] rows — keys as they are, values their first
    ``v_width`` columns; every head reads the same rows. mask [B|1, 1, T,
    S]. Returns [B, T, H, v_width]."""
    logits = jnp.einsum("bthw,bsw->bhts", q_lat, lat).astype(jnp.float32)
    logits = jnp.where(mask, logits * sm_scale, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(lat.dtype)
    return jnp.einsum("bhts,bsr->bthr", probs, lat[..., :v_width])


def _mla_attention(p, cfg: ModelConfig, h, positions, mask, kv_hook=None,
                   attn_fn=None):
    """JoyAI-LLM-Flash / DeepSeek-V3 latent attention on ``h`` [B, T, D]
    (ln1's output). Returns the block's attention output [B, T, D].

    ``c_q = RMS(h W_qa)``; per head ``[q_nope | q_rope] = c_q W_qb``;
    ``[c_kv | k_r] = h W_kva``; the token's cached row is ``[RMS(c_kv) |
    RoPE(k_r)]`` (ONE roped key shared by the heads; pairs (2i, 2i+1)).
    Per head ``[k_nope | v] = c_kv W_kvb``. The score denominator is
    sqrt(nope + rope). Two paths, the same mathematics (tests hold them to
    each other), chosen by whether a cache exists:

    - a cache (``kv_hook``), the served path: ABSORBED. ``q_nope . k_nope =
      (q_nope W_kvb[k]^T) . c_kv``, so the query is taken into the latent
      space once (under ``mla.q_proj``), attention runs over the cached
      latent rows themselves — keys 576 wide, values their first 512
      columns — and the output comes back through ``W_kvb[v]`` (under
      ``mla.out``). Nothing per head is ever cached or expanded.
      ``kv_hook(latent [B, T, W]) -> what attention reads``: the paged
      paths write the chunk's rows into the pool and return the gathered
      view (dense) or the stacked pool itself (ragged: ``attn_fn`` is then
      the ragged reader's ``latent`` form, core._attention's ABI with no
      V, and reads it in place).
    - no cache (training, scoring, a whole-sequence pass): EXPANDED.
      ``k_nope`` and ``v`` are built from the chunk's own ``c_kv`` and
      plain causal attention runs per head (320 multiply-adds a (query,
      key) pair a head against the absorbed form's 1,088)."""
    B, T, _ = h.shape
    H = cfg.n_heads
    R, r, dn, dv = (cfg.mla_kv_rank, cfg.mla_rope_dim, cfg.mla_nope_dim,
                    cfg.mla_v_dim)
    rope = functools.partial(
        _rope, positions=positions, theta=cfg.rope_theta, style=cfg.rope_style)
    wkv_b = p["wkv_b"].reshape(R, H, dn + dv)
    sm_scale = 1.0 / math.sqrt(dn + r)

    with jax.named_scope("mla.q_proj"):
        c_q = _norm(matmul(h, p["wq_a"]), {"scale": p["q_a_norm"]}, cfg)
        q = matmul(c_q, p["wq_b"]).reshape(B, T, H, dn + r)
        q_nope, q_rope = q[..., :dn], rope(q[..., dn:])
    with jax.named_scope("mla.kv_proj"):
        kv = matmul(h, p["wkv_a"])  # [B, T, R + r]
        c_kv = _norm(kv[..., :R], {"scale": p["kv_a_norm"]}, cfg)
        k_rope = rope(kv[..., R:][:, :, None, :])[:, :, 0]
    if kv_hook is None:  # expanded: per-head keys and values of the chunk
        with jax.named_scope("mla.kv_proj"):
            kvb = jnp.einsum("bsr,rhn->bshn", c_kv, wkv_b)
            k_nope, v = kvb[..., :dn], kvb[..., dn:]
        with jax.named_scope("mla.read"):
            logits = (
                jnp.einsum("bthn,bshn->bhts", q_nope, k_nope)
                + jnp.einsum("bthr,bsr->bhts", q_rope, k_rope)
            ).astype(jnp.float32)
            logits = jnp.where(mask, logits * sm_scale, -1e30)
            probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
            o = jnp.einsum("bhts,bshv->bthv", probs, v)
    else:  # absorbed: the query into the latent space, the output back out
        with jax.named_scope("mla.q_proj"):
            q_abs = jnp.einsum("bthn,rhn->bthr", q_nope, wkv_b[..., :dn])
            q_lat = jnp.concatenate([q_abs, q_rope], axis=-1)  # [B, T, H, W]
        with jax.named_scope("mla.write"):
            cached = kv_hook(jnp.concatenate([c_kv, k_rope], axis=-1))
        with jax.named_scope("mla.read"):
            if attn_fn is not None:  # the ragged reader's latent form
                o_lat = attn_fn(
                    q_lat, cached, None, mask, cfg, positions=positions
                ).reshape(B, T, H, R)
            else:
                o_lat = _latent_attention(q_lat, cached, mask, R, sm_scale)
        with jax.named_scope("mla.out"):
            o = jnp.einsum("bthr,rhv->bthv", o_lat, wkv_b[..., dn:])
    with jax.named_scope("mla.out"):
        return matmul(o.reshape(B, T, H * dv), p["wo"])


# ------------------------------------------- recurrent mixer (falcon-h1)


def init_ssm_state(cfg: ModelConfig, batch: int, dtype=jnp.bfloat16):
    """A batch of rows' recurrent state, all zero (= "no token seen"), L =
    cfg.state_layers deep (every layer of falcon-h1; the "mamba" layers alone
    under cfg.layer_types, a layer's slot cfg.state_slots):
    {"ssm": [L, B, heads, head_dim, state] float32 — the recurrence
    accumulates over hundreds of steps, so it is not kept in the model
    dtype; "conv": [L, B, K-1, C] in ``dtype`` — the conv's last K-1
    inputs, channels minor (a trailing 3 would pad to 128 TPU lanes)}.
    The engine keeps one such tree beside the paged pool, one slot a row
    of the batch bucket (engine/scheduler.py)."""
    L = cfg.state_layers
    return {
        "ssm": jnp.zeros(
            (L, batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
            jnp.float32),
        "conv": jnp.zeros(
            (L, batch, cfg.ssm_conv - 1, cfg.ssm_conv_dim), dtype),
    }


def _ssm_mup_vector(cfg: ModelConfig):
    """The five zones of the in-projection (z, x, B, C, dt), each times its
    ssm_multipliers entry — a trace-time constant."""
    import numpy as np

    gn = cfg.ssm_groups * cfg.ssm_state
    widths = (cfg.ssm_inner, cfg.ssm_inner, gn, gn, cfg.ssm_heads)
    return np.concatenate([
        np.full((w,), m, np.float32)
        for w, m in zip(widths, cfg.ssm_multipliers)
    ])


def _ssm_chunked_scan(x, dt, A, Bm, Cm, h0, chunk: int):
    """Mamba-2's chunked (SSD) form of ``h_t = exp(dt_t A) h_{t-1} +
    dt_t x_t (outer) B_t; y_t = h_t C_t`` over T positions, float32.

    x [B,T,G,Hg,P]; dt [B,T,G,Hg] (0 at a position = that position leaves
    the state untouched); A [G,Hg]; Bm, Cm [B,T,G,N]; h0 [B,G,Hg,P,N].
    Inside a chunk of Q positions the outputs are one masked [Q, Q]
    product (attention-like); across chunks only the chunk-end states are
    carried, by a scan of T/Q steps. Returns (y [B,T,G,Hg,P], h_T)."""
    B, T, G, Hg, P = x.shape
    Q = min(chunk, T)
    nc = -(-T // Q)
    pad = nc * Q - T
    if pad:  # pad positions: dt = 0 (no decay, no input), x = B = C = 0
        pad_t = lambda a: jnp.pad(  # noqa: E731
            a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        x, dt, Bm, Cm = pad_t(x), pad_t(dt), pad_t(Bm), pad_t(Cm)
    xs = x.reshape(B, nc, Q, G, Hg, P)
    dts = dt.reshape(B, nc, Q, G, Hg)
    Bs = Bm.reshape(B, nc, Q, G, -1)
    Cs = Cm.reshape(B, nc, Q, G, -1)
    cum = jnp.cumsum(dts * A, axis=2)  # [B,nc,Q,G,Hg], <= 0, decreasing
    # inside a chunk: y_i += sum_{j<=i} (C_i.B_j) exp(cum_i - cum_j) dt_j x_j
    i_ge_j = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None, None]
    diff = cum[:, :, :, None] - cum[:, :, None, :]  # [B,nc,i,j,G,Hg]
    decay = jnp.where(i_ge_j, jnp.exp(jnp.where(i_ge_j, diff, 0.0)), 0.0)
    cb = jnp.einsum("bcign,bcjgn->bcijg", Cs, Bs, precision=_HI)
    m = cb[..., None] * decay * dts[:, :, None]
    y = jnp.einsum("bcijgh,bcjghp->bcighp", m, xs, precision=_HI)
    # each chunk's own contribution to its end state
    to_end = jnp.exp(cum[:, :, -1:] - cum) * dts  # [B,nc,Q,G,Hg]
    s_c = jnp.einsum("bcjghp,bcjgn->bcghpn", xs * to_end[..., None], Bs,
                     precision=_HI)
    chunk_decay = jnp.exp(cum[:, :, -1])  # [B,nc,G,Hg]

    def carry_state(h, inp):
        s, d = inp
        return h * d[..., None, None] + s, h  # emit the state BEFORE the chunk

    h_last, h_prev = lax.scan(
        carry_state, h0,
        (jnp.moveaxis(s_c, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    # what the earlier chunks left: y_i += exp(cum_i) (h_prev C_i)
    y_off = jnp.einsum("bcign,cbghpn->bcighp", Cs, h_prev, precision=_HI)
    y = y + y_off * jnp.exp(cum)[..., None]
    return y.reshape(B, nc * Q, G, Hg, P)[:, :T], h_last


def ssm_mixer(p: Params, cfg: ModelConfig, u, state=None, valid_len=None,
              layer=None):
    """falcon-h1's Mamba-2 mixer on ``u`` [B, T, D] (ln1's output).
    Returns (out [B, T, D], new_state or None).

    ``state`` is ONE layer's slice of init_ssm_state ({"ssm": [B, heads,
    head_dim, state] f32, "conv": [B, K-1, C]}) or None for a stateless
    full-sequence pass from zero (training / scoring / the cache-less
    forward). With ``layer`` (a traced scalar: core.forward's layer scan)
    ``state["ssm"]`` is the STACKED [L, B, heads, head_dim, state] and
    comes back whole with that layer's slice replaced. T == 1 over a
    carried state is one Mosaic call that steps the state in place and
    forms ``y`` in the same pass (ops/ssm_step.py: decode); longer chunks
    run the chunked scan from the carried state (prefill, chunked
    prefill); a stateless T == 1 keeps the recurrence as XLA ops.

    ``valid_len`` [B]: only each row's first ``valid_len`` positions are
    real (a prefill bucket's padded tail). Pads get dt = 0 and are left
    out of the conv tail, so the returned state is the state after the
    LAST REAL token: K/V pads are masked and later overwritten, a
    recurrent state has no "later"."""
    B, T, _ = u.shape
    Hs, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    G, K = cfg.ssm_groups, cfg.ssm_conv
    Hg, inner, C = Hs // G, cfg.ssm_inner, cfg.ssm_conv_dim
    f32 = jnp.float32

    with jax.named_scope("ssm.in_proj"):
        proj = matmul(u * jnp.asarray(cfg.ssm_in_multiplier, u.dtype), p["w_in"])
        proj = proj * jnp.asarray(_ssm_mup_vector(cfg), proj.dtype)
        z, xbc, dt = jnp.split(proj, [inner, inner + C], axis=-1)

    with jax.named_scope("ssm.conv"):
        prev = (state["conv"].astype(xbc.dtype) if state is not None
                else jnp.zeros((B, K - 1, C), xbc.dtype))
        ext = jnp.concatenate([prev, xbc], axis=1)  # [B, K-1+T, C]
        w = p["conv_w"].astype(f32)  # [C, K]; tap K-1 is the current token
        acc = p["conv_b"].astype(f32)
        for k in range(K):
            acc = acc + ext[:, k:k + T].astype(f32) * w[:, k]
        xbc_c = jax.nn.silu(acc)  # [B, T, C] f32
        if state is None:
            new_conv = None
        elif valid_len is None:
            new_conv = ext[:, T:]
        else:  # the K-1 inputs that end at each row's last real token
            new_conv = jax.vmap(
                lambda e, n: lax.dynamic_slice_in_dim(e, n, K - 1, axis=0)
            )(ext, valid_len)

    with jax.named_scope("ssm.conv"):  # the conv's three zones
        x = xbc_c[..., :inner].reshape(B, T, G, Hg, P)
        Bm = xbc_c[..., inner:inner + G * N].reshape(B, T, G, N)
        Cm = xbc_c[..., inner + G * N:].reshape(B, T, G, N)
    with jax.named_scope("ssm.in_proj"):  # the projection's dt zone, and A
        dt = jax.nn.softplus(dt.astype(f32) + p["dt_bias"]).reshape(B, T, G, Hg)
        if valid_len is not None:
            real = jnp.arange(T, dtype=jnp.int32)[None, :] < valid_len[:, None]
            dt = jnp.where(real[..., None, None], dt, 0.0)
        A = -jnp.exp(p["A_log"].astype(f32)).reshape(G, Hg)
    stacked = layer is not None

    if T == 1 and state is not None:
        # inside the scope: the benchmark books the call's device time by it
        with jax.named_scope("ssm.step"):
            hs, y = ssm_state_step(
                state["ssm"] if stacked else state["ssm"][None],
                layer if stacked else 0,
                dt.reshape(B, Hs), x.reshape(B, Hs, P), Bm[:, 0], Cm[:, 0],
                A.reshape(Hs))
            new_ssm = hs if stacked else hs[0]
            y = y.reshape(B, 1, G, Hg, P)
    else:
        if state is None:
            h0 = jnp.zeros((B, G, Hg, P, N), f32)
        else:
            h0 = (state["ssm"][layer] if stacked else state["ssm"]).reshape(
                B, G, Hg, P, N)
        if T == 1:
            with jax.named_scope("ssm.step"):
                h, y = ssm_state_step_xla(
                    h0.reshape(B, Hs, P, N), dt.reshape(B, Hs),
                    x.reshape(B, Hs, P), Bm[:, 0], Cm[:, 0], A.reshape(Hs))
                y = y.reshape(B, 1, G, Hg, P)
        else:
            with jax.named_scope("ssm.scan"):
                y, h = _ssm_chunked_scan(x, dt, A, Bm, Cm, h0, cfg.ssm_chunk)
        if state is not None:
            new_ssm = h.reshape(B, Hs, P, N)
            if stacked:
                # where the compiler puts a chunk's last state update (one
                # in-place dynamic-update-slice fusion a layer): a scope of
                # its own, or a device trace books it to no part of the mixer
                with jax.named_scope("ssm.state_write"):
                    new_ssm = state["ssm"].at[layer].set(new_ssm)

    with jax.named_scope("ssm.out_proj"):
        y = y + p["D"].astype(f32).reshape(G, Hg)[..., None] * x
        # gate first, then an RMS norm over each GROUP's channels
        # (mamba_rms_norm, norm_before_gate=False), then the learned scale
        g = y.reshape(B, T, inner) * jax.nn.silu(z.astype(f32))
        g = g.reshape(B, T, G, inner // G)
        g = g * lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + cfg.norm_eps)
        g = g.reshape(B, T, inner) * p["norm"].astype(f32)
        out = matmul(g.astype(u.dtype), p["w_out"])

    if state is None:
        return out, None
    return out, {"ssm": new_ssm, "conv": new_conv.astype(state["conv"].dtype)}


# ------------------------------------------------------- reusable blocks


def embed_tokens(params: Params, cfg: ModelConfig, input_ids, positions):
    """Token (+learned-pos) embedding. input_ids [B,T], positions [B,T]."""
    with jax.named_scope("embed.tokens"):
        x = jnp.take(params["tok_embed"], input_ids, axis=0)
        if cfg.embedding_scale:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
        if cfg.embedding_multiplier != 1.0:  # falcon-h1
            x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
        if cfg.pos_embedding == "learned":
            x = x + jnp.take(params["pos_embed"], positions, axis=0)
        if cfg.embedding_norm:  # bloom: LayerNorm before block 0
            x = _norm(x, params["embed_norm"], cfg)
    return x


# A call of at most this many rows (B * T, a static shape) keeps its q / k / v
# products plain [rows, D] x [D, N] products that read the layer's matrix out
# of the stacked parameter where it lies, as the MLP's and wo's do: a barrier
# keeps the compiler from folding the head split into them, for a product over
# heads takes no (stack, layer index) operand and every layer's wq / wk / wv is
# then copied out of the stack first. That copy is a fixed cost a layer; what
# the barrier costs, the activations re-laid for the heads behind it, grows
# with the rows and passes it between 2,048 and 4,096 of them on a v5e
# (both sides measured by PR 47 and kept in PERF.md section 6).
QKV_IN_PLACE_ROWS = 2048


def transformer_block(
    lp: Params, cfg: ModelConfig, x, positions, mask, kv_hook=None,
    attn_fn=None, rope_local=None, lora=None, ssm_hook=None,
    moe_kw=None, moe_sink=None,
):
    """One block. lp: a single layer's params (no leading L dim). x [B,T,D].

    kv_hook(k, v) -> (k_eff, v_eff), when given, intercepts the freshly
    projected K/V — the cached decode path uses it to write the chunk into
    the KV cache and attend over the cache instead (over the paged pool
    with the ragged reader, k_eff is the pool's ``kv`` leaf, K beside V,
    and v_eff None: forward's kv_hook). No hook = plain causal
    self-attention over the chunk (training/scoring/pipeline-stage path).

    attn_fn(q, k, v, mask, cfg, positions=positions) -> [B,T,H*hd] replaces
    the dense softmax attention — the sequence-parallel path passes ring
    attention here, the engine's flash path passes the pallas kernel
    (which derives per-batch cache offsets from `positions`).

    ``lora`` (multi-adapter serving, adapters/pool.py): one layer's
    stacked per-target A/B factors plus the batch's per-row slot ids —
    every projection goes through lora_matmul, which adds each row's
    low-rank delta after the (possibly quantized) base matmul.

    ``ssm_hook(h) -> [B,T,D]`` (cfg.has_ssm, falcon-h1): the recurrent
    mixer reads the SAME normed input as the attention and its output
    joins the attention's before the one residual add. The cached paths
    pass a hook that reads and writes the layer's recurrent state; no
    hook = a stateless pass from zero state (ssm_mixer).

    Latent attention (cfg.has_mla): ``kv_hook(latent)`` takes the chunk's
    one cached row a token and ``attn_fn`` is the ragged reader's latent
    form (_mla_attention). A sigmoid-routed expert layer (``"moe"`` in
    ``lp``, cfg.moe_dropless) takes ``moe_kw`` (_moe_dropless's ``live``,
    ``experts``, ``layer``) and hands its stats to ``moe_sink``; its router
    reads ``h``, the pre-attention norm, under cfg.moe_router_input
    "attn_norm". ``rope_local`` (the traced is-sliding flag of this layer,
    layer_rope_flag) also decides WHETHER a layer rotates under
    cfg.rope_sliding_only: the full layers carry no positional encoding.

    Every model's block runs under the parts of tracing.DEVICE_PARTS
    (``norm.block``, ``attn.qkv`` / ``attn.rope`` / ``attn.write`` /
    ``attn.read`` / ``attn.out``, ``mlp.*`` or ``moe.*``, ``mla.*``,
    ``ssm.*``): a small op between two products (a bias, a multiplier, the
    residual add) sits under the part whose result it finishes, for a device
    capture books a fusion by its root instruction's scope.

    Under cfg.layer_types (granite-4.0-h) the layer's tree holds ONE mixer:
    ``"ssm"`` (the mixer's output is the whole branch: no attention runs,
    no page is written) or ``"attn"`` (no mixer runs); both residual adds
    take their branch times cfg.residual_multiplier.

    Under cfg.single_branch (nemotron-h) the layer's tree holds ONE branch,
    ``"ssm"``, ``"attn"`` OR ``"moe"``, and ONE norm: ``x + branch(ln1(x))``,
    one residual add. Nothing runs in the place of a second branch.
    """
    B, T, _ = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    def norm(a, name):
        with jax.named_scope("norm.block"):
            return _norm(a, lp[name], cfg)

    def join(x, branch, part, post=None, scale=True):
        """``x`` + a branch's output, under the part that finishes the branch
        (its post norm's where it has one: ``post``). ``scale``: the branch
        times cfg.residual_multiplier (a latent-attention block adds bare)."""
        if post is not None:
            part, branch = "norm.block", norm(branch, post)
        with jax.named_scope(part):
            return x + (_residual(branch, cfg) if scale else branch)

    ffn_part = ("mlp.down" if "moe" not in lp
                else "moe.combine" if cfg.moe_dropless else "moe.experts")

    h = x if cfg.no_pre_norms else norm(x, "ln1")
    if cfg.has_mla:
        x = join(x, _mla_attention(
            lp["attn"], cfg, h, positions, mask, kv_hook, attn_fn), "mla.out",
            scale=False)
        return join(x, _ffn(norm(x, "ln2"), lp, cfg, lora, moe_kw, moe_sink),
                    ffn_part, scale=False)
    if cfg.single_branch and "moe" in lp:  # an expert layer alone
        return join(x, _ffn(h, lp, cfg, lora, moe_kw, moe_sink), ffn_part)
    if cfg.layer_types and "ssm" in lp:  # a recurrent-only layer
        mix_out = (ssm_hook(h) if ssm_hook is not None
                   else ssm_mixer(lp["ssm"], cfg, h)[0])
        x = join(x, mix_out, "ssm.out_proj")
        if cfg.single_branch:
            return x
        return join(x, _ffn(norm(x, "ln2"), lp, cfg, lora, moe_kw, moe_sink),
                    ffn_part)
    mix_out = None
    if cfg.has_ssm and not cfg.layer_types:  # beside attention (falcon-h1)
        mix_out = (ssm_hook(h) if ssm_hook is not None
                   else ssm_mixer(lp["ssm"], cfg, h)[0])
        with jax.named_scope("ssm.out_proj"):
            mix_out = mix_out * jnp.asarray(
                cfg.ssm_out_multiplier, mix_out.dtype)
        if cfg.attention_in_multiplier != 1.0:
            with jax.named_scope("attn.qkv"):
                h = h * jnp.asarray(cfg.attention_in_multiplier, h.dtype)
    with jax.named_scope("attn.qkv"):
        q = lora_matmul(h, lp["attn"]["wq"], "wq", lora)
        k = lora_matmul(h, lp["attn"]["wk"], "wk", lora)
        v = lora_matmul(h, lp["attn"]["wv"], "wv", lora)
        if B * T <= QKV_IN_PLACE_ROWS:
            q, k, v = lax.optimization_barrier((q, k, v))
        if "bq" in lp["attn"]:
            q = q + lp["attn"]["bq"]
            k = k + lp["attn"]["bk"]
            v = v + lp["attn"]["bv"]
    if "q_norm" in lp["attn"] and cfg.qk_norm_full:
        # olmo2: RMSNorm over the WHOLE projection width, before reshape
        with jax.named_scope("norm.block"):
            q = _qk_rmsnorm(q, lp["attn"]["q_norm"], cfg.norm_eps)
            k = _qk_rmsnorm(k, lp["attn"]["k_norm"], cfg.norm_eps)
    q = q.reshape(B, T, H, hd)
    k = k.reshape(B, T, Hkv, hd)
    v = v.reshape(B, T, Hkv, hd)
    if "q_norm" in lp["attn"] and not cfg.qk_norm_full:
        # qwen3/gemma3: head-wise RMSNorm BEFORE rope
        with jax.named_scope("norm.block"):
            q = _qk_rmsnorm(q, lp["attn"]["q_norm"], cfg.norm_eps)
            k = _qk_rmsnorm(k, lp["attn"]["k_norm"], cfg.norm_eps)
    if cfg.key_multiplier != 1.0:  # falcon-h1: k scaled BEFORE the rotation
        with jax.named_scope("attn.qkv"):
            k = k * jnp.asarray(cfg.key_multiplier, k.dtype)
    if cfg.rope_sliding_only:
        if rope_local is None:
            raise ValueError(
                f"{cfg.name!r} rotates its sliding layers only: this path "
                "hands transformer_block no per-layer flag (layer_rope_flag), "
                "so its full layers would be rotated too"
            )
        with jax.named_scope("attn.rope"):
            q, k = (jnp.where(rope_local, _rope(
                a, positions, cfg.rope_theta, cfg.rotary_dim, cfg.rope_style,
                None), a) for a in (q, k))
    elif cfg.pos_embedding == "rope":
        with jax.named_scope("attn.rope"):
            if cfg.local_rope_theta is not None and rope_local is not None:
                # gemma-3: SLIDING layers rotate with the local theta and no
                # scaling; global layers use rope_theta + rope_scaling.
                # rope_local is the (traced) is-sliding flag for this layer
                def rot2(v):
                    g_ = _rope(v, positions, cfg.rope_theta, cfg.rotary_dim,
                               cfg.rope_style, cfg.rope_scaling)
                    l_ = _rope(v, positions, cfg.local_rope_theta,
                               cfg.rotary_dim, cfg.rope_style, None)
                    return jnp.where(rope_local, l_, g_)

                q, k = rot2(q), rot2(k)
            else:
                q = _rope(q, positions, cfg.rope_theta, cfg.rotary_dim,
                          cfg.rope_style, cfg.rope_scaling)
                k = _rope(k, positions, cfg.rope_theta, cfg.rotary_dim,
                          cfg.rope_style, cfg.rope_scaling)
    if kv_hook is not None:
        with jax.named_scope("attn.write"):
            k, v = kv_hook(k, v)
    with jax.named_scope("attn.read"):
        if attn_fn is None:
            attn_out = _attention(q, k, v, mask, cfg)
        else:
            attn_out = attn_fn(q, k, v, mask, cfg, positions=positions)
    with jax.named_scope("attn.out"):
        attn_out = lora_matmul(attn_out, lp["attn"]["wo"], "wo", lora)
        if "bo" in lp["attn"]:
            attn_out = attn_out + lp["attn"]["bo"]
        if mix_out is not None:
            # two kinds of token mixer, one residual add
            attn_out = mix_out + attn_out * jnp.asarray(
                cfg.attention_out_multiplier, attn_out.dtype)
    if cfg.parallel_block:
        # parallel residual: attention and MLP branches sum into x. phi
        # (parallel_norms=1) feeds both from ln1's output; gpt-neox
        # (parallel_norms=2) norms the mlp branch separately with ln2
        h_mlp = h if cfg.parallel_norms == 1 else norm(x, "ln2")
        with jax.named_scope("attn.out"):
            x = x + attn_out
        mlp_out = _mlp(h_mlp, lp["mlp"], cfg, lora)
        with jax.named_scope("mlp.down"):
            return x + mlp_out
    # (gemma-2/olmo2, cfg.post_norms: a branch's OUTPUT is normed)
    x = join(x, attn_out, "attn.out", "ln1_post" if cfg.post_norms else None)
    if cfg.single_branch:  # an attention layer alone
        return x

    h2 = x if cfg.no_pre_norms else norm(x, "ln2")
    if cfg.moe_router_input == "attn_norm":
        moe_kw = dict(moe_kw or {}, router_x=h)
    mlp_out = _ffn(h2, lp, cfg, lora, moe_kw, moe_sink)
    return join(x, mlp_out, ffn_part, "ln2_post" if cfg.post_norms else None)


def _residual(branch, cfg: ModelConfig):
    """A branch's output as the residual add takes it: times granite's
    ``residual_multiplier`` (1 = as it is)."""
    if cfg.residual_multiplier == 1.0:
        return branch
    return branch * jnp.asarray(cfg.residual_multiplier, branch.dtype)


def _ffn(h2, lp: Params, cfg: ModelConfig, lora=None, moe_kw=None,
         moe_sink=None):
    """A block's feed-forward half on the normed ``h2``: the layer's dense
    MLP, or its expert layer where the layer's tree has one (a model's
    leading dense layers, cfg.first_k_dense, have none). MoE keeps base
    experts (lora MLP targets are rejected per-model by
    train/lora.validate_targets — expert weights carry an [L, E, ...] dim)."""
    if "moe" not in lp:
        return _mlp(h2, lp["mlp"], cfg, lora)
    if not cfg.moe_dropless:
        with jax.named_scope("moe.experts"):
            return _moe(h2, lp["moe"], cfg)
    out, stats = _moe_dropless(h2, lp["moe"], cfg, **(moe_kw or {}))
    if moe_sink is not None:
        moe_sink(stats)
    return out


def final_logits(params: Params, cfg: ModelConfig, x):
    """Final norm + LM head (+softcap), f32 logits, under ``head.logits``."""
    with jax.named_scope("head.logits"):
        x = _norm(x, params["final_norm"], cfg)
    return head_logits(params, cfg, x)


@jax.named_scope("head.logits")
def head_logits(params: Params, cfg: ModelConfig, x):
    """LM head (+softcap) on an ALREADY normed ``x``, f32 logits: a looped
    stack norms inside its pass loop (forward) and must not norm twice."""
    if cfg.tie_embeddings:
        logits = x @ params["tok_embed"].T
    else:
        logits = x @ params["lm_head"]
        if "lm_head_bias" in params:
            logits = logits + params["lm_head_bias"]
    if cfg.lm_head_multiplier != 1.0:  # falcon-h1
        logits = logits * jnp.asarray(cfg.lm_head_multiplier, logits.dtype)
    logits = logits.astype(jnp.float32)
    if cfg.logits_softcap:
        c = cfg.logits_softcap
        logits = jnp.tanh(logits / c) * c
    return logits


# ---------------------------------------------------------------- forward


def attn_mask(cfg: ModelConfig, positions, T: int, S: int | None = None,
              window: int | None | str = "cfg"):
    """THE attention mask builder (sliding window included) — core.forward
    and stages.stage_forward must agree or a pipeline-split model diverges
    from the monolithic one.

    Cached (S given): [B, 1, T, S] over cache positions — s visible to
    query t iff s <= pos(t), and with a sliding window only the last W
    positions (s > pos(t) - W). Uncached: causal [1, 1, T, T] with the
    same window restriction. `window` overrides cfg.sliding_window
    (None = full causal) — the gemma-2 alternating pattern builds both
    variants from the same config."""
    w = cfg.sliding_window if window == "cfg" else window
    if S is not None:
        s_idx = jnp.arange(S, dtype=jnp.int32)[None, None, :]  # [1,1,S]
        q_pos = positions[:, :, None]  # [B,T,1]
        mask = s_idx <= q_pos  # [B,T,S]
        if w:
            mask = mask & (s_idx > q_pos - w)
        return mask[:, None, :, :]
    causal = jnp.tril(jnp.ones((T, T), bool))
    if w:
        qi = jnp.arange(T, dtype=jnp.int32)[:, None]
        ki = jnp.arange(T, dtype=jnp.int32)[None, :]
        causal = causal & (qi - ki < w)
    return causal[None, None, :, :]


def layer_rope_flag(cfg: ModelConfig, global_idx):
    """transformer_block's ``rope_local`` for the layer at GLOBAL index: the
    traced is-sliding flag where a layer's rotation follows its kind
    (gemma-3's local theta, smallthinker's unrotated full layers), else
    None."""
    if cfg.local_rope_theta is None and not cfg.rope_sliding_only:
        return None
    return is_sliding_layer(cfg, global_idx)


def is_sliding_layer(cfg: ModelConfig, global_idx):
    """Traced bool: does the layer at GLOBAL index window? THE one
    implementation of the local/global layer pattern (gemma-2: residue 0
    mod 2; gemma-3: residues 0..4 mod 6)."""
    res = jnp.asarray(cfg.sliding_window_residues, jnp.int32)
    sliding = jnp.any(res == (global_idx % cfg.sliding_window_every))
    if cfg.mtp_layers:  # the block BEHIND the trunk attends fully (cfg.layer_windows)
        sliding = sliding & (global_idx < cfg.n_layers)
    return sliding


def make_layer_mask(cfg: ModelConfig, positions, T: int, S: int | None = None,
                    start: int = 0):
    """Per-layer mask selector — THE one implementation of the gemma-2/3
    local/global alternation, shared by core.forward (start=0) and
    stages.stage_forward (start=spec.start). Non-alternating configs get
    the single attn_mask back for every layer."""
    mask = attn_mask(cfg, positions, T, S)
    if not (cfg.sliding_window and cfg.sliding_window_every > 1):
        return lambda idx: mask
    mask_full = attn_mask(cfg, positions, T, S, window=None)
    return lambda idx: jnp.where(is_sliding_layer(cfg, start + idx),
                                 mask, mask_full)


def make_layer_window(cfg: ModelConfig):
    """Per-layer effective sliding window as a [1] int32 (0 = full
    causal) — the ragged paged kernel's compact replacement for the bool
    mask (ops/ragged.py derives causality and ragged lengths from the
    per-row offsets, so the window is the ONLY mask information it needs,
    and a 16-lane bool mask block would not tile on TPU anyway). The
    per-layer selection uses the SAME is_sliding_layer rule as
    make_layer_mask, so the gemma-2/3 local/global alternation is
    identical across the dense and ragged paths."""
    w = int(cfg.sliding_window or 0)
    if not (w and cfg.sliding_window_every > 1):
        const = jnp.full((1,), w, jnp.int32)
        return lambda idx: const
    return lambda idx: jnp.where(
        is_sliding_layer(cfg, idx), w, 0
    ).astype(jnp.int32).reshape(1)


def forward(
    params: Params,
    cfg: ModelConfig,
    input_ids,  # [B, T] int32
    cache,  # {"k": [L,B,S,Hkv,hd], "v": ...} or None (no-cache full forward)
    offset,  # [] or [B] int32: write position of input_ids[:, 0] in the cache
    remat: bool = False,  # jax.checkpoint each layer (training: HBM for FLOPs)
    attn_fn=None,  # custom attention (ops.flash / parallel.ring); None = dense
    block_tables=None,  # [B, MB] int32: paged cache — see below
    paged_write_floor=None,  # [] or [B] int32: drop a row's paged WRITES below this position
    paged_write_ceil=None,  # [] or [B] int32: drop a row's paged WRITES at/after this position
    adapters=None,  # multi-LoRA serving (adapters/pool.py): stacked pool
    # factors {target: {"a": [L, N, din, r], "b": [L, N, r, dout]}}
    adapter_ids=None,  # [B] int32: each row's pool slot (0 = no adapter)
    adapter_scales=None,  # [N] f32: per-slot alpha/rank scaling
    valid_len=None,  # [B] int32: real positions a row (rest = bucket pad)
    last_index=None,  # [B] int32: logits of THIS position only -> [B, 1, V]
    return_hidden: bool = False,  # also return the final norm's INPUT [B, T, D]
):
    """Run a [B, T] token chunk. Returns (logits [B, T, V], new_cache).

    With a cache: K/V for this chunk are written at [offset, offset+T) and
    attention looks at cache positions < offset+T (causally within the
    chunk). Without a cache (cache=None): plain causal self-attention over
    the chunk — the training/scoring path.

    With ``block_tables`` [B, MB], the cache is a PAGED pool
    {"kv": [L, num_blocks, 2, Hkv, block_size, hd]} (init_paged_pool: K
    beside V, page-major) and row b's logical cache position p lives at
    pool slot (block_tables[b, p // block_size], p % block_size) of both
    halves of every kv head.
    Writes scatter the chunk into the mapped blocks. Attention depends on
    the attn_fn: a RAGGED attn_fn (ops/ragged.make_ragged_attn_fn, marked
    by its ``ragged`` attribute) reads the pool directly — the kernel
    gathers a tile of blocks per grid step, so neither the [B, S, Hkv, hd]
    view nor the [T, S] scores ever materialize — and over a float pool
    it WRITES it too: the kv_hook stores the chunk's K and V with ONE
    page-write call and hands the stacked leaf and the layer index
    through, so the pool is the layer loop's carry, in place, and no layer
    slices it or writes a slice back (the int8 pool's requantising write
    is XLA's and keeps its per-layer slices). The dense path (attn_fn None)
    gathers the MB mapped blocks per row into that view; either way cache
    traffic per step scales with the table width the caller passes (live
    blocks, bucketed) instead of the pool capacity. The position→slot map
    is order-preserving, so every mask (causal, sliding-window, gemma
    alternation) and the ALiBi bias apply unchanged over the gathered
    [B, MB*block_size] coordinate space — the ragged kernel consumes the
    SAME mask, blocked per page. Table entries past a row's live extent
    must map to blocks whose positions are causally masked (the engine
    pads with the reserved null block 0).

    ``paged_write_floor`` / ``paged_write_ceil`` (paged only): scatter
    writes outside [floor, ceil) are redirected to the null block — reads
    still see the existing pool content. Each is one position for the
    whole chunk or one A ROW ([B]: the rows of a grouped prefill have
    their own share points and prompt ends; a row whose ceil is 0 writes
    nothing). The floor protects copy-on-write
    shares (the engine's chunked-prefill capacity re-anchor can re-feed
    tokens BELOW a share point, and recomputed K/V under a different
    chunk geometry is not guaranteed bit-identical, so shared donor
    blocks must stay read-only). The ceil drops a prefill bucket's padded
    tail, so a short prompt never needs pool blocks past
    ceil(prompt_len / block_size) — pad positions are causally masked and
    decode overwrites its own positions before reading them.

    **Quantized pool** (EngineConfig.cache_dtype="int8"): the pool dict
    additionally carries ``kv_scale`` [L, NB, 2, Hkv] f32
    per-page-per-head scales (init_paged_pool). The paged scatter becomes
    quantize-on-write (_quantized_page_write: amax per (page, K or V,
    head) → int8 + running-max scale, requantizing a page whose scale
    grew; K and V in one pass), the
    ragged attn_fn receives the (pool_slice, scale_slice) pair and
    dequantizes INSIDE its page loop, and the dense/sp fallback
    dequantizes the gathered view — K/V never materialize wider than one
    block (kernel) or the existing gathered view (fallback) anywhere.
    The write-floor CoW argument carries over unchanged: redirected
    positions touch only the null block, so shared donor pages keep both
    their bytes AND their scales.

    **Recurrent state** (cfg.has_ssm, falcon-h1): the cache dict also
    carries ``ssm`` [L, B, heads, head_dim, state] f32 and ``conv``
    [L, B, K-1, C] (init_ssm_state; L = cfg.state_layers), one slot a BATCH
    ROW (not a pool block: the state has no positions to page). Every
    layer's mixer reads its slice and writes the state after this chunk back; ``valid_len``
    marks a prefill bucket's padded tail, which must leave the state
    untouched. Chunks of one row must arrive in order, each starting
    where the last ended — a recurrent state cannot re-feed or skip a
    token (the scheduler's chunk walk guarantees it).

    ``last_index`` [B] computes the head for one position a row only
    (prefill needs nothing else; at a 261,120-token vocabulary the full
    [B, T, V] logits of a 512 bucket would be 0.5 GB).

    **A looped stack** (cfg.loop_steps > 1, ouro): the layer loop runs
    inside a ``lax.scan`` over passes with the same weights; pass ``t``'s
    layer ``l`` writes and reads cache layer ``t * n_layers + l`` on every
    cache path (every cache is cfg.cache_layers deep), the model's final
    norm follows EVERY pass, and the head reads the last pass's normed
    output with no second norm. With loop_steps == 1 there is no pass loop
    at all: a plain stack's program is what it was.

    **A multi-token-prediction layer** (cfg.mtp_layers, K-EXAONE): with
    ``return_hidden`` the trunk's last hidden state (the final norm's input,
    every position) is returned third, for mtp_forward, which runs the layer
    BEHIND the trunk over the same chunk (_run_layers with that one block).

    **One mixer kind a layer** (cfg.layer_types, granite-4.0-h): the layer
    loop is one ``lax.scan`` a RUN of like layers (cfg.layer_runs), each
    reading its layer of the stacked parameters where it lies; a "mamba"
    layer reads and writes slot cfg.state_slots[l] of the state and no page,
    an "attention" layer layer cfg.cache_slots[l] of the pool and no state.
    The state, the pool and the expert stack are the carries of every run,
    in place. Where the pattern repeats a UNIT of unlike layers (nemotron-h's
    ``E M`` five times, cfg.layer_units) the scan's body is the whole unit:
    an "moe" layer (cfg.single_branch) reads its slot cfg.moe_slots[l] of
    the expert stacks and neither state nor page.
    """
    B, T = input_ids.shape
    off_b, positions = _chunk_positions(offset, B, T)
    x = embed_tokens(params, cfg, input_ids, positions)
    x, new_cache = _run_layers(
        params, cfg, x, cache, off_b, positions, remat=remat, attn_fn=attn_fn,
        block_tables=block_tables, paged_write_floor=paged_write_floor,
        paged_write_ceil=paged_write_ceil, adapters=adapters,
        adapter_ids=adapter_ids, adapter_scales=adapter_scales,
        valid_len=valid_len)
    hidden = x
    if last_index is not None:
        with jax.named_scope("head.logits"):
            x = take_position(x, last_index)
    # (a looped stack's last pass's norm WAS the final norm)
    logits = (head_logits if cfg.loop_steps > 1 else final_logits)(
        params, cfg, x)
    if return_hidden:
        return logits, new_cache, hidden
    return logits, new_cache


def _chunk_positions(offset, B: int, T: int):
    """(a chunk's first position a row [B], every position [B, T]) from
    ``offset`` [] or [B]."""
    off = jnp.asarray(offset, jnp.int32)
    off_b = jnp.broadcast_to(off.reshape(-1), (B,))  # [B]
    return off_b, off_b[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]


def _run_layers(
    params: Params, cfg: ModelConfig, x, cache, off_b, positions, only=None, *,
    remat: bool = False, attn_fn=None, block_tables=None,
    paged_write_floor=None, paged_write_ceil=None, adapters=None,
    adapter_ids=None, adapter_scales=None, valid_len=None,
):
    """forward's layer loop over the embedded chunk ``x`` [B, T, D] at
    ``positions`` [B, T] (``off_b`` [B] the rows' first): every layer of the
    stack once, in order (every pass of a looped stack), through the cache
    paths forward's docstring describes; the keywords are forward's.
    ``only`` = (one layer's parameters, its index): that layer ALONE, as the
    layer at that index of the stack runs (its mask and rotation by
    is_sliding_layer's rule, cache layer ``index``): mtp_forward's block
    behind the trunk, at index cfg.n_layers. Returns (x, the cache after)."""
    B, T = positions.shape
    if cfg.has_ssm and cache is not None and "ssm" not in cache:
        raise ValueError(
            f"{cfg.name!r} has a recurrent mixer: its cache must carry the "
            "rows' state (core.init_ssm_state) beside K/V — decoding "
            "without it would restart the recurrence at every call"
        )
    if valid_len is not None:
        valid_len = jnp.asarray(valid_len, jnp.int32)
    # the leaf that holds the keys: a paged pool's latent rows or its K
    # beside V (init_paged_pool), the rectangular cache's K (init_cache)
    pool_key = ("latent" if cfg.has_mla
                else "kv" if block_tables is not None else "k")
    if cfg.has_mla and cache is not None and block_tables is None:
        raise ValueError(
            f"{cfg.name!r} has latent attention: its cache is the paged "
            "latent pool (core.init_paged_pool + block_tables); the "
            "rectangular cache has no latent form"
        )

    if block_tables is not None:
        bt = jnp.asarray(block_tables, jnp.int32)
        BS = cache[pool_key].shape[-2]  # pool block size
        S = bt.shape[1] * BS  # gathered view width = logical positions

        def row_limit(x):  # [] or [B] -> [B, 1], beside positions [B, T]
            if x is None:
                return None
            x = jnp.asarray(x, jnp.int32).reshape(-1)
            return jnp.broadcast_to(x, (B,))[:, None]

        wfloor = row_limit(paged_write_floor)
        wceil = row_limit(paged_write_ceil)
    else:
        bt = None
        S = cache["k"].shape[2] if cache is not None else None
    # int8 cache: scales must ride along or writes would silently
    # astype-truncate K/V into garbage bit patterns — and only the PAGED
    # pool implements quantize-on-write, so an int8 rectangular cache is
    # rejected outright (static trace-time check, not a traced branch)
    quantized = bt is not None and cache is not None and "kv_scale" in cache
    if (
        cache is not None
        and cache[pool_key].dtype == jnp.int8
        and not quantized
    ):
        raise ValueError(
            "int8 KV cache requires the paged pool with its "
            "kv_scale scale array (init_paged_pool dtype=int8 "
            "+ block_tables); the rectangular cache has no quantized path"
        )
    # pool-direct attention: the ragged kernel gathers blocks itself, so
    # it needs the tables; kv_hook then skips the gathered-view build and
    # the per-layer "mask" becomes the compact window selector — nothing
    # S-wide is materialized on this path at all
    ragged = bt is not None and getattr(attn_fn, "ragged", False)
    # ... and on a float pool it writes the pages itself too: the stacked
    # pool stays the layer loop's carry, touched only by the two Mosaic
    # calls, so no layer slices it, re-lays it or writes a slice back
    # (ops/ragged.py "Layouts"). The int8 pool's requantising write is
    # XLA's and keeps the per-layer slices.
    page_write = attn_fn.write if ragged and not quantized else None
    if cfg.has_mla and attn_fn is not None and page_write is None:
        raise ValueError(
            f"{cfg.name!r} has latent attention: only the ragged reader over "
            "a float paged pool (attention='flash') or the dense path reads "
            "latent rows"
        )
    if ragged:
        attn_fn = functools.partial(
            attn_fn.latent if cfg.has_mla else attn_fn, block_tables=bt)
        layer_mask = make_layer_window(cfg)
    else:
        layer_mask = make_layer_mask(cfg, positions, T, S)

    # multi-adapter serving: the per-row slot ids and scales are batch-
    # constant across layers; the stacked factors ride the layer loop
    # (scan xs / per-layer index) so one layer's [N, din, r] slice — not
    # the whole [L, ...] stack — enters each block's gather
    if adapters is not None:
        aids = jnp.asarray(adapter_ids, jnp.int32)
        ascale = jnp.asarray(adapter_scales, jnp.float32)

        def lora_for(lad):
            return {"ab": lad, "ids": aids, "scale": ascale}
    else:
        lora_for = None

    rope_flag = functools.partial(layer_rope_flag, cfg)

    # the positions an expert layer may count as load: not a prefill
    # bucket's padded tail (past valid_len or the write ceil), not a row of
    # the batch bucket that maps no page (the ragged read's own rule for a
    # dead row: its table is the null block)
    token_live = None
    if cfg.moe_dropless:
        if valid_len is not None:
            token_live = jnp.arange(T, dtype=jnp.int32)[None, :] < valid_len[:, None]
        if bt is not None:
            mapped = jnp.broadcast_to(bt[:, :1] != 0, (B, T))
            token_live = mapped if token_live is None else token_live & mapped
            if wceil is not None:
                token_live = token_live & (positions < wceil)
    # the expert matrices of a stacked group stay out of the layer scan's
    # xs and are read in place (_moe_dropless): set per group below
    expert_stack = None
    # a looped stack (cfg.loop_steps > 1): the cache index of the running
    # pass's layer 0, set by the pass loop below (traced); None = a plain
    # stack, whose layer l reads and writes cache layer l
    cache_base = None

    def layer(carry, xs):
        x, lcache = carry
        lp, layer_idx = xs[0], xs[1]
        # the weights' layer ``layer_idx`` (masks, rotation, experts) against
        # the CACHE's ``cache_idx`` = pass * n_layers + layer (cfg.cache_layers)
        cache_idx = layer_idx if cache_base is None else cache_base + layer_idx
        state_idx = layer_idx
        if cfg.layer_types:  # a layer's slot of ITS kind's state or cache
            state_idx = _slot_of(cfg.state_slots, layer_idx)
            cache_idx = _slot_of(cfg.cache_slots, layer_idx)
        lora = lora_for(xs[2]) if len(xs) > 2 else None
        moe_kw = None
        if cfg.moe_dropless and "moe" in lp:
            moe_kw = {"live": token_live}
            if expert_stack is not None:
                moe_kw.update(
                    experts=expert_stack,
                    layer=_slot_of(cfg.moe_slots, layer_idx)
                    if cfg.single_branch else layer_idx - cfg.first_k_dense)

        if lcache is None:  # training/scoring path: plain block
            return (
                transformer_block(lp, cfg, x, positions,
                                  layer_mask(layer_idx), attn_fn=attn_fn,
                                  rope_local=rope_flag(layer_idx), lora=lora,
                                  moe_kw=moe_kw),
                None,
            ), None

        def moe_sink(stats):
            # the forward's expert-layer counters ride the cache dict when
            # the caller put them there (the engine's roots do)
            nonlocal lcache
            if "moe_stats" in lcache:
                lcache = dict(lcache, moe_stats=lcache["moe_stats"] + stats)

        def latent_hook(latent):
            """kv_hook under latent attention: write the chunk's rows
            [B, T, W] and return what attention reads — the stacked pool
            (ragged reader: the page-write kernel stores in place) or the
            gathered [B, S, W] view (dense: XLA's scatter on the layer's
            slice). The pool is [L, NB, 1, BS, W]: the unit axis stands
            where a K/V page has its two halves of heads."""
            nonlocal lcache
            if page_write is not None:
                lcache = dict(lcache, latent=page_write(
                    lcache["latent"], latent[:, :, None, :], bt, off_b,
                    layer_idx, paged_write_floor, paged_write_ceil))
                return lcache["latent"]
            # (the floor / ceil redirects to the null block: kv_hook below)
            blk = jnp.take_along_axis(bt, positions // BS, axis=1)
            if wfloor is not None:
                blk = jnp.where(positions >= wfloor, blk, 0)
            if wceil is not None:
                blk = jnp.where(positions < wceil, blk, 0)
            pool_l = lcache["latent"][layer_idx].at[blk, 0, positions % BS].set(
                latent.astype(lcache["latent"].dtype))
            lcache = dict(
                lcache, latent=lcache["latent"].at[layer_idx].set(pool_l))
            return pool_l[bt].reshape(B, S, -1).astype(latent.dtype)

        def ssm_hook(h):
            # this layer's recurrent state in, the state after the chunk out
            nonlocal lcache
            # (the mixer takes the STACKED state: a decode step updates
            # it in place, a longer chunk writes its slice back)
            out, new = ssm_mixer(
                lp["ssm"], cfg, h,
                {"ssm": lcache["ssm"], "conv": lcache["conv"][state_idx]},
                valid_len, layer=state_idx,
            )
            with jax.named_scope("ssm.state_write"):  # the conv's tail
                lcache = dict(
                    lcache, ssm=new["ssm"],
                    conv=lcache["conv"].at[state_idx].set(new["conv"]),
                )
            return out

        def kv_hook(k, v):
            """Write this chunk's K/V at [offset, offset+T) of every row and
            return what attention reads. Over the paged pool (block tables
            given) three variants remain, selected by ``attn_fn.ragged``
            and ``"kv_scale" in cache``; each stores K beside V in one
            pass over the ``kv`` leaf:

            - ragged reader, float pool: the page-write kernel stores the
              chunk into the STACKED leaf, which is returned whole, with
              no V beside it (the kernel reads layer ``cache_idx`` of it
              in place, a page's K and V in one copy);
            - int8 pool (``kv_scale`` present), either reader: XLA's
              requantising page write on the layer's slice; the ragged
              reader gets (pages, scales), the dense one the dequantised
              gathered view;
            - dense / sp reader, float pool: XLA's scatter on the layer's
              slice, then the gathered [B, S, Hkv, hd] views.

            Without tables (core.init_cache's rectangular cache: the model
            drafter) the chunk is a dynamic-update-
            slice into the row, and attention reads that row."""
            nonlocal lcache

            if bt is not None:
                kv = jnp.stack([k, v], axis=2)  # [B, T, 2, Hkv, hd], as a page lies
                if page_write is not None:
                    with jax.named_scope("kv.write"):
                        lcache = dict(lcache, kv=page_write(
                            lcache["kv"], kv, bt, off_b, cache_idx,
                            paged_write_floor, paged_write_ceil,
                        ))
                    return lcache["kv"], None
                # paged: scatter each position into its mapped (block, slot)
                # of every kv head. Rows own disjoint blocks (the engine's
                # allocator invariant), so the scatter indices never
                # collide across rows except in the garbage null block 0.
                Hkv, hd = k.shape[-2], k.shape[-1]
                blk = jnp.take_along_axis(bt, positions // BS, axis=1)
                slot = positions % BS  # [B, T]
                if wfloor is not None:
                    # re-fed positions below the share point write to the
                    # null block instead — shared donor blocks stay
                    # read-only (their content is already correct)
                    blk = jnp.where(positions >= wfloor, blk, 0)
                if wceil is not None:
                    # the bucket's padded tail writes to the null block —
                    # short prompts never claim blocks past their length
                    # (an out-of-table lookup above may have produced a
                    # fill value; this rewrites it to the real null block)
                    blk = jnp.where(positions < wceil, blk, 0)

                def views(pages):
                    # [B, MB, 2, Hkv, BS, hd] gathered pages -> K's and V's
                    # [B, S, Hkv, hd]
                    g = jnp.transpose(pages, (2, 0, 1, 4, 3, 5)).reshape(
                        2, B, S, Hkv, hd)
                    return g[0], g[1]

                if quantized:
                    # chunk-position → page-window slot for the touched-
                    # page dedup (positions[:, 0] == off_b)
                    wslot = positions // BS - (off_b // BS)[:, None]
                    ckv, sc = _quantized_page_write(
                        lcache["kv"][cache_idx],
                        lcache["kv_scale"][cache_idx], blk, slot, wslot, kv,
                    )
                    lcache = dict(
                        lcache,
                        kv=lcache["kv"].at[cache_idx].set(ckv),
                        kv_scale=lcache["kv_scale"].at[cache_idx].set(sc),
                    )
                    if ragged:
                        # (pool slice, scale slice): the kernel dequants
                        # inside its page loop — int8 is all that crosses
                        # HBM, one block's dequant lives in VMEM
                        return (ckv, sc), None
                    # dense/sp fallback: dequantize the gathered view —
                    # the same [B, S, Hkv, hd] width the bf16 path builds
                    return views((
                        ckv[bt].astype(jnp.float32) * sc[bt][..., None, None]
                    ).astype(k.dtype))
                # the layer's slice [NB, 2, Hkv, BS, hd]: the (blk, slot)
                # index arrays around the two sliced axes put [B, T] in
                # front, so the update operand is kv as it stands
                ckv = lcache["kv"][cache_idx].at[blk, :, :, slot].set(
                    kv.astype(lcache["kv"].dtype)
                )
                lcache = dict(lcache, kv=lcache["kv"].at[cache_idx].set(ckv))
                return views(ckv[bt])

            def write(cache_row, new_row, start):
                return lax.dynamic_update_slice(
                    cache_row, new_row.astype(cache_row.dtype), (start, 0, 0)
                )

            ck = jax.vmap(write)(lcache["k"][cache_idx], k, off_b)
            cv = jax.vmap(write)(lcache["v"][cache_idx], v, off_b)
            lcache = dict(
                lcache,
                k=lcache["k"].at[cache_idx].set(ck),
                v=lcache["v"].at[cache_idx].set(cv),
            )
            return ck, cv

        x = transformer_block(
            lp, cfg, x, positions, layer_mask(layer_idx),
            kv_hook=latent_hook if cfg.has_mla else kv_hook,
            attn_fn=(
                attn_fn if page_write is None
                else functools.partial(attn_fn, layer=cache_idx)
            ),
            rope_local=rope_flag(layer_idx), lora=lora,
            ssm_hook=ssm_hook if cfg.has_ssm else None,
            moe_kw=moe_kw, moe_sink=moe_sink,
        )
        return (x, lcache), None

    n_layers = cfg.n_layers
    # prevent_cse=False: checkpoint inside lax.scan doesn't need the CSE
    # barrier (scan's loop structure already prevents it) and the barrier
    # blocks XLA fusion otherwise
    layer_body = jax.checkpoint(layer, prevent_cse=False) if remat else layer

    def run_layers(carry):
        """Every layer once, in order: (x, cache) in, (x, cache) out."""
        nonlocal expert_stack
        layer_params = params["layers"]
        if cfg.layer_types and not isinstance(layer_params, (list, tuple)):
            if cfg.moe_dropless:
                rest, expert_stack = _split_expert_stack(layer_params["moe"])
                layer_params = dict(layer_params, moe=rest)
            return _scan_layer_runs(cfg, layer_params, layer_body, carry)[0]
        if isinstance(layer_params, (list, tuple)):
            # Unstacked layers (list of per-layer trees): unrolled loop. This
            # is the CPU serving fast path — XLA:CPU cannot pre-pack a GEMM
            # operand it first has to slice out of the stacked [L, ...] array,
            # so every dot inside scan falls off the packed-GEMM path
            # (measured: 24 ms vs 1.1 ms per distilgpt2 block at T=1).
            # Per-layer arrays arrive as separate, contiguous jit arguments
            # and GEMM packing works. TPU keeps the stacked scan below
            # (compile-time scales O(1) in depth; Mosaic handles layouts).
            # models.unstack_layers converts; engine does it when backend=cpu.
            for i, lp in enumerate(layer_params):
                if adapters is not None:
                    lad = jax.tree.map(lambda a: a[i], adapters)
                    carry, _ = layer_body(carry, (lp, i, lad))
                else:
                    carry, _ = layer_body(carry, (lp, i))
            return carry
        if "dense_layers" in params:
            # layers of unlike trees (cfg.first_k_dense): one scan a group of
            # like layers, the pool indexed by a layer's absolute place
            k_dense = cfg.first_k_dense
            carry, _ = lax.scan(
                layer_body, carry,
                (params["dense_layers"], jnp.arange(k_dense)))
            if cfg.moe_dropless:
                rest, expert_stack = _split_expert_stack(layer_params["moe"])
                layer_params = dict(layer_params, moe=rest)
            return lax.scan(
                layer_body, carry,
                (layer_params, jnp.arange(k_dense, n_layers)))[0]
        if cfg.moe_dropless:  # every layer an expert layer (smallthinker)
            rest, expert_stack = _split_expert_stack(layer_params["moe"])
            layer_params = dict(layer_params, moe=rest)
        xs = (layer_params, jnp.arange(n_layers))
        if adapters is not None:
            # the [L, N, ...] factor stacks join the scan xs, so each
            # layer body sees only its own [N, ...] slice; adapters=None
            # keeps the 2-tuple — the pre-adapter trace is unchanged
            xs = xs + (adapters,)
        return lax.scan(layer_body, carry, xs)[0]

    if only is not None:
        return layer_body((x, cache), only)[0]
    if cfg.loop_steps == 1:
        x, new_cache = run_layers((x, cache))
    else:
        # a looped stack (ouro): the SAME layers loop_steps times, a loop and
        # not an unrolling (a program's text and compile time stay one
        # pass's), the model's one final norm after EVERY pass, and pass t's
        # layer l on cache layer t * n_layers + l. The cache stays the carry
        # of both loops, in place (init_paged_pool)
        def one_pass(carry, t):
            nonlocal cache_base
            cache_base = t * n_layers
            x, pass_cache = run_layers(carry)
            with jax.named_scope("loop.norm"):
                x = _norm(x, params["final_norm"], cfg)
            return (x, pass_cache), None

        (x, new_cache), _ = lax.scan(
            one_pass, (x, cache), jnp.arange(cfg.loop_steps, dtype=jnp.int32))

    return x, new_cache


# a chunk of at most this many positions (a verify step's K + 1: far under
# the narrowest prefill bucket) has take_position SELECT its one position
SELECT_POSITIONS = 16


def take_position(x, index):
    """``x[b, index[b]]`` of x [B, T, W] as [B, 1, W]. Over a chunk of a few
    positions (T <= SELECT_POSITIONS) by a select and a sum over T (exact: one
    term a row is not zero), which reads the chunk once: an element-wise
    gather of [B, 1, W] indices costs the chip ~12 ns an ELEMENT (a
    [64, 1, 19200] take_along_axis read 16 ms a verify step, my chip run,
    PR 54). A prefill bucket's one position a row keeps the gather (a few
    rows of d_model once a prompt; the select there is not measured)."""
    B, T, W = x.shape
    index = jnp.asarray(index, jnp.int32)
    if T > SELECT_POSITIONS:
        idx = index.reshape(B, 1, 1)
        return jnp.take_along_axis(x, jnp.broadcast_to(idx, (B, 1, W)), axis=1)
    at = jnp.arange(T, dtype=jnp.int32)[None, :] == index.reshape(-1, 1)
    return jnp.sum(jnp.where(at[:, :, None], x, jnp.zeros((), x.dtype)),
                   axis=1, keepdims=True)


def mtp_input(params: Params, cfg: ModelConfig, hidden, next_ids, positions):
    """The MTP block's input ``W_eh [RMS_e(Emb(x_{t+1})) ; RMS_h(h_t)]``
    [B, T, D] of the trunk's last hidden state ``hidden`` [B, T, D] and the
    tokens that follow each position ``next_ids`` [B, T]."""
    mp = params["mtp"]
    e = embed_tokens(params, cfg, next_ids, positions)
    return matmul(jnp.concatenate(
        [_norm(e, mp["enorm"], cfg),
         _norm(hidden.astype(e.dtype), mp["hnorm"], cfg)], axis=-1),
        mp["eh_proj"])


def mtp_forward(params: Params, cfg: ModelConfig, hidden, next_ids, cache,
                offset, last_index=None, **kw):
    """The multi-token-prediction layer (cfg.mtp_layers) on a chunk the trunk
    has run: ``hidden`` [B, T, D] is forward's ``return_hidden`` (position
    t's h_t), ``next_ids`` [B, T] the tokens that FOLLOW each position
    (x_{t+1}), ``cache`` / ``offset``, ``last_index`` and the cache keywords
    (``attn_fn``, ``block_tables``, the write floor and ceil) as forward
    takes them for the same chunk. mtp_input, then ONE block of the trunk's
    kind run as the layer BEHIND the trunk (_run_layers: it attends fully,
    unrotated where the full layers are; its K/V go to cache layer
    cfg.n_layers at the chunk's own positions), then the trunk's final norm
    and head, under the scopes ``mtp.proj``, ``mtp.block`` (around the
    block's own ``attn.*`` / ``moe.*``) and ``mtp.head``. Returns (logits
    [B, T, V] over x_{t+2}, the model's draft of the token after next;
    cache)."""
    if not cfg.mtp_layers:
        raise ValueError(f"{cfg.name!r} has no multi-token-prediction layer")
    off_b, positions = _chunk_positions(offset, *next_ids.shape)
    with jax.named_scope("mtp.proj"):
        x = mtp_input(params, cfg, hidden, next_ids, positions)
    with jax.named_scope("mtp.block"):
        x, cache = _run_layers(
            params, cfg, x, cache, off_b, positions,
            (jax.tree.map(lambda a: a[0], params["mtp"]["block"]),
             cfg.n_layers), **kw)
    with jax.named_scope("mtp.head"):
        if last_index is not None:
            x = take_position(x, last_index)
        return final_logits(params, cfg, x), cache


def _layer_of(stack: Params, index):
    """Layer ``index`` (traced) of every stacked [L, ...] leaf of ``stack``."""
    return jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, index, keepdims=False), stack)


def _slot_of(slots: tuple, layer_idx):
    """``slots[layer_idx]`` (cfg.state_slots / cfg.cache_slots) for a host
    integer (the unrolled layer list) or a traced one (a layer scan)."""
    if isinstance(layer_idx, int):
        return slots[layer_idx]
    return jnp.asarray(slots, jnp.int32)[layer_idx]


_KIND_STACK = {"mamba": "ssm", "attention": "attn", "moe": "moe"}  # a layer
# kind -> the stack of ``layers`` that only layers of that kind hold


def _kind_stacks(cfg: ModelConfig) -> dict:
    """{kind: (its stack's name, layer -> slot)} for the kinds ``cfg``'s
    ``layer_types`` names; every other part of ``layers`` is stacked over all
    the layers (granite's expert layers: every layer has one)."""
    return {k: (_KIND_STACK[k], cfg.kind_slots[k]) for k in set(cfg.layer_types)}


def _scan_layer_runs(cfg: ModelConfig, layers: Params, body, carry):
    """The stacked layers of a model of one mixer kind a layer
    (cfg.layer_types), one ``lax.scan`` a RUN of a repeated unit of layers
    (cfg.layer_units: a run of like layers is a unit of one): ``body(carry,
    (lp, layer index)) -> (carry, y)`` gets a layer's tree read out of the
    stacks where they lie (what scan does with its xs): the common parts at
    the layer's index, the stack of its KIND (``ssm``, ``attn``, or ``moe``
    under cfg.single_branch) at its slot of that kind. A unit of several
    layers is ONE scan body that runs them in order. Returns (carry, [a run's
    ys that are not None, stacked in layer order])."""
    kinds = _kind_stacks(cfg)
    own = {name for name, _ in kinds.values()}
    common = {n: a for n, a in layers.items() if n not in own}
    ys = []
    for unit, start, repeats in cfg.layer_units:
        # (unit position j of repetition r: layer start + r p + j, at slot
        # slot0[j] + r x the unit's layers of that kind)
        p = len(unit)
        slot0 = [kinds[t][1][start + j] for j, t in enumerate(unit)]

        def run_body(c, r, unit=unit, start=start, p=p, slot0=slot0):
            out = []
            for j, t in enumerate(unit):
                name = kinds[t][0]
                i = start + r * p + j
                lp = dict(_layer_of(common, i), **{name: _layer_of(
                    layers[name], slot0[j] + r * unit.count(t))})
                c, y = body(c, (lp, i))
                out.append(y)
            return c, tuple(out)

        carry, y = lax.scan(
            run_body, carry, jnp.arange(repeats, dtype=jnp.int32))
        y = [a for a in y if a is not None]
        if y:  # [repeats, ...] a unit position -> layer order
            ys.append(y[0] if len(y) == 1 else jax.tree.map(
                lambda *a: jnp.stack(a, axis=1).reshape(-1, *a[0].shape[1:]),
                *y))
    return carry, ys


def unstack_layers(params: Params, cfg: ModelConfig | None = None) -> Params:
    """Convert stacked [L, ...] layer params into a list of per-layer
    contiguous trees (forward()'s unrolled path). Host-side numpy copies
    so each weight is its own packed buffer — the whole point is giving
    XLA:CPU pre-packable GEMM operands; quantized {"q","s"} subtrees pass
    through like any other leaves. A model of one mixer kind a layer
    (``cfg.layer_types``: the schema alone does not say which layer holds
    which) needs its ``cfg``: layer ``l``'s tree holds the common parts at
    ``l`` and ``ssm`` at cfg.state_slots[l] OR ``attn`` at cfg.cache_slots[l]
    (OR, under cfg.single_branch, ``moe`` at cfg.moe_slots[l])."""
    import numpy as np

    stacked = params["layers"]
    if isinstance(stacked, (list, tuple)):
        return params  # already unstacked: slicing again would shred weights
    out = dict(params)

    def at(tree, i):
        return jax.tree.map(
            lambda a: np.ascontiguousarray(np.asarray(a[i])), tree)

    if cfg is not None and cfg.layer_types:
        kinds = _kind_stacks(cfg)
        own = {name for name, _ in kinds.values()}
        common = {n: a for n, a in stacked.items() if n not in own}
        out["layers"] = [
            dict(at(common, i), **{
                kinds[t][0]: at(stacked[kinds[t][0]], kinds[t][1][i])})
            for i, t in enumerate(cfg.layer_types)]
        return out
    # a model's leading dense layers (their own stacked group) come first:
    # the list is in the layers' absolute order, trees unlike
    out["layers"] = [
        at(group, i)
        for group in (out.pop("dense_layers", None), stacked)
        if group is not None
        for i in range(len(jax.tree.leaves(group)[0]))
    ]
    return out


def restack_layers(params: Params) -> Params:
    """Inverse of unstack_layers: list of per-layer trees → stacked
    [L, ...] arrays. Consumers that serialize or shard the canonical
    layout (weight publishing, export) restack a CPU engine's params
    before use — np.asarray on the list would silently produce a
    dtype=object array of POINTERS, not weights."""
    import numpy as np

    layers = params["layers"]
    if not isinstance(layers, (list, tuple)):
        return params
    out = dict(params)

    def stack(group):
        return jax.tree.map(
            lambda *leaves: np.stack([np.asarray(a) for a in leaves]), *group)

    if any("attn" not in lp for lp in layers):
        # one mixer kind a layer: the common parts over every layer, each
        # kind's stack over the layers that hold it, in layer order (the
        # expert layers are such a kind where some layer has none)
        own = ("ssm", "attn") + (
            ("moe",) if any("moe" not in lp for lp in layers) else ())
        out["layers"] = dict(
            stack([{n: a for n, a in lp.items() if n not in own}
                   for lp in layers]),
            **{name: stack([lp[name] for lp in layers if name in lp])
               for name in own})
        return out

    # leading dense layers of an expert model go back to their own group
    k = 0
    if any("moe" in lp for lp in layers):
        while "moe" not in layers[k]:
            k += 1
    if k:
        out["dense_layers"] = stack(layers[:k])
    out["layers"] = stack(layers[k:])
    return out


def init_cache(cfg: ModelConfig, batch: int, max_len: int | None = None, dtype=jnp.bfloat16):
    """Preallocate a fixed-capacity KV cache: {"k","v"}: [L,B,S,Hkv,hd],
    L = cfg.cache_layers (a looped stack holds one a (pass, layer)).

    Model-level utility for forward()'s contiguous cache path (per-stage
    pipeline caches, scoring/offline use). The SERVING engine no longer
    allocates these — its one cache layout is the paged block pool
    (init_paged_pool; engine/scheduler.py)."""
    S = max_len or cfg.max_seq_len
    shape = (cfg.cache_layers, batch, S, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def pool_layout(cfg: ModelConfig) -> dict[str, tuple[int, int]]:
    """What a token stores in the paged pool, a layer: {part: (heads,
    width)}. K and V of every KV head for plain attention (the two halves
    of the stored ``kv`` leaf's pages, and two tensors of the block export
    format); under latent attention ONE row of cfg.latent_width numbers
    ([c_kv | k_rope], keys as it is and values by its first mla_kv_rank
    columns), its ``heads`` a unit axis. The engine's byte arithmetic,
    export format and the stored leaves' shapes (init_paged_pool) follow
    from this, never from (n_kv_heads, head_dim) directly."""
    if cfg.has_mla:
        return {"latent": (1, cfg.latent_width)}
    return {"k": (cfg.n_kv_heads, cfg.head_dim),
            "v": (cfg.n_kv_heads, cfg.head_dim)}


def pool_bytes_per_token(cfg: ModelConfig, itemsize: int = 2) -> int:
    """Bytes a cached token takes over all layers, as published (a
    lane-aligned pool stores more: its arrays' own nbytes say)."""
    return cfg.cache_layers * itemsize * sum(
        h * w for h, w in pool_layout(cfg).values())


def init_paged_pool(
    cfg: ModelConfig, num_blocks: int, block_size: int, dtype=jnp.bfloat16,
    lane_aligned: bool = False,
):
    """Preallocate the paged KV block pool, ONE leaf, page-major:
    {"kv": [L, num_blocks, 2, Hkv, block_size, hd]} — K beside V and every
    KV head of a block adjacent, so a page of a layer is one contiguous
    run of 2 x Hkv x block_size x hd numbers — or, under latent attention,
    {"latent": [L, num_blocks, 1, block_size, W]} (pool_layout: one row a
    token, no per-head K, no V; the unit axis stands where a K/V page has
    its two halves of heads). L is cfg.cache_layers: a looped stack (ouro)
    holds a layer of cache a (pass, weight layer), pass t's layer l at
    t * n_layers + l. The block axis is 1 on every leaf. Block 0
    is the engine's reserved null block (padding target for table entries
    past a row's live extent); rows map logical positions onto blocks via
    the block tables forward() takes.

    The trailing dims stay ``(block_size, hd)``: the ragged kernel
    (ops/ragged.py) fetches K and V of a tile of heads of one block as ONE
    operand a grid step, and Mosaic needs the trailing two dims of that
    block to be (block_size, hd) — a head axis blocked at 1 in trailing
    position fails to lower, the same constraint that shaped
    ops/flash.py's head-major transpose. On the ragged path a
    float pool is written and read by Mosaic calls alone, in place, as
    the layer loop's carry (forward's kv_hook): inside that loop it must
    never be sliced, scattered into or selected by XLA, or the compiler
    gives the carry XLA's layout and re-lays it for the kernel in every
    layer (ops/ragged.py, "Layouts").

    With ``dtype=int8`` (EngineConfig.cache_dtype="int8") the pool pages
    store quantized K/V and the dict grows ``kv_scale``
    [L, num_blocks, 2, Hkv] f32 per-page-per-head symmetric scales, K's
    beside V's as the pages lie —
    initialized to ZERO (= "page holds nothing"; forward's running-max
    quantize-on-write takes it from there, and the scheduler re-zeroes a
    block's entry when the allocator recycles it). Pool HBM halves vs
    bf16 at a 4 / (block_size * head_dim) scale overhead (~0.4% at the
    16x64 default).

    ``lane_aligned`` allocates the last axis at the TPU's 128-lane width
    (phi-3's 96 -> 128; pad lanes are zero and stay zero, the kernels pad
    what they store and cut what they return). The device's default layout
    for the stored array is then the kernels' own row-major one, so
    entering and leaving a program re-lays nothing: at head size 96 the
    default puts another axis minor-most ("it pads nothing"), every
    prefill call and decode window re-laid the whole pool in and out and
    held the padded copy as a temporary anyway. Only the in-place path
    (the ragged kernels over a float pool, on a TPU) asks for it; nothing
    but ops/ragged.py and the scheduler's block export / import ever
    looks at the pad."""
    if cfg.has_mla and jnp.dtype(dtype) == jnp.int8:
        raise ValueError(
            f"{cfg.name!r}: the latent pool has no int8 form (the "
            "requantising page write is per K/V head)")
    heads, width = next(iter(pool_layout(cfg).values()))  # K's are V's
    if lane_aligned:
        width = -(-width // 128) * 128
    # what stands in front of a page's (block_size, width): K and V of
    # every head, or a latent row's unit axis
    parts = (1,) if cfg.has_mla else (2, heads)
    pool = {"latent" if cfg.has_mla else "kv": jnp.zeros(
        (cfg.cache_layers, num_blocks, *parts, block_size, width), dtype)}
    if jnp.dtype(dtype) == jnp.int8:
        pool["kv_scale"] = jnp.zeros(
            (cfg.cache_layers, num_blocks, *parts), jnp.float32)
    return pool
