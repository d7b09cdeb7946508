"""Pipeline stages: layer-range partial models for cross-peer serving.

The reference's embryonic PP seed builds a DistilBERT partial (embeddings
if first stage, encoder layers [start, end) — reference hf.py:180-205) and
forwards hidden states between workers over the wire (reference
node.py:236-277, kinds hf_part_load/hf_part_forward). This module is the
TPU-native generalization for BASELINE config 4 (zephyr-7b split across
two peers):

- Stage s of S owns transformer layers [a, b) of the stacked [L, ...]
  param tree (a contiguous slice of every layer-stacked leaf — no pytree
  surgery, the layout was designed for this), plus the embedding if s == 0
  and final-norm + LM head if s == S-1.
- `stage_forward` runs ids (first stage) or a hidden-state chunk through
  the slice against a per-stage KV cache at a given offset — the same
  static-shape cached contract as core.forward, so prefill (T=bucket) and
  decode (T=1) reuse one compiled program per shape.
- Hidden states cross peer boundaries as [B, T, D] tensors in binary
  frames (protocol.encode_binary) — ~2 bytes/element bf16 rather than the
  reference's JSON float lists (~5x the bytes, node.py:96-98).

Per-stage memory: a stage holds (b - a)/L of the params and of the KV
cache — two v5e-8 hosts each hold half of zephyr-7b.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from . import core, support
from .config import ModelConfig

Params = dict[str, Any]


def layer_ranges(n_layers: int, n_stages: int) -> list[tuple[int, int]]:
    """Contiguous [a, b) per stage; remainders spread to the EARLY stages
    (the first stage also pays the embedding, but early stages finish
    earlier in the 1F1B schedule, so front-loading balances the bubble)."""
    if not 1 <= n_stages <= n_layers:
        raise ValueError(f"n_stages={n_stages} must be in [1, {n_layers}]")
    base, extra = divmod(n_layers, n_stages)
    out, a = [], 0
    for s in range(n_stages):
        b = a + base + (1 if s < extra else 0)
        out.append((a, b))
        a = b
    return out


@dataclass(frozen=True)
class StageSpec:
    n_stages: int
    stage: int  # 0-based
    start: int  # first layer (inclusive)
    end: int  # last layer (exclusive)

    @property
    def is_first(self) -> bool:
        return self.stage == 0

    @property
    def is_last(self) -> bool:
        return self.stage == self.n_stages - 1

    @classmethod
    def build(cls, cfg: ModelConfig, n_stages: int, stage: int) -> "StageSpec":
        if not 0 <= stage < n_stages:
            raise ValueError(f"stage={stage} must be in [0, {n_stages})")
        support.require(cfg, "pipeline_stage_split")
        a, b = layer_ranges(cfg.n_layers, n_stages)[stage]
        return cls(n_stages=n_stages, stage=stage, start=a, end=b)


def extract_stage_params(params: Params, cfg: ModelConfig, spec: StageSpec) -> Params:
    """Slice the full param tree down to one stage's share.

    Layer-stacked leaves ([L, ...]) keep rows [start, end); the embedding
    (+ learned pos) stays only on the first stage; final_norm + lm_head
    only on the last. Tied embeddings force tok_embed onto the last stage
    too (it IS the output head there)."""
    out: Params = {
        "layers": jax.tree.map(lambda a: a[spec.start : spec.end], params["layers"])
    }
    if spec.is_first:
        out["tok_embed"] = params["tok_embed"]
        if "pos_embed" in params:
            out["pos_embed"] = params["pos_embed"]
        if "embed_norm" in params:  # bloom's embedding LayerNorm
            out["embed_norm"] = params["embed_norm"]
    if spec.is_last:
        out["final_norm"] = params["final_norm"]
        if cfg.tie_embeddings:
            out["tok_embed"] = params["tok_embed"]
        elif "lm_head" in params:
            out["lm_head"] = params["lm_head"]
            if "lm_head_bias" in params:  # phi: untied head carries a bias
                out["lm_head_bias"] = params["lm_head_bias"]
    return out


def init_stage_cache(
    cfg: ModelConfig, spec: StageSpec, batch: int, max_len: int, dtype=jnp.bfloat16
):
    """KV cache for this stage's layers only: [end-start, B, S, Hkv, hd]."""
    shape = (spec.end - spec.start, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def stage_forward(
    sparams: Params,
    cfg: ModelConfig,
    spec: StageSpec,
    x,  # [B, T] int32 ids (first stage) | [B, T, D] hidden (later stages)
    cache,  # init_stage_cache pytree or None (uncached full forward)
    offset,  # [] or [B] int32 write position, as core.forward
    write_mask=None,  # [B] bool: rows whose cache this call may write
):
    """Run one stage. Returns (out, new_cache) where out is logits
    [B, T, V] on the last stage and hidden [B, T, D] otherwise.

    Mirrors core.forward's cache/mask semantics exactly — a chain of
    stage_forward calls over all stages is numerically identical to one
    core.forward (test_stages asserts this).

    `write_mask` enables continuous batching across the wire: a new
    request prefills into ITS row of a shared [B]-row session cache while
    the other rows' K/V stay untouched (their outputs for this call are
    discarded by the caller). None means write every row."""
    if spec.is_first:
        B, T = x.shape
    else:
        B, T, _ = x.shape

    off = jnp.asarray(offset, jnp.int32)
    off_b = jnp.broadcast_to(off.reshape(-1), (B,))
    positions = off_b[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]

    if spec.is_first:
        h = core.embed_tokens(sparams, cfg, x, positions)
    else:
        h = x

    S = cache["k"].shape[2] if cache is not None else None
    # gemma-2 alternation by GLOBAL layer index (spec.start + local idx):
    # the split model must window exactly the layers the monolith windows
    layer_mask = core.make_layer_mask(cfg, positions, T, S, start=spec.start)

    def rope_flag(idx):
        return core.layer_rope_flag(cfg, spec.start + idx)

    def layer(carry, xs):
        h, ck, cv = carry
        lp, idx = xs
        if ck is None:
            return (
                core.transformer_block(lp, cfg, h, positions,
                                       layer_mask(idx),
                                       rope_local=rope_flag(idx)),
                None,
                None,
            ), None

        def kv_hook(k, v):
            nonlocal ck, cv

            def write(row, new, start, keep):
                upd = lax.dynamic_update_slice(
                    row, new.astype(row.dtype), (start, 0, 0)
                )
                return jnp.where(keep, upd, row)

            keep_b = (
                jnp.ones((B,), bool)
                if write_mask is None
                else jnp.asarray(write_mask, bool)
            )
            wk = jax.vmap(write)(ck[idx], k, off_b, keep_b)
            wv = jax.vmap(write)(cv[idx], v, off_b, keep_b)
            ck = ck.at[idx].set(wk)
            cv = cv.at[idx].set(wv)
            return wk, wv

        h = core.transformer_block(lp, cfg, h, positions, layer_mask(idx),
                                   kv_hook=kv_hook,
                                   rope_local=rope_flag(idx))
        return (h, ck, cv), None

    n_local = spec.end - spec.start
    layer_params = sparams["layers"]
    if isinstance(layer_params, (list, tuple)):
        # Unstacked per-layer trees: unrolled loop (the CPU fast path —
        # XLA:CPU can't pre-pack GEMM operands sliced in-graph from the
        # stacked arrays; see core.forward / docs/PERF.md "CPU fallback").
        # The same `layer` body runs with a static layer index.
        carry = (h, cache["k"], cache["v"]) if cache is not None else (h, None, None)
        for i, lp in enumerate(layer_params):
            carry, _ = layer(carry, (lp, i))
        h, ck, cv = carry
        new_cache = {"k": ck, "v": cv} if cache is not None else None
    elif cache is not None:
        xs = (layer_params, jnp.arange(n_local))
        (h, ck, cv), _ = lax.scan(layer, (h, cache["k"], cache["v"]), xs)
        new_cache = {"k": ck, "v": cv}
    else:
        xs = (layer_params, jnp.arange(n_local))
        (h, _, _), _ = lax.scan(layer, (h, None, None), xs)
        new_cache = None

    if spec.is_last:
        return core.final_logits(sparams, cfg, h), new_cache
    return h, new_cache
