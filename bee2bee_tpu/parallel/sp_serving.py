"""Sequence-parallel SERVING: long-context attention over a seq-sharded
KV cache.

parallel/ring.py gives training its ring attention; this module gives the
*serving* engine the same first-class long-context story (the reference
has nothing here — SURVEY §5 "Long-context: absent"). Design:

- The paged KV pool [L, NB, 2, Hkv, BS, hd] is sharded over the `seq` mesh
  axis on its SLOT dim BS (models/partition.paged_cache_spec with
  seq_sharded=True — the engine sets it iff attention='sp'), so
  per-device pool HBM is 1/n — max context scales linearly with
  devices. The block gather stays local (it indexes only the block
  dim); XLA reshards the gathered [B, S, Hkv, hd] view into this
  shard_map's contiguous S/n layout, the collective sp attention pays
  anyway.
- Attention runs as a shard_map: every device scores the (replicated)
  queries against ITS S/n view shard with an online-softmax partial
  (o_unnormalized, m, l), then one pmax + two psums over `seq` combine
  the partials exactly — the all-to-all-free flash-style merge. Score
  memory per device is [T, S/n]: the quadratic prefill term is divided
  by the axis size too.
- Everything else (projections, MLP, sampling) stays in the engine's
  single jit program; XLA's partitioner handles the seq-sharded block
  scatter writes. The continuous-batching scheduler composes unchanged
  — its allocator/table ops never touch the slot dim.

Composes with TP (`model` axis shards heads, same rules as ops/flash:
GQA needs n_kv_heads % tp == 0, MQA replicates KV) and with DP on batch.

Engine flag: EngineConfig(attention="sp") on a mesh with seq > 1.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P
from ..compat import shard_map

NEG_INF = -1e30


def _partial_attention(q, k, v, mask, axis_name: str):
    """Local online-softmax partial + exact cross-shard merge.

    q [B, T, H_loc, hd] (replicated over `seq`); k/v [B, S_loc, Hkv_loc, hd]
    (this device's cache shard); mask [B, 1, T, S_loc]. Returns
    [B, T, H_loc*hd] replicated over `seq`.
    """
    B, T, H, hd = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, T, Hkv, G, hd).astype(jnp.float32)
    logits = jnp.einsum("btkgh,bskh->bkgts", qg, k.astype(jnp.float32))
    logits = logits / math.sqrt(hd)
    mb = mask[:, :, None, :, :]  # [B,1,1,T,S_loc] broadcast over (Hkv, G)
    logits = jnp.where(mb, logits, NEG_INF)
    m_loc = logits.max(axis=-1)  # [B, Hkv, G, T]
    p = jnp.exp(logits - m_loc[..., None])
    # a fully-masked local row is all NEG_INF: exp(0)=1 per entry — re-mask
    p = jnp.where(mb, p, 0.0)
    l_loc = p.sum(axis=-1)  # [B, Hkv, G, T]
    o_un = jnp.einsum("bkgts,bskh->btkgh", p, v.astype(jnp.float32))

    m = lax.pmax(m_loc, axis_name)
    corr = jnp.exp(m_loc - m)  # [B, Hkv, G, T]
    l = lax.psum(l_loc * corr, axis_name)
    o = lax.psum(o_un * corr.transpose(0, 3, 1, 2)[..., None], axis_name)
    out = o / jnp.where(l == 0.0, 1.0, l).transpose(0, 3, 1, 2)[..., None]
    return out.reshape(B, T, H * hd).astype(q.dtype)


def make_sp_attn_fn(mesh):
    """Build an attn_fn (core.transformer_block ABI) running seq-sharded
    cache attention. Batch rides `data` when divisible; heads ride `model`
    under TP (KV too when n_kv_heads divides, else MQA replication —
    exactly the ops/flash layout rules)."""

    def attn(q, k, v, mask, cfg, positions=None):
        B, _, H, _ = q.shape
        Hkv = k.shape[2]
        tp = mesh.shape.get("model", 1)
        data = mesh.shape.get("data", 1)
        b_ax = "data" if data > 1 and B % data == 0 else None
        h_ax = "model" if tp > 1 else None
        kv_ax = "model" if tp > 1 and Hkv % tp == 0 else None

        mapped = shard_map(
            lambda q_, k_, v_, m_: _partial_attention(q_, k_, v_, m_, "seq"),
            mesh=mesh,
            in_specs=(
                P(b_ax, None, h_ax, None),
                P(b_ax, "seq", kv_ax, None),
                P(b_ax, "seq", kv_ax, None),
                P(b_ax, None, None, "seq"),
            ),
            out_specs=P(b_ax, None, h_ax),
            check_vma=False,
        )
        return mapped(q, k, v, mask)

    return attn


def validate_sp_mesh(cfg, engine_cfg, mesh) -> None:
    """Fail fast when attention='sp' cannot run on this mesh/model."""
    sp = mesh.shape.get("seq", 1)
    if sp <= 1:
        raise ValueError(
            "attention='sp' needs a mesh with seq > 1 (got "
            f"{dict(mesh.shape)}); use attention='dense'/'flash' otherwise"
        )
    S = min(engine_cfg.max_seq_len, cfg.max_seq_len)
    if S % sp:
        raise ValueError(
            f"attention='sp' needs max_seq_len={S} divisible by the seq "
            f"axis {sp} (the cache capacity dim is sharded over it)"
        )
    bs = getattr(engine_cfg, "kv_block_size", 0) or 0
    if bs % sp:
        # the pool's SLOT dim carries the seq sharding and the gathered
        # view's width is table_width * kv_block_size: a block size the
        # axis doesn't divide would silently drop the 1/seq pool sharding
        # (engine._fit_spec falls back to replicated) AND crash the first
        # decode when shard_map can't split the narrow gathered view
        raise ValueError(
            f"attention='sp' needs kv_block_size={bs} divisible by the "
            f"seq axis {sp} (the pool's slot dim is sharded over it and "
            "every gathered-view width is a multiple of the block size)"
        )
    tp = mesh.shape.get("model", 1)
    if tp > 1:
        if cfg.n_heads % tp:
            raise ValueError(
                f"attention='sp' with TP needs n_heads={cfg.n_heads} "
                f"divisible by model axis {tp}"
            )
        if cfg.n_kv_heads % tp and cfg.n_kv_heads != 1:
            raise ValueError(
                f"attention='sp' cannot run GQA with n_kv_heads="
                f"{cfg.n_kv_heads} replicated across model axis {tp} "
                "(local kv-head mapping would be wrong); MQA (n_kv_heads=1) "
                "or divisible GQA only"
            )
