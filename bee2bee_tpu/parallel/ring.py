"""Ring attention: sequence/context parallelism over the `seq` mesh axis.

The reference has NOTHING here (SURVEY §2.4: SP/CP/ring "No — nothing
anywhere; sequence length is bounded by single-host HF generate"). For a
TPU framework long context is first-class, so this module provides:

- `ring_attention_local`: blockwise-causal attention with an online
  (flash-style) softmax whose K/V blocks rotate around the `seq` axis via
  `jax.lax.ppermute` — each device only ever holds O(T/n) keys, so max
  context scales linearly with the number of devices, and the permute
  rides ICI concurrently with compute.
- `ring_attention`: the shard_map wrapper over a Mesh for direct use.
- `make_sp_forward` / `make_sp_train_step`: a full causal-LM forward /
  train step sharded ('data','seq') where every attention is a ring —
  the DP×SP training path (TP composes via the dense-path trainer
  instead; the SP mesh must have model=expert=1).

Numerics: logits/softmax accumulate in f32 with the standard running
(max, sum, out) update; a fully-masked block contributes exp(-1e30-m)=0
rather than NaN.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import core, support
from ..compat import shard_map
from ..models.config import ModelConfig

NEG_INF = -1e30


def _block_attend(q, k, v, mask, acc):
    """One online-softmax update. q [B,Tq,Hkv,G,hd]; k/v [B,Tk,Hkv,hd];
    mask [Tq,Tk] bool; acc = (o [B,Tq,Hkv,G,hd] f32, m, l [B,Hkv,G,Tq] f32)."""
    o, m, l = acc
    hd = q.shape[-1]
    logits = jnp.einsum(
        "btkgh,bskh->bkgts", q.astype(jnp.float32), k.astype(jnp.float32)
    ) / math.sqrt(hd)
    logits = jnp.where(mask[None, None, None, :, :], logits, NEG_INF)
    m_new = jnp.maximum(m, logits.max(axis=-1))
    p = jnp.exp(logits - m_new[..., None])
    scale = jnp.exp(m - m_new)
    l = l * scale + p.sum(axis=-1)
    pv = jnp.einsum("bkgts,bskh->btkgh", p, v.astype(jnp.float32))
    o = o * scale.transpose(0, 3, 1, 2)[..., None] + pv
    return o, m_new, l


def ring_attention_local(q, k, v, axis_name: str, axis_size: int):
    """Causal ring attention on per-device shards (call inside shard_map).

    q [B, Tl, H, hd]; k, v [B, Tl, Hkv, hd] — Tl is the LOCAL chunk of a
    global sequence laid out contiguously along `axis_name` (device i owns
    positions [i*Tl, (i+1)*Tl)). Returns [B, Tl, H*hd].
    """
    B, Tl, H, hd = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    idx = lax.axis_index(axis_name)

    qg = q.reshape(B, Tl, Hkv, G, hd)
    o = jnp.zeros((B, Tl, Hkv, G, hd), jnp.float32)
    m = jnp.full((B, Hkv, G, Tl), NEG_INF, jnp.float32)
    l = jnp.zeros((B, Hkv, G, Tl), jnp.float32)

    t = jnp.arange(Tl, dtype=jnp.int32)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    k_cur, v_cur = k, v
    for step in range(axis_size):
        # after `step` rotations device idx holds the block that originated
        # on device (idx - step) mod n
        src = (idx - step) % axis_size
        qpos = idx * Tl + t  # global positions of local queries
        kpos = src * Tl + t
        mask = kpos[None, :] <= qpos[:, None]  # [Tl, Tl] causal
        o, m, l = _block_attend(qg, k_cur, v_cur, mask, (o, m, l))
        if step != axis_size - 1:  # skip the final (unused) rotation
            k_cur = lax.ppermute(k_cur, axis_name, perm)
            v_cur = lax.ppermute(v_cur, axis_name, perm)

    # l > 0 always: the self block's diagonal is never masked
    out = o / l.transpose(0, 3, 1, 2)[..., None]
    return out.reshape(B, Tl, H * hd).astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh, axis_name: str = "seq"):
    """shard_map wrapper: q [B,T,H,hd], k/v [B,T,Hkv,hd] with T divisible
    by mesh.shape[axis_name]; batch rides 'data' when present."""
    n = mesh.shape[axis_name]
    batch_axis = (
        "data"
        if mesh.shape.get("data", 1) > 1 and q.shape[0] % mesh.shape["data"] == 0
        else None
    )
    spec = P(batch_axis, axis_name, None, None)

    mapped = shard_map(
        partial(ring_attention_local, axis_name=axis_name, axis_size=n),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=P(batch_axis, axis_name, None),
        check_vma=False,
    )
    return mapped(q, k, v)


# ------------------------------------------------- sequence-parallel model


def make_sp_forward(cfg: ModelConfig, mesh: Mesh, remat: bool = False):
    """Full-model forward with every attention as a ring over `seq`.

    Requires model/expert axes of size 1 (TP/EP compose via the pjit path
    instead — mixing manual shard_map TP collectives into this would
    duplicate what XLA already does well there).

    Returns fn(params, input_ids [B,T]) -> logits [B,T,V]; params must be
    replicated across data/seq (they are: partition_specs only uses
    model/expert axes, which are singleton here).
    """
    for ax in ("model", "expert"):
        if mesh.shape.get(ax, 1) != 1:
            raise ValueError(
                f"make_sp_forward needs {ax}=1 in the mesh (got {mesh.shape})"
            )
    support.require(cfg, "ring_forward")
    n_seq = mesh.shape["seq"]
    attn = partial(ring_attention_local, axis_name="seq", axis_size=n_seq)

    def attn_fn(q, k, v, mask, _cfg, positions=None):
        return attn(q, k, v)

    def local_fn(params, ids):
        # ids: the LOCAL [B_loc, T_loc] chunk
        B, Tl = ids.shape
        start = lax.axis_index("seq") * Tl
        positions = jnp.broadcast_to(
            start + jnp.arange(Tl, dtype=jnp.int32), (B, Tl)
        )
        x = core.embed_tokens(params, cfg, ids, positions)

        def layer(x, lp):
            return (
                core.transformer_block(
                    lp, cfg, x, positions, mask=None, attn_fn=attn_fn
                ),
                None,
            )

        # long context is exactly where activation memory peaks — honor the
        # trainer's remat flag like core.forward does (prevent_cse=False:
        # scan's loop structure already blocks CSE)
        body = jax.checkpoint(layer, prevent_cse=False) if remat else layer
        x, _ = lax.scan(body, x, params["layers"])
        return core.final_logits(params, cfg, x)

    param_specs = jax.tree.map(lambda _: P(), jax.eval_shape(
        lambda: core.init_params(cfg, jax.random.key(0))
    ))

    mapped = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(param_specs, P("data", "seq")),
        out_specs=P("data", "seq", None),
        check_vma=False,
    )

    def sp_forward(params, ids):
        # guard at the PUBLIC surface (shape is static here): ring
        # attention builds plain-causal block masks, so a windowed model
        # past its window would silently attend beyond it and diverge
        # from core.forward inference
        if cfg.sliding_window and ids.shape[1] > cfg.sliding_window:
            raise ValueError(
                f"ring-SP does not implement sliding_window="
                f"{cfg.sliding_window} (seq len {ids.shape[1]} exceeds it); "
                "train/score at <= window length or use the dense path"
            )
        if cfg.local_rope_theta is not None:
            # the ring trunk calls transformer_block without the per-layer
            # rope flag — sliding layers would rotate with the global theta
            raise ValueError(
                "ring-SP does not implement per-layer dual rope "
                f"(local_rope_theta, {cfg.name!r}); use the dense path"
            )
        return mapped(params, ids)

    return sp_forward


def make_sp_train_step(cfg: ModelConfig, tcfg, mesh: Mesh, donate: bool = True):
    """DP×SP train step: ring attention inside, psum-mean loss/grads.

    Mirrors trainer.make_train_step's contract: (state, batch) ->
    (state, metrics) — same loss/step machinery (trainer.xent_loss_metrics
    / make_step_from_loss), only the forward differs.
    """
    from ..train.trainer import make_step_from_loss, xent_loss_metrics

    sp_forward = make_sp_forward(cfg, mesh, remat=tcfg.remat)

    def loss(params, batch):
        ids = batch["input_ids"]
        logits = sp_forward(params, ids)
        return xent_loss_metrics(logits, ids, batch.get("loss_mask"))

    return make_step_from_loss(
        loss, tcfg, NamedSharding(mesh, P("data", "seq")), donate=donate
    )
