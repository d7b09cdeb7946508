"""In-mesh pipeline parallelism: GPipe microbatching over a `pipe` axis.

The reference's pipeline mechanism is embryonic — layer-range partial models
with hidden states forwarded between worker processes as JSON floats over
WebSocket (reference hf.py:180-205, node.py:236-277). The TPU-native
realization keeps that capability for cross-peer splits (models/stages.py)
and adds this: when the pipeline stages are chips of ONE slice, activations
move over ICI via `lax.ppermute` inside a single compiled program, not over
the network.

Mechanics (`shard_map` over a Mesh that includes a `pipe` axis):
- layer-stacked params [L, ...] reshape to [S, L/S, ...]; the S dim is
  sharded on `pipe`, so each device holds its stage's layers only
- the batch splits into M microbatches; for M + S - 1 ticks every stage
  applies its layers to its current microbatch and ppermutes the result to
  the next stage (stage 0 ingests microbatch t, the last stage's outputs
  accumulate)
- embedding and LM head run outside the shard_map (replicated params),
  so the pipelined region is exactly the layer trunk

Everything is differentiable: the pp train step is jax.grad through the
shard_map. The `data` axis composes freely (microbatches carry a data-
sharded batch dim).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from ..compat import shard_map

from ..models import core, support
from ..models.config import ModelConfig

PIPE_AXIS = "pipe"


def split_pp_params(params, n_stages: int, mesh: Mesh | None = None):
    """(head_params, staged_layers): the trunk leaves the param dict and
    comes back stage-stacked (sharded on `pipe` when a mesh is given)."""
    head = {k: v for k, v in params.items() if k != "layers"}
    staged = stage_stack_params(params, n_stages)
    if mesh is not None:
        staged = shard_stage_params(staged, mesh)
    return head, staged


def stage_stack_params(params, n_stages: int):
    """Reshape every layer-stacked leaf [L, ...] → [S, L/S, ...]."""

    def reshape(leaf):
        L = leaf.shape[0]
        if L % n_stages:
            raise ValueError(f"n_layers={L} not divisible by {n_stages} stages")
        return leaf.reshape(n_stages, L // n_stages, *leaf.shape[1:])

    return jax.tree.map(reshape, params["layers"])


def shard_stage_params(staged, mesh: Mesh):
    """Place stage-stacked layer params with the S dim on `pipe`."""

    def put(leaf):
        spec = P(PIPE_AXIS, *([None] * (leaf.ndim - 1)))
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree.map(put, staged)


def _apply_stage(stage_params, cfg: ModelConfig, x, positions, mask):
    """Run this device's L/S layers (scan over the local stack)."""

    def body(h, lp):
        return core.transformer_block(lp, cfg, h, positions, mask), None

    out, _ = lax.scan(body, x, stage_params)
    return out


def pipeline_apply(staged_params, cfg: ModelConfig, mesh: Mesh, x_mbs):
    """Pipelined layer trunk. x_mbs: [M, B, T, D] microbatched hidden states
    (replicated over `pipe`, batch dim shardable on `data`). Returns the
    trunk output with the same shape.
    """
    support.require(cfg, "pipeline_trunk")
    S = mesh.shape[PIPE_AXIS]
    M = x_mbs.shape[0]
    T = x_mbs.shape[2]
    if cfg.sliding_window and T > cfg.sliding_window:
        raise ValueError(
            f"pipeline trunk builds plain-causal masks; sliding_window="
            f"{cfg.sliding_window} binds at T={T} — train at <= window "
            "length or use the dense trainer"
        )
    if cfg.local_rope_theta is not None:
        # the trunk calls transformer_block without the per-layer rope
        # flag — gemma-3's sliding layers would silently rotate with the
        # GLOBAL theta/scaling
        raise ValueError(
            "pipeline trunk does not implement per-layer dual rope "
            f"(local_rope_theta, {cfg.name!r}); use the dense trainer"
        )

    in_specs = (
        jax.tree.map(lambda _: P(PIPE_AXIS), staged_params),
        P(None, "data", None, None),
    )
    out_specs = P(None, "data", None, None)

    @partial(shard_map, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)
    def run(stage_params, x_local):
        # stage_params leaves arrive as [1, L/S, ...] on this pipe shard
        stage_params_sq = jax.tree.map(lambda a: a[0], stage_params)
        s = lax.axis_index(PIPE_AXIS)
        B_loc = x_local.shape[1]
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B_loc, T))
        mask = jnp.tril(jnp.ones((T, T), bool))[None, None, :, :]

        state = jnp.zeros_like(x_local[0])
        out_acc = jnp.zeros_like(x_local)

        def tick(carry, t):
            state, out_acc = carry
            inp = lax.dynamic_index_in_dim(
                x_local, jnp.clip(t, 0, M - 1), 0, keepdims=False
            )
            cur = jnp.where(s == 0, inp, state)
            y = _apply_stage(stage_params_sq, cfg, cur, positions, mask)
            # the last stage finished microbatch t-(S-1) this tick
            widx = jnp.clip(t - (S - 1), 0, M - 1)
            valid = (s == S - 1) & (t >= S - 1)
            prev_row = lax.dynamic_index_in_dim(out_acc, widx, 0, keepdims=False)
            out_acc = lax.dynamic_update_index_in_dim(
                out_acc, jnp.where(valid, y, prev_row), widx, 0
            )
            nxt = lax.ppermute(y, PIPE_AXIS, [(i, (i + 1) % S) for i in range(S)])
            return (nxt, out_acc), None

        (_, out_acc), _ = lax.scan(tick, (state, out_acc), jnp.arange(M + S - 1))
        # only the last stage holds real outputs; broadcast to every stage so
        # the (replicated) LM head can run anywhere
        out = lax.psum(jnp.where(s == S - 1, out_acc, jnp.zeros_like(out_acc)), PIPE_AXIS)
        return out

    return run(staged_params, x_mbs)


def pipeline_forward(params, staged_params, cfg: ModelConfig, mesh: Mesh, input_ids, n_microbatches: int):
    """Full forward with the trunk pipelined. input_ids [B, T] (B divisible
    by n_microbatches). Returns logits [B, T, V]."""
    B, T = input_ids.shape
    M = n_microbatches
    if B % M:
        raise ValueError(f"batch {B} not divisible by {M} microbatches")
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    x = core.embed_tokens(params, cfg, input_ids, positions)
    x_mbs = x.reshape(M, B // M, T, -1)
    out = pipeline_apply(staged_params, cfg, mesh, x_mbs)
    return core.final_logits(params, cfg, out.reshape(B, T, -1))


def make_pp_loss(cfg: ModelConfig, mesh: Mesh, n_microbatches: int):
    """(params_no_layers, staged_layers, batch) -> scalar CE loss."""

    def loss(params, staged, batch):
        ids = batch["input_ids"]
        logits = pipeline_forward(params, staged, cfg, mesh, ids, n_microbatches)
        logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, ids[:, 1:][..., None], axis=-1)[..., 0]
        return nll.mean()

    return loss


def make_pp_train_step(cfg: ModelConfig, mesh: Mesh, n_microbatches: int, lr: float = 1e-3):
    """Jitted SGD step through the pipelined forward: proof that the whole
    pp program (ppermute schedule included) differentiates and updates."""
    loss_fn = make_pp_loss(cfg, mesh, n_microbatches)

    @jax.jit
    def step(params, staged, batch):
        (l, grads) = jax.value_and_grad(loss_fn, argnums=(0, 1))(params, staged, batch)
        gp, gs = grads
        params = jax.tree.map(lambda p, g: p - lr * g.astype(p.dtype), params, gp)
        staged = jax.tree.map(lambda p, g: p - lr * g.astype(p.dtype), staged, gs)
        return params, staged, l

    return step
