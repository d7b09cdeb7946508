"""Ragged paged-attention pallas kernel: one decode path for paged +
flash + spec.

The dense paged path (models/core.forward's ``block_tables`` branch)
gathers every mapped block into a rectangular [B, S, Hkv, hd] view and
materializes [B, H, T, S] scores — the block pool saved cache HBM but
attention still paid the dense rectangle. This kernel (after "Ragged
Paged Attention" — PAPERS.md, arxiv 2604.15464) reads K/V straight from
the pool:

- **Pool-direct gather**: the pool is head-major ``[Hkv, NB, BS, hd]``
  (per-layer slice of core.init_paged_pool's ``[L, Hkv, NB, BS, hd]``)
  and the grid's page dimension DMAs exactly one block per step via a
  scalar-prefetched block-table lookup in the BlockSpec index_map —
  ``(h, tables[b, j], 0, 0)``. No gathered view, no [T, S] score
  materialization; per-step cache traffic is the table width, same as
  the pool's design point. Every tensor operand's trailing block dims
  are ``(rows, hd)`` — Mosaic-tileable (the [NB, BS, Hkv, hd] layout
  would put a 1-blocked head axis second-to-last and fail to lower, and
  a bool-mask operand blocked per 16-lane page would violate the same
  rule — the constraint that shaped ops/flash.py's head-major layout).
- **One kernel, every chunk shape**: queries fold to ``[B, Hkv, G*T,
  hd]`` rows (GQA group g major, chunk position t minor), so [B, 1]
  decode, [B, K+1] spec verify and ragged prefill chunks are all just
  different row counts of the same program. Rows tile over a q grid
  dimension so long prefill chunks bound VMEM.
- **Scalar-compact semantics**: no mask array at all. Causality and
  per-row ragged lengths derive from the prefetched per-row ``offset``;
  the sliding window (and the gemma-2/3 per-layer local/global
  alternation) arrives as ONE prefetched int32 ``window`` (0 = full
  causal) that core.forward selects per layer with the SAME
  is_sliding_layer rule the dense mask builder uses; logit softcap and
  the gemma score-scale override are scalar params. Null-block table
  entries past a row's live extent are beyond ``offset + T`` and
  therefore causally masked by construction. Two block-level skip
  predicates (page past the causal frontier / entirely below the
  window) avoid the dead MXU/VPU work on those pages — the BlockSpec
  gather still DMAs every table-width page into VMEM (skipping the DMA
  itself needs an index_map that can remap dead pages, a follow-up) —
  so the compute cost of windowed decode follows ~ceil(w/BS) pages
  while cache traffic remains the (pow2-bucketed) table width. ALiBi
  stays dense-only (the bias needs absolute key positions per head;
  the engine validates).
- **Online softmax** over the page iterations with f32 m/l/acc VMEM
  scratch, f32 MXU accumulation, storage dtype out — exactly
  ops/flash.py's numerics, so greedy parity with the dense path holds
  token-for-token.

- **Int8 pool dequant in the page loop**: with ``k_scale``/``v_scale``
  [Hkv, NB] f32 (the per-layer slice of core.init_paged_pool's
  per-page-per-head quantization scales), the pool blocks arrive int8
  and each grid step dequantizes ITS one block in VMEM — K before the
  QK^T dot, V before the PV dot — so the precision change rides the
  existing gather: HBM cache traffic halves and nothing wider than one
  block ever materializes. The scales ride the SAME scalar-prefetch
  channel as the block tables — pre-gathered through the tables to
  ``[Hkv, B, MB]`` outside the kernel, so the kernel reads one f32 per
  grid step at ``[h, b, j]`` from SMEM (a (1, 1)-blocked VMEM operand
  would violate the trailing-dims tiling rule above) and the SMEM
  footprint is table-sized — 2 * Hkv/shard * B * MB * 4 bytes, bounded
  by the pow2-bucketed LIVE width like every per-step operand, never by
  pool capacity. The f32 m/l/acc scratch already isolates accumulation
  from storage precision, so the quantized path changes no softmax math.

On devices that are not TPUs the kernel runs in pallas interpret mode
(ops/flash.interpret_off_tpu), so the CPU test suite exercises the exact
kernel code path; on a TPU it compiles or raises.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..compat import shard_map
from .flash import NEG_INF, _LANES, interpret_off_tpu, validate_flash_mesh


def _ragged_kernel(
    tables_ref,  # SMEM [B, MB] int32 (scalar-prefetch): per-row block tables
    off_ref,  # SMEM [B] int32 (scalar-prefetch): position of q[:, 0]
    win_ref,  # SMEM [1] int32 (scalar-prefetch): sliding window (0 = none)
    *refs,
    # quantized=True prepends two more scalar-prefetch refs:
    #   kscale_ref, vscale_ref  SMEM [Hkv, B, MB] f32 scales, pre-gathered
    #                           through the block tables per row
    # then the tensor operands either way:
    #   q_ref    [1, 1, BQ, hd]  q rows: GQA group g major, chunk pos t minor
    #   k_ref    [1, 1, BS, hd]  one pool block, gathered via index_map
    #   v_ref    [1, 1, BS, hd]
    #   o_ref    [1, 1, BQ, hd]
    #   m_ref    VMEM [BQ, 128] f32 running max
    #   l_ref    VMEM [BQ, 128] f32 running sum
    #   acc_ref  VMEM [BQ, hd] f32
    sm_scale: float,
    softcap: float,
    block_size: int,
    block_q: int,
    chunk: int,  # T: query positions per row (row r is chunk position r % T)
    quantized: bool = False,
):
    if quantized:
        (kscale_ref, vscale_ref, q_ref, k_ref, v_ref,
         o_ref, m_ref, l_ref, acc_ref) = refs
    else:
        kscale_ref = vscale_ref = None
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    h = pl.program_id(1)
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    off = off_ref[b]
    win = win_ref[0]
    # block-level skips, mirroring ops/flash.py's above-diagonal skip:
    # a page starting past the causal frontier (every query position is
    # <= off + chunk - 1) or ending below every query's window start
    # (>= off - win + 1 when the window binds) contributes nothing
    past_causal = j * block_size > off + chunk - 1
    below_window = (win > 0) & (j * block_size + block_size - 1 < off - win + 1)

    @pl.when(jnp.logical_not(past_causal | below_window))
    def _attend():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        if quantized:
            # every key/value row of this block shares ONE scale per kv
            # head: the wrapper pre-gathered the per-page scales through
            # the block tables to [Hkv, B, MB], so the grid coordinates
            # index them directly and the dequant touches only the one
            # block already resident in VMEM
            k = (k.astype(jnp.float32) * kscale_ref[h, b, j]).astype(q.dtype)
        s = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            * sm_scale
        )  # [BQ, BS]
        if softcap:  # gemma-2: tanh cap BEFORE masking, like core._attention
            s = jnp.tanh(s / softcap) * softcap
        # visibility from scalars: query row r sits at chunk position
        # (i*BQ + r) % T, key column c at pool position j*BS + c
        row = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_size), 0)
        qpos = off + (i * block_q + row) % chunk
        kvpos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_size), 1
        )
        msk = kvpos <= qpos
        msk = msk & ((win <= 0) | (kvpos > qpos - win))
        s = jnp.where(msk, s, NEG_INF)

        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        # a fully-masked ROW would otherwise contribute exp(-1e30+1e30)=1
        p = jnp.where(msk, jnp.exp(s - m_new[:, None]), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)

        v = v_ref[0, 0]
        if quantized:
            v = (v.astype(jnp.float32) * vscale_ref[h, b, j]).astype(q.dtype)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[:] = acc_ref[:] * alpha[:, None] + pv
        m_ref[:] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finalize():
        # l == 0 only for rows with nothing visible (every page skipped —
        # can't happen for live rows, but a dead batch row's stale offset
        # may land there): emit 0, not 0/0 = NaN
        l = l_ref[:, 0][:, None]
        o_ref[0, 0] = (
            acc_ref[:] / jnp.where(l == 0.0, 1.0, l)
        ).astype(o_ref.dtype)


def ragged_paged_attention(
    q,  # [B, T, H, hd]
    k_pool,  # [Hkv, NB, BS, hd] — per-layer slice of the paged pool
    v_pool,  # [Hkv, NB, BS, hd]
    block_tables,  # [B, MB] int32: pool block ids per row (0 = null block)
    offset,  # [] or [B] int32: global position of q[:, 0]
    window=None,  # [] or [1] int32 (traced ok) or python int: sliding
    #               window for THIS call's layer; None/0 = full causal
    sm_scale: float | None = None,
    logit_softcap: float = 0.0,
    block_q: int = 256,
    interpret: bool | None = None,
    k_scale=None,  # [Hkv, NB] f32: int8-pool per-page-per-head scales;
    v_scale=None,  # both present = quantized pool, dequant in-kernel
):
    """Causal attention for a [B, T] chunk over the paged pool; returns
    [B, T, H*hd] (core._attention ABI). T=1 is decode, T=K+1 spec verify,
    T=bucket a ragged prefill chunk — one compiled program per (T, table
    width) pair, both already bucketed by the engine. With
    ``k_scale``/``v_scale`` the pool is int8 (core.init_paged_pool's
    quantized layout) and each gathered block dequantizes in VMEM before
    its dot — same grid, same softmax math, half the pool HBM traffic."""
    B, T, H, hd = q.shape
    Hkv, NB, BS, _ = k_pool.shape
    MB = block_tables.shape[1]
    G = H // Hkv
    sm_scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(hd)
    interpret = interpret_off_tpu() if interpret is None else interpret
    quantized = k_scale is not None
    if quantized and v_scale is None:
        raise ValueError("quantized pool needs BOTH k_scale and v_scale")

    nq = G * T
    bq = min(block_q, max(nq, 8))
    nqp = -(-nq // bq) * bq
    # [B, T, H, hd] -> [B, Hkv, G*T, hd]: head h = kvh*G + g attends kv
    # head kvh = h // G, so heads of one group are contiguous rows
    qT = q.reshape(B, T, Hkv, G, hd).transpose(0, 2, 3, 1, 4).reshape(B, Hkv, nq, hd)
    if nqp != nq:
        qT = jnp.pad(qT, ((0, 0), (0, 0), (0, nqp - nq), (0, 0)))

    tables = jnp.asarray(block_tables, jnp.int32)
    off = jnp.broadcast_to(
        jnp.asarray(offset if offset is not None else 0, jnp.int32).reshape(-1),
        (B,),
    )
    win = jnp.asarray(window if window is not None else 0, jnp.int32).reshape(-1)[:1]

    grid = (B, Hkv, nqp // bq, MB)
    kernel = functools.partial(
        _ragged_kernel,
        sm_scale=sm_scale,
        softcap=float(logit_softcap or 0.0),
        block_size=BS,
        block_q=bq,
        chunk=T,
        quantized=quantized,
    )
    # index maps take the scalar-prefetch refs as trailing args (3 of
    # them, or 5 with the quantization scales — the variadic tail keeps
    # one lambda serving both); the K/V maps ARE the gather — page j of
    # row b reads pool block tables[b, j]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5 if quantized else 3,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, 1, bq, hd), lambda b, h, i, j, tb, *_: (b, h, i, 0)
            ),
            pl.BlockSpec(
                (1, 1, BS, hd), lambda b, h, i, j, tb, *_: (h, tb[b, j], 0, 0)
            ),
            pl.BlockSpec(
                (1, 1, BS, hd), lambda b, h, i, j, tb, *_: (h, tb[b, j], 0, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, bq, hd), lambda b, h, i, j, tb, *_: (b, h, i, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
    )
    # pre-gather the per-page scales through the block tables OUTSIDE the
    # kernel: the SMEM operand is then [Hkv, B, MB] — bounded by the
    # pow2-bucketed LIVE table width like every other per-step operand —
    # instead of the pool-sized [Hkv, NB], which scales with total
    # capacity and would overflow SMEM on production-sized pools. The
    # gather itself is B*MB*Hkv f32 per call — noise next to one block's
    # page traffic — and the kernel then indexes (h, b, j) directly.
    scales = (
        (
            jnp.asarray(k_scale, jnp.float32)[:, tables],
            jnp.asarray(v_scale, jnp.float32)[:, tables],
        )
        if quantized
        else ()
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, nqp, hd), q.dtype),
        interpret=interpret,
    )(tables, off, win, *scales, qT, k_pool, v_pool)
    # [B, Hkv, nqp, hd] -> [B, T, H*hd]
    out = out[:, :, :nq].reshape(B, Hkv, G, T, hd).transpose(0, 3, 1, 2, 4)
    return out.reshape(B, T, H * hd)


# ----------------------------------------------------- TP/mesh wrapper


def make_ragged_attn_fn(mesh=None, interpret: bool | None = None):
    """Build an attn_fn (core.transformer_block ABI) that reads the paged
    pool directly. core.forward marks it via the ``ragged`` attribute: on
    the block-tables path the kv_hook hands the POOL SLICES through as
    (k, v), forward partials in the block tables, and the per-layer mask
    argument becomes the compact [1] int32 window selector
    (core.make_layer_window) instead of a bool mask — nothing S-wide is
    ever built. On an int8 pool the hook hands (pool slice, [Hkv, NB]
    scale slice) TUPLES through and the kernel dequantizes per gathered
    block.

    Under a non-trivial mesh the kernel runs per-shard via shard_map
    (pallas_call has no SPMD partitioning rule): q heads and the pool's
    kv-head dim shard over `model` (replicated for MQA — the flash
    kernel's head-layout rules, enforced by validate_flash_mesh),
    batch/tables/offsets over `data` when it divides; the window scalar
    replicates. The pool's block/slot dims never shard here — any row
    gathers arbitrary blocks (partition.paged_cache_spec).

    ``interpret=None`` resolves from the MESH's devices
    (interpret_off_tpu), once, here. Called WITHOUT block tables it
    raises: the serving path always passes them (every engine root runs
    over the paged pool), and a quiet dense stand-in would let a run
    that asked for the kernel pass without it.
    """
    from jax.sharding import PartitionSpec as P

    if interpret is None:
        interpret = interpret_off_tpu(mesh)

    def attn(q, k, v, mask, cfg, positions=None, block_tables=None):
        if block_tables is None:
            raise ValueError(
                "the ragged paged-attention attn_fn needs block tables "
                "(a paged pool); pass attn_fn=None for a cache-less or "
                "rectangular-cache forward"
            )
        # int8 pool: the kv_hook hands (pool slice, scale slice) pairs
        # through — unpack them here so the kernel dequants in-loop
        k_scale = v_scale = None
        if isinstance(k, tuple):
            k, k_scale = k
            v, v_scale = v
        window = mask  # the ragged path's per-layer [1] int32 selector
        offset = positions[:, 0] if positions is not None else None
        sm_scale = 1.0 / math.sqrt(cfg.attn_scale or cfg.head_dim)
        softcap = float(cfg.attn_logit_softcap or 0.0)
        if mesh is None or all(n == 1 for n in mesh.shape.values()):
            return ragged_paged_attention(
                q, k, v, block_tables, offset, window,
                sm_scale=sm_scale, logit_softcap=softcap, interpret=interpret,
                k_scale=k_scale, v_scale=v_scale,
            )
        B = q.shape[0]
        Hkv = k.shape[0]
        tp = mesh.shape.get("model", 1)
        data = mesh.shape.get("data", 1)
        batch_ax = "data" if data > 1 and B % data == 0 else None
        head_ax = "model" if tp > 1 else None
        kv_ax = "model" if tp > 1 and Hkv % tp == 0 else None
        off = jnp.broadcast_to(
            jnp.asarray(offset if offset is not None else 0, jnp.int32).reshape(-1),
            (B,),
        )
        win = jnp.asarray(
            window if window is not None else 0, jnp.int32
        ).reshape(-1)[:1]
        # ONE shard_map for both pool precisions: the int8 scales shard
        # exactly like the pool's kv-head dim (their block dim, like the
        # pool's, never shards) and simply extend the operand tuple
        quant = k_scale is not None
        scale_args = (k_scale, v_scale) if quant else ()

        def body(q_, k_, v_, t_, o_, w_, *sc):
            return ragged_paged_attention(
                q_, k_, v_, t_, o_, w_,
                sm_scale=sm_scale, logit_softcap=softcap, interpret=interpret,
                k_scale=sc[0] if sc else None,
                v_scale=sc[1] if sc else None,
            )

        mapped = shard_map(
            body,
            mesh=mesh,
            in_specs=(
                P(batch_ax, None, head_ax, None),
                P(kv_ax),
                P(kv_ax),
                P(batch_ax),
                P(batch_ax),
                P(),
            ) + (P(kv_ax),) * len(scale_args),
            out_specs=P(batch_ax, None, head_ax),
            check_vma=False,
        )
        return mapped(
            q, k, v, jnp.asarray(block_tables, jnp.int32), off, win,
            *scale_args,
        )

    attn.ragged = True
    return attn


def validate_ragged_mesh(cfg, mesh) -> None:
    """Head-layout rules for the pool-direct kernel — identical to the
    rectangular flash kernel's (q heads divide `model`; GQA KV must shard,
    only MQA may replicate), so the one validator serves both."""
    validate_flash_mesh(cfg, mesh)
