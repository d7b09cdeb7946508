"""Flash attention pallas kernels (prefill + KV-cache decode).

Design notes (pallas_guide.md patterns):
- Online softmax: grid's innermost dim walks K/V blocks sequentially on
  one core; m/l/acc scratch in VMEM persists across those iterations and
  the output block is written on the last one.
- Accumulation in f32 (MXU `preferred_element_type`), storage dtype of
  the inputs.
- GQA: the kv-head index for a q-head h is h // (H // Hkv), computed in
  the BlockSpec index_map so each q-head grid step DMAs only its own KV
  block.
- `offset` rides SMEM as a [1,1] scalar so the SAME compiled kernel
  serves prefill (offset=0 mask within the chunk) and cached decode
  (queries live at positions offset..offset+T).
- On devices that are not TPUs the kernels run in pallas interpret mode
  (`interpret_off_tpu`) — the CPU test suite exercises the exact kernel
  code path. On a TPU a kernel compiles or raises.

Replaces the dense [B,H,T,S] score materialization of models/core
._attention on the hot path (engine flag attention="flash").
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128  # m/l scratch lane padding (min f32 tile is (8, 128))


def interpret_off_tpu(mesh=None) -> bool:
    """Should a kernel call run in pallas interpret mode? Only where the
    devices it runs on cannot take a Mosaic kernel: the mesh's devices
    when the caller has a mesh, else the default backend's (where an
    unplaced jit runs). A failure to reach the backend propagates — it
    must never read as "not a TPU" and quietly select the interpreter."""
    if mesh is not None:
        return mesh.devices.flat[0].platform != "tpu"
    return jax.default_backend() != "tpu"


# ------------------------------------------------------------- prefill


def _flash_kernel(
    off_ref,  # SMEM [B] int32 (scalar-prefetch): global position of q[:, 0]
    q_ref,  # [1, 1, BQ, hd]  (head-major layout: Mosaic requires the
    k_ref,  # [1, 1, BK, hd]   trailing two block dims to be (8,128)-tileable
    v_ref,  # [1, 1, BK, hd]   or dim-equal — [.., seq_block, hd] is; the
    o_ref,  # [1, 1, BQ, hd]   head axis blocked at 1 in trailing position
    m_ref,  # VMEM [BQ, 128] f32 running max         is NOT and fails to lower)
    l_ref,  # VMEM [BQ, 128] f32 running sum
    acc_ref,  # VMEM [BQ, hd] f32
    *,
    sm_scale: float,
    block_q: int,
    block_k: int,
    causal: bool,
):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    off = off_ref[pl.program_id(0)]

    @pl.when(kj == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # skip K blocks entirely above the diagonal (offset is dynamic, so the
    # grid can't be pruned statically — predicate out the wasted MXU work)
    last_qpos = off + (qi + 1) * block_q - 1
    visible = (kj * block_k <= last_qpos) if causal else jnp.bool_(True)

    @pl.when(visible)
    def _attend():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            * sm_scale
        )  # [BQ, BK]

        if causal:
            qpos = off + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            kpos = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            mask = kpos <= qpos
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        if causal:
            # a fully-masked ROW would otherwise contribute exp(-1e30+1e30)=1
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)

        v = v_ref[0, 0]
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[:] = acc_ref[:] * alpha[:, None] + pv
        m_ref[:] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(kj == pl.num_programs(3) - 1)
    def _finalize():
        # l == 0 only for rows with no visible keys (e.g. a decode row whose
        # lengths[b] == 0, offset -1): emit 0, not 0/0 = NaN
        l = l_ref[:, 0][:, None]
        o_ref[0, 0] = (
            acc_ref[:] / jnp.where(l == 0.0, 1.0, l)
        ).astype(o_ref.dtype)


def flash_attention(
    q,  # [B, T, H, hd]
    k,  # [B, S, Hkv, hd]
    v,  # [B, S, Hkv, hd]
    offset=None,  # [] or [B] int32: global position of q[:, 0] (None -> 0)
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    sm_scale: float | None = None,
    interpret: bool | None = None,
):
    """Tiled causal attention; returns [B, T, H*hd] (core._attention ABI).

    T and S are padded to the block sizes internally; with a KV cache pass
    S = cache capacity and `offset` = write position (future cache slots
    are masked by causality exactly like models/core.forward's mask).
    """
    B, T, H, hd = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    group = H // Hkv
    sm_scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(hd)
    interpret = interpret_off_tpu() if interpret is None else interpret

    block_q = min(block_q, max(T, 8))
    block_k = min(block_k, max(S, 8))
    Tp = -(-T // block_q) * block_q
    Sp = -(-S // block_k) * block_k
    # head-major layout [B, H(kv), seq, hd]: the kernel's trailing block
    # dims become (seq_block, hd), which Mosaic can tile; the original
    # [B, seq, H, hd] layout put the head axis (blocked at 1) second-to-
    # last and failed to lower on real TPU
    qT = jnp.transpose(q, (0, 2, 1, 3))
    kT = jnp.transpose(k, (0, 2, 1, 3))
    vT = jnp.transpose(v, (0, 2, 1, 3))
    if Tp != T:
        qT = jnp.pad(qT, ((0, 0), (0, 0), (0, Tp - T), (0, 0)))
    if Sp != S:
        kT = jnp.pad(kT, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
        vT = jnp.pad(vT, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
    if not causal and Sp != S:
        raise ValueError("non-causal flash requires S divisible by block_k")

    # per-batch offsets ride whole into SMEM via scalar prefetch — a
    # blocked [B,1] SMEM operand hits the same Mosaic trailing-dims rule
    off = jnp.broadcast_to(
        jnp.asarray(offset if offset is not None else 0, jnp.int32).reshape(-1),
        (B,),
    )

    grid = (B, H, Tp // block_q, Sp // block_k)
    kernel = functools.partial(
        _flash_kernel,
        sm_scale=sm_scale,
        block_q=block_q,
        block_k=block_k,
        causal=causal,
    )
    # index maps take the scalar-prefetch ref as a trailing arg
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j, off: (b, h, i, 0)),
            pl.BlockSpec(
                (1, 1, block_k, hd), lambda b, h, i, j, off: (b, h // group, j, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, hd), lambda b, h, i, j, off: (b, h // group, j, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, hd), lambda b, h, i, j, off: (b, h, i, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Tp, hd), q.dtype),
        interpret=interpret,
    )(off, qT, kT, vT)
    # [B, H, Tp, hd] -> [B, T, H*hd]
    return jnp.transpose(out[:, :, :T], (0, 2, 1, 3)).reshape(B, T, H * hd)


# ----------------------------------------------------- mesh validation
# (make_flash_attn_fn — the rectangular-cache engine wrapper — is gone
# with the rectangular cache itself: the engine's attention="flash" now
# runs the ragged paged kernel, ops/ragged.make_ragged_attn_fn, which
# reuses this kernel's head-layout rules below. flash_attention stays as
# the contiguous-K/V kernel: scoring/offline shapes and the kernel-level
# numerics tests.)


def validate_flash_mesh(cfg, mesh) -> None:
    """Fail fast when the head layout cannot run head-local flash:
    q heads must divide the `model` axis, and each shard's q-head count
    must cover its kv heads whole (GQA group stays integral)."""
    tp = mesh.shape.get("model", 1)
    if tp <= 1:
        return
    H, Hkv = cfg.n_heads, cfg.n_kv_heads
    if H % tp:
        raise ValueError(
            f"attention='flash' needs n_heads={H} divisible by model axis "
            f"{tp} (head-local kernel); use attention='dense'"
        )
    if Hkv % tp == 0:
        return  # sharded KV: local h // G maps to the correct local kv head
    if Hkv != 1:
        # replicated KV with Hkv > 1: shard s's local q heads all belong to
        # kv heads near s*H/tp/G globally, but the kernel's LOCAL
        # h // (H_local/Hkv) mapping would spread them over all Hkv heads —
        # silently wrong attention. Only MQA (Hkv == 1, every q head -> kv 0)
        # is layout-invariant under replication.
        raise ValueError(
            f"attention='flash' cannot run GQA with n_kv_heads={Hkv} "
            f"replicated across model axis {tp} (local kv-head mapping "
            "would be wrong); use attention='dense'"
        )


# Decode (T=1) rides the SAME kernel shape: flash_attention with a
# [B, 1, H, hd] query and offset = write position pads to one 8-row q
# block per head. The ENGINE's decode no longer comes through here — the
# paged pool is the only cache layout and attention="flash" runs the
# ragged paged kernel (ops/ragged.py) — but the T=1 contract stays
# tested in tests/test_ops_flash.py as the contiguous-K/V reference.
