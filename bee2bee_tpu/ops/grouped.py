"""Grouped matrix product for a dropless expert layer.

``grouped_matmul(x [M, K], w [G, K, N], group_sizes [G]) -> [M, N]``: the
rows of ``x`` are sorted by group, group g owns the next ``group_sizes[g]``
rows and multiplies them by ``w[g]``. Rows past the last group belong to
nobody: their output holds whatever the buffer held (the caller masks).

The kernel is jax's own Mosaic grouped matmul (``jax.experimental.pallas
.ops.tpu.megablox.gmm``): a grid step is one (row tile, group) pair that
has rows, so a group with no row is never visited and its matrix never
read, and a visited group's matrix is read from where it lies in ``w`` —
which lets core._moe_dropless hand it a whole layer STACK of experts viewed
as L*E groups with rows for one layer's E only. ``jax.lax.ragged_dot`` was
measured beside it on the v5e at JoyAI-LLM-Flash's shapes (512 sorted rows
over 227 of 256 experts of 2048 x 768, us a matrix a layer; PR 38's chip
runs): ragged_dot 3,300 (its lowering takes all 512 rows as one row tile,
so every visited expert pays a 512-row product: compute-bound), this
kernel 1,034 at a (128, K, N) tile against 872 for reading the touched
experts once at 819 GB/s; 4,096 rows over 256 experts: 3,945 / 1,313 / 983.
Row tiles of 16-256 read 1,126 / 1,067 / 1,044 / 1,034 / 1,093; splitting K
or N costs 2-15 %. So the tile is 128 rows x the whole matrix where that
fits (_k_tile: blocks of rows where it does not), from the shapes alone.

On devices that are not TPUs the kernel runs in pallas interpret mode
(ops/flash.interpret_off_tpu), so the CPU suite runs the same code.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

from .flash import interpret_off_tpu

_ROW_TILE = 128  # rows a grid step: past it a visit's product outgrows its copy
# A grid step holds its [tk, N] block of a group's matrix twice (the next
# one's copy runs under this one's product) beside the rows, the float32
# accumulator and the output tile, in 16 MiB of VMEM. A WHOLE matrix fits to
# 6 MiB (granite's [4096, 768], the largest served so far); a larger one
# (K-EXAONE's [6144, 2048] / [2048, 6144]: 24 MiB) is read in blocks of rows
# of at most 4 MiB: rows stay contiguous in HBM, the k steps of a visit
# accumulate in VMEM
_WHOLE_MATRIX_BYTES = 6 << 20
_MATRIX_BLOCK_BYTES = 4 << 20


def _k_tile(K: int, N: int, itemsize: int) -> int:
    """Rows of a group's [K, N] matrix a grid step reads: all of them where
    the matrix fits, else the largest multiple of 128 that divides K and
    keeps the block inside _MATRIX_BLOCK_BYTES."""
    if K * N * itemsize <= _WHOLE_MATRIX_BYTES:
        return K
    fits = [tk for tk in range(128, K, 128)
            if K % tk == 0 and tk * N * itemsize <= _MATRIX_BLOCK_BYTES]
    return max(fits) if fits else 128


def grouped_matmul(x, w, group_sizes, interpret: bool | None = None):
    """``x`` [M, K] (rows sorted by group) times ``w`` [G, K, N] by
    ``group_sizes`` [G] int32 -> [M, N] in x's dtype, float32 accumulation.
    Rows past ``sum(group_sizes)`` come back undefined."""
    M, K = x.shape
    N = w.shape[2]
    # 128 rows (fewer for a smaller call, in steps of 16: a bf16 sublane
    # pair) x a group's WHOLE [K, N] matrix: 3 MiB at the published shapes
    tm = min(_ROW_TILE, -(-M // 16) * 16)
    pad = -M % tm
    if pad:  # the kernel wants whole row tiles; pad rows are in no group
        x = jnp.pad(x, ((0, pad), (0, 0)))
    out = gmm(
        x, w.astype(x.dtype), group_sizes.astype(jnp.int32), x.dtype,
        (tm, _k_tile(K, N, x.dtype.itemsize), N),
        interpret=interpret_off_tpu() if interpret is None else interpret,
    )
    return out[:M] if pad else out
