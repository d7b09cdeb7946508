"""One decode step of a Mamba-2 mixer's recurrent state, in place.

``h = exp(dt A) h + (dt x) (outer) B``, ``y = sum_n h C`` for every row of
a batch bucket and every head of ONE layer, as one Mosaic call on the
STACKED state ``[L, B, H, P, N]`` float32 (core.init_ssm_state): the state
is read once and written once a step, and ``y`` is formed from the new
state while it is in VMEM. Written as XLA ops the compiler fuses the update
into the dynamic-update-slice that writes the layer's slice back and forms
``y`` in a second fusion that reads the slice again: one and a half passes,
7.30 ms a step against a floor of 3.96 ms for falcon-h1's 64 rows x 6
layers (PERF.md, Findings PR 34).

**The in-place contract** (the one ops/ragged.paged_kv_write keeps for the
pool): the state operand is aliased to the result and ``layer`` is a
prefetched scalar that the state's index map reads, so only layer
``layer``'s blocks are visited, every other byte of the buffer keeps its
value, and the layer scan that carries the state never slices it or writes
it back through XLA.

**The tile follows from the shapes** (_head_tile): a grid step holds ``Th``
heads of one row, block ``(Th, P, N)`` with the whole ``(P, N)`` of a head
(N on the lanes, P on the sublanes), so any head size and state size
compiles — nothing falls back to XLA by shape. ``Th`` is the largest divisor
of H that Mosaic can block ``x`` and ``y`` by (a multiple of 8, or H itself)
whose block stays within 2 MB: the pipeline holds four of them (in and out,
two buffers each). Both served mixers get the SAME 2 MB, 512 vregs a grid
step: falcon-h1 16 heads x [128, 256], granite-4.0-h-small 64 heads x
[64, 128].

**The body has to hide under the block's copy** (on a v5e 6.5 us for 2 MB in
and 2 MB out: a layer call of 64 rows 831-835 us = 643 GB/s of HBM read +
write, whatever ``Th``), and what decides that is its CROSS-LANE work, not
the VPU's 4 ops a vreg. A vreg of state ``[8, N <= 128]`` needs one lane
broadcast of ``dt x`` (a permute on one of three cross-lane units; at N 256
one serves two vregs) and, while ``y`` was a lane reduction a head
(``sum(h * C, -1)``, each result then selected into a ``[P, Th]`` column block
that was transposed at the end), one reduction too: 256 + 256 cross-lane
ops a block at falcon-h1's shapes, 512 + 512 at granite's. Either kind
alone hides (granite 834 us a call with ``y`` left out, 834 with the
broadcast left out: my chip runs, PR 52); both did not: **1,094 us against
the copy's 835**, where falcon-h1 read 833 against 831. So ``y`` is formed on
the MXU, which the step did not use: ``[8, N] x [P, N]^T`` a head (C's row
against the head's rows of state, the state the transposed operand) at
``Precision.HIGHEST``, float32 in and out (six bf16 passes), its first row
the head's ``[1, P]`` of ``y``, lane-dense: **835 us at granite's shapes,
831 at falcon-h1's: the copy's rate at both.** Also kept: B's and C's rows
are loaded once a run of ``gcd(Th, H / G)`` heads (such a run lies in one
group wherever the block does: the whole block in both models) and not once
a head at a dynamic group index: 1,094 -> 1,044 us alone. Tried and dropped,
granite / falcon-h1 us a call: the whole block's ``y`` as ONE product after
the loop (862 / 831: the MXU's pushes no longer interleave with the
update), a ``[128, 128]`` transpose and adds down the sublanes (860 / 832),
a butterfly of lane rotations and selects that sums 128 vregs in 127
rotations (841 / 832, and ``y`` leaves in an order XLA has to undo), ``dt x``
transposed by XLA outside the kernel (no gain beside the MXU form, and
falcon-h1 slower: 860 / 841), the lane broadcast of ``dt x`` as an identity
product on the MXU (890 / 835).

All float32; the state's update stays on the VPU (the MXU would round its
products), so the new state is bit-for-bit XLA's (the same products in the
same order); ``y`` differs by the order of its N-term sum and the MXU's
six-pass split only, far inside the bound any two orders of the sum keep
(at most 0.6 % of it at either model's shapes on the chip).

On devices that are not TPUs the kernel runs in pallas interpret mode
(ops/flash.interpret_off_tpu), so the CPU test suite runs the same code.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash import _LANES, interpret_off_tpu
from .ragged import _VMEM_LIMIT, _round_up

_TILE_BYTES = 2 * 2**20  # most bytes of state one grid step aims to hold


def _head_tile(H: int, P: int, N: int) -> tuple[int, int]:
    """(Th, bytes of one block as VMEM holds it): the heads of one grid
    step — a pure function of the shapes. A block of ``x`` and ``y`` is
    ``(Th, P)``, so ``Th`` is a multiple of 8 or H itself; of those
    divisors of H the largest whose state block fits _TILE_BYTES, else
    the smallest."""
    head = _round_up(P, 8) * _round_up(N, _LANES) * 4
    tiles = [d for d in range(1, H + 1) if H % d == 0 and (d % 8 == 0 or d == H)]
    fitting = [d for d in tiles if d * head <= _TILE_BYTES]
    Th = max(fitting) if fitting else min(tiles)
    return Th, Th * head


def _step_kernel(
    lay_ref,  # SMEM [1] int32: layer of the stacked state (index map)
    dA_ref,  # SMEM [B * H] f32: exp(dt A), a scalar a head
    dtx_ref,  # [Th, P] dt x, P on the lanes
    B_ref,  # [G, N]
    C_ref,  # [G, N]
    h_ref,  # [Th, P, N] the tile's state as the buffer holds it
    hout_ref,  # the same block of the same buffer (aliased)
    y_ref,  # [Th, P]
    *,
    heads: int,
    group_heads: int,
):
    Th, _, N = h_ref.shape
    first = pl.program_id(1) * Th
    scalars = pl.program_id(0) * heads + first
    dtx = dtx_ref[...].T  # [P, Th]: a head's dt x down the sublanes
    # heads [t0, t0 + span) share one group wherever the block lies (span
    # divides both counts): B's and C's rows are loaded once a span
    span = math.gcd(Th, group_heads)
    rows = []
    for t0 in range(0, Th, span):
        g = (first + t0) // group_heads
        Bg = B_ref[pl.ds(g, 1), :]
        Cg = jnp.broadcast_to(C_ref[pl.ds(g, 1), :], (8, N))  # an MXU operand's 8 rows
        for t in range(t0, t0 + span):
            h = h_ref[t] * dA_ref[scalars + t] + dtx[:, t:t + 1] * Bg
            hout_ref[t] = h
            # y of the head on the MXU, C's row against the head's rows of
            # state: [8, N] x [P, N]^T, float32 in six bf16 passes
            y = jax.lax.dot_general(
                Cg, h, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
            rows.append(y[:1])
    y_ref[...] = jnp.concatenate(rows, axis=0)


def ssm_state_step_xla(h, dt, x, Bm, Cm, A):
    """The same step as XLA ops on ONE layer's slice ``h`` [B, H, P, N]:
    (new h, y [B, H, P]). What a stateless pass runs (differentiable; from
    zero state there is nothing to keep in place) and what the kernel is
    checked against; on a carried, stacked state the compiler makes one and
    a half passes of it (module docstring)."""
    B, H, P, N = h.shape
    G = Bm.shape[1]

    def grouped(a):  # [B, H, ...] -> [B, G, H / G, ...]
        return a.reshape(B, G, H // G, *a.shape[2:])

    dt = grouped(dt)
    dBx = (dt[..., None] * grouped(x))[..., None] * Bm[:, :, None, None, :]
    h = grouped(h) * jnp.exp(dt * A.reshape(G, H // G))[..., None, None] + dBx
    y = jnp.sum(h * Cm[:, :, None, None, :], axis=-1)
    return h.reshape(B, H, P, N), y.reshape(B, H, P)


def ssm_state_step(
    state,  # [L, B, H, P, N] f32: the stacked state, stepped in place
    layer,  # [] or [1] int32 (traced ok)
    dt,  # [B, H] f32: 0 leaves a head's state as it is
    x,  # [B, H, P] f32
    Bm,  # [B, G, N] f32
    Cm,  # [B, G, N] f32
    A,  # [H] f32, negative
    interpret: bool | None = None,
):
    """One recurrence step of layer ``layer`` for every row and head:
    returns (state, y [B, H, P]) where state is the SAME buffer with
    ``state[layer] = exp(dt A) state[layer] + (dt x) (outer) B`` (head h
    reads group ``h // (H / G)`` of B and C) and ``y = sum_n state[layer]
    C`` of the NEW state. Grid ``(row, head tile)``; see the module
    docstring for the contract and the tile."""
    if state.dtype != jnp.float32:
        raise TypeError(f"the recurrent state is float32, not {state.dtype}")
    _, B, H, P, N = state.shape
    G = Bm.shape[1]
    interpret = interpret_off_tpu() if interpret is None else interpret
    Th, tile_bytes = _head_tile(H, P, N)
    f32 = jnp.float32
    dt = dt.astype(f32)
    lay = jnp.asarray(layer, jnp.int32).reshape(-1)[:1]
    dA = jnp.exp(dt * A.astype(f32)).reshape(B * H)
    dtx = dt[..., None] * x.astype(f32)

    state_spec = pl.BlockSpec(
        (None, None, Th, P, N), lambda b, j, lay_, dA_: (lay_[0], b, j, 0, 0))
    head_spec = pl.BlockSpec((None, Th, P), lambda b, j, *_: (b, j, 0))
    group_spec = pl.BlockSpec((None, G, N), lambda b, j, *_: (b, 0, 0))
    return pl.pallas_call(
        functools.partial(_step_kernel, heads=H, group_heads=H // G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H // Th),
            in_specs=[head_spec, group_spec, group_spec, state_spec],
            out_specs=[state_spec, head_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(state.shape, state.dtype),
            jax.ShapeDtypeStruct((B, H, P), f32),
        ],
        input_output_aliases={5: 0},  # the state, after 2 scalars, dtx, B, C
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),  # disjoint blocks
            vmem_limit_bytes=max(_VMEM_LIMIT, 4 * tile_bytes + 4 * 2**20),
        ),
        interpret=interpret,
    )(lay, dA, dtx, Bm.astype(f32), Cm.astype(f32), state)
