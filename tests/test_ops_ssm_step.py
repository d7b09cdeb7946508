"""The one-step state kernel (ops/ssm_step.py) against the XLA one-step
recurrence it replaced, in pallas interpret mode on the CPU: the same kernel
code the chip compiles. ``tests/test_tpu_compile.py`` compiles it for a
described v5e; ``chip_smoke.child_kernel()`` times it on the chip.

Tolerances. The new state is the same three float32 products and one sum in
the same order as the XLA lines, so it may differ only where one side
contracts ``a*b + c`` into a fused multiply-add: two units in the last place
of the larger term. ``y`` is a sum of N products whose ORDER differs (the
kernel forms it on the MXU, float32 at ``Precision.HIGHEST``: each product
split over bf16 passes and accumulated in float32 in the unit's own order;
XLA reduces as it likes): any two orders of a float32 sum of n terms differ
by at most ``2 (n - 1) eps sum|terms|``, which is the bound asserted,
element by element (the chip reads at most 0.6 % of it, chip_smoke)."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bee2bee_tpu.models import core
from bee2bee_tpu.models.config import get_config
from bee2bee_tpu.ops.ssm_step import _head_tile, ssm_state_step, ssm_state_step_xla

EPS = float(np.finfo(np.float32).eps)
# (heads, head size, state size, groups): the mixers of falcon-h1-34b, of
# granite-4.0-h-small (both cells' shapes) and of tiny-falcon-h1, and two whose
# block of 16 heads spans FOUR groups (a once-a-step load of B and C would
# give twelve heads another group's rows): lane-aligned, and off the tiling
SHAPES = {
    "falcon-h1": (32, 128, 256, 2), "granite": (128, 64, 128, 1),
    "tiny-falcon-h1": (4, 8, 16, 2),
    "four-groups": (16, 16, 128, 4), "four-groups-narrow": (16, 8, 16, 4),
}
# rows: one, an odd few, and the cells' 64-row bucket cut to what the CPU affords
ROWS = {
    "falcon-h1": (1, 3, 8), "granite": (1, 3, 8), "tiny-falcon-h1": (1, 3, 64),
    "four-groups": (1, 5), "four-groups-narrow": (1, 5),
}
LAYERS = 3


def test_shapes_are_the_presets():
    for name, preset in (
            ("falcon-h1", "falcon-h1-34b"), ("granite", "granite-4.0-h-small-10l-e36"),
            ("tiny-falcon-h1",) * 2):
        cfg = get_config(preset)
        assert SHAPES[name] == (
            cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups)


def _inputs(B, H, P, N, G, seed=0, layers=LAYERS):
    rng = np.random.default_rng(seed)

    def f(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    return dict(
        state=f(layers, B, H, P, N),
        dt=jnp.abs(f(B, H)) * 0.3,  # softplus's range
        x=f(B, H, P), Bm=f(B, G, N), Cm=f(B, G, N),
        A=-jnp.exp(f(H)),  # -exp(A_log)
    )


def xla_step(state, layer, dt, x, Bm, Cm, A):
    """The XLA lines (ssm_state_step_xla: what a stateless pass runs and what
    every decode step ran before the kernel) on one layer's slice: (new
    slice [B, H, P, N], y [B, H, P], sum_n |h C| for y's bound)."""
    _, B, H, P, N = state.shape
    G = Bm.shape[1]
    h, y = ssm_state_step_xla(state[layer], dt, x, Bm, Cm, A)
    terms = h.reshape(B, G, H // G, P, N) * Cm[:, :, None, None, :]
    return h, y, jnp.sum(jnp.abs(terms), -1).reshape(B, H, P)


def _assert_state_close(got, want):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=4 * EPS, atol=1e-6)


CASES = [(s, B, layer) for s in SHAPES for B in ROWS[s] for layer in range(LAYERS)]


@pytest.mark.parametrize("shape,B,layer", CASES, ids=[f"{s}-B{b}-L{l}" for s, b, l in CASES])
def test_kernel_equals_the_xla_step(shape, B, layer):
    """Every layer of an L-stack: y and the new slice within float32
    rounding, every other layer's slice BIT-FOR-BIT untouched."""
    H, P, N, G = SHAPES[shape]
    a = _inputs(B, H, P, N, G, seed=B)
    want_h, want_y, mag = xla_step(a["state"], layer, a["dt"], a["x"], a["Bm"], a["Cm"], a["A"])
    before = np.asarray(a["state"])
    state, y = jax.jit(ssm_state_step)(
        a["state"], jnp.int32(layer), a["dt"], a["x"], a["Bm"], a["Cm"], a["A"])
    assert state.dtype == jnp.float32 and y.dtype == jnp.float32
    assert y.shape == (B, H, P)
    state = np.asarray(state)
    _assert_state_close(state[layer], want_h)
    for other in range(LAYERS):
        if other != layer:
            assert np.array_equal(state[other], before[other]), f"layer {other} was touched"
    bound = 2 * (N - 1) * EPS * np.asarray(mag) + 1e-30
    assert np.all(np.abs(np.asarray(y) - np.asarray(want_y)) <= bound)


@pytest.mark.parametrize(
    "shape,B", [("falcon-h1", 2), ("granite", 2), ("tiny-falcon-h1", 5), ("four-groups", 3)])
def test_chained_steps_equal_chained_xla_steps(shape, B):
    """32 decode steps, the state carried through a donated buffer as the
    engine's window carries it, against 32 XLA steps. An error made in one
    step decays with the state (exp(dt A) < 1), so the one-step tolerances
    hold at the end too, with room for the carried rounding."""
    H, P, N, G = SHAPES[shape]
    a = _inputs(B, H, P, N, G, seed=7, layers=2)
    rng = np.random.default_rng(8)
    steps = [
        {k: jnp.asarray(rng.standard_normal(a[k].shape), jnp.float32) for k in ("x", "Bm", "Cm")}
        | {"dt": jnp.asarray(np.abs(rng.standard_normal(a["dt"].shape)) * 0.3, jnp.float32)}
        for _ in range(32)
    ]
    kernel = jax.jit(ssm_state_step, donate_argnums=(0,))
    ref_fn = jax.jit(xla_step)
    layer = jnp.int32(1)
    state, ref = a["state"] + 0, a["state"]
    untouched = np.asarray(a["state"][0])
    for s in steps:
        state, y = kernel(state, layer, s["dt"], s["x"], s["Bm"], s["Cm"], a["A"])
        h, want_y, mag = ref_fn(ref, layer, s["dt"], s["x"], s["Bm"], s["Cm"], a["A"])
        ref = ref.at[1].set(h)
    np.testing.assert_allclose(np.asarray(state[1]), np.asarray(h), rtol=1e-5, atol=1e-5)
    bound = 2 * (N - 1) * EPS * np.asarray(mag) + 1e-4
    assert np.all(np.abs(np.asarray(y) - np.asarray(want_y)) <= bound)
    assert np.array_equal(np.asarray(state[0]), untouched)


def test_dt_zero_leaves_a_row_bit_for_bit():
    """dt = 0 is how a bucket's pad position and a dead row's junk token
    leave the state alone: exp(0) h + 0 = h exactly."""
    H, P, N, G = SHAPES["tiny-falcon-h1"]
    a = _inputs(4, H, P, N, G, seed=3)
    dt = a["dt"].at[1].set(0.0).at[3, 2].set(0.0)
    state, _ = ssm_state_step(a["state"], 2, dt, a["x"], a["Bm"], a["Cm"], a["A"])
    assert np.array_equal(np.asarray(state[2, 1]), np.asarray(a["state"][2, 1]))
    assert np.array_equal(np.asarray(state[2, 3, 2]), np.asarray(a["state"][2, 3, 2]))
    assert not np.array_equal(np.asarray(state[2, 0]), np.asarray(a["state"][2, 0]))


def test_a_narrow_state_is_refused():
    """The recurrence accumulates over hundreds of steps: float32 only."""
    H, P, N, G = SHAPES["tiny-falcon-h1"]
    a = _inputs(1, H, P, N, G)
    with pytest.raises(TypeError, match="float32"):
        ssm_state_step(a["state"].astype(jnp.bfloat16), 0, a["dt"], a["x"],
                       a["Bm"], a["Cm"], a["A"])


@pytest.mark.parametrize("H,P,N,Th", [
    (32, 128, 256, 16),  # falcon-h1: 16 heads x 128 KB = the 2 MB a block aims at
    (4, 8, 16, 4),  # tiny-falcon-h1: the whole head axis (4 is no multiple of 8)
    (128, 64, 128, 64),  # mamba-2 2.7b's 64 x 128: 32 KB a head
    (24, 64, 128, 24),
    (12, 128, 256, 12),  # no divisor is a multiple of 8: the whole axis, 1.5 MB
    (20, 128, 256, 20),  # ... and when that passes 2 MB it is still the only block
    (64, 128, 1024, 8),  # half a megabyte a head: the smallest multiple of 8 is 4 MB
])
def test_head_tile_follows_the_shapes(H, P, N, Th):
    got, block = _head_tile(H, P, N)
    assert got == Th and H % got == 0 and (got % 8 == 0 or got == H)
    assert block == got * -(-P // 8) * 8 * -(-N // 128) * 128 * 4


def test_decode_through_the_stacked_state_matches_the_full_forward():
    """core.forward at lane-aligned mixer widths (state 128, head 16: a
    block Mosaic tiles without padding): prefill, then decode steps that
    run the kernel on the layer scan's stacked carry, against the
    cache-less full-sequence forward (the chunked scan from zero state)."""
    cfg = dataclasses.replace(
        get_config("tiny-falcon-h1"), ssm_heads=8, ssm_head_dim=16, ssm_state=128)
    params = core.init_params(cfg, jax.random.key(5), dtype=jnp.float32)
    ids = np.random.RandomState(2).randint(3, 500, (2, 20)).astype(np.int32)
    full, _ = core.forward(params, cfg, ids, None, 0)
    cache = core.init_paged_pool(cfg, 16, 8, jnp.float32)
    cache.update(core.init_ssm_state(cfg, 2, jnp.float32))
    tables = np.arange(1, 9, dtype=np.int32).reshape(2, 4)
    n = 11
    tok = np.zeros((2, 16), np.int32)
    tok[:, :n] = ids[:, :n]
    lg, cache = core.forward(
        params, cfg, tok, cache, np.int32(0), block_tables=tables,
        paged_write_ceil=np.int32(n), valid_len=np.asarray([n, n]),
        last_index=np.asarray([n - 1, n - 1]))
    np.testing.assert_allclose(np.asarray(lg[:, 0]), np.asarray(full[:, n - 1]), atol=2e-5)
    step = jax.jit(
        lambda tok, cache, off: core.forward(params, cfg, tok, cache, off, block_tables=tables),
        donate_argnums=(1,))
    for t in range(n, 20):
        lg, cache = step(ids[:, t:t + 1], cache, np.asarray([t, t], np.int32))
        np.testing.assert_allclose(np.asarray(lg[:, 0]), np.asarray(full[:, t]), atol=2e-5)
