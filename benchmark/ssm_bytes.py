"""What a Mamba-2 mixer's recurrent state NEEDS to move and compute, from shapes alone.

The yardstick for ``ssm.state_roofline``: the least time the chip could take
for the state work of the traced interval's tokens, against the device time
under the program's ``ssm.step`` (decode: the one-step recurrence), ``ssm.scan``
(prefill: the chunked scan) and ``ssm.state_write`` (the in-place write-back the
compiler fuses the update into) scopes. ``state`` is the
configuration file's ``state`` section: layers, heads, head size, state size,
groups, conv width and channels, bytes per element. The mixer's projections
(``ssm.in_proj``, ``ssm.out_proj``) are weight traffic like the MLP's and are
not in it. The program has no Pallas kernel for the step or the scan yet; one
that comes counts its operations and bytes here.
"""

from __future__ import annotations


def state_bytes_per_row(state: dict) -> float:
    """Bytes of recurrent state one row holds over all layers: the float32
    [heads, head_dim, d_state] SSM state and the conv's K-1 last inputs."""
    ssm = state["n_heads"] * state["head_dim"] * state["d_state"] * state["ssm_dtype_bytes"]
    conv = (state["d_conv"] - 1) * state["conv_channels"] * state["conv_dtype_bytes"]
    return float(state["n_layers"] * (ssm + conv))


def decode_step(state: dict) -> tuple[float, float]:
    """(bytes, flops) of ONE row's one-token update over all layers: the state
    is read once and written once; per state element ``h = dA * h + dBx``
    (3 flops with dBx's own product) and ``y += h * C`` (2 flops)."""
    elems = state["n_layers"] * state["n_heads"] * state["head_dim"] * state["d_state"]
    return 2.0 * state_bytes_per_row(state), 5.0 * elems


def prefill_scan(prompt: int, state: dict) -> tuple[float, float]:
    """(bytes, flops) of one row's chunked scan over ``prompt`` tokens, all
    layers: per token the scan reads x, B, C, dt and writes y in float32; the
    state is read (zero) and written once. Flops per token, chunk Q: the
    [Q, Q] score block 2 Q G N, its product with x 2 Q H P, the chunk's own
    state and the carried state's output 2 H P N each."""
    L, H, P, N = state["n_layers"], state["n_heads"], state["head_dim"], state["d_state"]
    G, Q = state["n_groups"], state["chunk"]
    per_token_elems = 2 * H * P + 2 * G * N + H  # x and y, B and C, dt
    nbytes = L * prompt * per_token_elems * 4.0 + 2.0 * state_bytes_per_row(state)
    q = min(Q, max(1, prompt))
    flops = L * prompt * (2.0 * q * (G * N + H * P) + 4.0 * H * P * N)
    return nbytes, flops
