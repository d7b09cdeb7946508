"""What a LOOPED stack must stream and compute, from shapes alone.

The yardstick for ``ouro.weights.stream_roofline``: the least time the chip
could take for the weight products of the traced interval's decode steps and
prefill calls, against the device time under the program's ``attn.qkv``,
``attn.out``, ``mlp.*`` and ``head.*`` scopes. ``loop`` is the configuration
file's ``loop`` section: ``passes`` (how often the stack runs a token),
``layers``, ``layer_bytes`` (one layer's weights), ``head_bytes`` (the output
head; the embedding is a lookup), ``dtype_bytes``.

A device call, whatever its rows, reads every layer's weights once a PASS and
the head once: ``passes x layers x layer_bytes + head_bytes``. Its products are
2 flops a weight a position. A decode step is one position a row (memory-bound
at the cells' widths); a prefill call of a few hundred positions is
compute-bound. Per call the larger of the two times counts, so a mix of both
kinds is not averaged into one bound.
"""

from __future__ import annotations


def step_bytes(loop: dict) -> float:
    """Bytes of weights ONE device call must stream: every layer once a pass, the head once."""
    return float(loop["passes"]) * loop["layers"] * loop["layer_bytes"] + loop["head_bytes"]


def position_flops(loop: dict) -> float:
    """Flops of the weight products for ONE position: 2 a weight, a layer's once a pass."""
    return 2.0 * step_bytes(loop) / loop["dtype_bytes"]


def least_seconds(decode_steps: float, decode_rows: float, prefill_calls: float,
                  prefill_positions: float, loop: dict, peak: dict) -> tuple[float, dict]:
    """(least seconds, {"memory": s, "compute": s} by which bound a kind of call
    met) for ``decode_steps`` steps of ``decode_rows`` rows each and
    ``prefill_calls`` calls over ``prefill_positions`` positions in all."""
    t_bytes = step_bytes(loop) / peak["hbm_bytes_per_s"]
    by = {"memory": 0.0, "compute": 0.0}
    for calls, positions in ((decode_steps, decode_steps * decode_rows),
                             (prefill_calls, prefill_positions)):
        if calls <= 0:
            continue
        t_flops = position_flops(loop) * positions / calls / peak["bf16_flops_per_s"]
        by["memory" if t_bytes >= t_flops else "compute"] += calls * max(t_bytes, t_flops)
    return by["memory"] + by["compute"], by
