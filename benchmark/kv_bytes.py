"""What paged attention NEEDS to read and compute, from shapes alone.

The yardstick for ``kernel.ragged_roofline``: the least time the chip could
take for the attention of the traced interval's tokens, against the time the
ragged kernel's device events took. ``kv`` is the configuration file's ``kv``
section: layers, KV heads, query heads, head size, bytes per element, window.
Under ``model:N`` the KV heads are split over the chips, so a chip reads and
computes 1/N of it.
"""

from __future__ import annotations


def kv_bytes_per_token(kv: dict, chips: int = 1) -> float:
    """Bytes of K and V one cached token holds on one chip: 2 * L * Hkv * hd * B."""
    return 2.0 * kv["n_layers"] * kv["n_kv_heads"] * kv["head_dim"] * kv["dtype_bytes"] / chips


def _visible(context: int, kv: dict) -> int:
    window = kv.get("window")
    return min(context, window) if window else context


def decode_token(context: int, kv: dict, chips: int = 1) -> tuple[float, float]:
    """(bytes, flops) on one chip for ONE new token over ``context`` cached
    tokens: every visible K and V is read once; QK^T and PV are 2 flops a
    multiply-add over all query heads."""
    n = _visible(context, kv)
    flops = 4.0 * n * kv["n_heads"] * kv["head_dim"] * kv["n_layers"] / chips
    return n * kv_bytes_per_token(kv, chips), flops


def prefill(prompt: int, kv: dict, chips: int = 1) -> tuple[float, float]:
    """(bytes, flops) on one chip for a causal prefill of ``prompt`` tokens:
    each position's K and V read once (a kernel that re-reads them per query
    tile reads more than it must), and the causal half of the score matrix."""
    pairs = sum(_visible(i + 1, kv) for i in range(prompt))
    flops = 4.0 * pairs * kv["n_heads"] * kv["head_dim"] * kv["n_layers"] / chips
    return prompt * kv_bytes_per_token(kv, chips), flops


def min_seconds(calls: list[tuple[float, float]], peak: dict) -> tuple[float, str]:
    """Roofline: per call the larger of bytes / peak bytes/s and flops / peak
    flop/s, summed; and which of the two bounds most of it."""
    by_mem = by_flops = total = 0.0
    for nbytes, flops in calls:
        tm, tf = nbytes / peak["hbm_bytes_per_s"], flops / peak["bf16_flops_per_s"]
        total += max(tm, tf)
        by_mem += tm if tm >= tf else 0.0
        by_flops += tf if tf > tm else 0.0
    return total, ("memory" if by_mem >= by_flops else "compute")
