"""What paged attention NEEDS to read and compute where a model's layers are of
several KINDS, each with its own window, from shapes alone.

The yardstick for ``st.attn.read_roofline``: the least time the chip could take
for the attention READS of the traced interval's tokens, against the device
time under the program's ``attn.read`` scope. ``kv`` is the configuration file's
``kv`` section: KV heads, query heads, head size, bytes per element, and
``kinds``: the layers by kind, ``{"layers": n, "window": w | null}`` (null = the
layer attends fully). ``kv_bytes.py`` knows one window for all layers; with
SmallThinker's 2 full layers beside 6 behind a 4,096 window it would count
either 1.5 times the keys a 6.3k context's window layers can see, or too few in
the full ones.

A query at context ``n`` (cached tokens, itself included) sees ``min(n, w)``
keys in a layer of window ``w``. Each visible K and V row is read once a layer
(decode), each position's once a layer however many chunks a prefill takes (a
kernel that re-reads them per chunk or per query tile reads more than it must);
QK^T and PV are 2 flops a multiply-add over all query heads.
"""

from __future__ import annotations


def kinds_of(kv: dict) -> list[tuple[int, int | None]]:
    """(layers, window or None) of every kind; their layers sum to ``n_layers``."""
    kinds = [(int(k["layers"]), k.get("window")) for k in kv["kinds"]]
    if sum(n for n, _ in kinds) != kv["n_layers"]:
        raise ValueError(f"kinds {kinds} do not sum to n_layers {kv['n_layers']}")
    return kinds


def layer_token_bytes(kv: dict, chips: int = 1) -> float:
    """Bytes of K and V one cached token holds in ONE layer on one chip."""
    return 2.0 * kv["n_kv_heads"] * kv["head_dim"] * kv["dtype_bytes"] / chips


def _pair_flops(kv: dict, chips: int) -> float:
    """Flops of one (query, key) pair in one layer: QK^T and PV, all query heads."""
    return 4.0 * kv["n_heads"] * kv["head_dim"] / chips


def visible_pairs(prompt: int, window: int | None) -> int:
    """(query, key) pairs of a causal prefill of ``prompt`` tokens in a layer of
    ``window``: sum over i = 1..prompt of min(i, window)."""
    if not window or prompt <= window:
        return prompt * (prompt + 1) // 2
    return window * (window + 1) // 2 + (prompt - window) * window


def decode_token(context: int, kv: dict, chips: int = 1) -> tuple[float, float]:
    """(bytes, flops) on one chip for ONE new token over ``context`` cached
    tokens: in every layer the keys its kind can see, each read once."""
    seen = sum(n * (min(context, w) if w else context) for n, w in kinds_of(kv))
    return seen * layer_token_bytes(kv, chips), seen * _pair_flops(kv, chips)


def prefill(prompt: int, kv: dict, chips: int = 1) -> tuple[float, float]:
    """(bytes, flops) on one chip for a causal prefill of ``prompt`` tokens:
    each position's K and V read once a layer, and each kind's visible pairs."""
    pairs = sum(n * visible_pairs(prompt, w) for n, w in kinds_of(kv))
    return (prompt * kv["n_layers"] * layer_token_bytes(kv, chips),
            pairs * _pair_flops(kv, chips))


def held_behind_window(context: int, kv: dict) -> tuple[int, int]:
    """(layer-tokens a row of ``context`` cached tokens holds, those of them no
    later read can see): the next query, at position ``context``, sees the keys
    above ``context - window``, so a window layer holds ``context - window + 1``
    dead ones (what ``engine.kv_tokens_behind_window`` counts)."""
    behind = sum(n * max(0, context - w + 1) for n, w in kinds_of(kv) if w)
    return context * kv["n_layers"], behind
