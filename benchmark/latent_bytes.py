"""What the latent (MLA) read NEEDS to fetch and compute, from shapes alone.

The yardstick for ``joyai.mla.read_roofline``: the least time the chip could
take to read the cached latent rows that the traced interval's decode steps
cover, against the device time under the program's ``mla.read`` scope.
``latent`` is the configuration file's ``latent`` section: layers, query heads,
the row's width as PUBLISHED (576 = 512 of ``c_kv`` + 64 of rotated ``k_pe``;
the program may store it wider, lane-aligned: that is its cost, not the
work's), the width of the part that serves as values (512), bytes per element.

One row a token a layer serves every head as key AND as value, so it is read
once: 1,152 B a token a layer at bf16. Flops, absorbed form: a query head's
score against a row is ``row_width`` multiply-adds and its value sum
``value_width`` more. The program counts the rows (``engine.latent_tokens_read``:
the live rows' context lengths summed over a decode window's steps, x layers);
a prefill chunk's reads are not in it, so the share errs low, never high.
"""

from __future__ import annotations


def row_bytes(latent: dict) -> float:
    """Bytes of ONE cached row: a token, a layer."""
    return float(latent["row_width"] * latent["dtype_bytes"])


def token_bytes(latent: dict) -> float:
    """Bytes one cached token holds over all layers."""
    return latent["n_layers"] * row_bytes(latent)


def read_work(rows: float, latent: dict) -> tuple[float, float]:
    """(bytes, flops) of reading ``rows`` cached rows (a layer counted each),
    every query head scoring each row and summing its value part."""
    flops = 2.0 * latent["n_heads"] * (latent["row_width"] + latent["value_width"])
    return rows * row_bytes(latent), rows * flops
