"""What the expert products of a chip that holds a SHARE of every layer's
experts NEED to read and compute, from shapes alone.

The yardstick for ``granite.moe.experts_roofline``: the least time the chip
could take for the HELD routed experts' and the shared expert's products of the
traced interval's forwards, against the device time under the program's
``moe.experts`` and ``moe.shared`` scopes. ``moe`` is the configuration file's
``moe`` section: expert layers, routed experts (``n_experts``) of which
``n_experts_held`` lie here, experts a token, model width, a routed expert's
width (``d_ff``) and the shared expert's OWN width (``d_ff_shared``), matrices
an expert, bytes per element.

``moe_bytes.py`` is wrong for a share twice over: it takes the live tokens as
``assignments / experts_per_token``, but the assignments counted HERE
(``engine.moe_assignments{kind="live"}``) are only those whose expert this
chip holds, the others being counted ``kind="elsewhere"``; and it takes a
shared expert to be as wide as a routed one. So here: an expert-layer call must
read every HELD expert that got at least one live assignment once
(``engine.moe_experts_hit`` counts held experts), the shared expert once, and do
the multiply-adds of the assignments computed here plus the shared expert's on
every live token, which is (here + elsewhere) / experts a token.
"""

from __future__ import annotations


def expert_bytes(moe: dict) -> float:
    """Bytes of ONE routed expert's matrices: gate, up [d_model, d_ff], down."""
    return float(moe["matrices"] * moe["d_model"] * moe["d_ff"] * moe["dtype_bytes"])


def shared_bytes(moe: dict) -> float:
    """Bytes of the shared expert's matrices, d_ff_shared wide (0 without one)."""
    if not moe.get("n_shared_experts"):
        return 0.0
    return float(moe["matrices"] * moe["d_model"] * moe["d_ff_shared"] * moe["dtype_bytes"])


def share_work(hit: float, layer_calls: float, here: float, elsewhere: float,
               moe: dict) -> tuple[float, float]:
    """(bytes, flops) of ``layer_calls`` expert-layer calls that hit ``hit``
    HELD experts in all, with ``here`` live assignments computed on this chip
    and ``elsewhere`` held by another: the hit experts and each call's shared
    expert read once; every assignment's product here and the shared expert's
    on each live token."""
    tokens = (here + elsewhere) / moe["experts_per_token"]
    per_elem = 2.0 / moe["dtype_bytes"]  # a multiply-add a weight element
    return (hit * expert_bytes(moe) + layer_calls * shared_bytes(moe),
            here * expert_bytes(moe) * per_elem + tokens * shared_bytes(moe) * per_elem)


def here_share(here: float, elsewhere: float) -> float | None:
    """Live assignments computed here as a share (%) of all live assignments."""
    total = here + elsewhere
    return 100.0 * here / total if total > 0 else None
