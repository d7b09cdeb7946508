"""What a dropless expert layer's expert products NEED to read and compute,
from shapes alone.

The yardstick for ``joyai.moe.experts_roofline``: the least time the chip could
take for the routed AND shared experts' products of the traced interval's
forwards, against the device time under the program's ``moe.experts`` and
``moe.shared`` scopes. ``moe`` is the configuration file's ``moe`` section:
expert layers, experts, experts a token, shared experts, model width, expert
width, matrices an expert (gate, up, down), bytes per element.

An expert-layer call must read every routed expert that got at least one live
assignment ONCE (its matrices whole: an expert with one row costs as much to
read as one with fifty), the shared expert once, and do every live assignment's
multiply-adds plus the shared expert's on every live token. The program counts
the first on the device (``engine.moe_experts_hit``, summed over expert-layer
calls), the calls (``engine.moe_layer_calls``) and the assignments
(``engine.moe_assignments{kind="live"}``) from its shapes. The router and the
sort's own traffic are not in it: the router is dense weight traffic like an
MLP's and a sorted copy of a few hundred rows is noise beside 9.4 MB an expert;
experts that no live token chose are not in it either (64 rows x 8 hit ~80 %
of 256: counting all 256 would count weights no step needed).
"""

from __future__ import annotations


def expert_bytes(moe: dict) -> float:
    """Bytes of ONE expert's matrices: gate, up [d_model, d_ff], down [d_ff, d_model]."""
    return float(moe["matrices"] * moe["d_model"] * moe["d_ff"] * moe["dtype_bytes"])


def assignment_flops(moe: dict) -> float:
    """Flops of ONE (token, expert) assignment: a multiply-add an element of
    each of the expert's matrices."""
    return 2.0 * moe["matrices"] * moe["d_model"] * moe["d_ff"]


def expert_work(hit: float, layer_calls: float, assignments: float, moe: dict) -> tuple[float, float]:
    """(bytes, flops) of ``layer_calls`` expert-layer calls that hit ``hit``
    routed experts in all with ``assignments`` live assignments: the hit
    experts and each call's shared experts read once; every assignment's
    product and the shared experts' on each live token."""
    shared = moe.get("n_shared_experts", 0)
    tokens = assignments / moe["experts_per_token"]
    return ((hit + shared * layer_calls) * expert_bytes(moe),
            (assignments + shared * tokens) * assignment_flops(moe))
