"""What an expert layer whose routed experts live in a LATENT needs to read and
compute, from shapes alone, for a chip that holds a share of the experts.

The yardstick for ``nemotron.moe.experts_roofline``: the least time the chip
could take for the HELD routed experts' grouped products of the traced
interval's forwards, against the device time under the program's
``moe.experts`` scope LESS its nested ``latent.*`` scopes. ``moe`` is the
configuration file's ``moe`` section: the model's width (``d_model``), the
latent's (``d_latent``), a routed expert's (``d_ff``) and the shared expert's
OWN (``d_ff_shared``), matrices an expert (2: no gate), routed experts
(``n_experts``) of which ``n_experts_held`` lie here, experts a token, bytes per
element.

``moe_share_bytes.py`` has ONE ``d_model`` for the routed and the shared
expert; here they differ (1,024 against 4,096), and two projections between
them are read once a call. So, a part at a time (``work``'s ``parts``):

``experts``  every HELD expert that got at least one live assignment is read
             once (``engine.moe_experts_hit`` counts held experts): 2 x d_latent
             x d_ff elements each; a multiply-add an element for every
             assignment computed here (``engine.moe_assignments{kind="live"}``).
``latent``   ``W_in`` and ``W_out`` (d_model x d_latent each) once a call; a
             multiply-add an element on every live token, which is (here +
             elsewhere) / experts a token.
``shared``   the shared expert (2 x d_model x d_ff_shared) once a call and on
             every live token.
"""

from __future__ import annotations

PARTS = ("experts", "latent", "shared")


def expert_bytes(moe: dict) -> float:
    """Bytes of ONE routed expert's matrices: [d_latent, d_ff] and back."""
    return float(moe["matrices"] * moe["d_latent"] * moe["d_ff"] * moe["dtype_bytes"])


def latent_bytes(moe: dict) -> float:
    """Bytes of the two projections into and out of the latent."""
    return float(2 * moe["d_model"] * moe["d_latent"] * moe["dtype_bytes"])


def shared_bytes(moe: dict) -> float:
    """Bytes of the shared expert's matrices at the model's width (0 without one)."""
    if not moe.get("n_shared_experts"):
        return 0.0
    return float(moe["matrices"] * moe["d_model"] * moe["d_ff_shared"] * moe["dtype_bytes"])


def work(hit: float, layer_calls: float, here: float, elsewhere: float, moe: dict,
         parts=PARTS) -> tuple[float, float]:
    """(bytes, flops) of ``parts`` of ``layer_calls`` expert-layer calls that
    hit ``hit`` HELD experts in all, with ``here`` live assignments computed on
    this chip and ``elsewhere`` held by another."""
    unknown = set(parts) - set(PARTS)
    if unknown:
        raise KeyError(f"unknown part {sorted(unknown)}")
    tokens = (here + elsewhere) / moe["experts_per_token"]
    per_elem = 2.0 / moe["dtype_bytes"]  # a multiply-add a weight element
    nbytes = flops = 0.0
    if "experts" in parts:
        nbytes += hit * expert_bytes(moe)
        flops += here * expert_bytes(moe) * per_elem
    for part, size in (("latent", latent_bytes(moe)), ("shared", shared_bytes(moe))):
        if part in parts:
            nbytes += layer_calls * size
            flops += tokens * size * per_elem
    return nbytes, flops
