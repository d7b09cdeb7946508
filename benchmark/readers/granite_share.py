"""Two counts of a chip that holds a share of every layer's experts, over the
window (growth of the server's counters between its two scrapes):

``what: "hit"``: the HELD experts hit as a share (%) of those the window's
expert-layer calls could have hit: growth of ``hit`` over growth of ``calls`` x
the configuration's ``moe.n_experts_held``.
``what: "here"``: the live assignments computed HERE as a share (%) of all live
assignments: ``here`` over ``here`` + ``elsewhere`` (~ held / routed where the
router is even: 50 % says the share is a fair half).

None where a counter did not grow or is not printed (a server without
``kind="elsewhere"``) or the configuration has no ``moe.n_experts_held``."""

from moe_share_bytes import here_share
from promtext import delta


def read(ctx, params):
    try:
        held = ctx["config"]["moe"]["n_experts_held"]
        grew = {k: delta(ctx["m0"], ctx["m1"], params[k])
                for k in ("hit", "calls", "here", "elsewhere") if k in params}
    except (KeyError, TypeError):
        return None
    if any(v is None for v in grew.values()):
        return None
    if params["what"] == "here":
        return here_share(grew["here"], grew["elsewhere"])
    if not grew["calls"]:
        return None
    return 100.0 * grew["hit"] / (grew["calls"] * held)
