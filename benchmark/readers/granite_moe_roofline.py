"""The HELD experts' share of their roofline over the traced interval, for a
chip that holds a share of every layer's experts.

Least time: what the interval's expert-layer calls had to read and compute
(``moe_share_bytes.py``): the held experts they HIT, each read once, from the
growth of the device-counted ``engine.moe_experts_hit`` over the WINDOW scaled
to the traced interval's length (``joyai_scopes.interval_growth``); the shared
expert, of its own width, once a call (``engine.moe_layer_calls``); the flops of
the assignments computed here (``engine.moe_assignments{kind="live"}``) and the
shared expert's on every live token (``kind="live"`` + ``kind="elsewhere"``
over the experts a token). The larger of bytes / peak bytes/s and flops / peak
flop/s (``peaks.json``), divided by the device self time under the scopes
``pattern`` names (``moe.experts``, ``moe.shared``). None where the capture, the
scopes, a counter (a server without ``kind="elsewhere"``) or the
configuration's ``moe.n_experts_held`` is absent."""

import json

from joyai_scopes import interval_growth, roofline_share, seconds_under
from moe_share_bytes import share_work


def read(ctx, params):
    try:
        moe = ctx["config"].get("moe") or {}
        got = seconds_under(ctx, params["pattern"]) if moe.get("n_experts_held") else None
        grew = got and interval_growth(
            ctx, params["hit"], params["calls"], params["here"], params["elsewhere"])
        if not grew:
            return None
        return roofline_share(ctx, "granite_moe_roofline", got[0], *share_work(*grew, moe),
                              experts_hit=grew[0], layer_calls=grew[1],
                              assignments_here=grew[2], assignments_elsewhere=grew[3])
    except Exception as e:  # noqa: BLE001 — a reader returns None, never raises
        print(json.dumps({"phase": "granite_moe_roofline", "error": repr(e)[:600]}), flush=True)
        return None
