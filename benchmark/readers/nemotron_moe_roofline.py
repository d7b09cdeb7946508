"""The HELD routed experts' share of their roofline over the traced interval,
for an expert layer whose experts live in a latent and of which the chip holds
a share.

Least time: what the interval's expert-layer calls had to read and compute
(``latent_moe_bytes.work``, its ``experts`` part: the grouped products alone,
which is what the scope below times): the held experts they HIT, each read once, from the growth of
the device-counted ``engine.moe_experts_hit`` over the WINDOW scaled to the
traced interval's length (``joyai_scopes.interval_growth``), and the flops of
the assignments computed here (``engine.moe_assignments{kind="live"}``). The
larger of bytes / peak bytes/s and flops / peak flop/s (``peaks.json``), divided
by the device self time under the scopes ``pattern`` names
(``nemotron_scopes.py``: ``^moe[.]experts$`` is the part LESS its nested latent
projections). None where the capture, the scopes, a counter (a server without
``kind="elsewhere"``) or the configuration's ``moe.d_latent`` is absent."""

import json

from joyai_scopes import interval_growth, roofline_share
from latent_moe_bytes import work
from nemotron_scopes import seconds_under


def read(ctx, params):
    try:
        moe = ctx["config"].get("moe") or {}
        latent = moe.get("d_latent") and moe.get("n_experts_held")
        got = seconds_under(ctx, params["pattern"]) if latent else None
        grew = got and interval_growth(
            ctx, params["hit"], params["calls"], params["here"], params["elsewhere"])
        if not grew:
            return None
        return roofline_share(ctx, "nemotron_moe_roofline", got[0],
                              *work(*grew, moe, ("experts",)),
                              experts_hit=grew[0], layer_calls=grew[1],
                              assignments_here=grew[2], assignments_elsewhere=grew[3])
    except Exception as e:  # noqa: BLE001 — a reader returns None, never raises
        print(json.dumps({"phase": "nemotron_moe_roofline", "error": repr(e)[:600]}), flush=True)
        return None
