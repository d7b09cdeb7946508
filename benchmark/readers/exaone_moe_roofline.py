"""The HELD experts' share of their roofline over the traced interval, for a
model whose chip holds a share of every layer's experts AND runs a multi-token-
prediction layer with an expert layer of its own (K-EXAONE).

As ``granite_moe_roofline.py``, through ``moe_share_bytes.py``: the held experts
HIT, each read once (``engine.moe_experts_hit``), the shared expert once a call
(``engine.moe_layer_calls``), the flops of the assignments computed here and the
shared expert's on every live position; the server counts the MTP block's calls,
hits and assignments with the trunk's, and a verify step's TWO positions a row
both. Divided by the device self time under the scopes ``pattern`` names IN THE
TRUNK AND IN THE MTP LAYER (``exaone_scopes.py``: ``moe.experts``, ``moe.shared``
and ``mtp.block/moe.experts``, ``mtp.block/moe.shared``). None where the capture, the
``mtp.*`` scopes, a counter or the configuration's ``moe.n_experts_held`` is
absent."""

import json

from exaone_scopes import seconds_under
from joyai_scopes import interval_growth, roofline_share
from moe_share_bytes import share_work


def read(ctx, params):
    try:
        moe = ctx["config"].get("moe") or {}
        got = seconds_under(ctx, params["pattern"]) if moe.get("n_experts_held") else None
        grew = got and interval_growth(
            ctx, params["hit"], params["calls"], params["here"], params["elsewhere"])
        if not grew:
            return None
        return roofline_share(ctx, "exaone_moe_roofline", got[0], *share_work(*grew, moe),
                              experts_hit=grew[0], layer_calls=grew[1],
                              assignments_here=grew[2], assignments_elsewhere=grew[3])
    except Exception as e:  # noqa: BLE001 — a reader returns None, never raises
        print(json.dumps({"phase": "exaone_moe_roofline", "error": repr(e)[:600]}), flush=True)
        return None
