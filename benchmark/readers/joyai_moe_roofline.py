"""The experts' share of their roofline over the traced interval.

Least time: what the interval's expert-layer calls had to read and compute
(``moe_bytes.py``): the routed experts they HIT, each read once at 9.44 MB, from
the growth of the device-counted ``engine.moe_experts_hit`` over the WINDOW (the
scrapes at its start and end) scaled to the traced interval's length; the shared
expert once a call (``engine.moe_layer_calls``); and the live assignments' flops
(``engine.moe_assignments{kind="live"}``), likewise. The mix is steady (a closed
loop at full width), so the window's rate is the interval's to a per cent or
two, and a counter that moves only when a window's tokens are FETCHED cannot
shift it (a form that read the 1 s polls around the interval came back empty in
one run of three: PERF.md section 6). The larger of bytes / peak bytes/s and
flops / peak flop/s (``peaks.json``; which bound it was is printed on an earlier
line). Divided by the device self time under the scopes ``pattern`` names
(``moe.experts``, ``moe.shared``). None where the capture, the scopes, the
counters or the configuration's ``moe`` section is absent."""

import json

from joyai_scopes import interval_growth, roofline_share, seconds_under
from moe_bytes import expert_work


def read(ctx, params):
    try:
        moe = ctx["config"].get("moe")
        got = seconds_under(ctx, params["pattern"]) if moe else None
        grew = got and interval_growth(ctx, params["hit"], params["calls"], params["assignments"])
        if not grew:
            return None
        return roofline_share(ctx, "joyai_moe_roofline", got[0], *expert_work(*grew, moe),
                              experts_hit=grew[0], layer_calls=grew[1], live_assignments=grew[2])
    except Exception as e:  # noqa: BLE001 — a reader returns None, never raises
        print(json.dumps({"phase": "joyai_moe_roofline", "error": repr(e)[:600]}), flush=True)
        return None
