"""A looped stack's weight products' share of their roofline over the traced
interval.

Least time (``loop_bytes.py``, the configuration's ``loop`` section,
``peaks.json``): every decode step and every prefill call streams the layers
once a pass and the head once; a call counts the larger of that and its
products' flops. Steps and calls: the growth of ``engine.loop_passes{kind}``
(passes of the stack dispatched, counted on the host) over the WINDOW divided
by the configuration's passes, the prefills' positions from
``engine.prefill_tokens{kind="real"}``, all scaled to the traced interval's
length as ``joyai_moe_roofline`` scales its counters (the mix is a closed loop
at full width: the window's rate is the interval's to a per cent or two). A
decode step's rows: the server's ``max_batch_size`` (the cell keeps every row
busy; the products run over the whole bucket either way). Divided by the device
self time under the scopes ``pattern`` names (``attn.qkv``, ``attn.out``,
``mlp.*``, ``head.*``). None where the capture, the scopes, the counter or the
configuration's ``loop`` section is absent."""

import json

from joyai_scopes import interval_growth
from loop_bytes import least_seconds
from ouro_scopes import seconds_under


def read(ctx, params):
    try:
        loop = ctx["config"].get("loop")
        got = seconds_under(ctx, params["pattern"]) if loop else None
        peak = ctx["peaks"].get(ctx["device"]["kind"])
        grew = got and peak and interval_growth(
            ctx, params["passes"] + '{kind="decode"}', params["passes"] + '{kind="prefill"}',
            params["prefill_tokens"])
        if not grew:
            return None
        steps, calls = grew[0] / loop["passes"], grew[1] / loop["passes"]
        rows = ctx["config"]["server"]["config_json"]["max_batch_size"]
        least, by = least_seconds(steps, rows, calls, grew[2], loop, peak)
        print(json.dumps({"phase": "loop_weight_roofline", "bound_by": max(by, key=by.get),
                          "least_s": least, "by": by, "scope_s": got[0], "decode_steps": steps,
                          "prefill_calls": calls, "prefill_positions": grew[2]}), flush=True)
        return 100.0 * least / got[0]
    except Exception as e:  # noqa: BLE001 — a reader returns None, never raises
        print(json.dumps({"phase": "loop_weight_roofline", "error": repr(e)[:600]}), flush=True)
        return None
