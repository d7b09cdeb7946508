"""The latent read's share of its roofline over the traced interval.

Least time: the cached latent rows the interval's decode steps cover, each read
once a layer at 1,152 B as published (not the padded storage), from the growth
of ``engine.latent_tokens_read`` over the WINDOW scaled to the traced interval's
length (as ``joyai_moe_roofline`` takes its counters), and every query head's
flops over them (``latent_bytes.py``): the larger of bytes / peak bytes/s and
flops / peak flop/s (``peaks.json``). Divided by the device self time under the
scope ``pattern`` names (``mla.read``), which also holds the prefill chunks'
reads: the share errs low. None where the capture, the scope, the counter or
the configuration's ``latent`` section is absent."""

import json

from joyai_scopes import interval_growth, roofline_share, seconds_under
from latent_bytes import read_work


def read(ctx, params):
    try:
        latent = ctx["config"].get("latent")
        got = seconds_under(ctx, params["pattern"]) if latent else None
        grew = got and interval_growth(ctx, params["rows"])
        if not grew:
            return None
        return roofline_share(ctx, "joyai_latent_roofline", got[0], *read_work(grew[0], latent),
                              rows_read=grew[0])
    except Exception as e:  # noqa: BLE001 — a reader returns None, never raises
        print(json.dumps({"phase": "joyai_latent_roofline", "error": repr(e)[:600]}), flush=True)
        return None
