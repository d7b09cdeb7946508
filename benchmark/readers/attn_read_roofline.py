"""The attention READ's share of its roofline over the traced interval, for a
model of one kind of K/V layer.

The least time is ``kv_roofline.py``'s own, to the letter (``kv_bytes.py``, the
configuration's ``kv`` section, everything GENERATED in the interval by the
client's records): that reader is handed the run's context with the parts'
seconds in place of the trace's op table, so the denominator is the device self
time under ``attn.read`` ALONE (``block_scopes.py``) where
``kernel.ragged_roofline`` divides by every ``tpu_custom_call``: the page-writes
(phi-3) and the state-step kernel (falcon-h1) are out of it. None where the
capture or the part is absent."""

import json

import kv_roofline
from block_scopes import scopes


def read(ctx, params):
    try:
        got = scopes(ctx)
        if not got or not ctx.get("trace") or not ctx.get("profile"):
            return None
        return kv_roofline.read(dict(ctx, trace=dict(ctx["trace"], ops=got["parts"])), params)
    except Exception as e:  # noqa: BLE001 — a reader returns None, never raises
        print(json.dumps({"phase": "attn_read_roofline", "error": repr(e)[:600]}), flush=True)
        return None
