"""The attention READ's share of its roofline over the traced interval, for a
model whose layers are of several kinds (``kv_mixed_bytes.py``).

Least time: the attention of everything GENERATED in the interval, as
``kv_roofline`` attributes it (a content event's tokens, and for a stream's
first event its prompt's causal prefill, were computed in the stretch since the
server's previous burst of events; each stretch counts by the share of it
inside the interval), every layer kind with its own window. Per call the larger
of bytes / peak bytes/s and flops / peak flop/s (``peaks.json``). Divided by
the device self time under the scope ``pattern`` names (``attn.read`` ONLY: in
this model ``tpu_custom_call`` is also the page-writes and the experts' grouped
products). None where the capture, the scope or the configuration's
``kv.kinds`` is absent."""

import json

from joyai_scopes import traced_interval
from kv_bytes import min_seconds
from kv_mixed_bytes import decode_token, prefill
from loadgen import generation_stretches, overlap_share
from st_scopes import seconds_under


def read(ctx, params):
    try:
        kv = ctx["config"].get("kv") or {}
        span = traced_interval(ctx) if kv.get("kinds") else None
        got = span and seconds_under(ctx, params["pattern"])
        peak = ctx["peaks"].get(ctx["device"]["kind"])
        if not got or not peak:
            return None
        chips = ctx["cell"]["chips"]
        least, by = 0.0, {"memory": 0.0, "compute": 0.0}
        made: dict[int, float] = {}  # tokens a stream had before this event
        for rec, k, since, t, n in generation_stretches(ctx["records"]):
            before = made.get(id(rec), 0.0)
            made[id(rec)] = before + n
            share = overlap_share(since, t, *span)
            if share <= 0.0:
                continue
            context = rec.spec.prompt_tokens + int(before)
            calls = [decode_token(context + i, kv, chips) for i in range(int(round(n)))]
            if k == 0:
                calls.append(prefill(rec.spec.prompt_tokens, kv, chips))
            secs, bound = min_seconds(calls, peak)
            least += share * secs
            by[bound] += share * secs
        if least <= 0.0:
            return None
        print(json.dumps({"phase": "st_kv_roofline", "bound_by": max(by, key=by.get),
                          "least_s": least, "scope_s": got[0]}), flush=True)
        return 100.0 * least / got[0]
    except Exception as e:  # noqa: BLE001 — a reader returns None, never raises
        print(json.dumps({"phase": "st_kv_roofline", "error": repr(e)[:600]}), flush=True)
        return None
