"""The attention READ's share of its roofline over the traced interval, for a
model that decodes by VERIFY steps (K-EXAONE's ``mtp`` tier) over layers of
several kinds (``kv_mixed_bytes.py``: window 128 in four layers of six, the full
layer and the MTP block's full).

As ``st_kv_roofline.py`` (everything GENERATED in the interval by the client's
records, each stretch by its share inside the interval, every layer kind with its
own window), with one difference: a verify step reads a row's pages ONCE for its
two query positions and emits ``tokens_per_step`` tokens (1 + accepted / drafted,
from the growth of ``engine.spec_accepted`` / ``engine.spec_drafted`` over the
window), so a stretch's decode reads are its tokens DIVIDED by that; at no
acceptance it is 1 and every token is a step. Divided by the device self time
under the scope ``pattern`` names in the trunk and the MTP layer
(``exaone_scopes.py``). None where the capture, the ``mtp.*`` scopes, the counters
or the configuration's ``kv.kinds`` is absent."""

import json

from exaone_scopes import seconds_under
from joyai_scopes import traced_interval
from kv_bytes import min_seconds
from kv_mixed_bytes import decode_token, prefill
from loadgen import generation_stretches, overlap_share
from promtext import delta


def read(ctx, params):
    try:
        kv = ctx["config"].get("kv") or {}
        span = traced_interval(ctx) if kv.get("kinds") else None
        got = span and seconds_under(ctx, params["pattern"])
        peak = ctx["peaks"].get(ctx["device"]["kind"])
        drafted = delta(ctx["m0"], ctx["m1"], params["drafted"])
        if not got or not peak or not drafted:
            return None
        per_step = 1.0 + (delta(ctx["m0"], ctx["m1"], params["accepted"]) or 0.0) / drafted
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
            steps = [decode_token(context + i, kv, chips) for i in range(int(round(n)))]
            secs, bound = min_seconds(steps, peak)
            least += share * secs / per_step
            by[bound] += share * secs / per_step
            if k == 0:
                secs, bound = min_seconds([prefill(rec.spec.prompt_tokens, kv, chips)], peak)
                least += share * secs
                by[bound] += share * secs
        if least <= 0.0:
            return None
        print(json.dumps({"phase": "exaone_kv_roofline", "bound_by": max(by, key=by.get),
                          "least_s": least, "scope_s": got[0], "tokens_per_step": per_step}),
              flush=True)
        return 100.0 * least / got[0]
    except Exception as e:  # noqa: BLE001 — a reader returns None, never raises
        print(json.dumps({"phase": "exaone_kv_roofline", "error": repr(e)[:600]}), flush=True)
        return None
