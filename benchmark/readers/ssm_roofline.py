"""The recurrent state's share of its roofline over the traced interval.

Least time: the state work of everything GENERATED in the interval, attributed
as ``kv_roofline`` does (``loadgen.generation_stretches``): each generated
token is one row's one-step update over all layers (state read once, written
once), and a stream's first event also its prompt's chunked scan. Per call the
larger of bytes / peak bytes/s and flops / peak flop/s (``ssm_bytes.py``,
``peaks.json``; which bound dominates is printed on an earlier line). Divided
by the device self time under the scopes that ``pattern`` names (``ssm.step``,
``ssm.scan`` and ``ssm.state_write``, where the compiler puts the state's update). Dead
rows of the batch bucket take device time and count no useful work."""

import json
import re
import time

from kv_bytes import min_seconds
from loadgen import generation_stretches, overlap_share
from scope_common import scopes
from ssm_bytes import decode_step, prefill_scan


def read(ctx, params):
    tr, prof, state = ctx["trace"], ctx["profile"], ctx["config"].get("state")
    red = scopes(ctx)
    if not red or not state or not tr or not prof or not tr.get("window_s"):
        return None
    rx = re.compile(params["pattern"])
    under = sum(sec for name, sec in red["scopes"].items() if rx.search(name))
    if under <= 0.0:
        return None
    peak = ctx["peaks"].get(ctx["device"]["kind"])
    if peak is None:
        raise KeyError(f"no peaks for device kind {ctx['device']['kind']!r} in peaks.json")
    header = prof["header"]
    mono_start = header["ts"] - (time.time() - time.monotonic())
    a = mono_start + max(0.0, header["duration_s"] - tr["window_s"]) / 2.0
    b = a + tr["window_s"]
    least, by = 0.0, {"memory": 0.0, "compute": 0.0}
    one_step = decode_step(state)
    for rec, k, since, t, n in generation_stretches(ctx["records"]):
        share = overlap_share(since, t, a, b)
        if share <= 0.0:
            continue
        calls = [one_step] * int(round(n))
        if k == 0:
            calls.append(prefill_scan(rec.spec.prompt_tokens, state))
        secs, bound = min_seconds(calls, peak)
        least += share * secs
        by[bound] += share * secs
    if least <= 0.0:
        return None
    print(json.dumps({"phase": "ssm_roofline", "bound_by": max(by, key=by.get),
                      "least_s": least, "scope_s": under}), flush=True)
    return 100.0 * least / under
