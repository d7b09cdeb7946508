"""The ragged kernel's share of its roofline over the traced interval.

Least time: the attention of everything GENERATED in the interval. A content
event's tokens (and, for a stream's first event, its prompt's causal prefill)
were computed in the stretch since the server's previous burst of events
(``loadgen.generation_stretches``, the same attribution as ``tok_s``); each
stretch counts by the share of it that lies inside the interval. Per call the
larger of bytes / peak bytes/s and flops / peak flop/s (``kv_bytes.py``,
``peaks.json``); which bound dominates is printed on an earlier line. Divided
by the device self time of the kernel's events.
"""

import json
import time

from kv_bytes import decode_token, min_seconds, prefill
from loadgen import generation_stretches, overlap_share
from trace_op_share import matched_seconds


def read(ctx, params):
    tr, prof = ctx["trace"], ctx["profile"]
    if not tr or not prof or not tr.get("window_s"):
        return None
    kernel_s = matched_seconds(tr, params["pattern"])
    if kernel_s <= 0:
        return None
    peak = ctx["peaks"].get(ctx["device"]["kind"])
    if peak is None:
        raise KeyError(f"no peaks for device kind {ctx['device']['kind']!r} in peaks.json")
    # the capture's wall-clock start on the client's monotonic clock; the
    # device events span window_s, centred in the captured stretch
    header = prof["header"]
    mono_start = header["ts"] - (time.time() - time.monotonic())
    a = mono_start + max(0.0, header["duration_s"] - tr["window_s"]) / 2.0
    b = a + tr["window_s"]
    kv, chips = ctx["config"]["kv"], ctx["cell"]["chips"]
    least, by = 0.0, {"memory": 0.0, "compute": 0.0}
    made: dict[int, float] = {}  # tokens a stream had before this event
    for rec, k, since, t, n in generation_stretches(ctx["records"]):
        before = made.get(id(rec), 0.0)
        made[id(rec)] = before + n
        share = overlap_share(since, t, a, b)
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
    print(json.dumps({"phase": "kv_roofline", "bound_by": max(by, key=by.get),
                      "least_s": least, "kernel_s": kernel_s}), flush=True)
    return 100.0 * least / kernel_s
