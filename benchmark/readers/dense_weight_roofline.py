"""A dense stack's weight products' share of their roofline over the traced
interval.

As ``loop_weight_roofline.py`` with the ``loop`` section made from the
configuration's published keys (``dense_bytes.loop_of``: one pass): every
decode step and every prefill call streams the layers and the head once, and a
call counts the larger of that and its products' flops
(``loop_bytes.least_seconds``, reused). One difference: a prefill call runs the
HEAD for one position a row (``forward``'s ``last_index``) where a looped
stack's cell is too narrow for it to matter; here (falcon-h1: the head is 2.67
of 7.0 GB a call) the head's flops over every prefill position would count work
no program does, so the prefill calls' layers are taken compute- or
memory-bound without the head and the head's bytes are added once a call. Steps
and calls: the growth of ``engine.loop_passes{kind}`` (1 a call for a plain
stack) and ``engine.prefill_tokens{kind="real"}`` over the window, scaled to
the traced interval (``joyai_scopes.interval_growth``). Divided by the device
self time under the parts ``pattern`` names (``block_scopes.py``). None where
the capture, the parts, the counters or a published key is absent."""

import json

from block_scopes import seconds_under
from dense_bytes import loop_of
from joyai_scopes import interval_growth
from loop_bytes import least_seconds


def read(ctx, params):
    try:
        got = seconds_under(ctx, params["pattern"])
        peak = ctx["peaks"].get(ctx["device"]["kind"])
        grew = got and peak and interval_growth(
            ctx, params["passes"] + '{kind="decode"}', params["passes"] + '{kind="prefill"}',
            params["prefill_tokens"])
        if not grew:
            return None
        steps, calls, positions = grew
        conf = ctx["config"]
        rows = conf["server"]["config_json"]["max_batch_size"]
        whole, layers = loop_of(conf), loop_of(conf, head=False)
        dec, by = least_seconds(steps, rows, 0.0, 0.0, whole, peak)
        pre, by_pre = least_seconds(0.0, 0.0, calls, positions, layers, peak)
        head = calls * whole["head_bytes"] / peak["hbm_bytes_per_s"]
        by = {"memory": by["memory"] + by_pre["memory"] + head,
              "compute": by["compute"] + by_pre["compute"]}
        least = dec + pre + head
        print(json.dumps({"phase": "dense_weight_roofline", "bound_by": max(by, key=by.get),
                          "least_s": least, "by": by, "scope_s": got[0], "decode_steps": steps,
                          "prefill_calls": calls, "prefill_positions": positions,
                          "layer_bytes": whole["layer_bytes"], "head_bytes": whole["head_bytes"],
                          "layers": whole["layers"]}), flush=True)
        return 100.0 * least / got[0]
    except Exception as e:  # noqa: BLE001 — a reader returns None, never raises
        print(json.dumps({"phase": "dense_weight_roofline", "error": repr(e)[:600]}), flush=True)
        return None
