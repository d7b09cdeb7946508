"""Shared by the readers of the ``moe.*`` and ``mla.*`` scopes' time: run
``scope_reduce.py`` (unchanged; it takes the capture regex as an argument) once
a traced run with THIS file's regex and keep its result in the run's context
under this file's own key (``scope_common.py`` captures ``ssm.*`` only and keeps
its own). None where there is no capture; a program without these scopes gives
an empty ``scopes`` and every reader of it None."""

import json
import os
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
PATTERN = r"((?:moe|mla)\.[a-z_]+)"
KEY = "_scope_reduce_moe_mla"


def scopes(ctx):
    if KEY in ctx:
        return ctx[KEY]
    ctx[KEY] = None
    try:
        xplane = BENCH.parent / ".bench_home" / ctx["cell"]["name"] / "profile.xplane.pb"
        if not ctx.get("trace") or not xplane.is_file():
            return None
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("BENCH_RUN", None)
        proc = subprocess.run(
            [sys.executable, str(BENCH / "scope_reduce.py"), str(xplane), PATTERN],
            env=env, capture_output=True, text=True, timeout=900.0)
        red = json.loads([ln for ln in proc.stdout.splitlines() if ln.strip()][-1])
    except Exception as e:  # noqa: BLE001 — a reader returns None, never raises
        print(json.dumps({"phase": "scope_reduce_moe_mla", "error": repr(e)[:600]}), flush=True)
        return None
    print(json.dumps({"phase": "scope_reduce_moe_mla", **red}), flush=True)
    ctx[KEY] = red
    return red


def seconds_under(ctx, pattern: str):
    """(self seconds under the scopes ``pattern`` names, busy seconds), or None."""
    import re

    red = scopes(ctx)
    if not red or not red.get("busy_s"):
        return None
    rx = re.compile(pattern)
    under = sum(sec for name, sec in (red.get("scopes") or {}).items() if rx.search(name))
    return (under, red["busy_s"]) if under > 0.0 else None


def traced_interval(ctx):
    """[a, b) of the capture's device events on the client's monotonic clock
    (as ``kv_roofline`` places it), or None."""
    import time

    tr, prof = ctx.get("trace"), ctx.get("profile")
    header = (prof or {}).get("header") or {}
    if not tr or not tr.get("window_s") or "ts" not in header or "duration_s" not in header:
        return None
    mono_start = header["ts"] - (time.time() - time.monotonic())
    a = mono_start + max(0.0, header["duration_s"] - tr["window_s"]) / 2.0
    return a, a + tr["window_s"]


def interval_growth(ctx, *names: str):
    """The growth of each counter of ``names`` over the WINDOW (the scrapes at
    its start and end) scaled to the traced interval's length, or None where
    there is no interval, no window or the first of them did not grow."""
    from promtext import delta

    span, window = traced_interval(ctx), ctx["t1"] - ctx["t0"]
    if not span or window <= 0:
        return None
    grew = [delta(ctx["m0"], ctx["m1"], name) for name in names]
    if not grew[0] or any(g is None for g in grew):
        return None
    return [g * (span[1] - span[0]) / window for g in grew]


def roofline_share(ctx, phase: str, scope_s: float, nbytes: float, flops: float, **note):
    """100 x (the larger of bytes / peak bytes/s and flops / peak flop/s of the
    run's device, ``peaks.json``) / ``scope_s``, with a line saying which bound
    it was and ``note``; None where the device has no peaks."""
    peak = ctx["peaks"].get(ctx["device"]["kind"])
    if not peak:
        return None
    t_mem, t_flop = nbytes / peak["hbm_bytes_per_s"], flops / peak["bf16_flops_per_s"]
    least = max(t_mem, t_flop)
    print(json.dumps({"phase": phase, "bound_by": "memory" if t_mem >= t_flop else "compute",
                      "least_s": least, "scope_s": scope_s, **note}), flush=True)
    return 100.0 * least / scope_s
