"""Shared by the readers of a looped stack's scopes (``attn.*``, ``mlp.*``,
``loop.*``, ``head.*``: Ouro's whole program): run ``scope_reduce.py``
(unchanged; it takes the capture regex as an argument) once a traced run with
THIS file's regex and keep its result in the run's context under this file's own
key (``st_scopes.py`` captures ``attn.`` and ``moe.`` only). The regex also
captures, where an op's path holds NO such scope, ``while/body/dynamic_slice``:
the layer scan's own slices of its stacked weights, which ``lax.scan`` makes
outside the body's scopes. On the chip they are where a layer's ``wq`` / ``wk``
/ ``wv`` leave HBM (a fusion copies the slice into the layout and the memory the
product reads: 0.62 s of a 4.0 s capture against 0.19 s under ``attn.qkv``), so
a reading of the weights' time that left them out would pass 100 % of its
roofline (107 % on the first traced run, PR 46). None where there is no capture;
a program without these scopes gives an empty ``scopes`` and every reader of it
None."""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
PATTERN = r"((?:attn|mlp|loop|head)\.[a-z_]+|while/body/dynamic_slice)"
KEY = "_scope_reduce_loop"


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
        print(json.dumps({"phase": "scope_reduce_loop", "error": repr(e)[:600]}), flush=True)
        return None
    print(json.dumps({"phase": "scope_reduce_loop", **red}), flush=True)
    ctx[KEY] = red
    return red


def seconds_under(ctx, pattern: str):
    """(self seconds under the scopes ``pattern`` names, busy seconds), or None."""
    red = scopes(ctx)
    if not red or not red.get("busy_s"):
        return None
    rx = re.compile(pattern)
    under = sum(sec for name, sec in (red.get("scopes") or {}).items() if rx.search(name))
    return (under, red["busy_s"]) if under > 0.0 else None


def read(ctx, params):
    """As a metric's reader: the scopes ``pattern`` names as a share (%) of the
    device's busy time in the traced interval."""
    got = seconds_under(ctx, params["pattern"])
    return None if got is None else 100.0 * got[0] / got[1]
