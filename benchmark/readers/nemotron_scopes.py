"""Shared by the readers of an expert layer whose routed experts live in a
latent: the ``moe.*`` parts, with the two latent projections, which the program
opens NESTED inside ``moe.experts`` (``moe.experts/latent.in``, ``moe.experts/
latent.out``: ``bee2bee_tpu/tracing.py`` ``DEVICE_NESTED``), booked under the
LONGER name, so that the grouped products (``^moe[.]experts$``) and the
projections (``^moe[.]experts/latent[.]``) can be told apart or summed
(``^moe[.]``). Runs ``scope_reduce.py`` (unchanged; it takes the capture regex as
an argument) once a traced run with THIS file's regex and keeps its result in
the run's context under this file's own key. None where there is no capture; a
program without these scopes gives an empty ``scopes`` and every reader None."""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
PATTERN = r"(moe\.experts/latent\.[a-z_]+|moe\.[a-z_]+)"
KEY = "_scope_reduce_nemotron"


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
        print(json.dumps({"phase": "scope_reduce_nemotron", "error": repr(e)[:600]}), flush=True)
        return None
    print(json.dumps({"phase": "scope_reduce_nemotron", **red}), flush=True)
    ctx[KEY] = red
    return red


def seconds_under(ctx, pattern: str):
    """(self seconds under the scopes ``pattern`` names, busy seconds), or None;
    None too where the capture holds no ``latent.*`` scope at all (another
    model's program: these metrics are the cell's)."""
    red = scopes(ctx)
    if not red or not red.get("busy_s"):
        return None
    found = red.get("scopes") or {}
    if not any("/latent." in name for name in found):
        return None
    rx = re.compile(pattern)
    under = sum(sec for name, sec in found.items() if rx.search(name))
    return (under, red["busy_s"]) if under > 0.0 else None


def read(ctx, params):
    """As a metric's reader: the scopes ``pattern`` names as a share (%) of the
    device's busy time in the traced interval."""
    got = seconds_under(ctx, params["pattern"])
    return None if got is None else 100.0 * got[0] / got[1]
