"""Shared by the readers of ``jax.named_scope`` time: run ``scope_reduce.py``
once a traced run on the capture ``run.py`` left under ``.bench_home/<cell>/``
and keep its result in the run's context. None where there is no capture."""

import json
import os
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
PATTERN = r"(ssm\.(?:in_proj|conv|scan|step|out_proj|state_write))"


def scopes(ctx):
    if "_scope_reduce" in ctx:
        return ctx["_scope_reduce"]
    ctx["_scope_reduce"] = None
    xplane = BENCH.parent / ".bench_home" / ctx["cell"]["name"] / "profile.xplane.pb"
    if not ctx.get("trace") or not xplane.is_file():
        return None
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    proc = subprocess.run([sys.executable, str(BENCH / "scope_reduce.py"), str(xplane), PATTERN],
                          env=env, capture_output=True, text=True, timeout=900.0)
    try:
        red = json.loads([ln for ln in proc.stdout.splitlines() if ln.strip()][-1])
    except (IndexError, ValueError):
        print(json.dumps({"phase": "scope_reduce", "error": proc.stderr[-1200:]}), flush=True)
        return None
    print(json.dumps({"phase": "scope_reduce", **red}), flush=True)
    ctx["_scope_reduce"] = red
    return red
