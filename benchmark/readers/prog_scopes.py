"""Shared by the readers of the PROGRAM roots' time (``prog.prefill``,
``prog.decode``, ``prog.verify``, ``prog.sample``, ``prog.pool``: the one
``jax.named_scope`` around the body of each jit root of the serving path,
``bee2bee_tpu/tracing.prog_scope``): run ``scope_reduce.py`` (unchanged; it
takes the capture regex as an argument) once a traced run with THIS file's
regex and keep its result in the run's context under this file's own key
(``scope_common.py`` and ``joyai_scopes.py`` keep theirs). ``scope_reduce``
books an op under the FIRST match in its ``op_name``
(``jit(_decode_fn)/prog.decode/while/body/...``), so the sampler inside a
decode window stays the decode program's. None where there is no capture; a
program without these scopes gives an empty ``scopes`` and every reader of it
None."""

import json
import os
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
PATTERN = r"(prog\.[a-z]+)"
KEY = "_scope_reduce_prog"


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
        print(json.dumps({"phase": "scope_reduce_prog", "error": repr(e)[:600]}), flush=True)
        return None
    print(json.dumps({"phase": "scope_reduce_prog", **red}), flush=True)
    ctx[KEY] = red
    return red
