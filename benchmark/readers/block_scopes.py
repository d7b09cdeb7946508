"""Shared by the readers of the block's parts (``bee2bee_tpu/tracing.py``'s
``DEVICE_PARTS``: the ``jax.named_scope``s every model's program carries): run
``scope_reduce.py`` (unchanged; it takes the capture regex as an argument) ONCE
a traced run with THIS file's regex and keep its result in the run's context
under this file's own key, for every metric that reads a part.

An op is booked under the FIRST part in its path: ``prog.decode/while/body/
attn.qkv/dot_general`` under ``attn.qkv``, ``moe.shared/mlp.down/...`` under
``moe.shared``. The wrappers ``spec.verify`` and ``mtp.block`` enclose a whole
forward and are no alternatives, so ``spec.verify/.../moe.experts/...`` books
under ``moe.experts``. An op whose path holds NO part is booked under its whole
path (the regex's second alternative), which is how the ``block_scopes`` line
can say what is left unnamed, by op, with its seconds. The line holds the
per-part seconds of the cell and the seconds this extra reduction took.

None where there is no capture; a program without these scopes (an older
commit) gives the parts it has and every reader of an absent one None."""

import json
import os
import re
import subprocess
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
PARTS = (
    "embed.tokens", "norm.block",
    "attn.qkv", "attn.rope", "attn.write", "kv.write", "attn.read", "attn.out",
    "mla.q_proj", "mla.kv_proj", "mla.write", "mla.read", "mla.out",
    "ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.step", "ssm.state_write", "ssm.out_proj",
    "mlp.gate_up", "mlp.down",
    "moe.router", "moe.dispatch", "moe.experts", "moe.combine", "moe.shared",
    "loop.norm", "head.logits", "mtp.proj", "mtp.head", "spec.accept", "sample.draw",
)
_PART = r"(?<![\w.])(?:" + "|".join(re.escape(p) for p in PARTS) + r")(?![\w.])"
PATTERN = "(" + _PART + r"|^(?!.*" + _PART + r").+$)"
KEY = "_scope_reduce_block"
LISTED = 12  # unnamed ops on the line, largest first


def part_of(op_name: str) -> str:
    """The part an op of this ``op_name`` is booked under, '' for none."""
    m = re.search(_PART, op_name)
    return m.group(0) if m else ""


def split(red: dict) -> dict:
    """``scope_reduce``'s result under PATTERN as {busy_s, parts {part: s},
    unnamed {op path: s}}."""
    parts, unnamed = {}, {}
    for label, sec in (red.get("scopes") or {}).items():
        (parts if label in PARTS else unnamed)[label] = sec
    return {"busy_s": red.get("busy_s") or 0.0, "parts": parts, "unnamed": unnamed}


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
        t0 = time.monotonic()
        proc = subprocess.run(
            [sys.executable, str(BENCH / "scope_reduce.py"), str(xplane), PATTERN],
            env=env, capture_output=True, text=True, timeout=900.0)
        got = split(json.loads([ln for ln in proc.stdout.splitlines() if ln.strip()][-1]))
        took = time.monotonic() - t0
    except Exception as e:  # noqa: BLE001 — a reader returns None, never raises
        print(json.dumps({"phase": "block_scopes", "error": repr(e)[:600]}), flush=True)
        return None
    left = sorted(got["unnamed"].items(), key=lambda kv: -kv[1])
    print(json.dumps({
        "phase": "block_scopes", "busy_s": got["busy_s"], "parts": got["parts"],
        "named_s": sum(got["parts"].values()), "unnamed_listed_s": sum(got["unnamed"].values()),
        "unnamed": [[name[-160:], sec] for name, sec in left[:LISTED]],
        "reduce_s": took}), flush=True)
    ctx[KEY] = got
    return got


def seconds_under(ctx, pattern: str):
    """(self seconds under the parts ``pattern`` names, busy seconds), or None."""
    got = scopes(ctx)
    if not got or not got["busy_s"]:
        return None
    rx = re.compile(pattern)
    under = sum(sec for name, sec in got["parts"].items() if rx.search(name))
    return (under, got["busy_s"]) if under > 0.0 else None


def read(ctx, params):
    """As a metric's reader: the parts ``pattern`` names (every part without
    one: the NAMED share) as a share (%) of the device's busy time in the
    traced interval."""
    got = seconds_under(ctx, params.get("pattern", ""))
    return None if got is None else 100.0 * got[0] / got[1]
