"""Device self time under the program's scopes that match ``pattern``
(``jax.named_scope`` names, ``scope_reduce.py``) as a share of the device's
busy time in the traced interval. None where the capture shows no such scope
(a program without the scopes, a configuration without the layer)."""

import re

from scope_common import scopes


def read(ctx, params):
    red = scopes(ctx)
    if not red or not red.get("busy_s"):
        return None
    rx = re.compile(params["pattern"])
    under = sum(sec for name, sec in red["scopes"].items() if rx.search(name))
    if under <= 0.0:
        return None
    return 100.0 * under / red["busy_s"]
