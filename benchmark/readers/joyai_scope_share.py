"""Device self time under the ``moe.*`` / ``mla.*`` scopes that match
``pattern`` as a share of the device's busy time in the traced interval
(``joyai_scopes.py``). None where the capture shows no such scope."""

from joyai_scopes import seconds_under


def read(ctx, params):
    got = seconds_under(ctx, params["pattern"])
    return None if got is None else 100.0 * got[0] / got[1]
