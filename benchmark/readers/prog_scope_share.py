"""Device self time under the program roots that match ``pattern``
(``prog_scopes.py``: ``prog.prefill``, ``prog.sample``, ...) as a share (%) of
the device's busy time in the traced interval: how much of what the chip did
was that kind of PROGRAM (``device.prefill_time_share``: prefill chunks and
first-token samples against decode windows). None where the capture shows no
such scope (a program without root scopes)."""

import re

from prog_scopes import scopes


def read(ctx, params):
    red = scopes(ctx)
    if not red or not red.get("busy_s"):
        return None
    rx = re.compile(params["pattern"])
    under = sum(sec for name, sec in (red.get("scopes") or {}).items() if rx.search(name))
    return 100.0 * under / red["busy_s"] if under > 0.0 else None
