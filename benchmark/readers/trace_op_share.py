"""Device self time of the ops whose name (``<instruction> <opcode>[:<target>]``)
matches ``pattern``, as a share of the device's busy time in the traced
interval (mean over the chips)."""

import re


def matched_seconds(trace, pattern):
    rx = re.compile(pattern)
    return sum(sec for name, sec in trace["ops"].items() if rx.search(name))


def read(ctx, params):
    tr = ctx["trace"]
    if not tr or not tr.get("busy_s"):
        return None
    return 100.0 * matched_seconds(tr, params["pattern"]) / tr["busy_s"]
