"""Share of the traced interval in which no operation ran on the device:
1 - (union of device-op intervals / traced interval), mean over the chips."""


def read(ctx, params):
    tr = ctx["trace"]
    if not tr or not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
