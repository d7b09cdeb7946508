"""Client-side mean of a sample series minus the server's own window mean of
the same quantity: what the gateway, admission and the wire add on top of
what the engine measured. The two means cover nearly, not exactly, the same
requests (the server counts a request when its first token leaves the engine)."""

from promtext import delta_mean


def read(ctx, params):
    samples = ctx["client"][params["series"]]
    inner = delta_mean(ctx["m0"], ctx["m1"], params["metric"])
    if not samples or inner is None:
        return None
    return sum(samples) / len(samples) - inner
