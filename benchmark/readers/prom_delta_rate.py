"""Growth of ONE server counter series over the window / the window's seconds,
times ``scale``: a rate, or (a counter of seconds, scale 100) the share of the
window's wall time in %. ``metric`` is the series' full key as the server
prints it, labels included (``name_total{phase="fetch"}``); a bare name sums
its label sets, as ``promtext.total`` does."""

from promtext import delta


def read(ctx, params):
    grown = delta(ctx["m0"], ctx["m1"], params["metric"])
    if grown is None:
        return None
    return grown / (ctx["t1"] - ctx["t0"]) * float(params.get("scale", 1.0))
