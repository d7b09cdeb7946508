"""Growth of ONE server counter series over the window as a share (%) of the
growth of a whole family (its label sets summed). ``part`` is a series' full
key (``name_total{kind="pad"}``), ``whole`` a bare name. None where the
family did not grow (or the server does not print it)."""

from promtext import delta


def read(ctx, params):
    whole = delta(ctx["m0"], ctx["m1"], params["whole"])
    if not whole:
        return None
    return 100.0 * (delta(ctx["m0"], ctx["m1"], params["part"]) or 0.0) / whole
