"""Growth of a server counter over the window, summed over its label sets."""

from promtext import delta


def read(ctx, params):
    return delta(ctx["m0"], ctx["m1"], params["metric"])
