"""Largest value, over the traced run's 1 s polls, of one gauge divided by another."""

from promtext import total


def read(ctx, params):
    shares = []
    for _, sample in ctx["polls"]:
        num, den = total(sample, params["numerator"]), total(sample, params["denominator"])
        if num is not None and den:
            shares.append(num / den)
    if not shares:
        return None
    return max(shares) * float(params.get("scale", 1.0))
