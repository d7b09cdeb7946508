"""Mean of a server gauge over the traced run's 1 s polls of ``/metrics``."""

from promtext import total


def read(ctx, params):
    vals = [total(sample, params["metric"]) for _, sample in ctx["polls"]]
    vals = [v for v in vals if v is not None]
    if not vals:
        return None
    return sum(vals) / len(vals) * float(params.get("scale", 1.0))
