"""Window mean of a server histogram: delta(_sum) / delta(_count) between the
scrapes at the window's start and end (exact; the buckets are not read)."""

from promtext import delta_mean


def read(ctx, params):
    mean = delta_mean(ctx["m0"], ctx["m1"], params["metric"])
    return None if mean is None else mean * float(params.get("scale", 1.0))
