"""A percentile of one of the client's sample series over the window
(``ttft_ms``, ``gap_ms``): the tail of ALL the samples, none trimmed."""


def read(ctx, params):
    return ctx["percentile"](ctx["client"][params["series"]], float(params["p"]))
