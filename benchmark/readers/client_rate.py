"""A client-side count over the whole window divided by the window's seconds
(``tokens``: the output tokens GENERATED inside the window, each stream
event's tokens spread over the time since the server's previous burst and
counted by the share of that stretch inside the window, at both edges:
``loadgen.summarize``)."""


def read(ctx, params):
    return ctx["client"][params["series"]] / ctx["client"]["window_s"]
