"""Process start to the moment the window opens: boot, weight init, compile
or cache load, probes, warm-up passes, the mix reaching its steady state."""


def read(ctx, params):
    return ctx["setup_s"]
