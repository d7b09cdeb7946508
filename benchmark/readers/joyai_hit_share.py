"""Routed experts HIT as a share (%) of the experts the window's expert-layer
calls could have hit: growth of ``hit`` (``engine.moe_experts_hit``, counted on
the device) over growth of ``calls`` (``engine.moe_layer_calls``) x the experts
a layer (the configuration's ``moe.n_experts``). None where the calls did not
grow, a counter is not printed or the configuration has no ``moe`` section."""

from promtext import delta


def read(ctx, params):
    try:
        experts = ctx["config"]["moe"]["n_experts"]
        calls = delta(ctx["m0"], ctx["m1"], params["calls"])
        hit = delta(ctx["m0"], ctx["m1"], params["hit"])
    except (KeyError, TypeError):
        return None
    if not calls or hit is None:
        return None
    return 100.0 * hit / (calls * experts)
